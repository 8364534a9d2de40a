"""The decode program against the memory roofline: the bytes one step has to
read (every weight once in the dtype it is served in, and the keys and values
of the live tokens of the active slots; ``costs.gpt2_decode_bytes``) over the
HBM bandwidth, divided by the decode program's median device time.  The
decode program is the one the trace shows running most often."""
import statistics

from benchmark import costs


def read(record, ctx):
    t = record.get("trace") or {}
    programs, c = t.get("program_s"), record.get("counters", {})
    if not programs or not c.get("param_count") or "peak" not in ctx:
        return None
    runs = max(programs.values(), key=len)
    served = 2 if ctx["config"]["serve"]["dtype"] == "bfloat16" else 4
    need = costs.gpt2_decode_bytes(c["param_count"] * served,
                                   c["live_tokens_at_trace"], ctx["config"],
                                   served)
    least = need / ctx["peak"]["hbm_bytes_per_s"]
    return 100.0 * least / statistics.median(runs)
