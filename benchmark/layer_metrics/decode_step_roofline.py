"""The decode program against the memory roofline: the bytes one step has to
read (every weight once in the dtype it is served in, and the keys and values
of the cached rows the step attends to; ``costs.gpt2_decode_bytes``) over the
HBM bandwidth, divided by the decode program's device time a step.

The engine says how many cached rows a step reads on its
``engine.decode.dispatch`` span (``kv_tokens``: live rows of the active
slots, not whole pages), as ``paged_attn_roofline`` reads it.  Means over the
traced steps on both sides, so that a step cut by an edge of the profile
weighs on neither.  The decode program is the one named ``llm_decode``; a
program with no such name and no ``kv_tokens`` has nothing to read."""
import statistics

from benchmark import costs, program_spans


def read(record, ctx):
    programs = (record.get("trace") or {}).get("program_s") or {}
    runs = [s for name, v in programs.items()
            if name.endswith("llm_decode") for s in v]
    rows = program_spans.arg_values("engine.decode.dispatch", "kv_tokens")
    params = record.get("counters", {}).get("param_count")
    if not (runs and rows and params) or "peak" not in ctx:
        return None
    served = 2 if ctx["config"]["serve"]["dtype"] == "bfloat16" else 4
    need = costs.gpt2_decode_bytes(params * served, statistics.mean(rows),
                                   ctx["config"], served)
    return 100.0 * need / ctx["peak"]["hbm_bytes_per_s"] \
        / statistics.mean(runs)
