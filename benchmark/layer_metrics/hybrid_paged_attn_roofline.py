"""The paged decode-attention kernel in a hybrid decoder's cell against the
memory roofline: ``moe_paged_attn_roofline``'s reading with the pool's width
taken from the published ``head_dim`` x ``num_key_value_heads``
(``costs_hybrid.kv_width``; ``costs_moe.kv_width`` takes ``hidden / heads``,
which is not this family's head).  The keys and values of the cached rows a
decode step attends to, in every layer, over the HBM bandwidth, divided by
the device time a step spends in the kernel: the ``tpu_custom_call`` rows
whose first result is ``f32[<slots>,<query rows>,<pool width>]``.

``kv_tokens`` is what the engine says on its ``engine.decode.dispatch``
spans (live rows, not whole pages).  Means over the steps on both sides.  A
program without the kernel, or a run with no profile, has nothing to
read."""
import statistics

from benchmark import costs_hybrid, program_spans


def read(record, ctx):
    t = record.get("trace") or {}
    cfg = ctx["config"]
    if "mamba_d_state" not in cfg or "peak" not in ctx:
        return None
    head = f"tpu_custom_call f32[{cfg['serve']['max_slots']},"
    tail = f",{costs_hybrid.kv_width(cfg)}]"
    spent = sum(s for name, s in (t.get("op_s") or {}).items()
                if name.startswith(head) and name.endswith(tail)
                and name.count(",") == 2)
    steps = sum(len(v) for name, v in (t.get("program_s") or {}).items()
                if name.endswith("llm_decode"))
    rows = program_spans.arg_values("engine.decode.dispatch", "kv_tokens")
    if spent <= 0 or not steps or not rows:
        return None
    size = 2 if cfg["serve"]["dtype"] == "bfloat16" else 4
    need = costs_hybrid.kv_read_bytes(cfg, statistics.mean(rows), size)
    return 100.0 * need / ctx["peak"]["hbm_bytes_per_s"] / (spent / steps)
