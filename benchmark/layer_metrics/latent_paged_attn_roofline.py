"""The paged decode-attention kernel reading latent rows, against the memory
roofline: the latent rows of the cached tokens a decode step attends to,
once as K and once as V as the absorbed form reads them today
(``costs_linear_moe.kv_read_bytes``: ``kv_lora_rank + qk_rope_head_dim``
columns a row, the padding to whole lane registers not counted), over the
HBM bandwidth, divided by the device time a step spends in the kernel: the
``tpu_custom_call`` rows whose first result is ``f32[<slots>,<query
rows>,<pool width>]`` (as ``hybrid_paged_attn_roofline`` reads its), the
pool's width being the latent row's rounded up to 128 lanes.

``kv_tokens`` is what the engine says on its ``engine.decode.dispatch``
spans (live rows, not whole pages).  Means over the steps on both sides.  A
configuration of another family, a program without the kernel, or a run with
no profile, has nothing to read."""
import statistics

from benchmark import costs_linear_moe, program_spans


def read(record, ctx):
    t = record.get("trace") or {}
    cfg = ctx["config"]
    if "kv_lora_rank" not in cfg or "peak" not in ctx:
        return None
    width = -(-costs_linear_moe.latent_width(cfg) // 128) * 128
    head = f"tpu_custom_call f32[{cfg['serve']['max_slots']},"
    tail = f",{width}]"
    spent = sum(s for name, s in (t.get("op_s") or {}).items()
                if name.startswith(head) and name.endswith(tail)
                and name.count(",") == 2)
    steps = sum(len(v) for name, v in (t.get("program_s") or {}).items()
                if name.endswith("llm_decode"))
    rows = program_spans.arg_values("engine.decode.dispatch", "kv_tokens")
    if spent <= 0 or not steps or not rows:
        return None
    size = 2 if cfg["serve"]["dtype"] == "bfloat16" else 4
    need = costs_linear_moe.kv_read_bytes(cfg, statistics.mean(rows), size)
    return 100.0 * need / ctx["peak"]["hbm_bytes_per_s"] / (spent / steps)
