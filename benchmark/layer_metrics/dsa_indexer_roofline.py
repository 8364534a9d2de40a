"""The decode step's index scoring (the kernel ``dsa_index``) against the
memory roofline: the index keys of the cached rows a step's indexers score
(``costs_sparse_moe.index_key_bytes`` of the step's ``index_rows``:
``index_head_dim`` columns a cached row a layer) over the HBM bandwidth,
divided by the device time a step spends in the kernel: the
``tpu_custom_call`` rows whose first result is ``f32[<slots>,1,<max_ctx>]``,
a slot's scores over its whole table row (told by the shape, as
``latent_paged_attn_roofline`` tells its kernel).

``index_rows`` is what the engine says on its ``engine.decode.dispatch``
spans (live rows, not whole pages).  Means over the steps on both sides.  A
configuration of another family, a program without the kernel, or a run with
no profile, has nothing to read."""
import statistics

from benchmark import costs_sparse_moe, program_spans


def read(record, ctx):
    t = record.get("trace") or {}
    cfg = ctx["config"]
    if "index_topk" not in cfg or "peak" not in ctx:
        return None
    s = cfg["serve"]
    ctx_rows = -(-s["max_ctx"] // s["page_size"]) * s["page_size"]
    kernel = f"tpu_custom_call f32[{s['max_slots']},1,{ctx_rows}]"
    spent = (t.get("op_s") or {}).get(kernel, 0.0)
    steps = sum(len(v) for name, v in (t.get("program_s") or {}).items()
                if name.endswith("llm_decode"))
    rows = program_spans.arg_values("engine.decode.dispatch", "index_rows")
    if spent <= 0 or not steps or not rows:
        return None
    size = 2 if s["dtype"] == "bfloat16" else 4
    need = costs_sparse_moe.index_key_bytes(cfg, statistics.mean(rows), size)
    return 100.0 * need / ctx["peak"]["hbm_bytes_per_s"] / (spent / steps)
