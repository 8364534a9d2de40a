"""The decode step's attention over the selected rows against the memory
roofline: the latent rows a step's attention selected, as stored
(``costs_sparse_moe.rows_read_bytes`` of the step's ``kv_rows_read``), over
the HBM bandwidth, divided by the device time a step spends gathering them
and attending to them: the operations of ``llm_decode`` whose first result
is ``[<slots x index_topk>,<pool width>]`` (the gather by row out of the
pool addressed as rows: the operation that reads the bytes counted here),
``[<slots>,<index_topk>,<pool width>]`` (the gathered rows' mask) or
``[<slots>,<heads>,<index_topk>]`` (the scores, the softmax and what feeds
the second product), of any dtype, told by the shape of their first result
as the other readers tell theirs.  The selection itself (the sort behind
``lax.top_k``) is the indexer's side and is not in this time.

``kv_rows_read`` is the program's own count on the engine's
``engine.decode.fetch`` spans; the token's own row, which no gather reads,
is among it: one row in up to 2,048.  Means over the steps on both sides.
A configuration of another family, a program without these operations, or a
run with no profile, has nothing to read."""
import statistics

from benchmark import costs_sparse_moe, program_spans


def read(record, ctx):
    t = record.get("trace") or {}
    cfg = ctx["config"]
    if "index_topk" not in cfg or "peak" not in ctx:
        return None
    s = cfg["serve"]
    topk = min(cfg["index_topk"], s["max_ctx"] + 1)
    slots, width = s["max_slots"], costs_sparse_moe.stored_width(cfg)
    tails = (f"[{slots * topk},{width}]", f"[{slots},{topk},{width}]",
             f"[{slots},{cfg['num_attention_heads']},{topk}]")
    spent = sum(v for name, v in (t.get("op_s") or {}).items()
                if name.endswith(tails))
    steps = sum(len(v) for name, v in (t.get("program_s") or {}).items()
                if name.endswith("llm_decode"))
    rows = program_spans.arg_values("engine.decode.fetch", "kv_rows_read")
    if spent <= 0 or not steps or not rows:
        return None
    size = 2 if s["dtype"] == "bfloat16" else 4
    need = costs_sparse_moe.rows_read_bytes(cfg, statistics.mean(rows), size)
    return 100.0 * need / ctx["peak"]["hbm_bytes_per_s"] / (spent / steps)
