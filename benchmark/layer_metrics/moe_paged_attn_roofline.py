"""The paged decode-attention kernel in a ``serve_decoder`` cell against the
memory roofline: the keys and values of the cached rows a decode step
attends to, in every layer (``costs_moe.kv_read_bytes``), over the HBM
bandwidth, divided by the device time a step spends in the kernel.

``paged_attn_roofline`` cannot be read here: it takes GPT-2's key names and
sums every ``tpu_custom_call``, which in a routed model's longer prefills
includes the compiler's own grouped-matmul kernel for ``lax.ragged_dot``.
The two are told apart by the shape the operation's name carries: the paged
kernel's first result is ``f32[<slots>,<query rows>,<pool width>]`` (pool
width: every KV head's columns), the grouped matmul's has two dimensions.
Only the first kind is summed.

``kv_tokens`` is what the engine says on its ``engine.decode.dispatch``
spans (the active slots' lengths: live rows, not whole pages, so the count
cannot carry the share past 100%).  Means over the steps on both sides, as
``moe_decode_roofline`` takes them.  A program without the kernel, or a run
with no profile, has nothing to read."""
import statistics

from benchmark import costs_moe, program_spans


def read(record, ctx):
    t = record.get("trace") or {}
    cfg = ctx["config"]
    head = f"tpu_custom_call f32[{cfg['serve']['max_slots']},"
    tail = f",{costs_moe.kv_width(cfg)}]"
    spent = sum(s for name, s in (t.get("op_s") or {}).items()
                if name.startswith(head) and name.endswith(tail)
                and name.count(",") == 2)
    steps = sum(len(v) for name, v in (t.get("program_s") or {}).items()
                if name.endswith("llm_decode"))
    rows = program_spans.arg_values("engine.decode.dispatch", "kv_tokens")
    if spent <= 0 or not steps or not rows or "peak" not in ctx:
        return None
    size = 2 if cfg["serve"]["dtype"] == "bfloat16" else 4
    need = costs_moe.kv_read_bytes(cfg, statistics.mean(rows), size)
    return 100.0 * need / ctx["peak"]["hbm_bytes_per_s"] / (spent / steps)
