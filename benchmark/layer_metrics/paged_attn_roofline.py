"""The paged decode-attention kernel against the memory roofline: the keys
and values of the cached tokens that the decode steps of the traced window
attended to, in every layer (``costs.gpt2_decode_bytes`` with no weights),
over the HBM bandwidth, divided by the summed device time of the Pallas
kernels' operations.  The engine says how many cached rows a step reads in
its ``engine.decode.dispatch`` span (``kv_tokens``, the sum of the active
slots' lengths): live tokens, not whole pages, so the count cannot carry the
share past 100%.  The trace shows a Pallas kernel as a custom call with the
target ``tpu_custom_call``; in the serve cell that is the paged kernel and
nothing else (prefill attends through XLA).  A program without the kernel
has neither the operation nor the span's argument: nothing to read."""
from benchmark import costs, program_spans

KERNEL = "tpu_custom_call"


def read(record, ctx):
    t = record.get("trace") or {}
    spent = sum(s for name, s in (t.get("op_s") or {}).items()
                if name.startswith(KERNEL))
    if spent <= 0 or "peak" not in ctx:
        return None
    rows = program_spans.arg_values("engine.decode.dispatch", "kv_tokens")
    if not rows:
        return None
    served = 2 if ctx["config"]["serve"]["dtype"] == "bfloat16" else 4
    need = costs.gpt2_decode_bytes(0, sum(rows), ctx["config"], served)
    return 100.0 * need / ctx["peak"]["hbm_bytes_per_s"] / spent
