"""The three flash-attention kernels (forward, dq, dk/dv) against their
roofline: the least time the chip could take for the attention of the traced
steps (``costs.flash_train_cost``; at these shapes the compute bound applies,
the memory bound is some thirty times lower) over the summed device time of
the kernels' operations.  The trace shows a Pallas kernel as a custom call
with the target ``tpu_custom_call``; in the train step those are the three
flash kernels and nothing else."""
from benchmark import costs

KERNEL = "tpu_custom_call"


def read(record, ctx):
    t, steps = record.get("trace"), record.get("trace_steps")
    if not t or not steps or "peak" not in ctx:
        return None
    spent = sum(s for name, s in t["op_s"].items()
                if name.startswith(KERNEL))
    if spent <= 0:
        return None
    cfg, traffic = ctx["config"], ctx["traffic"]
    cost = costs.flash_train_cost(
        traffic["per_chip_batch"], traffic["seq"], cfg["n_head"],
        cfg["n_embd"] // cfg["n_head"])
    least = costs.roofline_seconds(cost["flops"], cost["bytes"], ctx["peak"])
    return 100.0 * least["seconds"] * cfg["n_layer"] * steps / spent
