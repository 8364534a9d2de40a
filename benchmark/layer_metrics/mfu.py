"""Model FLOP/s utilization: the operations the forward and backward passes
require per token (``costs.gpt2_train_flops_per_token``) times this run's
tokens per second per chip, over the chip's bf16 peak."""
from benchmark import costs


def read(record, ctx):
    rate = record["end_to_end"].get("tokens_per_s_chip")
    if rate is None or "peak" not in ctx:
        return None
    flops = costs.gpt2_train_flops_per_token(ctx["config"],
                                             ctx["traffic"]["seq"])
    return 100.0 * rate * flops / ctx["peak"]["bf16_flops"]
