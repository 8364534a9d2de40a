"""Of the choices the live rows of the traced decode steps made (rows x
expert layers x ``num_experts_per_tok``: ``choices`` on the engine's
``engine.decode.fetch`` spans), the share that landed on an expert held
here (``local_choices``).  The held share of a layer's experts (a quarter,
at 128 of 512) where routing is even: what says how near this chip's load
is to a deployment's mean.  A program that holds no share counts neither:
nothing to read."""
from benchmark import program_spans


def read(record, ctx):
    landed = program_spans.arg_values("engine.decode.fetch", "local_choices")
    choices = program_spans.arg_values("engine.decode.fetch", "choices")
    if not landed or not sum(choices):
        return None
    return 100.0 * sum(landed) / sum(choices)
