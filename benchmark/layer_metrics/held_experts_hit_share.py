"""Of the experts this program holds (every expert layer's share:
``experts_held`` on the engine's ``engine.decode.fetch`` spans), the share
that a decode step hit, and so had to read: the mean of ``experts_hit`` over
the traced steps, over ``experts_held``.  The fewer, the less a step
streams; it rises with the live slots.  A program that holds no share has
no ``experts_held``: nothing to read."""
import statistics

from benchmark import program_spans


def read(record, ctx):
    held = program_spans.arg_values("engine.decode.fetch", "experts_held")
    hit = program_spans.arg_values("engine.decode.fetch", "experts_hit")
    if not held or not hit:
        return None
    return 100.0 * statistics.mean(hit) / held[0]
