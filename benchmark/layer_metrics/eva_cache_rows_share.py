"""Of the positions the live slots held in the traced decode steps
(``ctx_tokens`` on the engine's ``engine.decode.dispatch`` spans: what a
cache of one row a token would have read), the share of rows the steps read
(``kv_tokens``: the summaries of the windows before and the open window's
rows): what the traffic really gets of EVA's 1/16.  100% while every context
is inside its first window; ``(n // 2048 * 128 + n % 2048) / n`` beyond.  A
program whose cached rows are its tokens says no ``ctx_tokens``: nothing to
read."""
from benchmark import program_spans


def read(record, ctx):
    held = program_spans.arg_values("engine.decode.dispatch", "ctx_tokens")
    rows = program_spans.arg_values("engine.decode.dispatch", "kv_tokens")
    if not held or not sum(held) or len(rows) != len(held):
        return None
    return 100.0 * sum(rows) / sum(held)
