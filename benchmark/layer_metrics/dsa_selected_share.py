"""Of the rows dense attention would have read in the traced decode steps
(every cached row of every live slot, in every layer: ``kv_tokens`` on the
engine's ``engine.decode.dispatch`` spans times the layers, which is what
their ``index_rows`` says), the share the selection kept and the attention
read (``kv_rows_read`` on ``engine.decode.fetch``, the program's own count;
the token's own row is among it).  Near 100% while contexts are shorter
than ``index_topk``; ``index_topk`` over the mean context beyond.  A
program whose layers select nothing counts neither: nothing to read."""
from benchmark import program_spans


def read(record, ctx):
    scored = program_spans.arg_values("engine.decode.dispatch", "index_rows")
    read_rows = program_spans.arg_values("engine.decode.fetch",
                                         "kv_rows_read")
    if not read_rows or not sum(scored):
        return None
    return 100.0 * sum(read_rows) / sum(scored)
