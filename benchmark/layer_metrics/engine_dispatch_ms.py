"""Host time of the call that enqueues the decode program
(``engine.decode.dispatch``), median over the traced window."""
from benchmark import program_spans


def read(record, ctx):
    return program_spans.median_ms("engine.decode.dispatch")
