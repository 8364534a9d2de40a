"""Host time of handing one step's tokens to their requests, retiring and
freeing pages (``engine.emit``), median."""
from benchmark import program_spans


def read(record, ctx):
    return program_spans.median_ms("engine.emit")
