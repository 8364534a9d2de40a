"""Host time of reading the decode step's tokens back
(``engine.decode.fetch``), median: where the loop thread waits for the
device."""
from benchmark import program_spans


def read(record, ctx):
    return program_spans.median_ms("engine.decode.fetch")
