"""Host time of one admission pass that admitted (``engine.admit``, its
prefills and their blocking reads included), median: what an arrival adds to
the step it lands in."""
from benchmark import program_spans


def read(record, ctx):
    return program_spans.median_ms("engine.admit", program_spans.admitted)
