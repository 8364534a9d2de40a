"""Producer-thread time for one batch (``ingest.produce``: block fetch,
slicing, host-to-device copy), median: how busy the thread that feeds the
step is, against the step's length."""
from benchmark import program_spans


def read(record, ctx):
    return program_spans.median_ms("ingest.produce")
