"""From ``submit`` to the moment admission takes the request off the queue
(``request.queued``), median over the requests admitted in the traced
window."""
from benchmark import program_spans


def read(record, ctx):
    return program_spans.median_ms("request.queued")
