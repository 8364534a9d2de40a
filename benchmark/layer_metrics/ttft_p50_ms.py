"""Time to first token, from when the request was due to the client's stamp
of its first chunk; median over the requests due inside the window."""
from benchmark import loadgen


def read(record, ctx):
    ttft = (record.get("samples") or {}).get("ttft_ms")
    return loadgen.percentile(ttft, 50) if ttft else None
