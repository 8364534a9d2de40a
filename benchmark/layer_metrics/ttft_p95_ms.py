"""Time to first token, from when the request was due to the client's stamp
of its first chunk; 95th percentile over the requests due inside the window.
A stall of admission shows here and in no token gap."""
from benchmark import loadgen


def read(record, ctx):
    ttft = (record.get("samples") or {}).get("ttft_ms")
    return loadgen.percentile(ttft, 95) if ttft else None
