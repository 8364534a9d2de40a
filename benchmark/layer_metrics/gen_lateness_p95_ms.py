"""How late the load generator sent: send time minus due time, 95th
percentile over the requests due inside the window."""
from benchmark import loadgen


def read(record, ctx):
    late = (record.get("samples") or {}).get("lateness_ms")
    return loadgen.percentile(late, 95) if late else None
