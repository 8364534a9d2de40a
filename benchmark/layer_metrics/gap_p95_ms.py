"""The 95th percentile of the window's token gaps, as a record: the same
sample as ``token_gap_p50_ms``, at the place where a cell's tail is not
steady enough to be judged (PERF.md section 2)."""
from benchmark import loadgen


def read(record, ctx):
    gaps = (record.get("samples") or {}).get("gap_ms")
    return loadgen.percentile(gaps, 95) if gaps else None
