"""The median of the window's token gaps, where a cell is not judged on it
(``token_gap_p50_ms`` is the end-to-end metric of the cells that are): the
same sample, a record."""
from benchmark import loadgen


def read(record, ctx):
    gaps = (record.get("samples") or {}).get("gap_ms")
    return loadgen.percentile(gaps, 50) if gaps else None
