"""Host time of ``next(batch)`` on ``iter_device_batches``, per step, median:
what the step loop waits for its input."""
import statistics


def read(record, ctx):
    waits = (record.get("samples") or {}).get("ingest_ms")
    return statistics.median(waits) if waits else None
