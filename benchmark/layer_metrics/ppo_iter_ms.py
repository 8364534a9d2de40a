"""Host clock around one ``train()`` with its metrics fetched, median."""
import statistics


def read(record, ctx):
    it = (record.get("samples") or {}).get("ppo_iter_ms")
    return statistics.median(it) if it else None
