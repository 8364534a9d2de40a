"""Share of the window's token gaps longer than 1.5 times their median: the
gaps that something cut into, in a serve cell an admission's prefill between
two decode steps or the serve front's own queue.  Where it lies beside 5%
says on which side of the cut the 95th percentile stands: well above, the
tail measures what cuts; well below, the jitter of plain gaps; near it, an
edge that swings.  A record, judged by nothing."""
import statistics


def read(record, ctx):
    gaps = (record.get("samples") or {}).get("gap_ms")
    if not gaps:
        return None
    cut = 1.5 * statistics.median(gaps)
    return 100.0 * sum(1 for g in gaps if g > cut) / len(gaps)
