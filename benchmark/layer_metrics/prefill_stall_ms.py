"""Device time of one prefill program between two decode steps, median over
the prefills in the traced window: how long an admission holds up every
decoding slot."""
import statistics


def read(record, ctx):
    programs = (record.get("trace") or {}).get("program_s") or {}
    runs = [s for name, v in programs.items() if "prefill" in name for s in v]
    return 1e3 * statistics.median(runs) if runs else None
