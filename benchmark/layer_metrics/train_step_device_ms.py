"""Device time of one run of the step program: the program that took most
of the traced device time, median over its runs."""
import statistics


def read(record, ctx):
    programs = (record.get("trace") or {}).get("program_s")
    if not programs:
        return None
    runs = max(programs.values(), key=sum)
    return 1e3 * statistics.median(runs)
