"""Per step, the device time of collective operations (all-reduce,
reduce-scatter, all-gather, ...) during which no compute operation ran on
that device; mean over the chips."""


def read(record, ctx):
    t, steps = record.get("trace"), record.get("trace_steps")
    if not t or not steps or t.get("devices", 1) < 2:
        return None
    return 1e3 * t["collective_exposed_s"] / steps
