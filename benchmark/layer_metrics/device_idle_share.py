"""Share of the traced window in which no operation ran on the device
(mean over the chips used): 1 - union of device-op intervals / window."""


def read(record, ctx):
    t = record.get("trace")
    if not t:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
