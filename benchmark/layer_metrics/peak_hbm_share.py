"""Peak bytes in use on the fullest chip over its limit, after the window."""


def read(record, ctx):
    d = record["device"]
    if not d.get("memory_limit_bytes"):
        return None
    return 100.0 * d["memory_peak_bytes"] / d["memory_limit_bytes"]
