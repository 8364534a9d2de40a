"""Mean share of the engine's slots that were decoding, per step of the
window: ``LLMEngine.stats()["avg_batch_occupancy"]`` is a mean over the
engine's life, so the driver takes it with ``steps`` at both ends of the
window and this is the window's own (``counters.window_occupancy``)."""


def read(record, ctx):
    occ = record.get("counters", {}).get("window_occupancy")
    return None if occ is None else 100.0 * occ
