"""Mean share of the engine's slots that were decoding, per step
(``LLMEngine.stats()["avg_batch_occupancy"]``)."""


def read(record, ctx):
    occ = record.get("counters", {}).get("engine", {}).get(
        "avg_batch_occupancy")
    return None if occ is None else 100.0 * occ
