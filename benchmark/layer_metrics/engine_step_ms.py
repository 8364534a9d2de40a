"""Time between the ends of consecutive engine iterations
(``LLMEngine.recent_step_stamps()``), median over the window."""
import statistics


def read(record, ctx):
    steps = (record.get("samples") or {}).get("engine_step_ms")
    return statistics.median(steps) if steps else None
