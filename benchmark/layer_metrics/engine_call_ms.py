"""Host time of the call of the jitted decode program and nothing else
(``engine.decode.call``: argument flattening, PJRT's enqueue), median over
the traced window's iterations that admitted nothing."""
from benchmark import step_account


def read(record, ctx):
    return step_account.dispatch_part_ms("engine.decode.call")
