"""Host time of a decode step's ``engine.decode.stage``: the comparisons of
the slot state with the device's copy and the ``device_put`` of what changed,
median over the traced window's iterations that admitted nothing."""
from benchmark import step_account


def read(record, ctx):
    return step_account.dispatch_part_ms("engine.decode.stage")
