"""Host time of setting off a decode step's copies to the host
(``engine.decode.readback``: the ``copy_to_host_async`` loop), median over
the traced window's iterations that admitted nothing."""
from benchmark import step_account


def read(record, ctx):
    return step_account.dispatch_part_ms("engine.decode.readback")
