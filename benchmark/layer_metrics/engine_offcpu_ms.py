"""Time of an iteration in which the loop thread was runnable or descheduled
and did not run: its wall time less its ``cpu_ms`` (the thread's own CPU
time) less its ``engine.decode.fetch`` (the one wait that is meant), not
below 0; median over the iterations that admitted nothing."""
from benchmark import step_account


def read(record, ctx):
    return step_account.offcpu_ms()
