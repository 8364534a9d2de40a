"""Set-up seconds in which the most specific thing any process of the program
was doing was starting the runtime: ``runtime.init`` (head, raylet, driver
connected) and ``runtime.worker_start`` (spawn request to a registered worker:
interpreter start and imports).  One of the five parts of
``setup_s`` (``benchmark/setup_phases.py``)."""
from benchmark import setup_phases


def read(record, ctx):
    return setup_phases.phase_s(record, "runtime")
