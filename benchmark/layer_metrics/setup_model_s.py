"""Set-up seconds owned by ``model.build`` (config to initialised parameters on
the device) and ``engine.init`` (pools and per-slot state allocated), less the
compiles inside them.  One of the five parts of
``setup_s`` (``benchmark/setup_phases.py``)."""
from benchmark import setup_phases


def read(record, ctx):
    return setup_phases.phase_s(record, "model")
