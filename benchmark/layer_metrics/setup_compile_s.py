"""Set-up seconds in which JAX compiled or fetched a program (``jax.compile``)
or a program of the engine or the train step made its first call
(``engine.compile``, ``train.compile``: trace, lower and that first run
besides).  Compiles side by side count once: a union, not a sum.  One of the five parts of
``setup_s`` (``benchmark/setup_phases.py``)."""
from benchmark import setup_phases


def read(record, ctx):
    return setup_phases.phase_s(record, "compile")
