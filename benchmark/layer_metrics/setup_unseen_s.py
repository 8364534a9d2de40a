"""Set-up seconds in which no lifecycle span of the program was open: the
benchmark's own phases (data set, the reference's forward pass, the warm-up's
runs, the client processes, a serve cell's pre-roll) and whatever the program
has not named.  One of the five parts of
``setup_s`` (``benchmark/setup_phases.py``)."""
from benchmark import setup_phases


def read(record, ctx):
    return setup_phases.phase_s(record, "unseen")
