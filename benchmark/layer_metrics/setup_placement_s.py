"""Set-up seconds owned by placing the work: ``serve.deploy`` and
``serve.replica_init``, ``train.worker_group_start`` and ``train.rendezvous``
(the backend's first initialisation on each rank), less what lies deeper:
model, engine and compiles.  One of the five parts of
``setup_s`` (``benchmark/setup_phases.py``)."""
from benchmark import setup_phases


def read(record, ctx):
    return setup_phases.phase_s(record, "placement")
