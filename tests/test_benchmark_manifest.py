"""``BENCHMARK.json`` still stands for the files it names
(``benchmark/manifest_check.py``: no chip, no model, no jax), in tier-1."""
from benchmark import manifest_check


def test_the_manifest_stands_for_its_files():
    assert manifest_check.faults(manifest_check.load()) == []
