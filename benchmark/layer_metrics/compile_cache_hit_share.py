"""Of the compile requests JAX answered before the window opened (the
program's ``jax.compile`` lifecycle spans), the share the persistent compile
cache answered: near 100 on a warm machine, near 0 on an empty cache, and in
between where the cells have evicted one another's programs."""
from benchmark import setup_phases


def read(record, ctx):
    return setup_phases.cache_hit_share(record)
