"""Arrival process ``poisson``: independent exponential gaps."""


def gaps(rng, arrivals: dict, n: int):
    """n gaps between arrivals, in seconds, at ``rate_per_s`` on average."""
    return rng.exponential(1.0 / arrivals["rate_per_s"], n)
