"""Arrival process ``gamma``: gamma-distributed gaps of ``shape`` at the
mean rate ``rate_per_s``.  A shape under 1 gives bursts (0.25: a squared
coefficient of variation of 4); shape 1 is Poisson in law, though not the
same draws as ``poisson``."""


def gaps(rng, arrivals: dict, n: int):
    k = float(arrivals["shape"])
    return rng.gamma(k, 1.0 / (arrivals["rate_per_s"] * k), n)
