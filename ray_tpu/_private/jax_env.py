"""Process environment JAX reads at import — set before ``import jax``.

Two things are decided here, from outside the process that runs JAX: which
platform a worker may use, and where its compile cache lives.

JAX's persistent compilation cache is keyed by, among other things, the
directory it lives in, so a directory that moves between runs never hits.
The cache is therefore placed from outside: ``JAX_COMPILATION_CACHE_DIR``
is honoured when set, and otherwise points at one fixed directory inside
the checkout.  No code path calls
``jax.config.update("jax_compilation_cache_dir", ...)``.
"""
from __future__ import annotations

import os
from typing import Dict

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
# "1" in the environment of a worker started for a TPU request, "0" in
# every other worker's.  bootstrap_jax_distributed reports it, so that the
# rendezvous can tell a worker that was granted chips and came up on the
# CPU from one that was meant to run there.
CHIP_WORKER_ENV = "RAY_TPU_CHIP_WORKER"


def pin_platform(env: Dict[str, str]) -> None:
    """The spawner's last word on a worker's JAX platform, applied to the
    full environment (inherited + the raylet's overlay) by the raylet
    locally and by the node agent remotely.

    A worker that asked for no chips is held to the CPU, so it can never
    take a chip from the worker that owns it.  A worker that was granted
    chips must not inherit ``JAX_PLATFORMS=cpu`` from the shell that
    started the driver (the test setup exports it): the request for chips
    is the platform choice."""
    if env.get(CHIP_WORKER_ENV) == "1":
        if env.get("JAX_PLATFORMS") == "cpu":
            del env["JAX_PLATFORMS"]
    else:
        env["JAX_PLATFORMS"] = "cpu"


def ensure_compile_cache() -> str:
    """Point this process (and, through the environment, every process it
    spawns) at the persistent compile cache; returns the directory.

    Every executable is cached, however quick its compile: with JAX's
    default one-second floor a program that compiles in about a second
    is stored by one run and not by the next, and a warm run is then not
    reproducibly warm."""
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    return os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                                 os.path.join(_CHECKOUT, ".jax_cache"))
