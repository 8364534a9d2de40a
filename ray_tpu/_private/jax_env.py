"""Process environment JAX reads at import — set before ``import jax``.

Two things are decided here, from outside the process that runs JAX: which
platform a worker may use, and where its compile cache lives.  The third
piece runs inside it: the process's compile counter
(``ensure_compile_listener``) and the span around a program's first call.

JAX's persistent compilation cache is keyed by, among other things, the
directory it lives in, so a directory that moves between runs never hits.
The cache is therefore placed from outside: ``JAX_COMPILATION_CACHE_DIR``
is honoured when set, and otherwise points at one fixed directory inside
the checkout.  No code path calls
``jax.config.update("jax_compilation_cache_dir", ...)``.
"""
from __future__ import annotations

import os
import threading
import time
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


# ---------------------------------------------------------------------------
# the compile counter: what JAX compiled in this process, and how long
# ---------------------------------------------------------------------------
_compiles = {"compiles": 0, "compile_s": 0.0}
_compile_tl = threading.local()
_listening = False
_listen_lock = threading.Lock()


def _on_compile_event(name: str, secs: float, **kw) -> None:
    """One ``jax.compile`` lifecycle span and three counters a compile
    request.  JAX ends every request with ``backend_compile_duration``
    (which names the function) and, where the persistent cache answered,
    reports ``cache_retrieval_time_sec`` inside it, on the same thread:
    that one only marks the request a hit."""
    if "cache_retrieval" in name:
        _compile_tl.hit = True
        return
    if "backend_compile" not in name:
        return
    from ray_tpu import observability as obs

    hit = getattr(_compile_tl, "hit", False)
    _compile_tl.hit = False
    _compiles["compiles"] += 1  # under the GIL
    _compiles["compile_s"] += secs
    now = time.perf_counter()
    args = {"program": kw["fun_name"]} if kw.get("fun_name") else {}
    obs.record("jax.compile", now - secs, now, _lifecycle=True,
               event="cache_hit" if hit else "compile", seconds=secs, **args)
    try:  # no runtime in this process (the PPO driver's), or it is going
        from ray_tpu.util.metrics import Counter

        Counter("jax_compiles_total", "compile requests JAX answered",
                tag_keys=("cache",)).inc(
                    tags={"cache": "hit" if hit else "miss"})
        Counter("jax_compile_seconds_total",
                "seconds JAX spent compiling or fetching").inc(secs)
    except Exception:
        pass


def ensure_compile_listener() -> None:
    """Register the process's one ``jax.monitoring`` listener, once.  Called
    by the program's entry points that have jax in hand: it never brings
    jax into a process that has none."""
    global _listening
    with _listen_lock:
        if _listening:
            return
        from jax import monitoring

        monitoring.register_event_duration_secs_listener(_on_compile_event)
        _listening = True


def compile_totals() -> Dict[str, float]:
    """``compiles`` (requests, cache hits included) and ``compile_s`` of
    this process since the listener was registered."""
    return dict(_compiles)


class FirstCallSpan:
    """A jitted program whose first call runs inside a lifecycle span
    (``engine.compile``, ``train.compile``): trace, lower, compile or
    fetch, and that first run, waited for.  The ``jax.compile`` spans of
    the call are its children.  ``attrs``: facts settled when the program
    was built (which of two paths a shape chose), recorded as the span's
    arguments.  Later calls go straight through; every other attribute
    (``lower``, ``_cache_size``) is the program's own."""

    def __init__(self, fn, span_name: str, program: str, before=None,
                 **attrs):
        self._fn, self._span_name, self._program = fn, span_name, program
        self._before = before  # called with ``program`` as the span opens
        self._first = True
        self.attrs = attrs

    def __call__(self, *args, **kw):
        if not self._first:
            return self._fn(*args, **kw)
        self._first = False
        import jax

        from ray_tpu import observability as obs

        if self._before is not None:
            self._before(self._program)
        with obs.span(self._span_name, _lifecycle=True,
                      program=self._program, **self.attrs):
            return jax.block_until_ready(self._fn(*args, **kw))

    def __getattr__(self, name):
        if name == "_fn":  # a copy not yet filled in: no loop
            raise AttributeError(name)
        return getattr(self._fn, name)
