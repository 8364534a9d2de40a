"""What every driver shares: finding files by name, the device's facts, the
compile counter, the profiler window and host spans.

Nothing here imports jax at module level: the process that runs ``run.py``
must stay off the chip where a worker owns it.
"""
from __future__ import annotations

import contextlib
import glob
import importlib
import json
import os
import shutil
import sys
import time

WINDOW_SPAN = "bench:window"  # trace_reduce takes the window from it
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_json(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def merge(into: dict, patch: dict) -> None:
    """``patch`` laid over ``into``, group by group."""
    for k, v in patch.items():
        if isinstance(v, dict) and isinstance(into.get(k), dict):
            merge(into[k], v)
        else:
            into[k] = v


def load_traffic(name: str) -> dict:
    """``traffic/<name>.json``.  A file with an ``inherits`` key is the named
    file with this one's other keys laid over it: two cells that must offer
    the same work keep one set of numbers."""
    own = load_json("traffic", name + ".json")
    if "inherits" not in own:
        return own
    base = load_traffic(own.pop("inherits"))
    merge(base, own)
    return base


def load_module(kind: str, name: str):
    """``benchmark/<kind>/<stem>.py`` as a module, or None where no such file
    is.  The stem is the name up to its first dot: ``device_idle_share.train``
    and ``device_idle_share.serve`` are read by one ``device_idle_share.py``.
    Imported under its package name, so that a function of it can be sent to
    a worker, which imports it the same way."""
    stem = name.split(".", 1)[0]
    if not os.path.exists(os.path.join(HERE, kind, stem + ".py")):
        return None
    return importlib.import_module(f"benchmark.{kind}.{stem}")


def jax_seed(seed: int) -> int:
    """``--seed`` may be a little over 2**31; a PRNGKey wants 32 signed bits."""
    return int(seed) % (2 ** 31 - 1)


def peak_for(device_kind: str) -> dict:
    """The row of ``peaks.json`` for this device kind.  A kind that is not in
    the table is an error: a share of an assumed peak is not a measurement."""
    table = load_json("peaks.json")["peaks"]
    if device_kind not in table:
        raise ValueError(f"no peaks on file for device kind {device_kind!r}; "
                         f"known: {sorted(table)}")
    return table[device_kind]


def device_record(allow_cpu: bool = False) -> dict:
    """What JAX reports in THIS process.  Fails where there is no TPU."""
    import jax

    devs = jax.local_devices()
    d = devs[0]
    if d.platform != "tpu" and not allow_cpu:
        raise RuntimeError(
            f"no TPU: jax reports platform={d.platform!r} ({d.device_kind})")
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}


def memory_record(rec: dict) -> dict:
    """Adds the peak and the limit of the fullest chip to a device record.
    The TPU runtime keeps two books: ``peak_bytes_in_use`` for arrays, and
    ``peak_bytes_reserved`` for the scratch memory of compiled programs (a
    train step's activations, the PPO step's rollout buffers).  Both hold
    HBM at once (``largest_free_block_bytes`` is the limit less both), so
    the peak is their sum."""
    import jax

    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    rec = dict(rec)
    rec["memory_peak_bytes"] = max(
        int(s.get("peak_bytes_in_use", 0)) + int(
            s.get("peak_bytes_reserved", 0)) for s in stats)
    rec["memory_limit_bytes"] = max(int(s.get("bytes_limit", 0)) for s in stats)
    rec["memory_stats"] = stats[0]
    return rec


class CompileCounter:
    """Counts what JAX compiles or fetches from its persistent cache, from
    ``arm()`` on.  Either inside the measured window is a fault."""

    def __init__(self):
        from jax import monitoring

        self.events = []
        self.armed = False
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, _secs, **_kw):
        if self.armed and ("backend_compile" in name
                           or "cache_retrieval" in name):
            self.events.append(name)

    def arm(self):
        self.events.clear()
        self.armed = True

    def disarm(self) -> int:
        self.armed = False
        return len(self.events)


SPAN_NAMES = set()  # every name this process has made a span of


def span(name: str):
    """A host span in the profiler's own trace (nothing when no trace runs),
    so that idle gaps of the device can be named by what the host did.  A
    driver names its spans as it likes: ``stop_trace`` looks for every name
    used in its process."""
    import jax

    SPAN_NAMES.add(name)
    return jax.profiler.TraceAnnotation(name)


class SpanTimes:
    """Durations of named host spans by the host clock, kept in memory."""

    def __init__(self):
        self.ms = {}

    @contextlib.contextmanager
    def __call__(self, name: str):
        t0 = time.perf_counter()
        with span(name):
            yield
        self.ms.setdefault(name, []).append(
            (time.perf_counter() - t0) * 1e3)


def trace_dir(tag: str) -> str:
    """A fresh directory for one profile, inside the checkout."""
    path = os.path.join(ROOT, ".bench_trace", tag)
    shutil.rmtree(path, ignore_errors=True)
    os.makedirs(path, exist_ok=True)
    return path


def start_trace(path: str) -> None:
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # the interpreter's frames: large, slow
    opts.host_tracer_level = 2    # TraceAnnotation spans
    jax.profiler.start_trace(path, profiler_options=opts)


class TracedWindow:
    """A profile of the steps between its making and ``close()``, which hands
    back the reduced trace.  Made and closed by one thread: the window span
    is that thread's."""

    def __init__(self, tag: str):
        self._dir = trace_dir(tag)
        start_trace(self._dir)
        self._span = span(WINDOW_SPAN)
        self._span.__enter__()

    def close(self) -> dict:
        self._span.__exit__(None, None, None)
        return stop_trace(self._dir)


def stop_trace(path: str) -> dict:
    """Ends the profile and reduces it here, in the process that held the
    chip and made the spans; the reduced record is small enough to hand to
    the parent."""
    import jax

    from benchmark import trace_reduce

    t0 = time.perf_counter()
    jax.profiler.stop_trace()
    files = glob.glob(os.path.join(path, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not files:
        return {}
    t1 = time.perf_counter()
    events = trace_reduce.events_from_xplane(
        files[0], host_names=SPAN_NAMES | {WINDOW_SPAN})
    t2 = time.perf_counter()
    out = trace_reduce.reduce(
        events, span_names=sorted(SPAN_NAMES - {WINDOW_SPAN}))
    # a traced run has 360 s in all: say where the reading's share went
    print(f"[bench] trace: stop {t1 - t0:.1f} s, read {t2 - t1:.1f} s "
          f"({len(events)} events, {os.path.getsize(files[0])} bytes), "
          f"reduce {time.perf_counter() - t2:.1f} s", file=sys.stderr,
          flush=True)
    shutil.rmtree(path, ignore_errors=True)
    return out
