"""The tracing plane — cluster-wide trace context + span collection.

Reference: Ray's task-event pipeline (core worker task event buffer →
GCS task events → `ray.timeline()` / the state API) fused with
OpenTelemetry-style context propagation.  Four pieces live here:

1. **Trace context** — a compact ``(trace_id, parent_span_id)`` pair of
   hex strings, minted at driver API boundaries (``remote()``, ``put``,
   ``get``, ``generate_many``, pipeline step dispatch) and carried on
   every RPC frame, task spec, seal notify, and transfer pull.  The
   active context is thread-local; every span stamps it, so a span
   recorded in a worker three hops away still lands in the caller's
   trace.
2. **The one span recorder** — :func:`span` and :func:`record` (for a
   span whose start was stamped earlier), on the span clock
   ``time.perf_counter()``; ``util.tracing.span`` and
   ``_private.profiling.record_span`` are callers.  A per-call,
   per-step or per-request span is recorded when :func:`on` says so:
   the ``tracing_enabled`` flag, or a ``jax.profiler`` trace running in
   this process, in which the span is also a ``TraceAnnotation`` on a
   host line of the ``.xplane.pb``, on the device's clock.  A lifecycle
   span (``_lifecycle=True``: a site that runs at most a few dozen
   times in a process's life, such as ``runtime.init`` or a program's
   first compile) is recorded always.  One process-wide
   :class:`SpanRing` collects every completed span; off, :func:`span`
   is one shared no-op.
3. **Flush path** — ``flush(transport)`` drains the ring into a
   ``span_batch`` one-way request to the head.  A worker sends one on a
   cadence (:func:`flush_due`: a task's end asks, and sends when the
   ring is half full, holds a lifecycle span, or has waited
   ``FLUSH_PERIOD_S``), on the node-stats period, before a task under
   the flag, and as it leaves; node agents relay their ring inside
   ``node_stats`` frames, the head drains its own ring in-process.  The
   head keeps batches in a byte-budgeted TraceStore, which
   :func:`session_spans` still reads after ``ray_tpu.shutdown()``.
4. **Flight recorder** — the same rings double as the crash black box:
   see :mod:`ray_tpu.observability.flight_recorder`.

Everything here must be safe to import during bootstrap (no jax, no
eager config reads at module scope) and free when nothing records: the
fast path out of every function is two boolean checks.
"""
from __future__ import annotations

import contextlib
import os
import random
import sys
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional, Tuple

TraceContext = Tuple[str, str]  # (trace_id, span_id) — both 16-char hex

_tl = threading.local()
_proc_label: Optional[str] = None
_node_hex: Optional[str] = None


# The span clock is time.perf_counter(); a span's wall-clock position is
# that plus this offset, taken once per process.
_WALL_OFFSET = time.time() - time.perf_counter()


def enabled() -> bool:
    """True when the tracing plane is on (``tracing_enabled`` flag)."""
    from ray_tpu.util.tracing import tracing_enabled

    return tracing_enabled()


def _profile_annotation():
    """``jax.profiler.TraceAnnotation`` while a profile runs in this
    process, else None.  jax is looked up, never imported: head, raylet
    and node agent stay off it."""
    jax = sys.modules.get("jax")
    if jax is None:
        return None
    try:
        ta = jax.profiler.TraceAnnotation
        return ta if ta.is_enabled() else None
    except AttributeError:  # jax half-imported, or a build without it
        return None


def on() -> bool:
    """The rule for whether a per-call, per-step or per-request span is
    recorded: the tracing plane is on, or a ``jax.profiler`` trace is
    running in this process.  Lifecycle spans do not ask."""
    return enabled() or _profile_annotation() is not None


# Span and trace ids come from a generator of this process's own, seeded
# once from the system's: ``os.urandom`` lets go of the interpreter around
# its system call, and a thread that does so at every span it opens waits
# for the interpreter again behind whoever else wanted it (in a serve
# replica, the threads answering a thousand calls a second): the recorder
# then slows the thread it measures.
_ids = random.Random()  # seeds itself from os.urandom


def _reseed_ids() -> None:
    _ids.seed()


if hasattr(os, "register_at_fork"):  # a forked child draws its own ids
    os.register_at_fork(after_in_child=_reseed_ids)


def new_id() -> str:
    return "%016x" % _ids.getrandbits(64)


# ---------------------------------------------------------------------------
# identity: who this process is in the assembled timeline
# ---------------------------------------------------------------------------
def set_identity(proc: str, node: Optional[str] = None) -> None:
    """Label this process's spans (e.g. ``worker:ab12cd34`` on node X).
    Called once from CoreWorker / node agent / head bootstrap."""
    global _proc_label, _node_hex
    _proc_label = proc
    if node is not None:
        _node_hex = node


def identity() -> Tuple[str, Optional[str]]:
    return (_proc_label or f"pid:{os.getpid()}", _node_hex)


# ---------------------------------------------------------------------------
# trace context (thread-local)
# ---------------------------------------------------------------------------
def get_context() -> Optional[TraceContext]:
    """The active (trace_id, span_id) pair, or None."""
    return getattr(_tl, "ctx", None)


def set_context(ctx: Optional[TraceContext]) -> Optional[TraceContext]:
    """Install ``ctx`` as the active context; returns the previous one."""
    old = getattr(_tl, "ctx", None)
    _tl.ctx = ctx
    return old


@contextlib.contextmanager
def use_context(ctx: Optional[TraceContext]):
    old = set_context(ctx)
    try:
        yield ctx
    finally:
        set_context(old)


def mint_context() -> TraceContext:
    """A fresh root context: new trace_id, new root span id."""
    return (new_id(), new_id())


def clear_context() -> None:
    """Drop this thread's active context, at session boundaries: one
    that ``ensure_context`` installed must not outlive its session, or
    every later operation here joins one stale, rootless trace."""
    _tl.ctx = None


def ensure_context() -> Optional[TraceContext]:
    """Driver API boundary helper: the active context, minting a new
    trace root if none is active.  None while tracing is off."""
    if not enabled():
        return None
    ctx = get_context()
    if ctx is None:
        ctx = mint_context()
        _tl.ctx = ctx
    return ctx


context_for_outbound = ensure_context  # what an outbound spec / frame carries


# ---------------------------------------------------------------------------
# SpanRing: the shared bounded span buffer
# ---------------------------------------------------------------------------
class SpanRing:
    """Bounded span buffer: drop-oldest with a dropped counter."""

    def __init__(self, capacity: int = 4096):
        self.capacity = max(16, int(capacity))
        self._items: deque = deque(maxlen=self.capacity)
        self._lock = threading.Lock()
        self.dropped_total = 0

    def append(self, item: Dict[str, Any]) -> None:
        with self._lock:
            if len(self._items) >= self.capacity:
                self.dropped_total += 1
            self._items.append(item)

    def drain(self) -> List[Dict[str, Any]]:
        with self._lock:
            out, self._items = list(self._items), deque(maxlen=self.capacity)
            return out

    def snapshot(self) -> List[Dict[str, Any]]:
        with self._lock:
            return list(self._items)

    def __len__(self) -> int:
        with self._lock:
            return len(self._items)


_ring: Optional[SpanRing] = None
_ring_lock = threading.Lock()


def ring() -> SpanRing:
    """The process-wide span ring (lazily sized from config)."""
    global _ring
    if _ring is None:
        with _ring_lock:
            if _ring is None:
                try:
                    from ray_tpu._private.config import CONFIG

                    cap = int(CONFIG.tracing_buffer_size)
                except Exception:
                    cap = 4096
                _ring = SpanRing(cap)
    return _ring


# ---------------------------------------------------------------------------
# recording
# ---------------------------------------------------------------------------
def _append(name, start, end, ctx, parent_id, span_id, args,
            lifecycle: bool = False) -> str:
    """One completed span (wall-clock seconds) into the process ring.
    ``lifecycle``: the next task's end sends it (:func:`flush_due`)."""
    global _lifecycle_held
    trace_id = ctx[0] if ctx else None
    if parent_id is None and ctx is not None:
        parent_id = ctx[1]
    sid = span_id or new_id()
    if parent_id == sid:
        parent_id = None  # a root span is not its own parent
    proc, node = identity()
    ring().append({
        "name": name, "start": start, "end": end,
        "trace_id": trace_id, "span_id": sid, "parent_id": parent_id,
        "proc": proc, "node": node, "os_pid": os.getpid(),
        "args": args,
    })
    if lifecycle:
        _lifecycle_held = True
    return sid


def record(name: str, start: float, end: float,
           ctx: Optional[TraceContext] = None,
           parent_id: Optional[str] = None,
           span_id: Optional[str] = None,
           _lifecycle: bool = False,
           **args) -> Optional[str]:
    """Record one completed span whose ends were stamped on the span
    clock, e.g. a request's queue wait.  ``ctx`` defaults to the active
    context; ``parent_id`` to the context's span id, else to the
    thread's open span.  A profile cannot take a span after the fact:
    it gets a marker of that name where the span ends, with ``dur_us``.
    ``_lifecycle``: as for :func:`span`."""
    ann = _profile_annotation()
    if ann is None and not _lifecycle and not enabled():
        return None
    if ann is not None:
        with ann(name, dur_us=int((end - start) * 1e6), **args):
            pass
    if ctx is None:
        ctx = get_context()
    if ctx is None and parent_id is None:
        parent_id = getattr(_tl, "open", None)
    return _append(name, start + _WALL_OFFSET, end + _WALL_OFFSET, ctx,
                   parent_id, span_id, args, _lifecycle)


def record_instant(name: str, **args) -> Optional[str]:
    """Zero-duration marker span (e.g. ``task.begin`` — flushed before
    execution so a SIGKILLed worker's last act is on record)."""
    now = time.perf_counter()
    return record(name, now, now, **args)


class _NoSpan:
    """What :func:`span` hands back while nothing records."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **args) -> None:
        pass

    def cancel(self) -> None:
        pass


NO_SPAN = _NoSpan()


class _Span:
    """An open span.  While open it is the parent of the thread's next
    span and, inside a trace, of anything submitted from the thread."""
    __slots__ = ("name", "args", "span_id", "_ctx", "_ann", "_saved", "_t0")
    _lifecycle = False  # the class's, not a field: see _LifecycleSpan

    def __init__(self, name, args, ctx, ann):
        self.name, self.args, self._ctx = name, args, ctx
        self.span_id = new_id()
        self._ann = ann(name, **args) if ann is not None else None

    def set(self, **args) -> None:
        """Arguments known only when the work is done.  They reach the
        ring, not the profile (an annotation's are fixed as it opens)."""
        self.args.update(args)

    def cancel(self) -> None:
        """Closing still restores the thread's state; nothing is kept."""
        self.name = None

    def __enter__(self):
        active = getattr(_tl, "ctx", None)
        self._saved = (active, getattr(_tl, "open", None))
        if self._ctx is None:
            self._ctx = active or (None, self._saved[1])
        if self._ctx[0] is not None:
            _tl.ctx = (self._ctx[0], self.span_id)
        _tl.open = self.span_id
        if self._ann is not None:
            self._ann.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        dur = time.perf_counter_ns() - self._t0
        if self._ann is not None:
            self._ann.__exit__(*exc)
        _tl.ctx, _tl.open = self._saved
        if self.name is None:
            return False
        start = _WALL_OFFSET + self._t0 * 1e-9
        trace_id, parent_id = self._ctx
        _append(self.name, start, start + dur * 1e-9,
                (trace_id, parent_id) if trace_id is not None else None,
                parent_id, self.span_id, self.args, self._lifecycle)
        return False


class _LifecycleSpan(_Span):
    """A span of a process's set-up: the next task's end sends it."""
    __slots__ = ()
    _lifecycle = True


def span(name: str, _ctx: Optional[TraceContext] = None,
         _lifecycle: bool = False, **args):
    """Context manager around one piece of work.  ``_ctx`` pins it to an
    explicit ``(trace_id, parent_span_id)``; otherwise it joins the
    thread's active context, the enclosing open span its parent.
    ``_lifecycle`` marks a span of a process's set-up, recorded whatever
    :func:`on` says: only for a site that runs at most a few dozen times
    in a process's life (the table of names in docs/OBSERVABILITY.md),
    never one a call, a step, a request, a task or an object."""
    ann = _profile_annotation()
    if ann is None and not _lifecycle and not enabled():
        return NO_SPAN
    return (_LifecycleSpan if _lifecycle else _Span)(name, args, _ctx, ann)


# ---------------------------------------------------------------------------
# flush path
# ---------------------------------------------------------------------------
def drain_spans() -> List[Dict[str, Any]]:
    """Drain the process ring, feeding the drop counter to util.metrics
    (``tracing_spans_dropped_total``) best-effort along the way."""
    r = _ring
    if r is None:
        return []
    spans = r.drain()
    _export_dropped(r)
    return spans


_dropped_exported = 0


def _export_dropped(r: SpanRing) -> None:
    """Publish the drop counter delta through util.metrics.  Off the hot
    path (flush cadence only) and best-effort: no live driver, no KV."""
    global _dropped_exported
    delta = r.dropped_total - _dropped_exported
    if delta <= 0:
        return
    try:
        from ray_tpu.util.metrics import Counter

        Counter("tracing_spans_dropped_total",
                "spans dropped by full ring buffers").inc(delta)
        _dropped_exported += delta
    except Exception:
        pass


# When a task's end sends the ring (docs/OBSERVABILITY.md, "Buffering and
# flush", has the reckoning): at half of the ring's places, so that twice
# the busiest cell's spans a second still fit between two looks; and no
# later than this after the last batch, which is also what a reader of a
# live session waits for a span at most.
FLUSH_RING_SHARE = 0.5
FLUSH_PERIOD_S = 0.5

_lifecycle_held = False  # the ring holds a lifecycle span not yet sent
_last_flush = 0.0        # time.monotonic() of the last batch that left
_dropped_sent = 0        # of the ring's dropped_total, what batches told
_flush_lock = threading.Lock()  # one sender at a time: batches in order


def flush_due() -> bool:
    """Whether a task's end should send the ring now: it holds a
    lifecycle span (set-up is read promptly, and is a few dozen spans a
    process), it is at least half full, or the last batch left more than
    ``FLUSH_PERIOD_S`` ago.  An empty ring costs one length check, as
    before; a ring that a profile keeps filling leaves in batches of
    hundreds, not once a call this process answers."""
    r = _ring
    if r is None:
        return False
    held = len(r)
    return held > 0 and (
        _lifecycle_held or held >= r.capacity * FLUSH_RING_SHARE
        or time.monotonic() - _last_flush > FLUSH_PERIOD_S)


def flush(transport) -> int:
    """Drain the ring and ship the batch to the head as a one-way
    ``span_batch`` request; returns how many spans went.  Goes by what
    the ring holds, not by a flag: spans recorded because a profile ran
    leave the worker too.  The batch says how many spans the ring pushed
    out since the last one, so the head's count of lost spans
    (:func:`session_spans_dropped`) has this process's too."""
    global _lifecycle_held, _last_flush, _dropped_sent
    if _ring is None or not len(_ring):
        return 0
    with _flush_lock:
        _lifecycle_held = False  # before the drain: one set after it stays
        spans = drain_spans()
        if not spans:
            return 0
        dropped = _ring.dropped_total - _dropped_sent
        try:
            transport.request_oneway("span_batch",
                                     {"spans": spans, "dropped": dropped})
        except Exception:
            # Head restarting / conn mid-replace: spans are droppable
            # telemetry, never worth failing the caller for.
            return 0
        _dropped_sent += dropped
        _last_flush = time.monotonic()
        return len(spans)


def flush_worker() -> int:
    """Send whatever this worker's ring holds, now: an actor's tear-down
    calls it (a serve replica's ``drain``: the kill behind it is abrupt).
    0 where this process is no worker."""
    from ray_tpu._private.worker import global_worker as w

    if getattr(w, "mode", None) != "worker":  # None, a driver, local mode
        return 0
    return flush(w.transport)


# The TraceStore of this process's newest head, put here by Head.__init__
# and kept until the next: readable after ray_tpu.shutdown().
_session_store = None


def set_session_store(store) -> None:
    global _session_store
    _session_store = store


def session_spans(name: Optional[str] = None) -> List[Dict[str, Any]]:
    """The spans of the newest session that this process can see: its
    head's store (readable until the next ``ray_tpu.init``) plus its own
    ring, which is all there is where no runtime ran."""
    spans = _session_store.spans() if _session_store is not None else []
    if _ring is not None:
        spans = spans + _ring.snapshot()
    if name is not None:
        spans = [s for s in spans if s["name"] == name]
    return spans


def session_spans_dropped() -> int:
    """How many spans the session :func:`session_spans` reads from lost
    before they could be read: what its head's store refused, what its
    workers' rings pushed out between two batches (each batch says) and
    what this process's ring pushed out.  A reader that adds spans up
    (the phases of set-up) has holes where this is not 0."""
    lost = _session_store.spans_dropped if _session_store is not None else 0
    return lost + (_ring.dropped_total if _ring is not None else 0)


def flight_record(reason: str) -> None:
    """Driver-side trigger: ask the head to snapshot a postmortem bundle
    (gang restarts, MeshGroupError handlers).  No-op unless a flight
    record dir is configured."""
    from ray_tpu.observability.flight_recorder import flight_record_dir

    if flight_record_dir() is None:
        return
    try:
        from ray_tpu._private.worker import global_worker

        if global_worker is None:
            return
        flush(global_worker.transport)
        global_worker.transport.request_oneway(
            "flight_record", {"reason": reason})
    except Exception:
        pass


# ---------------------------------------------------------------------------
# task-spec adoption (executor side)
# ---------------------------------------------------------------------------
def adopt_spec_context(spec) -> Optional[TraceContext]:
    """Install a task spec's carried context as this thread's active
    context for the task's duration; returns the previous context (pass
    it back to :func:`set_context` in the caller's finally)."""
    tc = getattr(spec, "trace_ctx", None)
    return set_context(tuple(tc) if tc else None)
