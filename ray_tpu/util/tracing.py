"""OpenTelemetry tracing integration.

Reference: python/ray/util/tracing/tracing_helper.py — the runtime is
instrumented against the opentelemetry *API* (present in this image);
span data goes wherever the application's TracerProvider sends it, so
wiring an SDK/exporter is the user's call exactly as in the reference
(`ray.init(_tracing_startup_hook=...)`).  Without a provider the API's
no-op tracer makes every span free.

Surface:
- ``enable_tracing()`` / ``tracing_enabled()`` — process-local switch
  (also on via the ``tracing_enabled`` config flag / RAY_TPU_TRACING_ENABLED).
- ``span(name, **attrs)`` — context manager used at the runtime's
  instrumentation points (task submit, task execute, actor calls).
  Each span joins the active distributed trace context
  (ray_tpu.observability) and becomes the active parent for anything
  submitted inside it, so cross-process timelines assemble.
- Spans ALSO land in the process's one span ring (ray_tpu.observability;
  ``pop_local_spans`` drains it) so `ray_tpu.timeline()`-style tooling
  sees them even with no SDK.  Overflow is counted, not silently
  truncated, and the counter is exported as
  ``tracing_spans_dropped_total`` through util.metrics.
"""
from __future__ import annotations

import contextlib
from typing import Any, Dict, List, Optional

from ray_tpu import observability as obs

_enabled: Optional[bool] = None


def enable_tracing():
    global _enabled
    _enabled = True


def disable_tracing():
    global _enabled
    _enabled = False
    # The tracing session's implicit driver context dies with it:
    # obs.ensure_context() installs one on this thread at API boundaries,
    # and a leftover would absorb the next session's spans into a stale
    # rootless trace.
    obs.clear_context()


def tracing_enabled() -> bool:
    global _enabled
    if _enabled is None:
        from ray_tpu._private.config import CONFIG

        _enabled = bool(CONFIG.tracing_enabled)
    return _enabled


def _tracer():
    try:
        from opentelemetry import trace

        return trace.get_tracer("ray_tpu")
    except Exception:
        return None


@contextlib.contextmanager
def span(name: str, **attributes):
    """Instrumentation point: otel span (no-op without a provider) plus
    an ``observability.span`` for timeline tooling.  Joins the active
    trace context, or roots a new trace, and is the active parent for
    nested work while open."""
    if not obs.on():
        yield
        return
    tracer = _tracer()
    otel = (tracer.start_as_current_span(name, attributes=attributes)
            if tracer is not None else contextlib.nullcontext())
    ctx = obs.get_context() or (obs.new_id(), None)
    with obs.span(name, _ctx=ctx, **attributes), otel:
        yield


def pop_local_spans() -> List[Dict[str, Any]]:
    return obs.drain_spans()
