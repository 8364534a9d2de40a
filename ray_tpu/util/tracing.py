"""The tracing switch and the runtime's instrumentation point.

Reference: python/ray/util/tracing/tracing_helper.py.  Spans go to the
one recorder, ray_tpu.observability, and nowhere else.

Surface:
- ``enable_tracing()`` / ``tracing_enabled()`` — process-local switch
  (also on via the ``tracing_enabled`` config flag / RAY_TPU_TRACING_ENABLED).
- ``span(name, **attrs)`` — context manager used at the runtime's
  instrumentation points (task submit, task execute, actor calls).
  Each span joins the active distributed trace context
  (ray_tpu.observability) and becomes the active parent for anything
  submitted inside it, so cross-process timelines assemble.
- Spans land in the process's one span ring (ray_tpu.observability;
  ``pop_local_spans`` drains it), which `ray_tpu.timeline()`-style
  tooling reads.  Overflow is counted, not silently truncated, and the
  counter is exported as ``tracing_spans_dropped_total`` through
  util.metrics.
"""
from __future__ import annotations

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


def span(name: str, **attributes):
    """Instrumentation point: an ``observability.span`` that joins the
    active trace context, or roots a new trace, and is the active parent
    for nested work while open."""
    if not obs.on():
        return obs.NO_SPAN
    ctx = obs.get_context() or (obs.new_id(), None)
    return obs.span(name, _ctx=ctx, **attributes)


def pop_local_spans() -> List[Dict[str, Any]]:
    return obs.drain_spans()
