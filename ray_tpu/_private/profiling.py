"""Chrome-trace timeline export (reference: ray.timeline() →
chrome_tracing_dump, python/ray/_private/profiling.py:43 over core-worker
profile events, src/ray/core_worker/profile_event.h) plus ``record_span``,
the call that driver-side hot paths use (pipeline dispatch and drain spans
from ray_tpu.parallel.mesh_group.StepPipeline, flow stages, checkpoints).

``record_span`` is a caller of the one recorder, ray_tpu.observability:
same ring, clock and rule for when a span is recorded (``on()``).  It
stamps the active (or passed) trace context, so the ``mpmd_stage_*`` /
``rollout_*`` / ``flow_*`` / ``replay_*`` families assemble into
cross-process traces.  Readers (tools/perf_smoke.py, tests) turn tracing
on and pull spans with ``recorded_spans``.
"""
from __future__ import annotations

from typing import List, Optional

from ray_tpu import observability as obs


def record_span(name: str, start: float, end: float,
                _trace_ctx=None, _root=False, **args) -> None:
    """Record one completed span (timestamps from time.perf_counter()).
    Never raises.  ``_trace_ctx`` pins the span to an explicit
    (trace_id, parent_span_id) pair for emitters that run off the
    submitting thread (flow stage workers); otherwise the thread's
    active context is stamped.  ``_root=True`` records the span AS the
    context's root (span_id = ctx[1]) — the mint point uses it once per
    trace so children parented to the root id resolve to a real span
    and cross-process flow arrows have an anchor."""
    try:
        sid = _trace_ctx[1] if _root and _trace_ctx is not None else None
        obs.record(name, float(start), float(end), ctx=_trace_ctx,
                   span_id=sid, **args)
    except Exception:
        pass


def recorded_spans(name: Optional[str] = None,
                   clear: bool = False) -> List[dict]:
    """This process's recorded spans (optionally filtered by name),
    oldest first; ``clear`` drains the ring."""
    spans = obs.drain_spans() if clear else obs.ring().snapshot()
    if name is not None:
        spans = [s for s in spans if s["name"] == name]
    return spans


def clear_recorded_spans() -> None:
    obs.drain_spans()


def chrome_tracing_dump(task_events: List[dict],
                        filename: Optional[str] = None,
                        spans: Optional[List[dict]] = None) -> List[dict]:
    """Convert the state API's task list into chrome://tracing events.

    ``spans`` (TraceStore records, or ``recorded_spans()``: one format)
    merge in with per-node pid lanes, per-process tid lanes, and
    cross-process flow arrows — see ray_tpu.observability.timeline."""
    from ray_tpu.observability.timeline import build_chrome_trace

    return build_chrome_trace(task_events, spans or [], filename)
