"""The serve loop thread's step, part by part, for the per-layer readers of
the engine's own account (``ray_tpu/serve/llm_engine.py::_iteration`` names
the spans): what an ``engine.iteration`` is made of, what lies between two of
them, how much of one the thread was not on a CPU, and the handle calls the
process answered meanwhile.

As ``engine_self_ms`` does, the readers leave out an iteration in which a
request got its slot: an admission's prefill is ``engine_admit_ms``'s, and
the step that carries one is not the steady step.  A program without these
spans or arguments (an older commit) gives None, as ``program_spans``'s
readers do.
"""
import statistics

from benchmark import program_spans
from benchmark.program_spans import ms

DISPATCH = "engine.decode.dispatch"
BOOK = ("engine.grow", "engine.decode.prepare", "engine.decode.settle")


def tree():
    """``(iterations, children)``: the window's ``engine.iteration`` spans
    that admitted nothing, and every span's children by its ``span_id``."""
    children = {}
    for s in program_spans.spans(None):
        children.setdefault(s.get("parent_id"), []).append(s)
    kept = [it for it in program_spans.spans("engine.iteration")
            if not any(c["name"] == "engine.admit" and program_spans.admitted(c)
                       for c in children.get(it["span_id"], []))]
    return kept, children


def median(values):
    values = list(values)
    return statistics.median(values) if values else None


def dispatch_part_ms(name: str):
    """Median length of the spans ``name`` directly under an
    ``engine.decode.dispatch`` of a kept iteration."""
    kept, children = tree()
    return median(
        ms(part)
        for it in kept
        for d in children.get(it["span_id"], []) if d["name"] == DISPATCH
        for part in children.get(d["span_id"], []) if part["name"] == name)


def book_ms():
    """Median over the kept iterations of their bookkeeping spans' sum."""
    kept, children = tree()
    sums = []
    for it in kept:
        mine = [ms(c) for c in children.get(it["span_id"], [])
                if c["name"] in BOOK]
        if mine:
            sums.append(sum(mine))
    return median(sums)


def offcpu_ms():
    """Median over the kept iterations of wall time less the thread's CPU
    time (``cpu_ms``) less the wait that is meant (``engine.decode.fetch``),
    not below 0: the two clocks' grain can put CPU time above wall time."""
    kept, children = tree()
    left = []
    for it in kept:
        cpu = (it.get("args") or {}).get("cpu_ms")
        if cpu is None:
            continue
        fetch = sum(ms(c) for c in children.get(it["span_id"], [])
                    if c["name"] == "engine.decode.fetch")
        left.append(max(0.0, ms(it) - cpu - fetch))
    return median(left)


def turnaround_ms():
    """Median stretch from one iteration's end to the next one's start in
    the same process (``flow.Stage``'s hand-back through ``_tick_source``,
    ``_nothing_to_do``), a pair with an ``engine.idle`` between left out."""
    by_pid = {}
    for s in program_spans.spans(None):
        if s["name"] in ("engine.iteration", "engine.idle"):
            by_pid.setdefault(s.get("os_pid"), []).append(s)
    gaps = []
    for mine in by_pid.values():
        mine.sort(key=lambda s: s["start"])
        for a, b in zip(mine, mine[1:]):
            if a["name"] == b["name"] == "engine.iteration":
                gaps.append(max(0.0, (b["start"] - a["end"]) * 1e3))
    return median(gaps)


def reply_calls_per_step():
    """Handle calls the process answered over the window's iterations
    (``reply_calls``, every iteration's), over the iterations that
    dispatched a step."""
    spans = program_spans.spans("engine.iteration")
    calls = [(s.get("args") or {}).get("reply_calls") for s in spans]
    calls = [c for c in calls if c is not None]
    parents = {s.get("parent_id") for s in program_spans.spans(DISPATCH)}
    steps = sum(1 for s in spans if s["span_id"] in parents)
    return sum(calls) / steps if calls and steps else None
