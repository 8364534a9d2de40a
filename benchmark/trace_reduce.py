"""From a profiler trace to numbers: device busy and idle time, time per
operation and per program, exposed collective time, and the idle gaps named
by the host span that covered them.

``events_from_xplane`` reads the ``.xplane.pb`` the JAX profiler writes, with
nothing but JAX.  ``reduce`` works on plain tuples
``(plane, line, name, start_ns, dur_ns)``, so it can be checked on a small
recorded list (``tests/trace_small.json``).

How a TPU trace is laid out (looked at by hand, TPU v5 lite, jax 0.9.0):
one plane ``/device:TPU:<n>`` per chip, with the lines ``XLA Modules`` (one
event per run of a compiled program, named ``jit_<fn>(<fingerprint>)``),
``XLA Ops`` (one event per operation inside it, named by its whole HLO
instruction, ``%fusion.5 = bf16[12,1024]{...} fusion(...)``; a Pallas kernel
is a ``custom-call`` with ``custom_call_target="tpu_custom_call"``), ``Async
XLA Ops`` (copies and collectives in flight, overlapping the former) and
``Steps``; the host's threads are lines of the plane ``/host:CPU``, where
``TraceAnnotation`` spans appear under their own names, on the same clock.
"""
from __future__ import annotations

import bisect
import re

OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench:window"  # common.TracedWindow writes it
_COLLECTIVE = re.compile(
    r"all-reduce|reduce-scatter|all-gather|all-to-all|collective-permute")


def events_from_xplane(path: str, host_names=None) -> list:
    """The device's operations, programs and copies, and the host's events;
    with ``host_names``, of the host's only those so named (``reduce`` reads
    no other, and a busy engine's host plane holds millions)."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith("/device:")
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, ASYNC_LINE,
                                            MODULES_LINE):
                continue
            for ev in line.events:
                name = ev.name
                if device or host_names is None or name in host_names:
                    out.append((plane.name, line.name, name,
                                int(ev.start_ns), int(ev.duration_ns)))
    return out


def union(intervals: list) -> list:
    """Sorted, disjoint intervals covering the same points."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1][1] = b
        elif b > a:
            out.append([a, b])
    return out


def total(intervals: list) -> int:
    return sum(b - a for a, b in intervals)


def clip(intervals: list, lo: int, hi: int) -> list:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def subtract(intervals: list, holes: list) -> list:
    """The parts of disjoint sorted ``intervals`` that no hole covers."""
    out, holes, j = [], union(holes), 0
    for a, b in intervals:
        while j < len(holes) and holes[j][1] <= a:
            j += 1
        k, cur = j, a
        while k < len(holes) and holes[k][0] < b:
            if holes[k][0] > cur:
                out.append((cur, holes[k][0]))
            cur = max(cur, holes[k][1])
            k += 1
        if cur < b:
            out.append((cur, b))
    return out


def covered(intervals: list, ends: list, a: int, b: int) -> int:
    """How much of [a, b] the disjoint sorted ``intervals`` cover; ``ends``
    is the list of their ends, for the bisection that finds the first one
    reaching past ``a``.  A trace of a busy engine has some 10^5 idle gaps
    and 10^4 intervals of a span: clipping every interval for every gap took
    a quarter of an hour, this takes seconds."""
    got, i = 0, bisect.bisect_right(ends, a)
    while i < len(intervals) and intervals[i][0] < b:
        got += min(intervals[i][1], b) - max(intervals[i][0], a)
        i += 1
    return got


def is_collective(name: str) -> bool:
    """By the instruction's own name, not by what it consumes."""
    return bool(_COLLECTIVE.search(name.split(" = ")[0]))


def op_key(name: str) -> str:
    """What an operation is, without the counters XLA appends, so that the
    same operation of every layer adds up: the instruction's name stem (or a
    custom call's target) and the shape of its first result.
    ``%fusion.5.remat = (f32[12,1023]{1,0}, ...) fusion(...)`` ->
    ``fusion.remat f32[12,1023]``."""
    head, _, rest = name.partition(" = ")
    stem = re.sub(r"\.\d+", "", head.lstrip("%"))
    target = re.search(r'custom_call_target="([^"]+)"', rest)
    shape = re.search(r"[a-z]+\d+\[[\d,]*\]", rest)
    stem = target.group(1) if target else stem
    return f"{stem} {shape.group(0)}" if shape else stem


def program_key(name: str) -> str:
    """``jit_step(1234567)`` -> ``jit_step``."""
    return name.split("(")[0]


def reduce(events: list, span_names=()) -> dict:
    """All times in seconds.  ``busy_s`` is the mean over the device planes of
    the union of their operations' intervals inside the window; the window is
    the ``bench:window`` host span where there is one, and otherwise runs from
    the first to the last device event."""
    planes = sorted({e[0] for e in events if e[0].startswith("/device:")})
    window = [(e[3], e[3] + e[4]) for e in events if e[2] == WINDOW_SPAN]
    dev = [e for e in events if e[0].startswith("/device:")
           and e[1] in (OPS_LINE, ASYNC_LINE, MODULES_LINE)]
    if not dev:
        return {}
    sync = [e for e in dev if e[1] != ASYNC_LINE]
    lo, hi = (window[0] if window else
              (min(e[3] for e in sync), max(e[3] + e[4] for e in sync)))
    spans = {n: union([(e[3], e[3] + e[4]) for e in events
                       if not e[0].startswith("/device:") and e[2] == n])
             for n in span_names}
    span_ends = {n: [b for _, b in iv] for n, iv in spans.items()}

    busy, exposed, ops, programs, gaps = [], [], {}, {}, {}
    for p in planes:
        mine = [e for e in dev if e[0] == p]
        op_iv = clip([(e[3], e[3] + e[4]) for e in mine
                      if e[1] == OPS_LINE], lo, hi)
        if not op_iv:  # a plane without an operations line: use programs
            op_iv = clip([(e[3], e[3] + e[4]) for e in mine], lo, hi)
        u = union(op_iv)
        busy.append(total(u))
        coll = union(clip([(e[3], e[3] + e[4]) for e in mine
                           if e[1] != MODULES_LINE and is_collective(e[2])],
                          lo, hi))
        compute = [(e[3], e[3] + e[4]) for e in mine
                   if e[1] == OPS_LINE and not is_collective(e[2])]
        exposed.append(total(subtract(coll, compute)))
        if p != planes[0]:
            continue
        inside = [e for e in mine if e[3] + e[4] > lo and e[3] < hi]
        for e in inside:
            if e[1] == MODULES_LINE:
                programs.setdefault(program_key(e[2]), []).append(e[4] / 1e9)
        # Self time: a ``while`` or a ``conditional`` is an event around the
        # events of its body, and its own time is what they leave.
        stack = []
        for e in sorted((e for e in inside if e[1] == OPS_LINE),
                        key=lambda e: (e[3], -e[4])):
            while stack and stack[-1][0] <= e[3]:
                stack.pop()
            key = op_key(e[2])
            ops[key] = ops.get(key, 0) + e[4]
            if stack:
                ops[stack[-1][1]] -= e[4]
            stack.append((e[3] + e[4], key))
        for a, b in subtract([(lo, hi)], u):
            best, cover = "none", 0
            for n, iv in spans.items():
                c = covered(iv, span_ends[n], a, b)
                if c > cover:
                    best, cover = n, c
            gaps[best] = gaps.get(best, 0) + (b - a)

    def top(d):
        return [[k, v / 1e9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:10]]

    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy) / len(busy) / 1e9,
        "devices": len(planes),
        "collective_exposed_s": sum(exposed) / len(exposed) / 1e9,
        "op_s": {k: v / 1e9 for k, v in ops.items()},
        "program_s": programs,
        "device_ops": top(ops),
        "idle_gaps": top(gaps),
    }
