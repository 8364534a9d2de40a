"""The seven readers of the serve loop thread's account (``step_account``)
on a hand-made span list: a plain iteration, one that admitted (left out),
two with an ``engine.idle`` between them (no turnaround counted), one whose
CPU time exceeds its wall time by the clocks' grain, and a program without
the new spans."""
import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import common, program_spans  # noqa: E402


def span(name, start_ms, end_ms, sid, parent=None, pid=7, **args):
    return {"name": name, "start": 100.0 + start_ms / 1e3,
            "end": 100.0 + end_ms / 1e3, "span_id": sid,
            "parent_id": parent, "os_pid": pid, "args": args}


def iteration(key, at, *, stage, call, readback, grow=0.1, prepare=0.2,
              settle=(0.3, 0.2), fetch=4.0, emit=0.5, cpu_ms, calls,
              admitted=None, length=10.0):
    """An ``engine.iteration`` of ``length`` ms from ``at`` with its
    children laid end to end, 0.1 ms after the iteration opens."""
    out = [span("engine.iteration", at, at + length, key, active=3,
                pending=0, cpu_ms=cpu_ms, reply_calls=calls)]
    t = at + 0.1

    def child(name, took, sid, parent=key, **args):
        nonlocal t
        out.append(span(name, t, t + took, key + sid, parent, **args))
        t += took

    if admitted is not None:
        child("engine.admit", 2.0, "a", admitted=admitted)
    child("engine.grow", grow, "g", pages=0)
    child("engine.decode.prepare", prepare, "p")
    d0 = t
    child("engine.decode.stage", stage, "ds", key + "d", uploads=1,
          upload_bytes=64)
    child("engine.decode.call", call, "dc", key + "d")
    child("engine.decode.readback", readback, "dr", key + "d", arrays=2)
    out.append(span("engine.decode.dispatch", d0, t, key + "d", key))
    child("engine.decode.settle", settle[0], "s1")
    child("engine.decode.fetch", fetch, "f")
    child("engine.decode.settle", settle[1], "s2")
    child("engine.emit", emit, "e", tokens=3)
    assert t <= at + length
    return out


# A: plain.  10 ms long, 5.0 on the CPU, 4.0 in the fetch: 1.0 off it.
# B: admitted a request: left out of every median but the turnaround's,
#    and its calls and its step still count for the calls a step.
# C: CPU time 6.2 where wall less fetch is 6.0: off-CPU 0, not -0.2.
# then an engine.idle, then D, like A but with 3.0 off the CPU.
# Turnarounds: A->B 0.4, B->C 0.6; C->idle->D not counted.
SPANS = (
    iteration("A", 0.0, stage=0.6, call=1.0, readback=0.1, cpu_ms=5.0,
              calls=8)
    + iteration("B", 10.4, stage=0.9, call=3.0, readback=0.3, cpu_ms=9.0,
                calls=20, admitted=1, length=14.0)
    + iteration("C", 25.0, stage=0.2, call=2.0, readback=0.3, cpu_ms=6.2,
                calls=2, admitted=0, settle=(0.5, 0.4), emit=0.1)
    + [span("engine.idle", 35.1, 80.0, "idle")]
    + iteration("D", 80.2, stage=0.4, call=1.4, readback=0.2, cpu_ms=3.0,
                calls=10, grow=0.3)
    + [span("engine.iteration", 0.0, 5.0, "X", pid=8, active=0, pending=0,
            cpu_ms=4.0, reply_calls=0)]  # another process: no pair with A
)

WANT = {
    "engine_stage_ms": 0.4,        # of 0.6, 0.2, 0.4
    "engine_call_ms": 1.4,         # of 1.0, 2.0, 1.4
    "engine_readback_ms": 0.2,     # of 0.1, 0.3, 0.2
    "engine_book_ms": 1.0,         # of 0.8 (A), 1.4 (C), 1.0 (D)
    "engine_turnaround_ms": 0.5,   # of 0.4 and 0.6
    "engine_offcpu_ms": 1.0,       # of 1.0 (A), 0.0 (C), 3.0 (D), 1.0 (X)
    "reply_calls_per_step": 10.0,  # 8 + 20 + 2 + 10 + 0 over 4 steps
}


def fake(spans, monkeypatch):
    monkeypatch.setattr(
        program_spans, "spans",
        lambda name=None: [s for s in spans if name in (None, s["name"])])


def read(metric):
    return common.load_module("layer_metrics", metric).read({}, {})


@pytest.mark.parametrize("metric", sorted(WANT))
def test_reader_on_hand_made_spans(metric, monkeypatch):
    fake(SPANS, monkeypatch)
    assert read(metric) == pytest.approx(WANT[metric], abs=1e-6)


def test_cpu_time_above_wall_time_reads_zero_not_less(monkeypatch):
    fake([s for s in SPANS if s["span_id"].startswith("C")], monkeypatch)
    assert read("engine_offcpu_ms") == 0.0


def test_an_idle_between_two_iterations_is_no_turnaround(monkeypatch):
    fake([s for s in SPANS if s["span_id"][0] in "CDi"], monkeypatch)
    assert read("engine_turnaround_ms") is None


def test_an_iteration_that_admitted_is_left_out(monkeypatch):
    fake([s for s in SPANS if s["span_id"].startswith("B")], monkeypatch)
    for metric in ("engine_stage_ms", "engine_call_ms", "engine_readback_ms",
                   "engine_book_ms", "engine_offcpu_ms"):
        assert read(metric) is None
    assert read("reply_calls_per_step") == 20.0


@pytest.mark.parametrize("metric", sorted(WANT))
def test_a_program_without_spans_reads_none(metric, monkeypatch):
    fake([], monkeypatch)
    assert read(metric) is None


def test_a_program_without_the_new_spans(monkeypatch):
    """The parent commit's iterations, with the children and arguments it
    had: nothing of the account but the stretch between two iterations,
    which is read from the ends of spans that it has too."""
    from benchmark.tests.test_program_spans import SPANS as OLD

    fake([dict(s, os_pid=7) for s in OLD], monkeypatch)
    for metric in sorted(set(WANT) - {"engine_turnaround_ms"}):
        assert read(metric) is None
    assert read("engine_turnaround_ms") == 0.0  # they lie end to end


def test_the_seven_are_in_the_manifest_as_the_dispatch_metric_is():
    with open(os.path.join(common.ROOT, "BENCHMARK.json")) as f:
        per_layer = json.load(f)["per_layer"]
    by_name = {m["name"]: m for m in per_layer}
    assert len(per_layer) == 70
    model = by_name["engine_dispatch_ms"]
    assert [m["name"] for m in per_layer[-7:]] == [
        "engine_stage_ms", "engine_call_ms", "engine_readback_ms",
        "engine_book_ms", "engine_turnaround_ms", "engine_offcpu_ms",
        "reply_calls_per_step"]
    for m in per_layer[-7:]:
        want = dict(model, name=m["name"])
        if m["name"] == "reply_calls_per_step":
            want.update(unit="calls", source="program_counter")
        assert m == want
