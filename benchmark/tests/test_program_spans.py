"""The seven ``program_span`` readers on a hand-made span list."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import common, program_spans  # noqa: E402


def span(name, start_ms, end_ms, sid, parent=None, **args):
    return {"name": name, "start": 100.0 + start_ms / 1e3,
            "end": 100.0 + end_ms / 1e3, "span_id": sid,
            "parent_id": parent, "args": args}


# Iteration A decodes only: 100 ms, children cover 2 + 90 + 3 ms, and a
# metrics flush that overlaps the emit by 1 ms adds 2 ms: 3 ms are left.
# Iteration B admits one request: 140 ms, and is left out of self time.
# Iteration C decodes only, all of it covered but 1 ms.
SPANS = [
    span("engine.iteration", 0, 100, "A", active=3, pending=0),
    span("engine.decode.dispatch", 1, 3, "a1", "A"),
    span("engine.decode.fetch", 3, 93, "a2", "A"),
    span("engine.emit", 93, 96, "a3", "A", tokens=3),
    span("request.decode", 10, 95, "a4", "a3", request_id=1),
    span("engine.metrics_flush", 95, 98, "a5", "A"),
    span("engine.iteration", 100, 240, "B", active=3, pending=1),
    span("engine.admit", 100, 130, "b0", "B", admitted=1),
    span("request.queued", 60, 101, "b01", "b0", request_id=7),
    span("engine.prefill", 102, 129, "b02", "b0", request_id=7),
    span("engine.decode.dispatch", 131, 135, "b1", "B"),
    span("engine.decode.fetch", 135, 231, "b2", "B"),
    span("engine.emit", 231, 236, "b3", "B", tokens=4),
    span("engine.iteration", 240, 340, "C", active=4, pending=1),
    span("engine.admit", 240, 241, "c0", "C", admitted=0),
    span("engine.decode.dispatch", 241, 244, "c1", "C"),
    span("engine.decode.fetch", 244, 335, "c2", "C"),
    span("engine.emit", 335, 339, "c3", "C", tokens=4),
    span("request.queued", 300, 320, "q2", None, request_id=8),
    span("ingest.produce", 0, 4, "i1", rows=8),
    span("ingest.h2d", 1, 4, "i2", "i1", bytes=32768),
    span("ingest.produce", 200, 210, "i3", rows=8),
    span("ingest.produce", 400, 406, "i4", rows=8),
]

WANT = {"engine_dispatch_ms": 3.0, "engine_fetch_ms": 91.0,
        "engine_emit_ms": 4.0, "engine_self_ms": 2.0,  # median of 3 and 1
        "engine_admit_ms": 30.0, "queue_wait_p50_ms": 30.5,
        "ingest_produce_ms": 6.0}


def fake(spans, monkeypatch):
    monkeypatch.setattr(
        program_spans, "spans",
        lambda name=None: [s for s in spans if name in (None, s["name"])])


@pytest.mark.parametrize("metric", sorted(WANT))
def test_reader_on_hand_made_spans(metric, monkeypatch):
    fake(SPANS, monkeypatch)
    got = common.load_module("layer_metrics", metric).read({}, {})
    assert got == pytest.approx(WANT[metric], abs=1e-6)


@pytest.mark.parametrize("metric", sorted(WANT))
def test_reader_without_spans_returns_none(metric, monkeypatch):
    fake([], monkeypatch)
    assert common.load_module("layer_metrics", metric).read({}, {}) is None


def test_spans_of_a_program_without_the_recorder(monkeypatch):
    """A parent commit has no ``session_spans``: nothing, and no raise."""
    from ray_tpu import observability

    monkeypatch.delattr(observability, "session_spans")
    assert program_spans.spans("engine.iteration") == []
    assert program_spans.median_ms("engine.iteration") is None


def test_every_new_metric_is_in_the_manifest():
    import json

    with open(os.path.join(common.ROOT, "BENCHMARK.json")) as f:
        per_layer = {m["name"]: m for m in json.load(f)["per_layer"]}
    for metric in WANT:
        assert per_layer[metric]["source"] == "program_span"
