"""The six readers of the *set-up* layer on hand-made lifecycle spans: the
five ``_s`` metrics are a partition of ``[T_PROCESS, window_start]``."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import common, program_spans, setup_phases  # noqa: E402

T0, SETUP_S = 1000.0, 100.0
RECORD = {"window_start": T0 + SETUP_S, "end_to_end": {"setup_s": SETUP_S}}
SECONDS = ("setup_runtime_s", "setup_placement_s", "setup_model_s",
           "setup_compile_s", "setup_unseen_s")
ALL = SECONDS + ("compile_cache_hit_share",)


def span(name, start, end, proc="driver", **args):
    return {"name": name, "start": T0 + start, "end": T0 + end,
            "proc": proc, "args": args}


# A serve cell's set-up, seconds after T_PROCESS.  Unseen: [0, 2] before
# init, [40, 60] the warm-up's runs between two compiles, [66, 100] the
# pre-roll, less the late compile that the window's edge cuts at 100.
SPANS = [
    span("runtime.init", 2, 3, mode="head"),
    span("serve.deploy", 3, 30, deployment="llm", replicas=1),
    span("runtime.worker_start", 3, 5, proc="head", worker_id="w"),
    span("serve.replica_init", 5, 30, proc="worker", deployment="llm"),
    span("model.build", 6, 20, proc="worker", model_kind="gpt2"),
    # two eager compiles inside the build, on two threads, 1 s overlapping
    span("jax.compile", 8, 11, proc="worker", event="compile", seconds=3.0),
    span("jax.compile", 10, 12, proc="worker", event="cache_hit",
         seconds=2.0),
    span("engine.init", 20, 29, proc="worker", slots=4),
    span("engine.compile", 30, 40, proc="worker", program="llm_prefill_16"),
    span("jax.compile", 31, 39, proc="worker", event="compile", seconds=8.0),
    span("engine.compile", 60, 66, proc="worker", program="llm_decode"),
    span("jax.compile", 61, 65, proc="worker", event="cache_hit",
         seconds=4.0),
    # opens before the window and ends inside it: cut at window_start
    span("engine.compile", 97, 104, proc="worker", program="llm_prefill_32"),
    span("jax.compile", 98, 103, proc="worker", event="compile",
         seconds=5.0),
    # a per-step span of the traced window: not a lifecycle span
    span("engine.iteration", 101, 102, proc="worker"),
]
WANT = {
    "setup_runtime_s": 1.0,            # init; the worker's start lies in deploy
    "setup_placement_s": 27.0 - 23.0,  # deploy, less build and engine.init
    "setup_model_s": 23.0 - 4.0,       # less the union of the two compiles
    "setup_compile_s": 4.0 + 10.0 + 6.0 + 3.0,
    "setup_unseen_s": 2.0 + 20.0 + 31.0,
    # the fifth compile ends after window_start and is not counted
    "compile_cache_hit_share": 50.0,
}


def fake(spans, monkeypatch, dropped=0):
    from ray_tpu import observability

    monkeypatch.setattr(
        program_spans, "spans",
        lambda name=None: [s for s in spans if name in (None, s["name"])])
    monkeypatch.setattr(observability, "session_spans_dropped",
                        lambda: dropped, raising=False)


def read(metric):
    return common.load_module("layer_metrics", metric).read(RECORD, {})


@pytest.mark.parametrize("metric", ALL)
def test_reader_on_hand_made_spans(metric, monkeypatch):
    fake(SPANS, monkeypatch)
    assert read(metric) == pytest.approx(WANT[metric], abs=1e-9)


def test_the_five_phases_add_up_to_setup_s(monkeypatch):
    fake(SPANS, monkeypatch)
    assert sum(read(m) for m in SECONDS) == pytest.approx(SETUP_S, abs=1e-9)


def test_side_by_side_compiles_count_once():
    two = [span("jax.compile", 10, 40, proc="w", event="compile"),
           span("jax.compile", 20, 50, proc="w", event="compile")]
    got = setup_phases.partition(two, T0, T0 + SETUP_S)
    assert got["compile"] == pytest.approx(40.0)
    assert got["unseen"] == pytest.approx(60.0)


def test_a_deeper_span_takes_its_seconds_from_the_one_around_it():
    nest = [span("train.worker_group_start", 0, 50, workers=1),
            span("runtime.worker_start", 1, 6, proc="head"),
            span("train.rendezvous", 10, 40, world=1),
            span("train.compile", 60, 90, proc="w", program="step"),
            span("jax.compile", 62, 80, proc="w", event="compile")]
    got = setup_phases.partition(nest, T0, T0 + SETUP_S)
    assert got == pytest.approx({"runtime": 0.0, "placement": 50.0,
                                 "model": 0.0, "compile": 30.0,
                                 "unseen": 20.0})


def test_spans_are_cut_at_both_ends_of_set_up():
    out = [span("runtime.init", -5, 5), span("jax.compile", 95, 130,
                                             event="compile")]
    got = setup_phases.partition(out, T0, T0 + SETUP_S)
    assert got["runtime"] == pytest.approx(5.0)
    assert got["compile"] == pytest.approx(5.0)
    assert sum(got.values()) == pytest.approx(SETUP_S)


@pytest.mark.parametrize("metric", ALL)
def test_no_lifecycle_span_gives_none(metric, monkeypatch):
    """The parent commit in a traced run: spans of the window, none of
    set-up."""
    fake([s for s in SPANS if s["name"] == "engine.iteration"], monkeypatch)
    assert read(metric) is None


@pytest.mark.parametrize("metric", ALL)
def test_a_session_that_dropped_spans_gives_none(metric, monkeypatch):
    fake(SPANS, monkeypatch, dropped=3)
    assert read(metric) is None


def test_a_program_that_cannot_say_what_it_dropped(monkeypatch):
    """Lifecycle spans but no ``session_spans_dropped``: read as none lost."""
    from ray_tpu import observability

    fake(SPANS, monkeypatch)
    monkeypatch.delattr(observability, "session_spans_dropped")
    assert read("setup_model_s") == pytest.approx(WANT["setup_model_s"])


def test_the_stores_own_drop_count_reaches_the_readers(monkeypatch):
    """Through the program's own ``session_spans_dropped``: a store whose
    budget refused a span."""
    from ray_tpu import observability
    from ray_tpu.observability.trace_store import TraceStore

    store = TraceStore(per_trace_bytes=400)
    store.ingest([dict(s, trace_id=None) for s in SPANS[:4]])
    assert store.spans_dropped > 0
    monkeypatch.setattr(observability, "_session_store", store)
    assert observability.session_spans_dropped() >= store.spans_dropped
    assert read("setup_unseen_s") is None


def test_every_set_up_metric_is_in_the_manifest_with_a_reader():
    import json

    with open(os.path.join(common.ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    mine = {m["name"]: m for m in manifest["per_layer"]
            if m["layer"] == "set-up"}
    assert set(mine) == set(ALL)
    cells = [w["name"] for w in manifest["workloads"]]
    for name, m in mine.items():
        assert (m["source"], m["moves"]) == ("program_span", "setup_s")
        assert set(m["workloads"]) <= set(cells)
        assert common.load_module("layer_metrics", name) is not None
    assert "ppo_atari84_anakin" not in mine["setup_runtime_s"]["workloads"]
    assert sorted(mine["setup_unseen_s"]["workloads"]) == sorted(cells)
