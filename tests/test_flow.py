"""The async dataflow substrate (ray_tpu.parallel.flow): backpressure by
construction, fan-in ordering modes, typed error propagation, cooperative
cancellation/drain, observability — plus the streaming Dataset execution
built on it (byte-identity vs the eager engine, windowed residency) and
the decorrelated random_shuffle fix."""
import threading
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu.parallel.flow import (
    CancellationToken,
    FlowCancelled,
    RefStream,
    Stage,
    Window,
    chain_stages,
)

MB = 1024 * 1024


def _wait(pred, timeout=5.0):
    deadline = time.monotonic() + timeout
    while not pred() and time.monotonic() < deadline:
        time.sleep(0.02)
    return pred()


# ---------------------------------------------------------------------------
# CancellationToken / Window
# ---------------------------------------------------------------------------

def test_cancellation_token_callbacks_and_children():
    root = CancellationToken()
    child = root.child()
    fired = []
    child.on_cancel(lambda: fired.append("child"))
    root.on_cancel(lambda: fired.append("root"))
    assert not root.cancelled and not child.cancelled
    root.cancel()
    assert root.cancelled and child.cancelled
    assert set(fired) == {"root", "child"}
    # Late registration on a cancelled token fires immediately; cancel is
    # idempotent.
    child.on_cancel(lambda: fired.append("late"))
    root.cancel()
    assert "late" in fired and fired.count("root") == 1
    with pytest.raises(FlowCancelled):
        root.raise_if_cancelled()


def test_child_cancel_does_not_cancel_parent():
    root = CancellationToken()
    child = root.child()
    child.cancel()
    assert child.cancelled and not root.cancelled


def test_window_bound_semantics():
    w = Window(2)
    assert not w.full
    w.append("a")
    w.append("b")
    assert w.full and not w.over_depth
    w.append("c")
    assert w.over_depth and len(w) == 3
    assert w.popleft() == "a"
    assert w.clear() == ["b", "c"] and not w
    with pytest.raises(ValueError):
        Window(0)


# ---------------------------------------------------------------------------
# Stage: backpressure, ordering, errors, lifecycle
# ---------------------------------------------------------------------------

def test_backpressure_bound_held_under_slow_consumer():
    """A fast producer against a slow consumer: the stage never
    materializes more than depth finished + workers in-progress items
    ahead of the consumer — backpressure by construction, not cooperation."""
    depth, workers = 2, 1
    started = []
    lock = threading.Lock()

    def work(i):
        with lock:
            started.append(i)
        return i

    stage = Stage(iter(range(50)), work, depth=depth, workers=workers,
                  name="bp", export_metrics=False)
    overshoot = []
    out = []
    for item in stage:
        time.sleep(0.01)  # slow consumer
        out.append(item)
        with lock:
            overshoot.append(len(started) - len(out))
    assert out == list(range(50))
    # items in flight beyond the consumer = queue (depth) + in-fn
    # (workers) + the one just handed over.
    assert max(overshoot) <= depth + workers + 1, max(overshoot)
    assert stage.peak_occupancy <= depth


def test_fan_in_ordered_mode_restores_source_order():
    def work(i):
        time.sleep(0.03 if i % 3 == 0 else 0.0)  # jumble completion
        return i * 10

    stage = Stage(iter(range(12)), work, depth=4, workers=4, ordered=True,
                  name="ordered", export_metrics=False)
    assert list(stage) == [i * 10 for i in range(12)]


def test_fan_in_completion_mode_yields_as_completed():
    release = threading.Event()

    def work(i):
        if i == 0:
            release.wait(5.0)  # item 0 finishes LAST
        return i

    stage = Stage(iter(range(4)), work, depth=4, workers=4, ordered=False,
                  name="completed", export_metrics=False)
    first = next(stage)
    release.set()
    rest = list(stage)
    assert first != 0, "completion order ignored"
    assert sorted([first] + rest) == list(range(4))


def test_source_error_reaches_consumer_typed():
    def bad_source():
        yield 1
        yield 2
        raise ValueError("reader exploded")

    stage = Stage(bad_source(), lambda x: x * 2, depth=2, name="src-err",
                  export_metrics=False)
    assert next(stage) == 2 and next(stage) == 4
    with pytest.raises(ValueError, match="reader exploded") as ei:
        next(stage)
    assert ei.value.flow_stage == "src-err"
    with pytest.raises(ValueError):  # sticky, not StopIteration
        next(stage)


def test_fn_error_ordered_is_delivered_at_its_position():
    def work(i):
        if i == 3:
            raise KeyError("item 3")
        return i

    stage = Stage(iter(range(8)), work, depth=4, workers=4, ordered=True,
                  name="fn-err", export_metrics=False)
    got = []
    with pytest.raises(KeyError):
        for item in stage:
            got.append(item)
    assert got == [0, 1, 2], got


def test_close_joins_all_threads_no_leak():
    before = threading.active_count()
    stage = Stage(iter(int(1e9) for _ in iter(int, 1)), lambda x: x,
                  depth=1, workers=3, name="leak", export_metrics=False)
    threads = stage.worker_threads
    assert len(threads) == 3 and all(t.is_alive() for t in threads)
    next(stage)
    stage.close()  # producers are parked on the full queue right now
    assert all(not t.is_alive() for t in threads), "close leaked threads"
    assert threading.active_count() <= before
    with pytest.raises(StopIteration):
        next(stage)


def test_gc_joins_threads():
    import gc

    stage = Stage(iter(int, 1), lambda x: x, depth=1, workers=2,
                  name="gc", export_metrics=False)
    threads = stage.worker_threads
    del stage
    gc.collect()
    assert _wait(lambda: not any(t.is_alive() for t in threads)), \
        "dropping the stage leaked its threads"


def test_chain_close_drains_whole_pipeline():
    tail = chain_stages(
        iter(int, 1),  # infinite zeros
        (lambda x: x + 1, {"depth": 1, "name": "a"}),
        (lambda x: x * 2, {"depth": 1, "name": "b"}),
    )
    assert next(tail) == 2
    inner_threads = [t for t in threading.enumerate()
                     if t.name.startswith("rtpu-flow-")]
    assert len(inner_threads) >= 2
    tail.close()
    assert _wait(lambda: not any(t.is_alive() for t in inner_threads)), \
        "closing the tail did not drain upstream stages"


def test_external_cancel_unblocks_consumer():
    token = CancellationToken()
    stage = Stage(iter(int, 1), lambda x: time.sleep(0.01) or x,
                  depth=1, workers=1, token=token, name="cancel",
                  export_metrics=False)
    next(stage)

    threading.Timer(0.2, token.cancel).start()
    with pytest.raises(FlowCancelled):
        for _ in stage:
            pass
    assert _wait(lambda: not any(t.is_alive()
                                 for t in stage.worker_threads))


# ---------------------------------------------------------------------------
# Observability
# ---------------------------------------------------------------------------

def test_stage_spans_recorded():
    from ray_tpu._private import profiling
    from ray_tpu.util import tracing

    tracing.enable_tracing()  # recorded_spans reads the one span ring
    try:
        profiling.clear_recorded_spans()
        stage = Stage(iter(range(5)), lambda x: x, depth=2,
                      name="spanstage", export_metrics=False)
        assert list(stage) == list(range(5))
    finally:
        tracing.disable_tracing()
    spans = profiling.recorded_spans("flow_spanstage", clear=True)
    assert len(spans) == 5
    assert {s["args"]["seq"] for s in spans} == set(range(5))


def test_flow_metrics_reach_prometheus(shutdown_only):
    ray_tpu.init(num_cpus=2, object_store_memory=64 * MB)
    from ray_tpu.util.metrics import prometheus_text

    stage = Stage(iter(range(7)), lambda x: x, depth=2, name="promstage")
    assert list(stage) == list(range(7))
    stage.close()
    text = prometheus_text()
    assert 'flow_items_total{stage="promstage"} 7' in text, text
    assert 'flow_queue_peak{stage="promstage"}' in text


# ---------------------------------------------------------------------------
# RefStream
# ---------------------------------------------------------------------------

def test_refstream_bounded_inflight_and_order(shutdown_only):
    ray_tpu.init(num_cpus=4, object_store_memory=128 * MB)

    @ray_tpu.remote
    def make(i):
        return i * 11

    stream = RefStream((lambda i=i: make.remote(i) for i in range(10)),
                       depth=3, name="refs")
    vals = [ray_tpu.get(r) for r in stream]
    assert vals == [i * 11 for i in range(10)]
    st = stream.stats()
    assert st["peak_in_flight"] <= 3
    assert st["submitted"] == 10 and st["items_out"] == 10


def test_refstream_close_stops_submission(shutdown_only):
    ray_tpu.init(num_cpus=4, object_store_memory=128 * MB)

    @ray_tpu.remote
    def make(i):
        return i

    stream = RefStream((lambda i=i: make.remote(i) for i in range(100)),
                       depth=2, name="refs-close")
    next(stream)
    submitted = stream.submitted
    stream.close()
    assert stream.submitted == submitted, "close kept submitting"
    assert len(stream._window) == 0, "close leaked in-flight refs"
    with pytest.raises(StopIteration):
        next(stream)


# ---------------------------------------------------------------------------
# Streaming Dataset execution on flow
# ---------------------------------------------------------------------------

def test_dataset_streaming_execution_byte_identical_to_eager(shutdown_only):
    """The acceptance gate: a map_batches→filter→map chain consumed
    through the windowed plan executor produces byte-identical results to
    the eagerly materialized engine, while the executor keeps at most
    `window` blocks in flight."""
    from ray_tpu.data import Dataset

    ray_tpu.init(num_cpus=4, object_store_memory=256 * MB)
    rng = np.random.default_rng(7)
    vals = rng.integers(0, 1000, size=4000)

    def build():
        ds = Dataset.from_numpy({"v": vals}, parallelism=16)
        return (ds.map_batches(lambda b: {"v": b["v"] * 3})
                  .filter(lambda r: r["v"] % 2 == 0)
                  .map(lambda r: {"v": r["v"] + 1}))

    lazy = build()
    assert lazy._plan, "transforms no longer build a lazy plan"
    window = 3
    streamed = list(lazy.iter_batches(batch_size=128, window=window))
    ex = lazy._executor(window)
    assert ex.window == window

    eager = build()
    eager_blocks = eager._blocks  # materialize the old engine's way
    assert eager._plan == [] and eager_blocks
    from ray_tpu.data.block import block_to_numpy

    eager_rows = np.concatenate(
        [block_to_numpy(b)["v"] for b in ray_tpu.get(eager_blocks)])
    streamed_rows = np.concatenate([b["v"] for b in streamed])
    np.testing.assert_array_equal(streamed_rows, eager_rows)
    assert streamed_rows.dtype == eager_rows.dtype

    # Count drives the same plan without materializing blocks driver-side.
    assert lazy.count(window=window) == len(eager_rows)


def test_dataset_plan_window_bounds_inflight(shutdown_only):
    from ray_tpu.data import Dataset

    ray_tpu.init(num_cpus=4, object_store_memory=256 * MB)
    ds = Dataset.range(8000, parallelism=16).map_batches(
        lambda b: {"id": b["id"] + 1})
    ex = ds._executor(window=2, name="boundcheck")
    total = 0
    for ref in ex.iter_block_refs():
        total += ray_tpu.get(ref).num_rows
        del ref
    assert total == 8000
    assert ex.last_stream_stats["peak_in_flight"] <= 2, ex.last_stream_stats


def test_lazy_read_fuses_and_matches_eager(shutdown_only, tmp_path):
    import pyarrow.parquet as pq

    from ray_tpu.data import Dataset
    from ray_tpu.data.block import block_from_numpy
    from ray_tpu.data.execution import is_read_source

    ray_tpu.init(num_cpus=4, object_store_memory=256 * MB)
    for i in range(6):
        pq.write_table(block_from_numpy(
            {"v": np.arange(i * 50, (i + 1) * 50)}),
            str(tmp_path / f"p{i}.parquet"))
    ds = Dataset.read(str(tmp_path / "*.parquet"), "parquet")
    assert all(is_read_source(s) for s in ds._sources), "read ran eagerly"
    got = np.concatenate(
        [b["v"] for b in ds.map_batches(lambda b: {"v": b["v"] * 2})
         .iter_batches(batch_size=64, window=2)])
    np.testing.assert_array_equal(np.sort(got), np.arange(300) * 2)


# ---------------------------------------------------------------------------
# random_shuffle decorrelation + determinism (the dataset.py:192 fix)
# ---------------------------------------------------------------------------

def test_random_shuffle_blocks_decorrelated_and_seed_deterministic(
        shutdown_only):
    from ray_tpu.data import Dataset

    ray_tpu.init(num_cpus=4, object_store_memory=256 * MB)
    n, blocks = 2000, 8
    per = n // blocks

    def block_perms(ds):
        """Per-block permutation patterns (values mod per-block base)."""
        out = []
        for b in ds.iter_batches(batch_size=per):
            out.append(np.asarray(b["id"]) % per)
        return out

    base = Dataset.range(n, parallelism=blocks)
    s1 = base.random_shuffle(seed=42)
    perms = block_perms(s1)
    assert len(perms) == blocks
    # Every block genuinely shuffled...
    assert all(not np.array_equal(p, np.arange(per)) for p in perms)
    # ...and the blocks are NOT all permuted identically (the old bug:
    # every block reused np.random.default_rng(seed) with the same seed).
    distinct = {tuple(p.tolist()) for p in perms}
    assert len(distinct) > 1, "all blocks share one permutation"

    # Same seed → identical rows (reproducible)...
    again = block_perms(base.random_shuffle(seed=42))
    for a, b in zip(perms, again):
        np.testing.assert_array_equal(a, b)
    # ...different seed → different permutation; seed=None differs per
    # call (irreproducible by request).
    other = block_perms(base.random_shuffle(seed=43))
    assert any(not np.array_equal(a, b) for a, b in zip(perms, other))
    n1 = block_perms(base.random_shuffle())
    n2 = block_perms(base.random_shuffle())
    assert any(not np.array_equal(a, b) for a, b in zip(n1, n2))
    # Rows are preserved exactly.
    got = np.sort(np.concatenate(
        [np.asarray(b["id"]) for b in s1.iter_batches(batch_size=500)]))
    np.testing.assert_array_equal(got, np.arange(n))
