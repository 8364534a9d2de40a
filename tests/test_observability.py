"""Tracing-plane gates: span rings, TraceStore budgets, cross-process
context propagation, resend dedup (PR 6 idempotency x tracing), the
crash flight recorder, and the dashboard export formats.

Reference: the chrome://tracing export contract in
python/ray/_private/state.py:chrome_tracing_dump and the GCS task-event
path (gcs_task_manager.h) — but the assertions here are against OUR
plane: one trace id assembled across processes, duplicate RPC frames
never double-recorded, and a SIGKILLed node leaving its last spans in
the flight bundle.
"""
import json
import os
import time
import urllib.request

import numpy as np
import pytest

import ray_tpu
from ray_tpu import observability as obs
from ray_tpu.observability.flight_recorder import read_bundle, write_bundle
from ray_tpu.observability.trace_store import TraceStore
from ray_tpu.util import tracing
from ray_tpu.util.testing import start_node_agent, wait_for_condition

MB = 1024 * 1024


@pytest.fixture
def traced(shutdown_only):
    tracing.enable_tracing()
    yield
    tracing.disable_tracing()
    tracing.pop_local_spans()
    obs.drain_spans()


# ---------------------------------------------------------------------------
# Primitives: the ring and the store
# ---------------------------------------------------------------------------
def test_span_ring_drop_oldest_counts():
    """The bounded buffer drops OLDEST and counts what it dropped —
    the fix for util.tracing's old silent 10k truncation."""
    ring = obs.SpanRing(capacity=16)
    for i in range(40):
        ring.append({"i": i})
    assert len(ring) == 16
    assert ring.dropped_total == 24
    drained = ring.drain()
    assert [s["i"] for s in drained] == list(range(24, 40))
    assert len(ring) == 0
    # drain resets contents but the counter is cumulative
    ring.append({"i": 99})
    assert ring.dropped_total == 24


def test_trace_store_budgets():
    """Per-trace byte cap drops that trace's overflow; the global cap
    evicts whole least-recently-updated traces."""
    store = TraceStore(max_bytes=4000, per_trace_bytes=1200)

    def mk(tid, i):
        return {"trace_id": tid, "name": f"s{i}", "start": float(i),
                "end": float(i) + 0.5, "proc": "p", "node": None,
                "span_id": obs.new_id(), "parent_id": None,
                "args": {"pad": "x" * 100}}

    store.ingest([mk("aaaa", i) for i in range(20)])
    kept = len(store.spans("aaaa"))
    assert 0 < kept < 20
    assert store.spans_dropped == 20 - kept
    for tid in ("bbbb", "cccc", "dddd", "eeee"):
        store.ingest([mk(tid, i) for i in range(4)])
    assert store.traces_evicted >= 1
    assert store.total_bytes <= store.max_bytes
    rows = store.list_traces()
    assert all("duration" in r and "procs" in r for r in rows)


def test_flight_bundle_roundtrip(tmp_path):
    """write_bundle/read_bundle round-trip, bundle-count pruning."""
    spans = [{"trace_id": "t1", "name": "x", "start": 1.0, "end": 2.0,
              "span_id": "s1", "parent_id": None, "proc": "p",
              "node": None, "args": {}}]
    path = write_bundle("unit test: reason/with bad chars",
                        spans=spans, tasks=[{"task_id": "t"}],
                        events=[{"event": "e"}], root=str(tmp_path))
    assert path is not None and os.path.isdir(path)
    assert "/" not in os.path.basename(path).split("_", 1)[1]
    back = read_bundle(path)
    assert back["meta"]["spans"] == 1
    assert back["spans"] == spans
    assert back["tasks"] == [{"task_id": "t"}]
    assert back["events"] == [{"event": "e"}]


# ---------------------------------------------------------------------------
# Propagation: one trace id across processes
# ---------------------------------------------------------------------------
@ray_tpu.remote
def _traced_child(x):
    return x + 1


def test_trace_context_propagates_cross_process(traced):
    """A driver-side root span's trace id rides the task specs: worker
    execute spans land in the head's TraceStore under the SAME trace,
    parented into the driver's span tree (the flow-arrow contract)."""
    ray_tpu.init(num_cpus=2, object_store_memory=128 * MB)
    with tracing.span("obs.test_root"):
        tid = obs.get_context()[0]
        assert ray_tpu.get([_traced_child.remote(i) for i in range(3)]) \
            == [1, 2, 3]
    head = ray_tpu._head

    def assembled():
        head._drain_local_spans()
        spans = head.trace_store.spans(tid)
        names = {s["name"] for s in spans}
        return len({s["proc"] for s in spans}) >= 2 \
            and "task.execute" in names and "obs.test_root" in names
    wait_for_condition(assembled, timeout=30)

    spans = head.trace_store.spans(tid)
    ids = {s["span_id"] for s in spans}
    execs = [s for s in spans if s["name"] == "task.execute"]
    # every cross-process span resolves its parent INSIDE the trace —
    # without this the chrome dump has slices but no flow edges
    assert execs and all(s["parent_id"] in ids for s in execs)
    assert all(s["trace_id"] == tid for s in spans)


def test_resent_rpc_frame_records_one_span(traced):
    """PR 6 idempotency x tracing: a duplicate keyed frame is answered
    from the ReplyCache and must NOT mint a second head-side span."""
    ray_tpu.init(num_cpus=1, object_store_memory=64 * MB)
    head = ray_tpu._head
    head._drain_local_spans()
    ctx = obs.mint_context()
    replies = []

    def reply(value=None, error=None):
        replies.append((value, error))

    key = b"obs-resend-test-key"
    with obs.use_context(ctx):
        head.handle_request_keyed("cluster_resources", {}, reply, None, key)
        head.handle_request_keyed("cluster_resources", {}, reply, None, key)
    # both frames answered, identically, no error
    assert len(replies) == 2
    assert replies[0] == replies[1] and replies[0][1] is None

    head._drain_local_spans()
    spans = [s for s in head.trace_store.spans(ctx[0])
             if s["name"] == "head.cluster_resources"]
    assert len(spans) == 1


# ---------------------------------------------------------------------------
# Crash flight recorder: SIGKILL a node, read the black box
# ---------------------------------------------------------------------------
@ray_tpu.remote(max_retries=0)
def _sleepy(n):
    import time

    time.sleep(n)
    return n


def test_sigkill_flight_bundle_has_victim_spans(tmp_path, monkeypatch):
    """A SIGKILLed node's flight bundle contains the dying task's spans:
    workers flush a task.begin marker BEFORE executing, so the head's
    snapshot at remove_node still has the victim's last act."""
    from ray_tpu._private import chaos

    monkeypatch.setenv("RAY_TPU_FLIGHT_RECORD_DIR", str(tmp_path))
    tracing.enable_tracing()
    try:
        ray_tpu.init(num_cpus=1, object_store_memory=128 * MB)
        head = ray_tpu._head
        agent = start_node_agent(head, num_cpus=2,
                                 resources={"victim": 1.0})
        wait_for_condition(lambda: len(head.raylets) >= 2, timeout=30)

        with tracing.span("obs.flight_root"):
            tid = obs.get_context()[0]
            ref = _sleepy.options(resources={"victim": 1.0}).remote(60)

        def begin_arrived():
            head._drain_local_spans()
            return any(s["name"] == "task.begin" and s["trace_id"] == tid
                       for s in head.trace_store.spans())
        wait_for_condition(begin_arrived, timeout=30)

        assert chaos.kill_node(agent)
        wait_for_condition(lambda: len(os.listdir(tmp_path)) >= 1,
                           timeout=60)
        bundle_dir = os.path.join(
            str(tmp_path), sorted(os.listdir(tmp_path))[0])
        bundle = read_bundle(bundle_dir)
        assert bundle["meta"]["reason"]
        victim = [s for s in bundle["spans"]
                  if s["trace_id"] == tid and s["name"] == "task.begin"]
        assert victim, "dying task's task.begin span missing from bundle"
        # the marker came from the killed node's worker, not the driver
        assert all(s["proc"] != obs.identity()[0] for s in victim)
        assert isinstance(bundle["events"], list)
        del ref
    finally:
        tracing.disable_tracing()
        ray_tpu.shutdown()


# ---------------------------------------------------------------------------
# Acceptance paths: one MPMD step / one generate_many = one trace
# ---------------------------------------------------------------------------
def test_mpmd_step_assembles_one_trace(traced):
    """One 2-stage MPMD training step is ONE trace: the driver's
    per-step dispatch root, the mpmd_stage_* spans stamped with the
    step's context, and execute spans from both stage-worker processes
    (>= 3 procs), joined by cross-process flow edges."""
    import optax

    from ray_tpu.observability.timeline import trace_stats
    from ray_tpu.parallel.mpmd_pipeline import MPMDPipeline

    ray_tpu.init(num_cpus=6, object_store_memory=256 * MB)

    def _stage0(params, x):
        import jax.numpy as jnp

        return jnp.tanh(x @ params["w0"] + params["b0"])

    def _stage1_loss(params, h, target):
        import jax.numpy as jnp

        pred = h @ params["w1"] + params["b1"]
        return jnp.mean((pred - target) ** 2)

    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    p0 = {"w0": jnp.asarray(rng.normal(0, 0.3, (6, 16)), jnp.float32),
          "b0": jnp.zeros((16,), jnp.float32)}
    p1 = {"w1": jnp.asarray(rng.normal(0, 0.3, (16, 3)), jnp.float32),
          "b1": jnp.zeros((3,), jnp.float32)}
    x = rng.normal(size=(16, 6)).astype(np.float32)
    t = rng.normal(size=(16, 3)).astype(np.float32)

    pipe = MPMDPipeline([_stage0, _stage1_loss], [p0, p1],
                        optimizer=optax.sgd(0.05), num_microbatches=2)
    try:
        for _ in range(4):
            pipe.train_step(x, t)
    finally:
        pipe.stop()

    head = ray_tpu._head
    good = []

    def one_step_trace():
        head._drain_local_spans()
        tids = {s["trace_id"] for s in head.trace_store.spans()
                if s["name"] == "mpmd_step_dispatch" and s["trace_id"]}
        for tid in tids:
            st = trace_stats(ray_tpu.timeline(trace_id=tid))
            if st["procs"] >= 3 and st["flow_edges"] >= 1:
                good.append(tid)
                return True
        return False
    wait_for_condition(one_step_trace, timeout=30)

    names = {s["name"] for s in head.trace_store.spans(good[0])}
    assert "mpmd_step_dispatch" in names
    assert names & {"mpmd_stage_fwd", "mpmd_stage_bwd", "mpmd_stage_apply"}


@pytest.mark.slow  # e2e serve path (model compile): nightly covers it
def test_generate_many_assembles_one_trace(monkeypatch):
    """One generate_many request is ONE trace spanning the driver and
    two replica processes on two virtual nodes, with flow edges."""
    from ray_tpu._private.config import CONFIG
    from ray_tpu.cluster_utils import Cluster
    from ray_tpu.observability.timeline import trace_stats
    from ray_tpu.serve.controller import reset_controller

    monkeypatch.setenv("RAY_TPU_SERVE_CONTROL_INTERVAL_S", "0.2")
    CONFIG.reset()
    reset_controller()
    tracing.enable_tracing()
    try:
        ray_tpu.init(num_cpus=1, object_store_memory=256 * MB)
        cluster = Cluster(initialize_head=False)
        cluster.add_node(num_cpus=1, object_store_memory=128 * MB)
        from ray_tpu import serve
        from ray_tpu.models import GPT2Config
        from ray_tpu.serve.llm_engine import LLMServer, generate_many

        vocab = GPT2Config.tiny().vocab_size
        dep = serve.deployment(LLMServer, name="llm_traced",
                               num_replicas=2)
        handle = serve.run(dep.bind(
            "gpt2", {"tiny": True, "dtype": "float32"}, 0,
            max_slots=4, page_size=8, max_ctx=64))
        rng = np.random.default_rng(7)
        # 12 distinct prefixes -> 12 affinity keys: rendezvous routing
        # spreads them over both replicas with overwhelming probability
        prompts = [list(map(int, rng.integers(0, vocab, size=n)))
                   for n in rng.integers(4, 12, size=12)]
        outs = generate_many(handle, prompts, max_new_tokens=4)
        assert all(len(o) > 0 for o in outs)

        head = ray_tpu._head
        good = []

        def assembled():
            head._drain_local_spans()
            tids = {s["trace_id"] for s in head.trace_store.spans()
                    if s["name"] == "serve.generate_many"}
            for tid in tids:
                st = trace_stats(ray_tpu.timeline(trace_id=tid))
                if st["procs"] >= 3 and st["nodes"] >= 2 \
                        and st["flow_edges"] >= 1:
                    good.append(tid)
                    return True
            return False
        wait_for_condition(assembled, timeout=30)

        names = {s["name"] for s in head.trace_store.spans(good[0])}
        # the engine stamps a request's own spans with its context
        assert {"request.queued", "request.decode"} <= names
        serve.shutdown()
    finally:
        tracing.disable_tracing()
        ray_tpu.shutdown()
        CONFIG.reset()


# ---------------------------------------------------------------------------
# Dashboard export formats
# ---------------------------------------------------------------------------
def _get(dash, path):
    with urllib.request.urlopen(dash.url + path, timeout=10) as r:
        return json.loads(r.read())


def test_dashboard_trace_export_formats(traced):
    """/traces, /timeline?trace_id=, /state/tasks serve JSON; the
    timeline is a valid chrome://tracing event list (M metadata, X
    slices with ts/dur, s/f flow arrows across processes)."""
    from ray_tpu.dashboard import start_dashboard, stop_dashboard

    ray_tpu.init(num_cpus=2, object_store_memory=128 * MB)
    dash = start_dashboard()
    try:
        with tracing.span("obs.dash_root"):
            tid = obs.get_context()[0]
            assert ray_tpu.get(_traced_child.remote(1)) == 2
        head = ray_tpu._head

        def ready():
            head._drain_local_spans()
            return len({s["proc"]
                        for s in head.trace_store.spans(tid)}) >= 2
        wait_for_condition(ready, timeout=30)

        traces = _get(dash, "/traces")
        row = next(r for r in traces if r["trace_id"] == tid)
        for col in ("spans", "start", "duration", "procs", "nodes"):
            assert col in row
        assert row["procs"] >= 2

        events = _get(dash, f"/timeline?trace_id={tid}")
        assert isinstance(events, list) and events
        phases = {e["ph"] for e in events}
        assert "M" in phases and "X" in phases
        for e in events:
            assert "pid" in e
            if e["ph"] == "X":
                assert {"name", "ts", "dur", "tid"} <= set(e)
        # cross-process flow arrows bind the driver's submit to the
        # worker's execute — the acceptance-criterion edge
        assert {"s", "f"} <= phases

        tasks = _get(dash, "/state/tasks")
        assert any(t.get("trace_id") == tid for t in tasks)
        assert _get(dash, "/state/traces")  # alias of /traces
    finally:
        stop_dashboard()
