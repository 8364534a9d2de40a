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


# ---------------------------------------------------------------------------
# The flush cadence (ISSUE 59): a task's end sends the ring when it is due,
# not whenever it holds something
# ---------------------------------------------------------------------------
class _Sink:
    """A transport that keeps what it is asked to send."""

    def __init__(self):
        self.sent = []

    def request_oneway(self, op, payload):
        self.sent.append((op, payload))


@pytest.fixture
def own_ring(monkeypatch):
    """A small ring of this test's own in the recorder's place, its last
    batch just gone: nothing is due until the test makes it so."""
    ring = obs.SpanRing(capacity=16)
    monkeypatch.setattr(obs, "_ring", ring)
    monkeypatch.setattr(obs, "_lifecycle_held", False)
    monkeypatch.setattr(obs, "_dropped_sent", 0)
    monkeypatch.setattr(obs, "_dropped_exported", 0)
    monkeypatch.setattr(obs, "_last_flush", time.monotonic())
    monkeypatch.setattr(obs, "FLUSH_PERIOD_S", 3600.0)
    return ring


def _plain(n=1):
    for i in range(n):
        obs._append("plain", float(i), float(i) + 0.5, None, None, None, {})


def test_an_empty_or_young_ring_is_not_due(own_ring):
    assert not obs.flush_due()  # empty: one length check, as before
    _plain(7)  # under half of 16, no lifecycle span, the last batch young
    assert not obs.flush_due()
    sink = _Sink()
    assert obs.flush(sink) == 7  # flush itself goes by what the ring holds
    assert [op for op, _ in sink.sent] == ["span_batch"]
    assert obs.flush(sink) == 0 and len(sink.sent) == 1


@pytest.mark.parametrize("why", ["lifecycle_span", "lifecycle_record",
                                 "half_full", "age"])
def test_what_makes_a_tasks_end_send(own_ring, monkeypatch, why):
    _plain(2)
    assert not obs.flush_due()
    if why == "lifecycle_span":
        with obs.span("set.up", _lifecycle=True):
            assert not obs.flush_due()  # open: nothing to send yet
    elif why == "lifecycle_record":
        obs.record("jax.compile", 0.0, 1.0, _lifecycle=True)
    elif why == "half_full":
        _plain(5)
        assert not obs.flush_due()  # 7 of 16
        _plain(1)
    else:
        monkeypatch.setattr(obs, "_last_flush", time.monotonic() - 7200.0)
    assert obs.flush_due()
    sink = _Sink()
    sent = obs.flush(sink)
    assert sent == len(sink.sent[0][1]["spans"]) >= 2
    assert not obs.flush_due() and len(own_ring) == 0
    _plain(1)  # the batch that left made the ring young again
    assert not obs.flush_due()


def test_a_batch_says_what_the_ring_lost_since_the_last(own_ring):
    _plain(20)  # 16 places: four pushed out
    sink = _Sink()
    assert obs.flush(sink) == 16
    _plain(3)
    obs.flush(sink)
    _plain(40)  # 24 more
    obs.flush(sink)
    assert [p["dropped"] for _, p in sink.sent] == [4, 0, 24]
    store = TraceStore()
    for _, payload in sink.sent:
        store.ingest(payload["spans"], dropped=payload["dropped"])
    assert store.spans_dropped == 28 and store.spans_ingested == 35
    store.ingest([], dropped=2)  # nothing left to send but the count
    assert store.spans_dropped == 30


def test_a_batch_that_could_not_leave_tells_its_losses_later(own_ring):
    class Down:
        def request_oneway(self, op, payload):
            raise OSError("head restarting")

    _plain(20)
    assert obs.flush(Down()) == 0  # the spans are gone, the count is kept
    _plain(1)
    sink = _Sink()
    obs.flush(sink)
    assert sink.sent[0][1]["dropped"] == 4


@pytest.fixture
def counted_batches(monkeypatch, shutdown_only):
    """A session whose workers send spans only as a task's end decides
    (their periodic flusher is put out of reach), and the ``span_batch``
    requests its head takes."""
    from ray_tpu._private.head import Head

    monkeypatch.setenv("RAY_TPU_NODE_STATS_PERIOD_S", "600")
    batches = []
    handler = Head.req_span_batch

    def counted(self, payload, reply, caller):
        batches.append(len(payload.get("spans") or []))
        return handler(self, payload, reply, caller)

    monkeypatch.setattr(Head, "req_span_batch", counted)
    ray_tpu.init(num_cpus=1)
    return batches


@ray_tpu.remote
class _Recorder:
    """Records one span a call in its worker, the flag off around it."""

    def note(self, name, n=1, lifecycle=False, period=None):
        from ray_tpu import observability as o

        if period is not None:
            o.FLUSH_PERIOD_S = period
        if lifecycle:
            o.record(name, 0.0, 1.0, _lifecycle=True)
            return len(o.ring())
        tracing.enable_tracing()
        try:
            for i in range(n):
                with o.span(name, i=i):
                    pass
        finally:
            tracing.disable_tracing()
        return len(o.ring())


def test_500_task_ends_send_a_handful_of_batches_and_lose_nothing(
        counted_batches):
    rec = _Recorder.remote()
    # the worker's first batch is due by age; after it the period is out
    # of reach, so only the ring's filling or a lifecycle span sends
    ray_tpu.get(rec.note.remote("first", period=3600.0))
    wait_for_condition(lambda: obs.session_spans("first"))
    before = len(counted_batches)
    held = ray_tpu.get([rec.note.remote("tick") for _ in range(500)])
    assert held[-1] >= 500  # 500 task ends, each with a ring that held some
    assert len(counted_batches) - before <= 5
    ray_tpu.shutdown()  # the worker's exit sends what its ring holds
    assert len(obs.session_spans("tick")) == 500
    assert len(counted_batches) - before <= 6


def test_a_lifecycle_span_leaves_at_its_tasks_end(counted_batches):
    rec = _Recorder.remote()
    ray_tpu.get(rec.note.remote("first", period=3600.0))
    wait_for_condition(lambda: obs.session_spans("first"))
    assert ray_tpu.get(rec.note.remote("plain")) >= 1  # stays in the ring
    ray_tpu.get(rec.note.remote("set.up", lifecycle=True))
    # in the head's store while the session lives, and what waited with it
    wait_for_condition(lambda: obs.session_spans("set.up"), timeout=30)
    assert len(obs.session_spans("plain")) == 1
    ray_tpu.shutdown()
    assert len(obs.session_spans("set.up")) == 1


@pytest.fixture
def rings_of_16(monkeypatch):
    """Set before the session starts: its workers read it as they do.
    This process's ring is made first, at the size every other test has."""
    obs.ring()
    monkeypatch.setenv("RAY_TPU_TRACING_BUFFER_SIZE", "16")


def test_a_workers_overflowed_ring_shows_in_the_sessions_count(
        rings_of_16, counted_batches):
    """16 places (the least a ring has) and 40 spans in one task: the
    batch at its end tells the head of the 24 that were pushed out."""
    before = obs.session_spans_dropped()
    rec = _Recorder.remote()
    assert ray_tpu.get(rec.note.remote("burst", n=40)) == 16
    wait_for_condition(
        lambda: obs.session_spans_dropped() - before == 24, timeout=30)
    assert len(obs.session_spans("burst")) == 16



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
