"""Multi-source striped transfers and cooperative broadcast (ISSUE 20).

Pure in-process tests against the transfer-plane primitives: whole-pull
backward compat, byte-exact range reads, the partial-holder registry
(chunk-bitmap semantics, norange refusals, eviction cap), striped
multi-source pulls with per-range failover, seeded chaos drops that
retry exactly one range, the prometheus export of the transfer_*
counters — plus two cluster tests for the worker-side integration:
same-object pull coalescing across threads and the shm-defuse path
when a pulled object is freed while views are live.
"""
import os
import threading
import time

import pytest

from ray_tpu._private import transfer
from ray_tpu._private.ids import ObjectID
from ray_tpu._private.object_store import SharedMemoryStore
from ray_tpu._private.transfer import (ObjectTransferServer,
                                       RangeUnavailableError,
                                       TransferClient, pull_striped,
                                       transfer_stats)

AUTH = b"test-transfer-striped"
CHUNK = 64 * 1024


def _oid():
    return ObjectID(os.urandom(20))


@pytest.fixture
def store():
    s = SharedMemoryStore(capacity_bytes=64 * 1024**2)
    yield s
    s.shutdown()


@pytest.fixture
def client():
    c = TransferClient(AUTH)
    yield c
    c.close()


# ---------------------------------------------------------------------------
# Backward compat + range protocol
# ---------------------------------------------------------------------------
def test_whole_object_pull_roundtrip(store, client):
    srv = ObjectTransferServer(store, AUTH)
    try:
        oid, data = _oid(), os.urandom(1 << 20)
        store.put(oid, b"meta", data)
        meta, got = client.pull(srv.address, oid)
        assert bytes(meta) == b"meta"
        assert bytes(got) == data
    finally:
        srv.shutdown()


def test_pull_range_byte_exact_and_bw_accounting(store, client):
    srv = ObjectTransferServer(store, AUTH)
    try:
        oid, data = _oid(), os.urandom(1 << 20)
        store.put(oid, b"m", data)
        off, ln = 123456, 300000
        sink = bytearray(ln)
        meta, n = client.pull_range(srv.address, oid, off, ln, sink)
        assert n == ln
        assert bytes(sink) == data[off:off + ln]
        assert bytes(meta) == b"m"
        # The stream fed the per-peer EWMA that rank_sources uses.
        assert client.peer_bandwidth(srv.address) > 0
    finally:
        srv.shutdown()


def test_rank_sources_least_loaded_then_fastest(client):
    a, b, c = ("10.9.0.1", 1), ("10.9.0.2", 2), ("10.9.0.3", 3)
    client._peer_active[b] = 2          # two streams in flight
    client._peer_bw[a] = 100.0
    client._peer_bw[c] = 1000.0
    assert client.rank_sources([a, b, c]) == [c, a, b]
    # Unmeasured peers sort ahead of known-slow ones (optimism).
    d = ("10.9.0.4", 4)
    assert client.rank_sources([a, d])[0] == d


# ---------------------------------------------------------------------------
# Partial-holder registry (cooperative broadcast server side)
# ---------------------------------------------------------------------------
def test_partial_peer_serves_landed_refuses_unlanded(client):
    peer = ObjectTransferServer(None, AUTH)  # store-less peer mode
    try:
        oid = _oid()
        size = 8 * CHUNK
        data = os.urandom(size)
        buf = bytearray(size)
        peer.register_partial(oid, buf, size, CHUNK)
        buf[0:2 * CHUNK] = data[0:2 * CHUNK]
        assert peer.mark_range(oid, 0, 2 * CHUNK) == [0, 1]

        sink = bytearray(CHUNK)
        meta, n = client.pull_range(peer.address, oid, 0, CHUNK, sink)
        assert bytes(sink) == data[:CHUNK]
        assert meta is None  # in-progress partials are meta-less
        # A range that has not landed is a norange refusal, not a hang
        # and not a generic KeyError (the source survives for other work).
        with pytest.raises(RangeUnavailableError):
            client.pull_range(peer.address, oid, 3 * CHUNK, CHUNK,
                              bytearray(CHUNK), retries=0)
        # Whole-object requests need meta: only a sealed record answers.
        with pytest.raises(KeyError):
            client.pull(peer.address, oid)

        buf[:] = data
        peer.complete_partial(oid, b"M")
        meta, got = client.pull(peer.address, oid)
        assert bytes(meta) == b"M"
        assert bytes(got) == data

        assert peer.drop_partial(oid) is True
        assert peer.drop_partial(oid) is False
    finally:
        peer.shutdown()


def test_mark_range_chunk_alignment_semantics():
    peer = ObjectTransferServer(None, AUTH)
    try:
        oid, chunk, size = _oid(), 1000, 4500  # 5 chunks, 500-byte tail
        peer.register_partial(oid, bytearray(size), size, chunk)
        # Only chunks FULLY inside the landed span become servable.
        assert peer.mark_range(oid, 500, 1000) == []
        assert peer.mark_range(oid, 1000, 1500) == [1]
        # A range reaching the object's end completes the tail chunk.
        assert peer.mark_range(oid, 4000, 500) == [4]
        rec = peer._partials[oid]
        assert rec.covers(1000, 1000)
        assert not rec.covers(2000, 1000)
    finally:
        peer.shutdown()


def test_partial_cap_evicts_completed_records_only():
    peer = ObjectTransferServer(None, AUTH)
    try:
        oids = [_oid() for _ in range(peer.PARTIAL_CAP + 1)]
        for oid in oids:
            peer.register_partial(oid, bytearray(8), 8, 8)
        # All in-progress: nothing is evictable (owners drop their own).
        assert len(peer._partials) == peer.PARTIAL_CAP + 1
        peer.complete_partial(oids[0], b"")
        peer.register_partial(_oid(), bytearray(8), 8, 8)
        assert oids[0] not in peer._partials  # the sealed one was evicted
        assert oids[1] in peer._partials
    finally:
        peer.shutdown()


# ---------------------------------------------------------------------------
# Striped pulls
# ---------------------------------------------------------------------------
def test_pull_striped_single_source_byte_exact(store, client):
    srv = ObjectTransferServer(store, AUTH)
    try:
        oid, data = _oid(), os.urandom(2 * 1024 * 1024)
        store.put(oid, b"meta", data)
        sink = bytearray(len(data))
        meta, stats = pull_striped(client, oid, len(data),
                                   [(srv.address, None)], sink,
                                   chunk=CHUNK)
        assert bytes(sink) == data
        assert bytes(meta) == b"meta"
        assert stats["nranges"] >= 2
        assert sum(stats["bytes_from"].values()) == len(data)
        assert stats["reassigned"] == 0
    finally:
        srv.shutdown()


def test_pull_striped_complementary_partial_holders(client):
    """Two partial holders with disjoint bitmaps: every range is eligible
    at exactly one source, so the scheduler MUST stripe across both and
    the result must still be byte-exact (the dissemination-mesh case)."""
    nch = 16
    size = nch * CHUNK
    data = os.urandom(size)
    oid = _oid()
    peers, sources = [], []
    try:
        for chunks in (range(0, nch // 2), range(nch // 2, nch)):
            p = ObjectTransferServer(None, AUTH)
            buf = bytearray(size)
            p.register_partial(oid, buf, size, CHUNK)
            lo, hi = chunks[0] * CHUNK, (chunks[-1] + 1) * CHUNK
            buf[lo:hi] = data[lo:hi]
            p.mark_range(oid, lo, hi - lo)
            peers.append(p)
            sources.append((p.address, set(chunks)))

        before = transfer_stats()
        sink = bytearray(size)
        meta, stats = pull_striped(client, oid, size, sources, sink,
                                   chunk=CHUNK, meta_hint=b"hint")
        assert bytes(sink) == data
        assert meta == b"hint"  # partial-only sources never carry meta
        assert len(stats["bytes_from"]) == 2
        assert stats["partial_ranges"] == stats["nranges"]
        after = transfer_stats()
        assert (after["ranges_from_partial"]
                > before["ranges_from_partial"])
        assert (after["served_partial_bytes"]
                >= before["served_partial_bytes"] + size)
    finally:
        for p in peers:
            p.shutdown()


def test_pull_striped_dead_source_reassigns_ranges(store, client):
    """A source that dies loses only its claimed ranges: they requeue to
    the survivor and the pull completes byte-exact (per-range failover,
    not a whole-pull restart)."""
    srv = ObjectTransferServer(store, AUTH)
    dead = ObjectTransferServer(None, AUTH)
    dead_addr = dead.address
    dead.shutdown()  # connections to this addr now refuse
    try:
        oid, data = _oid(), os.urandom(2 * 1024 * 1024)
        store.put(oid, b"meta", data)
        before = transfer_stats()
        sink = bytearray(len(data))
        meta, stats = pull_striped(client, oid, len(data),
                                   [(dead_addr, None),
                                    (srv.address, None)], sink,
                                   chunk=CHUNK)
        assert bytes(sink) == data
        assert bytes(meta) == b"meta"
        assert stats["reassigned"] >= 1
        after = transfer_stats()
        assert (after["range_reassignments"]
                >= before["range_reassignments"] + 1)
    finally:
        srv.shutdown()


def test_pull_striped_refresh_admits_late_sources(store, client):
    """When every initial source is dead, refresh() re-asks the directory
    and a newly-advertised holder joins MID-pull instead of failing it."""
    srv = ObjectTransferServer(store, AUTH)
    dead = ObjectTransferServer(None, AUTH)
    dead_addr = dead.address
    dead.shutdown()
    try:
        oid, data = _oid(), os.urandom(512 * 1024)
        store.put(oid, b"meta", data)
        calls = []

        def refresh():
            calls.append(1)
            return [(srv.address, None)]

        sink = bytearray(len(data))
        meta, stats = pull_striped(client, oid, len(data),
                                   [(dead_addr, None)], sink,
                                   chunk=CHUNK, refresh=refresh)
        assert bytes(sink) == data
        assert calls  # the directory was actually re-consulted
        assert stats["refreshes"] >= 1
    finally:
        srv.shutdown()


def test_netschedule_drop_retries_only_that_range(store, client,
                                                  monkeypatch):
    """A seeded chaos drop on the data channel re-requests ONE range over
    a fresh connection; the other ranges of the striped pull are
    untouched (no reassignment, no source death, byte-exact result)."""
    monkeypatch.setenv("RAY_TPU_TESTING_NET_SCHEDULE", "pull:drop:1.0:7:1")
    srv = ObjectTransferServer(store, AUTH)
    try:
        oid, data = _oid(), os.urandom(2 * 1024 * 1024)
        store.put(oid, b"meta", data)
        before = transfer_stats()
        sink = bytearray(len(data))
        meta, stats = pull_striped(client, oid, len(data),
                                   [(srv.address, None)], sink,
                                   chunk=CHUNK)
        assert bytes(sink) == data
        after = transfer_stats()
        # Exactly the one scheduled drop fired, retried per-range.
        assert after["range_retries"] - before["range_retries"] == 1
        assert stats["reassigned"] == 0
    finally:
        srv.shutdown()


def test_progress_hook_fires_per_landed_range(store, client):
    srv = ObjectTransferServer(store, AUTH)
    try:
        oid, data = _oid(), os.urandom(1024 * 1024)
        store.put(oid, b"m", data)
        landed = []
        sink = bytearray(len(data))
        pull_striped(client, oid, len(data), [(srv.address, None)], sink,
                     chunk=CHUNK,
                     progress=lambda off, ln: landed.append((off, ln)))
        assert sum(ln for _, ln in landed) == len(data)
        # Ranges are disjoint and cover [0, size).
        spans = sorted(landed)
        pos = 0
        for off, ln in spans:
            assert off == pos
            pos += ln
        assert pos == len(data)
    finally:
        srv.shutdown()


# ---------------------------------------------------------------------------
# Metrics export
# ---------------------------------------------------------------------------
def test_transfer_metrics_prometheus_export(store, client, shutdown_only):
    import ray_tpu
    from ray_tpu.util.metrics import prometheus_text

    # The metrics mirror lands in the GCS KV: needs a live driver.
    ray_tpu.init(num_cpus=1, object_store_memory=64 * 1024**2,
                 ignore_reinit_error=True)
    srv = ObjectTransferServer(store, AUTH)
    try:
        oid, data = _oid(), os.urandom(512 * 1024)
        store.put(oid, b"m", data)
        sink = bytearray(len(data))
        pull_striped(client, oid, len(data), [(srv.address, None)], sink,
                     chunk=CHUNK)
        # Meters batch their KV writes; force the flush the scrape
        # endpoint would otherwise wait ≤flush_interval for.
        for m in list(transfer._meters.values()):
            if hasattr(m, "flush"):
                m.flush()
        txt = prometheus_text()
        assert "transfer_striped_pulls_total" in txt
        assert "transfer_ranges_completed_total" in txt
        assert "transfer_striped_bytes_total" in txt
        assert "transfer_active_streams" in txt
        assert "transfer_peer_bytes_total" in txt  # per-peer meter
    finally:
        srv.shutdown()
        ray_tpu.shutdown()


# ---------------------------------------------------------------------------
# Worker-side integration: coalescing + shm defuse on free
# ---------------------------------------------------------------------------
def _start_one_agent(head, tag):
    from ray_tpu.util.testing import start_node_agent, wait_for_condition

    baseline = len(head.raylets)
    agent = start_node_agent(head, num_cpus=1, resources={tag: 1},
                             store_capacity=128 * 1024**2)
    wait_for_condition(lambda: len(head.raylets) >= baseline + 1,
                       timeout=60)
    return agent


def test_concurrent_same_object_pull_coalesces(shutdown_only, monkeypatch):
    """Satellite (a): two threads resolving the same remote object must
    produce ONE wire pull — the follower parks on the leader's event and
    reads the landed value, instead of double-pulling into a segment-name
    collision."""
    import numpy as np

    import ray_tpu
    import ray_tpu._private.worker as worker_mod

    ray_tpu.init(num_cpus=1, object_store_memory=128 * 1024**2,
                 ignore_reinit_error=True)
    agent = _start_one_agent(ray_tpu._head, "co")
    try:
        @ray_tpu.remote(resources={"co": 1})
        def make():
            return np.arange(1_000_000, dtype=np.int64)

        ref = make.remote()

        # Widen the race window: the leader's resolved-pull path pauses
        # long enough for the second thread to observe the in-flight
        # record deterministically.
        orig = worker_mod.CoreWorker._pull_resolved
        entered = threading.Event()

        def slow(self, oid, msg, _failovers=2):
            entered.set()
            time.sleep(0.4)
            return orig(self, oid, msg, _failovers)

        monkeypatch.setattr(worker_mod.CoreWorker, "_pull_resolved", slow)

        before = transfer_stats()["coalesced_pulls"]
        results = [None, None]

        def getter(i):
            results[i] = ray_tpu.get(ref, timeout=60)

        t1 = threading.Thread(target=getter, args=(0,))
        t2 = threading.Thread(target=getter, args=(1,))
        t1.start()
        assert entered.wait(30)
        t2.start()
        t1.join(60)
        t2.join(60)
        assert results[0] is not None and results[1] is not None
        assert np.array_equal(results[0], results[1])
        assert transfer_stats()["coalesced_pulls"] >= before + 1
    finally:
        try:
            agent.kill()
            agent.wait(timeout=10)
        except Exception:
            pass
        ray_tpu.shutdown()


def test_freed_pulled_object_defuses_shm_with_live_views(shutdown_only):
    """Satellite (b): freeing a pulled object while a consumer still
    holds a zero-copy view must defuse the backing segment instead of
    raising BufferError out of a destructor."""
    import gc

    import numpy as np

    import ray_tpu
    import ray_tpu._private.worker as worker_mod

    ray_tpu.init(num_cpus=1, object_store_memory=128 * 1024**2,
                 ignore_reinit_error=True)
    agent = _start_one_agent(ray_tpu._head, "dz")
    try:
        @ray_tpu.remote(resources={"dz": 1})
        def make():
            return np.arange(500_000, dtype=np.int64)

        ref = make.remote()
        value = ray_tpu.get(ref, timeout=60)
        gw = worker_mod.global_worker
        oid = ref._id if hasattr(ref, "_id") else ObjectID(
            bytes.fromhex(ref.hex()))
        view = np.asarray(value)  # zero-copy consumer still alive

        # The free path must not raise even though `view` exports the
        # buffer; the partial record (if any) is dropped with it.
        gw._drop_local_shm(oid)
        assert int(view[123]) == 123  # bytes stay readable (deferred)
        del value, view
        gc.collect()
    finally:
        try:
            agent.kill()
            agent.wait(timeout=10)
        except Exception:
            pass
        ray_tpu.shutdown()
