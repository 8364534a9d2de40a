"""Object-plane hot paths: segment pool recycling, parallel pack_into,
batched puts/gets + coalesced control-plane notifies, spill→restore under
eviction pressure, and the bookkeeping bounds that keep long-lived
drivers leak-free."""
import gc
import os
import pickle
import tempfile

import numpy as np
import pytest

import ray_tpu
from ray_tpu._private import object_store as store_mod
from ray_tpu._private import serialization as ser
from ray_tpu._private.ids import ObjectID
from ray_tpu._private.object_store import SegmentPool, SharedMemoryStore


def _oid():
    return ObjectID(os.urandom(20))


# ---------------------------------------------------------------------------
# Segment pool
# ---------------------------------------------------------------------------
def test_pool_size_classes():
    assert SegmentPool.class_for(1) == SegmentPool.MIN_CLASS
    assert SegmentPool.class_for(SegmentPool.MIN_CLASS) == SegmentPool.MIN_CLASS
    assert SegmentPool.class_for(SegmentPool.MIN_CLASS + 1) == 2 * SegmentPool.MIN_CLASS
    assert SegmentPool.class_for(SegmentPool.MAX_CLASS + 1) is None


def test_pooled_segment_reuse_across_put_delete_cycles():
    store = SharedMemoryStore(capacity_bytes=64 * 1024**2)
    try:
        data = os.urandom(2 * 1024 * 1024)
        seg_names = set()
        for i in range(5):
            oid = _oid()
            store.put(oid, b"m", data)
            name = store.segment_of(oid)
            assert name is not None  # pooled, non-canonical segment
            seg_names.add(name)
            got = store.get(oid)
            assert got is not None and bytes(got[1]) == data
            store.delete(oid)
        # Steady state: one physical segment served every cycle.
        assert len(seg_names) == 1
        st = store.stats()
        assert st["pool_created"] == 1
        assert st["pool_hits"] == 4
    finally:
        store.shutdown()


def test_pool_cap_unlinks_overflow():
    store = SharedMemoryStore(capacity_bytes=64 * 1024**2)
    try:
        store.pool.max_bytes = SegmentPool.MIN_CLASS  # room for ONE segment
        data = os.urandom(1024 * 1024 + 1)  # 2 MiB class
        a, b = _oid(), _oid()
        store.put(a, b"", data)
        store.put(b, b"", data)
        store.delete(a)   # 2 MiB > 1 MiB cap: unlinked, not pooled
        store.delete(b)
        assert store.stats()["pool_free_bytes"] == 0
    finally:
        store.shutdown()


def test_pool_prewarm_spec_parses_and_prefaults():
    pool = SegmentPool(max_bytes=16 * 1024**2)
    try:
        pool.prewarm("1MiB:2, bogus, 3nonsense:4")
        pool._prewarm_thread.join(timeout=10)
        st = pool.stats()
        assert st["pool_free_segments"] == 2
        assert st["pool_free_bytes"] == 2 * SegmentPool.MIN_CLASS
        shm, cls = pool.acquire(1000 * 1000)
        assert cls == SegmentPool.MIN_CLASS
        assert pool.hits == 1
        pool.release(shm, cls)
    finally:
        pool.close()


def test_unlinked_segment_drops_untracked_entry():
    store = SharedMemoryStore(capacity_bytes=64 * 1024**2)
    try:
        oid = _oid()
        store.put(oid, b"", os.urandom(512))  # tiny: dedicated segment
        shm = store_mod.attach(oid)
        name = shm._name
        shm.close()
        assert name in store_mod._untracked or name in store_mod._process_owned
        store.delete(oid)
        assert name not in store_mod._untracked
        assert name not in store_mod._process_owned
    finally:
        store.shutdown()


# ---------------------------------------------------------------------------
# Parallel pack_into
# ---------------------------------------------------------------------------
def test_parallel_pack_into_matches_single_threaded():
    values = [np.random.randint(0, 255, (9 * 1024 * 1024,), dtype=np.uint8),
              np.random.rand(512, 512), {"k": np.arange(100000)}, b"x" * 100]
    s = ser.serialize(values)
    size = ser.packed_size(s)
    meta_ref, data_ref = ser.pack(s)

    # Force the parallel path even on 1-cpu machines: 3 copy threads,
    # tiny threshold.
    saved = (ser._copy_pool, ser._copy_threads)
    from concurrent.futures import ThreadPoolExecutor
    ser._copy_pool, ser._copy_threads = ThreadPoolExecutor(2), 3
    try:
        from ray_tpu._private.config import CONFIG
        CONFIG.apply_system_config({"parallel_copy_min_bytes": 1024})
        buf = bytearray(size)
        meta = ser.pack_into(s, memoryview(buf))
    finally:
        CONFIG.reset()
        pool, (ser._copy_pool, ser._copy_threads) = ser._copy_pool, saved
        pool.shutdown(wait=True)

    assert pickle.loads(meta) == pickle.loads(meta_ref)
    assert bytes(buf[:len(data_ref)]) == bytes(data_ref)
    out, _ = ser.unpack(meta, memoryview(buf))
    assert np.array_equal(out[0], values[0])
    assert np.array_equal(out[1], values[1])
    assert np.array_equal(out[2]["k"], values[2]["k"])
    assert out[3] == values[3]


def test_single_thread_fallback_below_threshold():
    s = ser.serialize(np.arange(2048, dtype=np.int64))
    size = ser.packed_size(s)
    buf = bytearray(size)
    meta = ser.pack_into(s, memoryview(buf))  # below parallel threshold
    out, _ = ser.unpack(meta, memoryview(buf))
    assert np.array_equal(out, np.arange(2048))


# ---------------------------------------------------------------------------
# put_many / get_many + coalesced notifies
# ---------------------------------------------------------------------------
def test_put_many_get_many_roundtrip(ray_start_regular):
    values = [7, "s", None, np.arange(5),
              np.random.randint(0, 255, (300 * 1024,), dtype=np.uint8),
              {"a": np.random.rand(200, 300)}]
    refs = ray_tpu.put_many(values)
    assert len(refs) == len(values)
    out = ray_tpu.get_many(refs)
    assert out[0] == 7 and out[1] == "s" and out[2] is None
    assert np.array_equal(out[3], values[3])
    assert np.array_equal(out[4], values[4])
    assert np.array_equal(out[5]["a"], values[5]["a"])
    # refs also resolve through plain get / task args
    @ray_tpu.remote
    def total(a, b):
        return int(a.sum()) + int(b.sum())

    assert ray_tpu.get(total.remote(refs[3], refs[4])) == \
        int(values[3].sum()) + int(values[4].sum())


def test_put_many_coalesces_notifies_in_order(ray_start_regular):
    from ray_tpu._private.worker import global_worker as gw

    notifies = []
    orig = gw.transport.notify

    def spy(msg):
        notifies.append(msg)
        return orig(msg)

    gw.transport.notify = spy
    try:
        big = [np.full((200 * 1024,), i, dtype=np.uint8) for i in range(5)]
        refs = ray_tpu.put_many(big)
    finally:
        gw.transport.notify = orig
    batch = [m for m in notifies if m["type"] == "seal_batch"]
    singles = [m for m in notifies if m["type"] in ("seal", "put_inline")]
    assert len(batch) == 1 and not singles, \
        [m["type"] for m in notifies]
    # Ordering: batch items appear in submission order.
    assert [it["oid"] for it in batch[0]["items"]] == \
        [r.id.binary() for r in refs]
    out = ray_tpu.get_many(refs)
    for i, v in enumerate(out):
        assert v[0] == i and len(v) == 200 * 1024


def test_put_many_refs_survive_free_cycle(ray_start_regular):
    """Batched-holder registration must compose with the ref-gc batch
    removal path: freeing the refs releases the store bytes."""
    from ray_tpu._private.worker import global_worker as gw

    store = gw.transport.head.raylets[gw.node_id].store
    base = store.stats()["num_objects"]
    refs = ray_tpu.put_many(
        [np.random.randint(0, 255, (256 * 1024,), dtype=np.uint8)
         for _ in range(4)])
    assert store.stats()["num_objects"] == base + 4
    del refs
    gw._drain_ref_gc_queue()
    assert store.stats()["num_objects"] == base


def test_driver_put_lands_in_a_pooled_segment_and_worker_reads_it_in_place(
        ray_start_regular):
    """A driver's large put is one object in the head raylet's store, in a
    pooled segment; a worker that takes the ref as an argument reads the
    segment where it lies: its array borrows the mapping, it owns no
    copy."""
    from ray_tpu._private.worker import global_worker as gw

    store = gw.transport.head.raylets[gw.node_id].store
    base = store.stats()["num_objects"]
    x = np.arange(300_000, dtype=np.float64)  # 2.4 MB: a pooled size class
    ref = ray_tpu.put(x)
    assert store.stats()["num_objects"] == base + 1
    assert store.segment_of(ref.id).startswith("rtpu_pool_")

    @ray_tpu.remote
    def total(a):
        return float(a.sum()), bool(a.flags.owndata)

    assert ray_tpu.get(total.remote(ref)) == (float(x.sum()), False)
    # The driver reads it back through the store too, once its cache is
    # cleared, and as a view.
    gw._value_cache.clear()
    y = ray_tpu.get(ref)
    np.testing.assert_array_equal(x, y)
    assert not y.flags.owndata


def test_batched_get_with_a_failing_ref_strands_nothing_on_the_others(
        ray_start_regular):
    """get([bad, good]) raises at bad.  The resolution already handed out
    for good must leave no pin in the store: good is still readable, and
    dropping its ref frees its bytes."""
    from ray_tpu import exceptions as exc
    from ray_tpu._private.worker import global_worker as gw

    store = gw.transport.head.raylets[gw.node_id].store
    base = store.stats()["num_objects"]

    @ray_tpu.remote
    def boom():
        raise ValueError("nope")

    bad = boom.remote()
    good = ray_tpu.put(np.ones((1024, 512), np.float32))  # 2 MB: the store
    ray_tpu.wait([bad], num_returns=1)
    gw._value_cache.clear()  # force a real store read
    with pytest.raises(exc.TaskError):
        ray_tpu.get([bad, good])  # bad materializes first and raises
    assert store.stats()["num_pinned"] == 0
    assert float(ray_tpu.get(good).sum()) == 1024 * 512
    del good
    gw._value_cache.clear()
    gc.collect()  # the TaskError's traceback held get()'s list of refs
    gw._drain_ref_gc_queue()
    stats = store.stats()
    assert stats["num_objects"] == base and stats["num_pinned"] == 0


# ---------------------------------------------------------------------------
# Spill → restore under eviction pressure
# ---------------------------------------------------------------------------
def test_spill_and_restore_under_pressure():
    spill_dir = tempfile.mkdtemp()
    store = SharedMemoryStore(capacity_bytes=4 * 1024 * 1024,
                              spill_dir=spill_dir)
    try:
        a, b, c = _oid(), _oid(), _oid()
        da = os.urandom(2 * 1024 * 1024)
        db = os.urandom(2 * 1024 * 1024)
        dc = os.urandom(2 * 1024 * 1024)
        store.put(a, b"ma", da)
        store.put(b, b"mb", db)
        store.put(c, b"mc", dc)  # evicts a (LRU) to disk
        assert store.get(a) is None
        rec = store.spilled_lookup(a)
        assert rec is not None and rec["size"] == len(da)
        meta, data = store.read_spilled(a)
        assert meta == b"ma" and data == da
        # the other two are still memory-resident
        assert bytes(store.get(b)[1]) == db
        assert bytes(store.get(c)[1]) == dc
    finally:
        store.shutdown()


def test_adopt_over_capacity_triggers_spill():
    """Satellite: an adopt that lands over capacity must shed OTHER
    objects (spill/evict) instead of only logging."""
    spill_dir = tempfile.mkdtemp()
    store = SharedMemoryStore(capacity_bytes=3 * 1024 * 1024,
                              spill_dir=spill_dir)
    try:
        resident = _oid()
        store.put(resident, b"r", os.urandom(2 * 1024 * 1024))
        # Simulate a worker-created segment adopted by the raylet.
        from multiprocessing import shared_memory

        adopted = _oid()
        payload = os.urandom(2 * 1024 * 1024)
        shm = shared_memory.SharedMemory(
            name=store_mod._segment_name(adopted), create=True,
            size=len(payload))
        shm.buf[:] = payload
        store.adopt(adopted, len(payload), b"x")
        shm.close()
        # Over capacity resolved by spilling the resident object...
        assert store.used <= store.capacity
        assert store.spilled_lookup(resident) is not None
        # ...never the freshly adopted one.
        assert bytes(store.get(adopted)[1]) == payload
    finally:
        store.shutdown()


def test_adopt_pooled_segment_name():
    """adopt() must attach by the explicit segment name when given."""
    store = SharedMemoryStore(capacity_bytes=16 * 1024 * 1024)
    try:
        from multiprocessing import shared_memory

        oid = _oid()
        payload = os.urandom(4096)
        shm = shared_memory.SharedMemory(name="rtpu_test_seg_xyz",
                                         create=True, size=len(payload))
        store_mod.note_owned(shm)
        shm.buf[:] = payload
        store.adopt(oid, len(payload), b"m", segment="rtpu_test_seg_xyz")
        assert bytes(store.get(oid)[1]) == payload
        store.delete(oid)
    finally:
        store.shutdown()


# ---------------------------------------------------------------------------
# routable_ip caching
# ---------------------------------------------------------------------------
def test_routable_ip_cached(monkeypatch):
    from ray_tpu._private import transfer

    calls = {"n": 0}

    def probe():
        calls["n"] += 1
        return "10.1.2.3"

    monkeypatch.setattr(transfer, "_probe_routable_ip", probe)
    monkeypatch.setattr(transfer, "_routable_ip_cache", None)
    assert transfer.routable_ip() == "10.1.2.3"
    assert transfer.routable_ip() == "10.1.2.3"
    assert calls["n"] == 1
