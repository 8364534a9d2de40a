"""Object spilling under memory pressure (reference:
src/ray/raylet/local_object_manager.h:41 — referenced objects spill to disk
instead of failing; gets restore them transparently)."""
import os

import numpy as np
import pytest

import ray_tpu

MB = 1024 * 1024


@pytest.fixture
def small_store_cluster():
    ray_tpu.init(num_cpus=2, object_store_memory=8 * MB)
    yield
    ray_tpu.shutdown()


def test_put_twice_capacity_then_get_all(small_store_cluster):
    """2x store capacity of live referenced puts: older objects spill, every
    get returns correct bytes (the VERDICT's done-criterion)."""
    refs, expect = [], []
    for i in range(8):  # 8 x 2MB = 16MB through an 8MB store
        arr = np.full(2 * MB // 8, i, dtype=np.int64)
        refs.append(ray_tpu.put(arr))
        expect.append(arr)
    head = ray_tpu._head
    raylet = next(iter(head.raylets.values()))
    assert raylet.store._spilled, "nothing spilled under 2x pressure"
    for ref, arr in zip(refs, expect):
        got = ray_tpu.get(ref, timeout=30)
        np.testing.assert_array_equal(got, arr)


def test_task_returns_spill_and_restore(small_store_cluster):
    @ray_tpu.remote
    def make(i):
        return np.full(2 * MB // 8, i, dtype=np.int64)

    refs = [make.remote(i) for i in range(8)]
    for i, ref in enumerate(refs):
        got = ray_tpu.get(ref, timeout=60)
        assert got[0] == i and got[-1] == i


def test_worker_reads_spilled_object(small_store_cluster):
    @ray_tpu.remote
    def head_of(arr):
        return int(arr[0])

    refs = [ray_tpu.put(np.full(2 * MB // 8, i, dtype=np.int64))
            for i in range(8)]
    # Consume the OLDEST ref (most likely spilled) from a worker process.
    assert ray_tpu.get(head_of.remote(refs[0]), timeout=60) == 0


def test_unreferenced_objects_do_not_spill(small_store_cluster):
    from ray_tpu._private.worker import global_worker as gw

    for i in range(6):
        ref = ray_tpu.put(np.zeros(2 * MB // 8, dtype=np.int64))
        del ref  # release: eviction should drop, not spill
        # The drop rides the ref-gc thread, which lets drops settle into
        # batches; on a loaded host the next puts fill the store first and
        # the object, still referenced as far as the head knows, spills,
        # as it should.  The promise is about released objects: hand the
        # release over before the next put.
        gw._drain_ref_gc_queue()
    head = ray_tpu._head
    raylet = next(iter(head.raylets.values()))
    spill_dir = raylet.store.spill_dir
    n_files = len(os.listdir(spill_dir)) if os.path.isdir(spill_dir) else 0
    assert n_files == 0


# ---------------------------------------------------------------------------
# Node-loss durability (ISSUE 7): spill records outlive their store AND the
# head process, and restores are byte-exact.
# ---------------------------------------------------------------------------
@pytest.fixture
def two_node_spill_cluster():
    """Head node with room + a second tiny-store node whose referenced
    puts spill under pressure."""
    ray_tpu.init(num_cpus=2, object_store_memory=64 * MB)
    from ray_tpu.cluster_utils import Cluster

    cluster = Cluster(initialize_head=False)
    node2 = cluster.add_node(num_cpus=2, object_store_memory=8 * MB)
    yield ray_tpu._head, node2
    ray_tpu.shutdown()


def test_spill_then_owner_node_death_restores_byte_exact(
        two_node_spill_cluster):
    """Eviction-spilled objects survive their owning NODE's death: the
    head's directory-side spill record points at the on-disk file, and
    the restore into a surviving store is byte-exact."""
    from ray_tpu._private.recovery import (recovery_stats,
                                           reset_recovery_stats)
    from ray_tpu.util.scheduling_strategies import (
        NodeAffinitySchedulingStrategy,
    )
    from ray_tpu.util.testing import wait_for_condition

    reset_recovery_stats()
    head, node2 = two_node_spill_cluster
    # Hard affinity: every put must go THROUGH node2's tiny store (the
    # tasks all complete before the kill, so nothing needs rescheduling).
    aff = NodeAffinitySchedulingStrategy(node2, soft=False)

    @ray_tpu.remote
    def put_arr(i):
        import numpy as np

        import ray_tpu

        return ray_tpu.put(np.arange(2 * MB // 8, dtype=np.int64) * (i + 1))

    # 6 x 2MB of live referenced puts through node2's 8MB store: the
    # oldest spill to disk.
    refs = ray_tpu.get(
        [put_arr.options(scheduling_strategy=aff).remote(i)
         for i in range(6)], timeout=60)
    with head._lock:
        raylet2 = head.raylets[node2]
    assert raylet2.store._spilled, "nothing spilled under pressure"

    # The directory must know about every spill record (the piece that
    # survives the node) before the node dies.
    def records_known():
        with head._lock:
            spilled = list(raylet2.store._spilled)
            return spilled and all(
                (e := head.gcs.object_lookup(o)) is not None
                and e.spill is not None for o in spilled)
    wait_for_condition(records_known, timeout=30)

    with head._lock:
        spilled_pre_kill = set(raylet2.store._spilled)
    head.kill_node(node2)
    restored = 0
    for i, ref in enumerate(refs):
        if ref.id in spilled_pre_kill:
            # On disk when the node died: restored byte-exact.
            got = ray_tpu.get(ref, timeout=60)
            np.testing.assert_array_equal(
                got, np.arange(2 * MB // 8, dtype=np.int64) * (i + 1))
            restored += 1
        else:
            # Memory-only put, durability off: typed loss, never a hang.
            with pytest.raises(ray_tpu.exceptions.ObjectLostError):
                ray_tpu.get(ref, timeout=60)
    assert restored >= 1
    assert recovery_stats()["objects_restored"] >= restored


def test_spill_record_survives_head_kill9_restart(tmp_path, monkeypatch):
    """The durability contract's last leg: a spill record written before
    the head is SIGKILLed is restored from the GCS snapshot by the next
    head incarnation, and the object's bytes come back byte-exact from
    the on-disk file (reference: GCS FT over redis_store_client.h:28)."""
    from ray_tpu._private.config import CONFIG
    from ray_tpu._private.head import Head
    from ray_tpu._private.ids import ObjectID, TaskID
    from ray_tpu.util.testing import wait_for_condition

    monkeypatch.setenv("RAY_TPU_OBJECT_DURABILITY", "spill")
    CONFIG.reset()
    session = str(tmp_path / "session")
    head1 = Head(session_dir=session)
    try:
        node = head1.add_node({"CPU": 1.0}, store_capacity=64 * MB)
        oid = ObjectID.for_put(TaskID.from_random(), 1)
        data = np.arange(300_000, dtype=np.int64).tobytes()
        raylet = head1.raylets[node]
        buf = raylet.store.create(oid, len(data))
        buf[:] = data
        raylet.store.seal(oid, b"meta")
        head1.on_seal({"oid": oid.binary(), "node_id": node.binary(),
                       "size": len(data), "meta": b"meta"})

        def backed_up():
            with head1._lock:
                e = head1.gcs.object_lookup(oid)
                return e is not None and e.spill is not None
        wait_for_condition(backed_up, timeout=30)
        head1.gcs.save_snapshot(head1.gcs_snapshot_path)
    finally:
        # kill9: no graceful shutdown — stores are NOT drained, spill
        # files are NOT cleaned; just stop the listeners so the restarted
        # head can rebind the session socket.
        head1._shutdown = True
        for lsn in (head1._listener, head1._tcp_listener):
            try:
                lsn.close()
            except Exception:
                pass

    head2 = Head(session_dir=session)
    try:
        entry = head2.gcs.object_lookup(oid)
        assert entry is not None and entry.spill is not None, \
            "spill record did not survive the head restart"
        node2 = head2.add_node({"CPU": 1.0}, store_capacity=64 * MB)
        with head2._lock:
            assert head2._try_reconstruct(oid, entry), \
                "restore from spill record failed"
        got = head2.raylets[node2].store.get(oid)
        assert got is not None
        meta, view = got
        assert bytes(view) == data  # byte-exact restore
        assert meta == b"meta"
    finally:
        head2.shutdown()
        CONFIG.reset()
