"""SharedMemoryStore view lifecycle: the canonical zero-copy view is
shared by all readers and reclaimed deterministically at delete/shutdown,
so shm.close() succeeds instead of spamming "BufferError: cannot close
exported pointers exist" in the bench tail (ISSUE 2 satellite)."""
import os
import warnings

import pytest

from ray_tpu._private.ids import ObjectID
from ray_tpu._private.object_store import SharedMemoryStore


def _oid():
    return ObjectID(os.urandom(20))


@pytest.fixture
def store():
    s = SharedMemoryStore(capacity_bytes=64 * 1024 * 1024)
    yield s
    s.shutdown()


def test_get_hands_out_one_canonical_view(store):
    oid = _oid()
    store.put(oid, b"meta", b"abcd" * 256)
    _, v1 = store.get(oid)
    _, v2 = store.get(oid)
    assert v1 is v2  # repeated reads don't accumulate exported pointers
    assert bytes(v1[:4]) == b"abcd"


def test_delete_reclaims_view_and_closes_segment(store):
    oid = _oid()
    buf = store.create(oid, 1024)
    buf[:4] = b"wxyz"
    store.seal(oid)
    _, view = store.get(oid)
    store.delete(oid)
    # Deterministic reclaim: the handed-out view is dead, not leaked.
    with pytest.raises(ValueError):
        view[:1]
    with pytest.raises(ValueError):
        buf[:1]
    assert store.stats()["num_objects"] == 0


def test_shutdown_with_exported_views_is_silent(store):
    views = []
    for _ in range(8):
        oid = _oid()
        store.put(oid, b"", b"x" * 4096)
        views.append(store.get(oid)[1])
    with warnings.catch_warnings():
        warnings.simplefilter("error")  # any BufferError noise -> failure
        store.shutdown()
    assert store.stats()["num_objects"] == 0
    for v in views:  # every handed-out view was reclaimed
        with pytest.raises(ValueError):
            v[:1]


def test_reader_chunk_slices_survive_parent_reclaim(store):
    """Chunked senders slice the canonical view; those slices borrow the
    mmap directly, so reclaiming the parent mid-send must not invalidate
    an in-flight chunk (it just defers the segment close)."""
    oid = _oid()
    store.put(oid, b"", b"ab" * 512)
    _, view = store.get(oid)
    chunk = view[0:4]
    store.delete(oid)
    assert bytes(chunk) == b"abab"  # still valid until the reader drops it
    del chunk


@pytest.mark.parametrize("reader", ["export", "pin", "none"])
def test_segment_under_a_reader_is_not_recycled(store, reader):
    """A pooled segment goes back to the pool's free list at delete only
    when nothing in this process still reads it: a C-level export of the
    canonical view (a numpy array over it) or a pin (a transfer mid-send)
    keeps it out, so the next put of that size class gets other memory and
    the reader keeps seeing the bytes it was given."""
    import pickle

    payload = b"\x07" * (2 * 1024 * 1024)  # 2 MiB: a pooled size class
    oid = _oid()
    store.put(oid, b"m", payload)
    first = store.segment_of(oid)
    assert first is not None  # pooled, non-canonical name
    _, view = store.get(oid)
    held = None
    if reader == "export":
        # Holds a Py_buffer taken from the view itself (numpy's frombuffer
        # gives its one back at once and would not count).
        held = pickle.PickleBuffer(view)
    elif reader == "pin":
        store.pin(oid)
        held = view[:16]  # the chunk in flight
    del view
    store.delete(oid)
    recycled = store.stats()["pool_free_segments"]
    nxt = _oid()
    store.put(nxt, b"m", b"\x00" * len(payload))
    if reader == "none":
        assert recycled == 1 and store.segment_of(nxt) == first
    else:
        assert recycled == 0 and store.segment_of(nxt) != first
        assert bytes(memoryview(held)[:16]) == payload[:16]  # not zeros
        if reader == "export":
            held.release()
    del held


def test_defuse_shm_silences_del_with_live_exports():
    """The interpreter-shutdown guard (ISSUE 5 satellite): a segment whose
    mmap still has C-level buffer exports (numpy views) cannot close() —
    defuse_shm must drop the handles so SharedMemory.__del__'s close() is
    a silent no-op instead of the bench-tail BufferError traceback."""
    from multiprocessing import shared_memory

    import numpy as np

    from ray_tpu._private import object_store as store_mod

    shm = shared_memory.SharedMemory(create=True, size=4096)
    store_mod.note_owned(shm)
    store_mod.track_for_exit(shm)
    arr = np.frombuffer(shm.buf, dtype=np.uint8)  # live C-level export
    arr[:4] = 7
    name = shm.name
    assert store_mod.defuse_shm(shm) is False  # export kept close() from
    # completing, but the handles are gone:
    assert getattr(shm, "_mmap", None) is None
    assert getattr(shm, "_fd", -1) == -1
    shm.close()  # what __del__ does at interpreter shutdown — now silent
    assert (arr[:4] == 7).all()  # the mapping survives for the exporter
    del arr
    # Clean the name from /dev/shm (a fresh handle owns the unlink).
    cleanup = shared_memory.SharedMemory(name=name)
    store_mod.untrack(cleanup)
    cleanup.close()
    try:
        cleanup.unlink()
    except FileNotFoundError:
        pass


def test_exit_guard_defuses_tracked_segments():
    """_defuse_all_at_exit walks every tracked handle: segments with live
    exports are defused, fully-closeable ones are closed."""
    from multiprocessing import shared_memory

    import numpy as np

    from ray_tpu._private import object_store as store_mod

    a = shared_memory.SharedMemory(create=True, size=1024)
    b = shared_memory.SharedMemory(create=True, size=1024)
    for s in (a, b):
        store_mod.note_owned(s)
        store_mod.track_for_exit(s)
    view = np.frombuffer(a.buf, dtype=np.uint8)  # pin a only
    store_mod._defuse_all_at_exit()
    assert getattr(a, "_mmap", None) is None  # defused (export live)
    assert getattr(b, "_mmap", None) is None  # plain-closed
    a.close()  # both now silent under __del__-style retries
    b.close()
    del view
    for s in (a, b):
        try:
            shared_memory.SharedMemory(name=s.name).unlink()
        except FileNotFoundError:
            pass


def test_patched_del_never_raises_with_live_exports():
    """The ISSUE 12 satellite: SharedMemory.__del__ itself routes
    through the defuse guard, so GC'ing a handle whose mmap still has
    numpy-view exports never prints an ignored BufferError — even for
    segments nobody registered with track_for_exit (the mid-run GC
    case, not just interpreter shutdown)."""
    import gc
    from multiprocessing import shared_memory

    import numpy as np

    from ray_tpu._private import object_store as store_mod

    assert shared_memory.SharedMemory.__del__ is store_mod._shm_del

    shm = shared_memory.SharedMemory(create=True, size=2048)
    store_mod.untrack(shm)
    name = shm.name
    view = np.frombuffer(shm.buf, dtype=np.uint8)  # live C-level export
    view[:2] = 9
    with warnings.catch_warnings():
        # An escaping __del__ exception surfaces as an "Exception
        # ignored" unraisable event; fail the test if one fires.
        warnings.simplefilter("error")
        shm.__del__()  # exactly what GC runs — must be silent
    assert (view[:2] == 9).all()  # exporter's mapping survives
    del view, shm
    gc.collect()
    cleanup = shared_memory.SharedMemory(name=name)
    store_mod.untrack(cleanup)
    cleanup.close()
    try:
        cleanup.unlink()
    except FileNotFoundError:
        pass
