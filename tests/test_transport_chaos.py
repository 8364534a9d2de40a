"""Transport fault-injection tests: deadlines, retries, dedup, reconnect.

Layer 1 (unit, tier-1): ConnTransport/DirectTransport against fake heads
over in-process Pipes — timeout enforcement, transparent retry with
exactly-once application, the close()/replace_conn() races, reconnect
resend, the reply cache, and the hung-call watchdog surface.

Layer 2 (integration, tier-1): a real cluster under deterministic
RAY_TPU_TESTING_NET_SCHEDULE fault schedules — dropped replies, dropped
seal notifies, duplicated submit/actor frames.

Layer 3 (full matrix, @pytest.mark.chaos + slow, nightly): every fault
kind crossed with every op class.

The no-hang invariant is enforced with an outer alarm: every blocking
call must resolve within 2x its deadline or the alarm fails the test
instead of wedging the suite.
"""
import contextlib
import os
import signal
import threading
import time
from multiprocessing.connection import Pipe

import pytest

import ray_tpu
from ray_tpu import exceptions as exc
from ray_tpu._private import chaos as chaos_mod
from ray_tpu._private import retry as retry_mod
from ray_tpu._private.config import CONFIG
from ray_tpu._private.retry import ReplyCache
from ray_tpu._private.worker import ConnTransport


@contextlib.contextmanager
def no_hang(seconds: float):
    """Outer alarm: fail (don't wedge) if the body blocks past the bound."""

    def on_alarm(signum, frame):
        raise AssertionError(
            f"no-hang invariant violated: test body exceeded {seconds}s")

    old = signal.signal(signal.SIGALRM, on_alarm)
    signal.setitimer(signal.ITIMER_REAL, seconds)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, old)


@pytest.fixture
def fast_rpc():
    """Short attempt timeouts so retries happen at test speed."""
    CONFIG.apply_system_config({"rpc_attempt_timeout": 0.25,
                                "rpc_watchdog_interval_s": 0.1})
    yield
    CONFIG.reset()


@pytest.fixture
def net_env(monkeypatch):
    """Set a net-fault schedule + fast-retry env BEFORE init so spawned
    workers inherit it; direct transport is disabled so every submission
    rides the RPC plane under test."""

    def set_schedule(spec: str):
        ray_tpu.shutdown()
        monkeypatch.setenv(chaos_mod.NET_SCHEDULE_ENV, spec)
        monkeypatch.setenv("RAY_TPU_RPC_ATTEMPT_TIMEOUT", "0.3")
        monkeypatch.setenv("RAY_TPU_DIRECT_TRANSPORT", "0")
        CONFIG.reset()

    yield set_schedule
    ray_tpu.shutdown()
    monkeypatch.delenv(chaos_mod.NET_SCHEDULE_ENV, raising=False)
    CONFIG.reset()


class _FakeHead:
    """Minimal head over a Pipe: serves `request` frames through a REAL
    ReplyCache, so client retries exercise the same exactly-once
    admission the live head runs.  ``behavior(op, n)`` decides what
    happens to the n-th reply *delivery* for a key: "reply" | "drop"."""

    def __init__(self, conn, behavior=None, die_after_frames=None):
        self.conn = conn
        self.behavior = behavior or (lambda op, n: "reply")
        self.die_after_frames = die_after_frames
        self.cache = ReplyCache()
        self.executed = []      # ops actually applied (post-dedup)
        self.frames = []        # every request frame received
        self.lock = threading.Lock()
        self._deliveries = {}
        self._thread = threading.Thread(target=self._serve, daemon=True)
        self._thread.start()

    def _serve(self):
        while True:
            try:
                msg = self.conn.recv()
            except (EOFError, OSError):
                return
            if msg.get("type") != "request":
                continue
            op = msg["op"]
            key = msg.get("rpc_key")
            with self.lock:
                self.frames.append(msg)
            if (self.die_after_frames is not None
                    and len(self.frames) >= self.die_after_frames):
                # Die from the serve thread itself so the close actually
                # shuts the socket down (a real head death delivers EOF).
                self.conn.close()
                return

            def send_reply(value=None, error=None, _op=op, _key=key,
                           _mid=msg["msg_id"]):
                with self.lock:
                    n = self._deliveries.get(_key, 0) + 1
                    self._deliveries[_key] = n
                if self.behavior(_op, n) == "drop":
                    return
                try:
                    self.conn.send({"type": "reply", "msg_id": _mid,
                                    "op": _op, "ok": error is None,
                                    "value": value, "error": error})
                except (OSError, BrokenPipeError):
                    pass

            if key is not None:
                run, wrapped = self.cache.admit(key, send_reply)
                if not run:
                    continue
                send_reply = wrapped
            with self.lock:
                self.executed.append(op)
            send_reply({"op": op})


def _wire(transport):
    """Reader thread pumping replies into the transport; survives conn
    replacement (re-reads transport.conn like default_worker's loop)."""

    stop = threading.Event()

    def reader():
        while not stop.is_set():
            try:
                msg = transport.conn.recv()
            except (EOFError, OSError):
                time.sleep(0.02)
                continue
            if msg.get("type") == "reply":
                transport.on_reply(msg)

    t = threading.Thread(target=reader, daemon=True)
    t.start()
    return stop


# ---------------------------------------------------------------------------
# Layer 1: transport units
# ---------------------------------------------------------------------------

def test_conn_request_timeout_enforced():
    """Satellite 1: a lost reply must raise RpcTimeoutError within the
    caller's budget, not block forever (worker.py used fut.result())."""
    a, b = Pipe()
    _FakeHead(b, behavior=lambda op, n: "drop")
    tr = ConnTransport(a, authkey=b"k")
    stop = _wire(tr)
    with no_hang(10.0):
        t0 = time.monotonic()
        with pytest.raises(exc.RpcTimeoutError) as ei:
            tr.request("resolve_batch", {"oids": []}, timeout=0.4)
        elapsed = time.monotonic() - t0
    assert elapsed < 0.8 * 2, f"blocked {elapsed:.2f}s past 2x deadline"
    assert "resolve_batch" in str(ei.value)
    stop.set()
    tr.close()


def test_direct_request_timeout_enforced():
    """DirectTransport.request must enforce its timeout too (worker.py:62):
    a head handler that defers its reply forever may not wedge the driver."""
    from ray_tpu._private.ids import WorkerID
    from ray_tpu._private.worker import DirectTransport

    class _NeverHead:
        authkey = b"k"
        raylets = {}

        def handle_request(self, op, payload, reply, caller):
            pass  # deferred reply that never fires

    tr = DirectTransport(_NeverHead(), WorkerID.from_random())
    with no_hang(10.0):
        with pytest.raises(exc.RpcTimeoutError):
            tr.request("get_locations", {"oid": None}, timeout=0.3)


def test_dropped_reply_transparent_retry_exactly_once(fast_rpc):
    """A dropped reply is invisible to the caller: the frame is resent,
    the head's reply cache replays the recorded reply, and the op is
    applied exactly once."""
    a, b = Pipe()
    head = _FakeHead(b, behavior=lambda op, n: "drop" if n == 1 else "reply")
    tr = ConnTransport(a, authkey=b"k")
    stop = _wire(tr)
    before = retry_mod.rpc_stats()["retries"]
    with no_hang(20.0):
        out = tr.request("object_info", {"oid": b"x"}, timeout=10.0)
    assert out == {"op": "object_info"}
    assert head.executed.count("object_info") == 1, head.executed
    assert len(head.frames) >= 2, "no resend happened"
    assert retry_mod.rpc_stats()["retries"] > before
    stop.set()
    tr.close()


def test_duplicated_frame_applied_once(fast_rpc):
    """Chaos dup on the wire: both frames reach the head; the reply cache
    applies the op once and answers both."""
    a, b = Pipe()
    head = _FakeHead(b)
    dup_ops = {"count": 0}

    def sched(label):
        if label.startswith("request:kv"):
            dup_ops["count"] += 1
            return ("dup", 0.0)
        return None

    tr = ConnTransport(chaos_mod.FaultableConn(a, schedule_fn=sched),
                       authkey=b"k")
    stop = _wire(tr)
    with no_hang(20.0):
        out = tr.request("kv", {"verb": "get"}, timeout=10.0)
    assert out == {"op": "kv"}
    # The reply to the first frame can beat the head's reader thread to
    # the second: wait for it as long as the request itself may take,
    # not for a share of a loaded host's next two seconds.
    deadline = time.monotonic() + 20.0
    while len(head.frames) < 2 and time.monotonic() < deadline:
        time.sleep(0.01)
    assert len(head.frames) == 2, "dup frame did not reach the head"
    assert head.executed.count("kv") == 1, head.executed
    stop.set()
    tr.close()


def test_close_covers_allocate_then_send_window(fast_rpc):
    """Satellite 2 regression: a request that allocated its future but
    has not yet sent must fail promptly across close(), not hang."""
    a, b = Pipe()
    _FakeHead(b)
    tr = ConnTransport(a, authkey=b"k")
    stop = _wire(tr)
    in_send = threading.Event()
    gate = threading.Event()
    orig_send = tr.send

    def stalled_send(msg):
        in_send.set()
        gate.wait(5.0)
        return orig_send(msg)

    tr.send = stalled_send
    result = {}

    def run():
        try:
            tr.request("ping", {}, timeout=10.0)
            result["r"] = "returned"
        except BaseException as e:  # noqa: BLE001
            result["r"] = e

    th = threading.Thread(target=run, daemon=True)
    with no_hang(10.0):
        th.start()
        assert in_send.wait(2.0)
        tr.close()        # sweeps the allocated-but-unsent future
        gate.set()        # the send now proceeds against a closed conn
        th.join(3.0)
        assert not th.is_alive(), "request hung across close()"
    assert isinstance(result["r"], exc.RayTpuError), result
    stop.set()


def test_replace_conn_resends_unacked(fast_rpc):
    """Reconnect resend: an in-flight request survives replace_conn —
    it is resent (same idempotency key) on the new conn after the
    handshake instead of erroring."""
    a1, b1 = Pipe()
    a2, b2 = Pipe()
    # Drops the first request's reply, dies on the resend: the classic
    # lost-reply-then-head-death sequence.
    head1 = _FakeHead(b1, behavior=lambda op, n: "drop", die_after_frames=2)
    tr = ConnTransport(a1, authkey=b"k")
    stop = _wire(tr)
    result = {}

    def run():
        try:
            result["r"] = tr.request("object_info", {"oid": b"y"},
                                     timeout=15.0)
        except BaseException as e:  # noqa: BLE001
            result["r"] = e

    th = threading.Thread(target=run, daemon=True)
    with no_hang(30.0):
        th.start()
        head1._thread.join(10.0)   # head processed 2 frames and died
        assert not head1._thread.is_alive()
        assert head1.frames, "request never reached the first head"
        time.sleep(0.1)            # reader observes the EOF
        tr.replace_conn(a2, hold_resend=True)
        head2 = _FakeHead(b2)
        tr.release_resend()
        th.join(10.0)
        assert not th.is_alive(), "request hung across replace_conn"
    assert result["r"] == {"op": "object_info"}, result
    assert head2.executed.count("object_info") == 1
    # Same logical rpc on both conns: identical idempotency key.
    k1 = head1.frames[0]["rpc_key"]
    assert any(f["rpc_key"] == k1 for f in head2.frames)
    stop.set()
    tr.close()


def test_reply_cache_exactly_once_semantics():
    cache = ReplyCache(cap=8, ttl=60.0)
    got = []

    def reply_a(value=None, error=None):
        got.append(("a", value))

    def reply_b(value=None, error=None):
        got.append(("b", value))

    def reply_c(value=None, error=None):
        got.append(("c", value))

    run, wrapped = cache.admit(b"k1", reply_a)
    assert run
    # Duplicate while in progress: attaches, does not run.
    run2, w2 = cache.admit(b"k1", reply_b)
    assert not run2 and w2 is None
    assert got == []
    wrapped(42)   # first execution replies -> original + attached waiter
    assert ("a", 42) in got and ("b", 42) in got
    # Late duplicate after done: replayed immediately from the cache.
    run3, _ = cache.admit(b"k1", reply_c)
    assert not run3
    assert ("c", 42) in got


def test_inflight_stats_and_hang_dump(fast_rpc):
    """The watchdog surface: pending RPC age is observable and a call
    older than rpc_hang_dump_s gets its stack dumped (once)."""
    CONFIG.apply_system_config({"rpc_hang_dump_s": 0.3,
                                "rpc_attempt_timeout": 0.25,
                                "rpc_watchdog_interval_s": 0.05})
    a, b = Pipe()
    _FakeHead(b, behavior=lambda op, n: "drop")
    tr = ConnTransport(a, authkey=b"k")
    stop = _wire(tr)
    dumps_before = retry_mod.rpc_stats()["hang_dumps"]
    result = {}

    def run():
        try:
            tr.request("wait_ready", {}, timeout=2.0)
        except BaseException as e:  # noqa: BLE001
            result["r"] = e

    th = threading.Thread(target=run, daemon=True)
    with no_hang(15.0):
        th.start()
        time.sleep(0.15)
        stats = retry_mod.rpc_inflight_stats()
        assert stats["count"] >= 1
        assert any(r.op == "wait_ready" for r in tr.pending_rpcs())
        deadline = time.monotonic() + 3.0
        while (retry_mod.rpc_stats()["hang_dumps"] <= dumps_before
               and time.monotonic() < deadline):
            time.sleep(0.05)
        assert retry_mod.rpc_stats()["hang_dumps"] > dumps_before
        th.join(5.0)
    assert isinstance(result["r"], exc.RpcTimeoutError)
    stop.set()
    tr.close()


def test_net_schedule_parse_and_determinism():
    spec = "reply:resolve:drop:0.5:42;submit:dup:1.0:7:2"
    s1 = chaos_mod.NetSchedule.from_spec(spec)
    s2 = chaos_mod.NetSchedule.from_spec(spec)
    seq1 = [s1.fault("reply:resolve_batch") for _ in range(32)]
    seq2 = [s2.fault("reply:resolve_batch") for _ in range(32)]
    assert seq1 == seq2, "seeded schedule must replay identically"
    assert any(f is not None for f in seq1)
    # times cap: exactly 2 dup triggers, then the link heals.
    hits = [s1.fault("request:submit") for _ in range(10)]
    assert sum(1 for h in hits if h is not None) == 2


def test_faultable_conn_sever_breaks_both_ends():
    a, b = Pipe()
    fc = chaos_mod.FaultableConn(a, schedule_fn=lambda label: ("sever", 0.0))
    with pytest.raises(OSError):
        fc.send({"type": "request", "op": "x", "msg_id": 1})
    with pytest.raises((EOFError, OSError)):
        b.recv()  # peer observes the severed conn too


def test_driver_registration_error_is_typed():
    """Satellite 3: joining a dead head raises HeadConnectionError naming
    the address and whether the socket ever connected."""
    from ray_tpu._private.driver_client import RemoteDriverRuntime

    with no_hang(30.0):
        with pytest.raises(exc.HeadConnectionError) as ei:
            RemoteDriverRuntime("127.0.0.1:9", authkey=b"deadbeef",
                                store_capacity=1 * 1024**2, timeout=0.5)
    err = ei.value
    assert "127.0.0.1:9" in str(err)
    assert err.socket_connected is False
    assert isinstance(err, ConnectionError)


def test_driver_registration_timeout_socket_connected():
    """The head accepted the socket but never completed registration:
    socket_connected must be True and the elapsed time reported."""
    from multiprocessing.connection import Listener

    from ray_tpu._private.driver_client import RemoteDriverRuntime

    authkey = b"secret-key"
    listener = Listener(("127.0.0.1", 0), family="AF_INET", authkey=authkey)
    addr = f"127.0.0.1:{listener.address[1]}"
    conns = []

    def accept_loop():
        try:
            while True:
                conns.append(listener.accept())  # handshake, then silence
        except (OSError, EOFError):
            pass

    t = threading.Thread(target=accept_loop, daemon=True)
    t.start()
    try:
        with no_hang(30.0):
            with pytest.raises(exc.HeadConnectionError) as ei:
                RemoteDriverRuntime(addr, authkey=authkey,
                                    store_capacity=1 * 1024**2, timeout=0.6)
        err = ei.value
        assert err.socket_connected is True
        assert addr in str(err)
        assert err.elapsed >= 0.5
    finally:
        listener.close()
        for c in conns:
            try:
                c.close()
            except Exception:
                pass


# ---------------------------------------------------------------------------
# Layer 2: real cluster under fault schedules (fast, tier-1)
# ---------------------------------------------------------------------------

def _sum_task_workload(n=12):
    @ray_tpu.remote
    def double(i):
        return i * 2

    refs = [double.remote(i) for i in range(n)]
    return ray_tpu.get(refs), [i * 2 for i in range(n)]


def test_cluster_dropped_replies_exact_results(net_env):
    """~30% of resolve/get_locations replies vanish: every get() still
    returns exact results via transparent retry — the drop is invisible."""
    net_env("reply:resolve:drop:0.3:11;reply:get_locations:drop:0.3:12;"
            "reply:submit:drop:0.3:13")
    ray_tpu.init(num_cpus=2, object_store_memory=128 * 1024**2)
    with no_hang(120.0):
        got, want = _sum_task_workload()
    assert got == want


def test_cluster_actor_counter_linearizable_under_dup(net_env):
    """Every actor_call/submit frame duplicated: the counter must stay
    linearizable (each inc applied exactly once via the reply cache)."""
    net_env("request:actor_call:dup:1.0:5;request:submit:dup:1.0:6")
    ray_tpu.init(num_cpus=2, object_store_memory=128 * 1024**2)

    @ray_tpu.remote
    class Counter:
        def __init__(self):
            self.n = 0

        def inc(self):
            self.n += 1
            return self.n

        def value(self):
            return self.n

    with no_hang(120.0):
        c = Counter.remote()
        ray_tpu.get([c.inc.remote() for _ in range(20)])
        assert ray_tpu.get(c.value.remote()) == 20


def test_cluster_seal_drop_acked_notifies(net_env):
    """Dropped seal/seal_batch notifies are retried (acked mode) so large
    puts stay resolvable — exact bytes back."""
    import numpy as np

    net_env("seal:drop:0.4:7")
    ray_tpu.init(num_cpus=2, object_store_memory=128 * 1024**2)
    with no_hang(120.0):
        arrays = [np.full((256 * 1024,), i, dtype=np.int32)
                  for i in range(5)]
        refs = [ray_tpu.put(a) for a in arrays]
        out = ray_tpu.get(refs)
    for a, o in zip(arrays, out):
        assert (a == o).all()


def test_cluster_no_leaked_refs_under_remove_ref_drop(net_env):
    """Dropped remove_ref frames are retried: freed objects leave the
    directory (no permanently leaked holders)."""
    import gc

    net_env("request:remove_ref:drop:0.5:9;notify_msg:remove_ref:drop:0.5:10")
    ray_tpu.init(num_cpus=1, object_store_memory=128 * 1024**2)
    with no_hang(120.0):
        import numpy as np

        ref = ray_tpu.put(np.zeros(300 * 1024, dtype=np.uint8))
        oid = ref.id
        head = ray_tpu._global_head()
        assert head.gcs.object_lookup(oid) is not None
        del ref
        gc.collect()
        deadline = time.monotonic() + 60.0
        while (head.gcs.object_lookup(oid) is not None
               and time.monotonic() < deadline):
            time.sleep(0.1)
        assert head.gcs.object_lookup(oid) is None, \
            "dropped remove_ref leaked the object"


# ---------------------------------------------------------------------------
# Layer 3: full fault x op matrix (nightly: pytest -m chaos)
# ---------------------------------------------------------------------------

_MATRIX_FAULTS = ["drop", "dup", "delay"]
_MATRIX_PLANES = {
    "submit": "request:submit:{kind}:0.3:21",
    "actor_call": "request:actor_call:{kind}:0.3:22",
    "resolve": "reply:resolve:{kind}:0.3:23;reply:get_locations:{kind}:0.3:24",
    "seal": "seal:{kind}:0.3:25",
    "kv_commit": "request:kv:{kind}:0.3:26",
}


@pytest.mark.chaos
@pytest.mark.slow
@pytest.mark.parametrize("kind", _MATRIX_FAULTS)
@pytest.mark.parametrize("plane", sorted(_MATRIX_PLANES))
def test_fault_matrix(net_env, kind, plane):
    """Full sweep: each fault kind on each op class — the workload must
    finish with exact results, the actor counter stays linearizable, and
    nothing blocks past the outer alarm."""
    import numpy as np

    net_env(_MATRIX_PLANES[plane].format(kind=kind))
    ray_tpu.init(num_cpus=2, object_store_memory=128 * 1024**2)

    @ray_tpu.remote
    class Counter:
        def __init__(self):
            self.n = 0

        def set_weights(self, delta):
            self.n += delta
            return self.n

        def value(self):
            return self.n

    with no_hang(180.0):
        got, want = _sum_task_workload(8)
        assert got == want
        c = Counter.remote()
        ray_tpu.get([c.set_weights.remote(1) for _ in range(10)])
        assert ray_tpu.get(c.value.remote()) == 10
        data = np.arange(200 * 1024, dtype=np.int64)
        assert (ray_tpu.get(ray_tpu.put(data)) == data).all()
        from ray_tpu import internal_kv

        internal_kv.kv_put(b"ckpt/commit", b"manifest-v1")
        assert internal_kv.kv_get(b"ckpt/commit") == b"manifest-v1"


@pytest.mark.chaos
@pytest.mark.slow
def test_sever_on_worker_conn_recovers_via_respawn(net_env):
    """sever: the worker's control conn dies mid-run — the head treats it
    as a worker death, respawns, and retried tasks still complete."""
    net_env("notify:task_done:sever:0.2:31:2")
    ray_tpu.init(num_cpus=2, object_store_memory=128 * 1024**2)
    with no_hang(180.0):
        got, want = _sum_task_workload(8)
    assert got == want
