"""Memory-pressure policing (reference: src/ray/common/memory_monitor.h:52,
src/ray/raylet/worker_killing_policy.h:33): under host memory pressure the
node kills a policy-chosen worker instead of crashing; the victim's task is
retried within budget, else fails with OutOfMemoryError."""
import os
import time
from types import SimpleNamespace

import pytest

import ray_tpu
from ray_tpu._private.memory_monitor import (group_by_owner_policy,
                                             retriable_lifo_policy)
from ray_tpu.exceptions import OutOfMemoryError


def _cand(name, owner, attempt, max_retries, started):
    handle = SimpleNamespace(name=name)
    spec = SimpleNamespace(owner_worker_id=SimpleNamespace(
        binary=lambda o=owner: o), attempt=attempt, max_retries=max_retries)
    return (handle, spec, started)


class TestPolicies:
    def test_retriable_lifo_prefers_newest_retriable(self):
        cands = [
            _cand("old-retriable", b"a", 0, 3, 1.0),
            _cand("new-retriable", b"a", 0, 3, 5.0),
            _cand("newest-unretriable", b"b", 3, 3, 9.0),
        ]
        assert retriable_lifo_policy(cands).name == "new-retriable"

    def test_retriable_lifo_falls_back_to_unretriable(self):
        cands = [
            _cand("older", b"a", 1, 1, 1.0),
            _cand("newer", b"b", 1, 1, 2.0),
        ]
        assert retriable_lifo_policy(cands).name == "newer"

    def test_retriable_lifo_empty(self):
        assert retriable_lifo_policy([]) is None

    def test_invalid_policy_name_warns_and_defaults(self, monkeypatch):
        from ray_tpu._private.config import CONFIG
        from ray_tpu._private.memory_monitor import MemoryMonitor

        monkeypatch.setenv("RAY_TPU_WORKER_KILLING_POLICY", "groupby_owner")
        CONFIG.reset()
        try:
            with pytest.warns(UserWarning, match="worker_killing_policy"):
                mon = MemoryMonitor(SimpleNamespace())
            assert mon.policy is retriable_lifo_policy
        finally:
            CONFIG.reset()

    def test_group_by_owner_prefers_larger_retriable_group(self):
        cands = [
            _cand("a1", b"a", 0, 3, 1.0),
            _cand("a2", b"a", 0, 3, 2.0),
            _cand("b1", b"b", 0, 3, 9.0),  # newer but smaller group
        ]
        assert group_by_owner_policy(cands).name == "a2"

    def test_group_by_owner_spares_unretriable_groups(self):
        cands = [
            _cand("u1", b"a", 3, 3, 5.0),
            _cand("u2", b"a", 3, 3, 6.0),
            _cand("r1", b"b", 0, 3, 1.0),
        ]
        assert group_by_owner_policy(cands).name == "r1"


@pytest.fixture
def pressure_cluster(tmp_path, monkeypatch):
    """Cluster whose memory monitor reads pressure from a file (the
    reference's fake-memory test hook)."""
    from ray_tpu._private.config import CONFIG

    gauge = tmp_path / "usage"
    gauge.write_text("0.1")
    monkeypatch.setenv("RAY_TPU_MEMORY_MONITOR_TEST_FILE", str(gauge))
    monkeypatch.setenv("RAY_TPU_MEMORY_MONITOR_REFRESH_MS", "100")
    monkeypatch.setenv("RAY_TPU_MEMORY_USAGE_THRESHOLD", "0.9")
    CONFIG.reset()
    ray_tpu.init(num_cpus=2)
    yield gauge
    ray_tpu.shutdown()
    CONFIG.reset()


def _wait_for_running_task(timeout=15.0):
    head = ray_tpu._head
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        with head._lock:
            for raylet in head.raylets.values():
                for h in raylet.workers.values():
                    if h.current_task is not None and h.actor_id is None:
                        return True
        time.sleep(0.05)
    return False


def test_oom_kill_retries_task(pressure_cluster, tmp_path):
    gauge = pressure_cluster
    marker = tmp_path / "attempt_marker"

    @ray_tpu.remote(max_retries=2)
    def victim(marker_path, gauge_path):
        if not os.path.exists(marker_path):
            with open(marker_path, "w") as f:
                f.write("1")
            time.sleep(120)  # first attempt: hang until OOM-killed
        with open(gauge_path, "w") as f:
            f.write("0.1")  # relieve pressure so the retry survives
        return 42

    ref = victim.remote(str(marker), str(gauge))
    assert _wait_for_running_task(), "task never started"
    time.sleep(0.3)  # let the first attempt write its marker
    gauge.write_text("0.99")
    assert ray_tpu.get(ref, timeout=60) == 42
    assert ray_tpu._head.memory_monitor.kill_count >= 1


def test_oom_kill_exhausted_budget_raises(pressure_cluster, tmp_path):
    gauge = pressure_cluster

    @ray_tpu.remote(max_retries=0)
    def hog():
        time.sleep(120)

    ref = hog.remote()
    assert _wait_for_running_task(), "task never started"
    gauge.write_text("0.99")
    with pytest.raises(OutOfMemoryError):
        ray_tpu.get(ref, timeout=60)


def test_host_memory_reader_sane():
    from ray_tpu._private.memory_monitor import host_memory_usage_fraction

    frac = host_memory_usage_fraction()
    assert 0.0 <= frac <= 1.0


def test_actor_killed_as_last_resort(pressure_cluster):
    """A host whose pressure comes entirely from actors still gets relief:
    actors become kill candidates once no task workers exist (advisor r3;
    the FSM restart path rebuilds the actor afterwards)."""
    gauge = pressure_cluster

    @ray_tpu.remote(max_restarts=1)
    class Hog:
        def ping(self):
            return "up"

    h = Hog.remote()
    assert ray_tpu.get(h.ping.remote(), timeout=60) == "up"
    gauge.write_text("0.99")
    deadline = time.time() + 20
    while time.time() < deadline:
        if ray_tpu._head.memory_monitor.kill_count >= 1:
            break
        time.sleep(0.2)
    assert ray_tpu._head.memory_monitor.kill_count >= 1
    gauge.write_text("0.1")
    # The actor restarts and serves again.
    assert ray_tpu.get(h.ping.remote(), timeout=60) == "up"


def test_remote_agent_relieves_own_pressure(tmp_path, monkeypatch):
    """Remote nodes run their own memory monitor in the node agent
    (advisor r3): under injected pressure the agent kills a child worker
    instead of leaving the host to the kernel OOM-killer."""
    gauge = tmp_path / "agent_mem"
    gauge.write_text("0.1")
    monkeypatch.setenv("RAY_TPU_MEMORY_MONITOR_TEST_FILE", str(gauge))
    monkeypatch.setenv("RAY_TPU_MEMORY_MONITOR_REFRESH_MS", "100")
    monkeypatch.setenv("RAY_TPU_MEMORY_USAGE_THRESHOLD", "0.9")
    monkeypatch.setenv("RAY_TPU_TCP_HOST", "127.0.0.1")
    from ray_tpu._private.config import CONFIG

    CONFIG.reset()
    ray_tpu.init(num_cpus=0, object_store_memory=64 * 1024**2)
    try:
        from ray_tpu.util.testing import remote_node_agents

        with remote_node_agents(ray_tpu._head, n=1, num_cpus=2):
            # Head host has 0 CPUs: the task must land on the agent node.
            @ray_tpu.remote(max_retries=2)
            def slow(marker_path, gauge_path):
                import os
                import time as _t

                if not os.path.exists(marker_path):
                    open(marker_path, "w").write("1")
                    _t.sleep(120)  # first attempt hangs under pressure
                open(gauge_path, "w").write("0.1")
                return "survived"

            marker = tmp_path / "attempt"
            ref = slow.remote(str(marker), str(gauge))
            deadline = time.time() + 60
            while time.time() < deadline and not marker.exists():
                time.sleep(0.2)
            assert marker.exists(), "task never started on the agent"
            time.sleep(0.3)
            gauge.write_text("0.99")  # agent's monitor kills the worker
            assert ray_tpu.get(ref, timeout=90) == "survived"
    finally:
        ray_tpu.shutdown()
        CONFIG.reset()


@pytest.mark.parametrize("mark_late_s", [0.0, 0.5])
def test_agent_oom_kill_is_typed_and_carries_usage(tmp_path, monkeypatch,
                                                   mark_late_s):
    """ISSUE 7 satellite: a worker killed by the node agent's memory loop
    must surface as OutOfMemoryError with the host usage fraction in the
    message (not a generic WorkerCrashedError) once retries run out —
    the head marks the victim's task BEFORE it has the agent kill it, so
    the death handler can type it whichever connection reports the death.
    ``mark_late_s`` holds the head's handling of the agent's message back,
    as a loaded host does: while the agent itself killed, the worker's own
    socket told the head first and the death came out untyped (PR 52)."""
    from ray_tpu._private.head import Head

    mark = Head.on_worker_oom

    def late_mark(self, worker_id, usage):
        time.sleep(mark_late_s)
        return mark(self, worker_id, usage)

    monkeypatch.setattr(Head, "on_worker_oom", late_mark)
    gauge = tmp_path / "agent_oom_gauge"
    gauge.write_text("0.1")
    monkeypatch.setenv("RAY_TPU_MEMORY_MONITOR_TEST_FILE", str(gauge))
    monkeypatch.setenv("RAY_TPU_MEMORY_MONITOR_REFRESH_MS", "100")
    monkeypatch.setenv("RAY_TPU_MEMORY_USAGE_THRESHOLD", "0.9")
    from ray_tpu._private.config import CONFIG

    CONFIG.reset()
    ray_tpu.init(num_cpus=0, object_store_memory=64 * 1024**2)
    try:
        from ray_tpu.util.testing import (remote_node_agents,
                                          wait_for_condition)

        with remote_node_agents(ray_tpu._head, n=1, num_cpus=2):
            @ray_tpu.remote(max_retries=0)
            def hog(marker_path):
                import time as _t

                open(marker_path, "w").write("1")
                _t.sleep(120)

            marker = tmp_path / "started"
            ref = hog.remote(str(marker))
            wait_for_condition(marker.exists, timeout=60)
            time.sleep(0.3)
            gauge.write_text("0.99")
            with pytest.raises(OutOfMemoryError) as ei:
                ray_tpu.get(ref, timeout=90)
            msg = str(ei.value)
            assert "memory" in msg and "99%" in msg, msg
    finally:
        ray_tpu.shutdown()
        CONFIG.reset()
