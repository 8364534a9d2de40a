"""Scheduler + placement group + multi-node tests (modeled on the
reference's test_placement_group*.py and cluster_utils-based tests)."""
import os
import time

import pytest

import ray_tpu
from ray_tpu.util import (
    NodeAffinitySchedulingStrategy,
    PlacementGroupSchedulingStrategy,
    placement_group,
    remove_placement_group,
)


def test_resource_gating(ray_start_regular):
    # 8 CPUs: 8 concurrent 1-CPU sleepers saturate; a 9th waits.
    @ray_tpu.remote
    def sleeper():
        time.sleep(0.6)
        return 1

    start = time.monotonic()
    refs = [sleeper.remote() for _ in range(9)]
    ray_tpu.get(refs)
    assert time.monotonic() - start >= 1.0


def test_fractional_cpus(ray_start_regular):
    @ray_tpu.remote(num_cpus=0.5)
    def f():
        return 1

    assert sum(ray_tpu.get([f.remote() for _ in range(16)])) == 16


def test_custom_resource(shutdown_only):
    ray_tpu.init(num_cpus=4, resources={"accel": 2})

    @ray_tpu.remote(resources={"accel": 1})
    def g():
        return "ok"

    assert ray_tpu.get(g.remote()) == "ok"


def test_infeasible_task_fails(ray_start_regular):
    @ray_tpu.remote(num_cpus=100)
    def f():
        return 1

    with pytest.raises(ray_tpu.exceptions.RayTpuError):
        ray_tpu.get(f.remote(), timeout=10)


def test_multi_node_cluster(ray_start_cluster):
    cluster = ray_start_cluster
    cluster.add_node(num_cpus=2, resources={"head": 1})
    cluster.add_node(num_cpus=2, resources={"extra": 1})
    cluster.connect()

    @ray_tpu.remote(resources={"extra": 0.1})
    def on_extra():
        return "extra"

    assert ray_tpu.get(on_extra.remote()) == "extra"
    assert ray_tpu.cluster_resources()["CPU"] == 4


def test_node_affinity(ray_start_cluster):
    cluster = ray_start_cluster
    cluster.add_node(num_cpus=2)
    n2 = cluster.add_node(num_cpus=2)
    cluster.connect()

    @ray_tpu.remote
    def whereami():
        return ray_tpu.get_runtime_context().get_node_id()

    nid = ray_tpu.get(whereami.options(
        scheduling_strategy=NodeAffinitySchedulingStrategy(n2)).remote())
    assert nid == n2.hex()


def test_placement_group_pack(ray_start_regular):
    pg = placement_group([{"CPU": 1}, {"CPU": 1}], strategy="PACK")
    assert pg.wait(10)

    @ray_tpu.remote(num_cpus=1)
    def inside():
        return "in-pg"

    ref = inside.options(
        scheduling_strategy=PlacementGroupSchedulingStrategy(pg)).remote()
    assert ray_tpu.get(ref) == "in-pg"
    remove_placement_group(pg)


def test_placement_group_strict_spread(ray_start_cluster):
    cluster = ray_start_cluster
    cluster.add_node(num_cpus=2)
    cluster.add_node(num_cpus=2)
    cluster.connect()
    pg = placement_group([{"CPU": 1}, {"CPU": 1}], strategy="STRICT_SPREAD")
    assert pg.wait(10)
    # Bundles must land on distinct nodes.
    head = ray_tpu._global_head()
    info = head.scheduler.placement_groups[pg.id]
    nodes = {b.node_id for b in info.bundles}
    assert len(nodes) == 2


def test_placement_group_infeasible(ray_start_regular):
    pg = placement_group([{"CPU": 100}], strategy="PACK")
    assert not pg.wait(2)


def test_placement_group_releases_resources(ray_start_regular):
    pg = placement_group([{"CPU": 8}], strategy="PACK")
    assert pg.wait(10)
    assert ray_tpu.available_resources().get("CPU", 0) == 0
    remove_placement_group(pg)
    time.sleep(0.2)
    assert ray_tpu.available_resources()["CPU"] == 8


def test_actor_in_placement_group(ray_start_regular):
    pg = placement_group([{"CPU": 2}], strategy="PACK")
    assert pg.wait(10)

    @ray_tpu.remote(num_cpus=1)
    class A:
        def ping(self):
            return "pong"

    a = A.options(
        scheduling_strategy=PlacementGroupSchedulingStrategy(pg)).remote()
    assert ray_tpu.get(a.ping.remote()) == "pong"


def test_spread_strategy(ray_start_cluster):
    cluster = ray_start_cluster
    cluster.add_node(num_cpus=4)
    cluster.add_node(num_cpus=4)
    cluster.connect()

    @ray_tpu.remote(scheduling_strategy="SPREAD")
    def whereami():
        time.sleep(0.2)
        return ray_tpu.get_runtime_context().get_node_id()

    nodes = set(ray_tpu.get([whereami.remote() for _ in range(4)]))
    assert len(nodes) == 2


# ---------------------------------------------------------------------------
# ClusterScheduler policy unit tests (no cluster: direct ledger checks)
# ---------------------------------------------------------------------------
def _sched():
    from ray_tpu._private.scheduler import ClusterScheduler

    return ClusterScheduler()


def _node_id():
    from ray_tpu._private.ids import NodeID

    return NodeID.from_random()


def _spec(resources=None, strategy=None):
    from ray_tpu._private.ids import JobID, TaskID
    from ray_tpu._private.task_spec import (SchedulingStrategy, TaskSpec,
                                            TaskType)

    return TaskSpec(
        task_id=TaskID.from_random(), job_id=JobID.from_random(),
        task_type=TaskType.NORMAL, name="t",
        resources=resources or {"CPU": 1},
        scheduling_strategy=strategy or SchedulingStrategy())


def test_locality_outranks_utilization_above_threshold():
    """A host holding >= locality_min_bytes of a task's args must win
    placement even when utilization packing prefers the other node."""
    s = _sched()
    busy, holder = _node_id(), _node_id()
    s.add_node(busy, {"CPU": 4})
    s.add_node(holder, {"CPU": 4})
    s.nodes[busy].allocate({"CPU": 2})  # packing would pick `busy`
    assert s.pick_node(_spec()) == busy  # no locality: utilization wins
    s.return_resources(busy, _spec())
    got = s.pick_node(_spec(), locality={holder: s.locality_min_bytes})
    assert got == holder


def test_tiny_args_never_unbalance_packing():
    """Below locality_min_bytes the resident-bytes signal is ignored —
    utilization packing decides, so small args can't spread the load."""
    s = _sched()
    busy, holder = _node_id(), _node_id()
    s.add_node(busy, {"CPU": 4})
    s.add_node(holder, {"CPU": 4})
    s.nodes[busy].allocate({"CPU": 2})
    got = s.pick_node(_spec(), locality={holder: s.locality_min_bytes - 1})
    assert got == busy


def test_soft_node_affinity_honors_locality():
    """A soft affinity to a dead node falls back to the default policy —
    WITH the locality signal, not blind packing."""
    from ray_tpu._private.task_spec import SchedulingStrategy

    s = _sched()
    gone, busy, holder = _node_id(), _node_id(), _node_id()
    s.add_node(busy, {"CPU": 4})
    s.add_node(holder, {"CPU": 4})
    s.nodes[busy].allocate({"CPU": 2})
    spec = _spec(strategy=SchedulingStrategy(
        kind="NODE_AFFINITY", node_id=gone, soft=True))
    got = s.pick_node(spec, locality={holder: 2 * s.locality_min_bytes})
    assert got == holder


def test_spread_cursor_deterministic():
    """SPREAD walks nodes round-robin in stable (node-id) order."""
    from ray_tpu._private.task_spec import SchedulingStrategy

    s = _sched()
    nodes = sorted([_node_id() for _ in range(3)],
                   key=lambda n: n.binary())
    for n in nodes:
        s.add_node(n, {"CPU": 2})
    got = [s.pick_node(_spec(strategy=SchedulingStrategy(kind="SPREAD")))
           for _ in range(6)]
    assert got == nodes * 2


def test_remove_node_releases_surviving_pg_bundles():
    """Demoting a PG on node loss must release the SURVIVING bundles'
    reservations: re-reserving the demoted group from the head's pending
    queue must not double-allocate (the leak left the cluster looking
    fuller than it was, permanently)."""
    from ray_tpu._private.ids import PlacementGroupID
    from ray_tpu._private.scheduler import PlacementGroupInfo

    s = _sched()
    a, b = _node_id(), _node_id()
    s.add_node(a, {"CPU": 2})
    s.add_node(b, {"CPU": 2})
    pg = PlacementGroupInfo(PlacementGroupID.from_random(),
                            [{"CPU": 2}, {"CPU": 2}], "STRICT_SPREAD")
    assert s.create_placement_group(pg)
    assert s.available_resources().get("CPU", 0) == 0
    demoted = s.remove_node(b)
    assert demoted == [pg] and pg.state == "PENDING"
    assert all(bd.node_id is None for bd in pg.bundles)
    # The survivor's reservation came back — nothing leaked.
    assert s.available_resources()["CPU"] == 2
    # A replacement node arrives: the demoted group re-reserves cleanly.
    c = _node_id()
    s.add_node(c, {"CPU": 2})
    assert s.create_placement_group(pg)
    assert s.available_resources().get("CPU", 0) == 0
    s.remove_placement_group(pg.pg_id)
    assert s.available_resources()["CPU"] == 4


def test_external_capacity_is_instance_state():
    """Two schedulers in one process must not share autoscaler capacity
    (the old class attribute leaked one head's shapes into another)."""
    s1, s2 = _sched(), _sched()
    s1.external_capacity.append({"CPU": 64})
    assert s2.external_capacity == []


def test_two_tpu_actors_same_node(shutdown_only):
    """A second TPU actor on a node must get its own TPU-visible worker
    instead of queueing forever behind an actor-pinned one (ADVICE r1)."""
    ray_tpu.init(num_cpus=4, num_tpus=2)

    @ray_tpu.remote(resources={"TPU": 1})
    class TpuActor:
        def ping(self):
            return os.getpid()

    a = TpuActor.remote()
    b = TpuActor.remote()
    pids = ray_tpu.get([a.ping.remote(), b.ping.remote()], timeout=60)
    assert pids[0] != pids[1]
