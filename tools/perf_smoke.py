"""Fast hot-path overlap smoke (CPU, virtual devices) — tier-1 guard.

Asserts the two PR 2 overlap invariants cheaply enough to run in every
test pass, so a regression fails tier-1 instead of only showing up on the
chip:

1. **Pipelined dispatch overlaps completion**: driving a real (tiny,
   donated) jax step through MeshGroup.pipeline, step N+1's dispatch span
   must be recorded BEFORE step N's drain, for every steady-state N —
   i.e. the driver never falls back to lockstep dispatch→wait→dispatch.
2. **Zero driver syncs**: the pipelined run leaves
   mesh_group.driver_sync_count() untouched.

The rule for every gate in this file: an assertion compares counts,
orderings and byte sizes.  These run on a host that other test workers
load, so a wall-clock reading appears only as a timeout: whether two
processes' intervals happened to overlap, or how fast a loop ran, is not
a property of the code.

Run standalone (``python tools/perf_smoke.py`` prints one JSON line) or
through tests/test_perf_smoke.py.
"""
from __future__ import annotations

import json
import os
import sys

# Standalone invocation (python tools/perf_smoke.py) from any cwd.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

STEPS = 8
DEPTH = 2


def _lockstep_steps(total: int, depth: int) -> list:
    """Steady-state steps N whose successor was NOT dispatched before N's
    result was fetched.  Both spans are recorded by the one driver thread
    that dispatches and drains, so their order in the span ring (oldest
    first) is the order the driver did the two things in.  The drain of
    the tail after the last submit is exempt: nothing is left to dispatch
    ahead of it."""
    from ray_tpu._private import profiling

    at = {(s["name"], s["args"]["step"]): i
          for i, s in enumerate(profiling.recorded_spans())
          if s["name"] in ("pipeline_dispatch", "pipeline_drain")}
    return [n for n in range(total - depth)
            if not (("pipeline_dispatch", n + 1) in at
                    and at["pipeline_dispatch", n + 1]
                    < at["pipeline_drain", n])]


def _get_within(refs, timeout: float):
    """``(values, True)``, or ``(None, False)`` when the get ran into
    its timeout: the no-hang gates' only use of the clock."""
    import ray_tpu

    try:
        return ray_tpu.get(refs, timeout=timeout), True
    except ray_tpu.exceptions.GetTimeoutError:
        return None, False


def _jax_step(state, scale):
    """Tiny donated carry update: representative shape (device-resident
    carry, jit + donate_argnums), negligible cost on CPU."""
    import jax
    import jax.numpy as jnp

    if "carry" not in state:
        state["carry"] = jnp.ones((32, 32))
        state["step_fn"] = jax.jit(
            lambda c, s: (c * s + 0.5).mean(keepdims=True) + c,
            donate_argnums=(0,))
    state["carry"] = state["step_fn"](state["carry"], scale)
    return {"mean": float(state["carry"].mean())}


def run_smoke(steps: int = STEPS, depth: int = DEPTH) -> dict:
    import ray_tpu
    from ray_tpu._private import profiling
    from ray_tpu.util import tracing
    from ray_tpu.parallel import MeshGroup, mesh_group

    ray_tpu.init(num_cpus=4, object_store_memory=256 * 1024**2,
                 ignore_reinit_error=True)
    mg = MeshGroup(num_hosts=1, platform="cpu", local_device_count=1,
                   pipeline_depth=depth)
    try:
        tracing.enable_tracing()  # recorded_spans reads the span ring
        profiling.clear_recorded_spans()
        syncs_before = mesh_group.driver_sync_count()
        with mg.pipeline(depth=depth, metrics_interval=1) as pipe:
            for _ in range(steps):
                pipe.submit(_jax_step, 1.0)
            results = pipe.flush()
        syncs = mesh_group.driver_sync_count() - syncs_before

        violations = _lockstep_steps(steps, depth)
        out = {
            "steps": steps,
            "depth": depth,
            "results_ok": len(results) == steps,
            "driver_syncs": syncs,
            "overlap_violations": violations,
            "overlap_ok": not violations,
        }
        out["ok"] = bool(out["results_ok"] and out["overlap_ok"]
                         and syncs == 0)
        return out
    finally:
        tracing.disable_tracing()
        mg.shutdown()
        ray_tpu.shutdown()


def run_object_plane_smoke(cycles: int = 4, burst: int = 4) -> dict:
    """Object-plane invariants (no timing assertions — tier-1 safe):

    1. **Pool reuse**: steady-state large puts are served from recycled
       pool segments — after a warmup put/free cycle, further puts of the
       same size class create NO new shm segment (``pool_created`` stays
       flat while ``pool_hits`` climbs).
    2. **Notify batching**: a ``put_many(K)`` burst of store-resident
       objects reaches the head as at most ONE control-plane notify
       (``seal_batch``), not K ``seal`` messages.
    """
    import numpy as np

    import ray_tpu

    ray_tpu.init(num_cpus=2, object_store_memory=256 * 1024**2,
                 ignore_reinit_error=True)
    try:
        from ray_tpu._private.worker import global_worker as gw

        store = gw.transport.head.raylets[gw.node_id].store
        out = {}
        data = np.random.randint(0, 255, (4 * 1024 * 1024,), dtype=np.uint8)

        def cycle():
            ref = ray_tpu.put(data)
            del ref
            gw._drain_ref_gc_queue()  # deterministic free (no GC races)

        cycle()  # warmup: the first put of this size class may create
        created_before = store.stats().get("pool_created", -1)
        hits_before = store.stats().get("pool_hits", 0)
        for _ in range(cycles):
            cycle()
        stats = store.stats()
        out["segments_created_steady"] = (
            stats.get("pool_created", -1) - created_before)
        out["pool_hits_steady"] = stats.get("pool_hits", 0) - hits_before
        out["pool_reuse_ok"] = (out["segments_created_steady"] == 0
                                and out["pool_hits_steady"] >= cycles)

        # --- notify batching ---
        notifies = []
        orig_notify = gw.transport.notify

        def counting_notify(msg):
            if msg.get("type") in ("seal", "put_inline", "seal_batch",
                                   "put_inline_batch"):
                notifies.append(msg["type"])
            return orig_notify(msg)

        gw.transport.notify = counting_notify
        try:
            big = [np.random.randint(0, 255, (256 * 1024,), dtype=np.uint8)
                   for _ in range(burst)]
            refs = ray_tpu.put_many(big)
        finally:
            gw.transport.notify = orig_notify
        got = ray_tpu.get_many(refs)
        out["burst_notifies"] = len(notifies)
        out["notify_types"] = sorted(set(notifies))
        out["batching_ok"] = len(notifies) <= 1
        out["roundtrip_ok"] = all(
            np.array_equal(a, b) for a, b in zip(big, got))
        out["ok"] = bool(out["pool_reuse_ok"] and out["batching_ok"]
                         and out["roundtrip_ok"])
        return out
    finally:
        ray_tpu.shutdown()


def _ckpt_save_step(state, root, step):
    """Pipeline-riding async sharded save of the smoke carry: the step
    pays only the bounded host snapshot; chunk writes ride the rank's
    background persist thread."""
    import os

    from ray_tpu.checkpoint.saver import ShardWriter

    rank = int(os.environ.get("RTPU_RANK", "0"))
    world = int(os.environ.get("RTPU_WORLD_SIZE", "1"))
    writer = state.get("_ckpt_writer")
    if writer is None:
        writer = ShardWriter(root, rank, world)
        state["_ckpt_writer"] = writer
    writer.persist_async(writer.snapshot({"carry": state["carry"]}), step)
    return {"rank": rank}


def run_checkpoint_smoke(steps: int = STEPS, depth: int = DEPTH) -> dict:
    """Async-checkpoint overlap guard (tier-1): an async sharded save
    submitted mid-stream must NOT degrade the pipelined step loop —

    1. every steady-state step still dispatches before its predecessor's
       drain (no lockstep fallback around the save),
    2. the whole run performs zero blocking driver syncs,
    3. the save still COMMITS (manifest lands, restorable state).
    """
    import shutil
    import tempfile

    import ray_tpu
    from ray_tpu._private import profiling
    from ray_tpu.util import tracing
    from ray_tpu.checkpoint import latest_committed_step, restore_tree
    from ray_tpu.checkpoint.coordinator import AsyncCommitter
    from ray_tpu.parallel import MeshGroup, mesh_group

    ray_tpu.init(num_cpus=4, object_store_memory=256 * 1024**2,
                 ignore_reinit_error=True)
    root = tempfile.mkdtemp(prefix="rtpu_ckpt_smoke_")
    mg = MeshGroup(num_hosts=1, platform="cpu", local_device_count=1,
                   pipeline_depth=depth)
    committer = AsyncCommitter()
    save_at = steps // 2
    try:
        tracing.enable_tracing()  # recorded_spans reads the span ring
        profiling.clear_recorded_spans()
        syncs_before = mesh_group.driver_sync_count()
        with mg.pipeline(depth=depth, metrics_interval=1) as pipe:
            for i in range(steps):
                pipe.submit(_jax_step, 1.0)
                if i == save_at:
                    pipe.submit(_ckpt_save_step, root, 1, fetch=True)
                    committer.commit_async(root, 1, mg.num_hosts)
            results = pipe.flush()
        syncs = mesh_group.driver_sync_count() - syncs_before
        committer.flush(timeout=30.0)

        total = steps + 1  # the save rides the stream as one extra step
        violations = _lockstep_steps(total, depth)
        committed = latest_committed_step(root)
        restored = None
        if committed is not None:
            restored = restore_tree(root, step=committed)
        out = {
            "steps": total,
            "depth": depth,
            "results_ok": len(results) == total,
            "driver_syncs": syncs,
            "overlap_violations": violations,
            "overlap_ok": not violations,
            "committed_step": committed,
            "restore_ok": bool(restored is not None
                               and "carry" in restored),
        }
        out["ok"] = bool(out["results_ok"] and out["overlap_ok"]
                         and syncs == 0 and out["restore_ok"])
        return out
    finally:
        tracing.disable_tracing()
        mg.shutdown()
        ray_tpu.shutdown()
        shutil.rmtree(root, ignore_errors=True)


def run_rollout_smoke(fragments: int = 6, k: int = 2) -> dict:
    """Rollout-plane invariants (tier-1 guard for ISSUE 5):

    1. **Sample/learn overlap**: with 2 workers and K=2 fragments in
       flight, the learner consuming a fragment never drains production —
       every time it is handed a fragment, all the other 2K - 1 are still
       queued at the workers (only the slot just consumed waits for the
       next call's refill), so both workers have work while it learns.
    2. **One put per version**: publishing W weight versions to N workers
       performs exactly W object-store puts (one ref, N borrowers), not
       W*N.
    """
    import jax

    import ray_tpu
    from ray_tpu.rllib import PPOConfig
    from ray_tpu.rllib.core.rl_module import RLModuleSpec
    from ray_tpu.rllib.env.py_envs import make_py_env
    from ray_tpu.rllib.evaluation.sample_stream import SampleStream
    from ray_tpu.rllib.evaluation.worker_set import WorkerSet

    ray_tpu.init(num_cpus=4, object_store_memory=256 * 1024**2,
                 ignore_reinit_error=True)
    try:
        config = (PPOConfig().environment("CartPole-v1")
                  .rollouts(num_rollout_workers=2, num_envs_per_worker=2,
                            rollout_fragment_length=16, mode="actor")
                  .training(model={"fcnet_hiddens": [16]}))
        spec = RLModuleSpec.for_env(make_py_env("CartPole-v1"),
                                    tuple(config.hiddens))
        workers = WorkerSet(config, spec)
        stream = SampleStream(workers, kind="gae",
                              max_in_flight_per_worker=k)
        module = spec.build()
        params = module.init(jax.random.PRNGKey(0), spec.example_obs())

        puts = []
        orig_put = ray_tpu.put

        def counting_put(value):
            puts.append(1)
            return orig_put(value)

        ray_tpu.put = counting_put
        try:
            versions = 3
            for _ in range(versions):
                stream.publish_weights(params)
        finally:
            ray_tpu.put = orig_put

        inflight_at_consume = []
        got = 0
        for _ in range(fragments):
            frag = stream.next_fragment(timeout=60.0)
            if frag is None:
                break
            got += 1
            inflight_at_consume.append(stream.inflight)
        stream.close()
        workers.stop()

        out = {
            "fragments": got,
            "k": k,
            "weight_versions": versions,
            "weight_puts": len(puts),
            "one_put_per_version": len(puts) == versions,
            "min_inflight_at_consume": min(inflight_at_consume or [0]),
            "inflight_ok": bool(inflight_at_consume
                                and min(inflight_at_consume) == 2 * k - 1),
        }
        out["ok"] = bool(got == fragments and out["one_put_per_version"]
                         and out["inflight_ok"])
        return out
    finally:
        ray_tpu.shutdown()


def run_rpc_chaos_smoke(tasks: int = 8) -> dict:
    """RPC-plane robustness invariant (tier-1 guard for ISSUE 6):

    Exactly ONE submit-path reply is dropped on the wire.  The call must
    time out its attempt, retry with the same idempotency key, and the
    workload must complete with exact results — zero hangs (the get
    returns inside its timeout), zero double-applied submits (exact
    result set).
    """
    import os as _os

    import ray_tpu
    from ray_tpu._private import retry as retry_mod
    from ray_tpu._private.chaos import NET_SCHEDULE_ENV
    from ray_tpu._private.config import CONFIG

    # One dropped reply on the submit path (times=1), then the link heals.
    _os.environ[NET_SCHEDULE_ENV] = "reply:submit:drop:1.0:3:1"
    CONFIG.reset()
    retry_mod.reset_rpc_stats()
    ray_tpu.init(num_cpus=2, object_store_memory=128 * 1024**2,
                 ignore_reinit_error=True,
                 _system_config={"rpc_attempt_timeout": 0.3,
                                 "direct_transport": False})
    try:
        @ray_tpu.remote
        def double(i):
            return i * 2

        # The dropped reply costs about one attempt timeout (0.3 s).
        vals, no_hang = _get_within(
            [double.remote(i) for i in range(tasks)], timeout=30.0)
        stats = retry_mod.rpc_stats()
        out = {
            "tasks": tasks,
            "exact_results": vals == [i * 2 for i in range(tasks)],
            "net_faults_injected": stats["net_faults"],
            "retries": stats["retries"] + stats["async_retries"],
            "timeouts_raised": stats["timeouts"],
            "no_hang": no_hang,
        }
        out["ok"] = bool(out["exact_results"]
                         and out["net_faults_injected"] >= 1
                         and out["retries"] >= 1
                         and out["no_hang"])
        return out
    finally:
        ray_tpu.shutdown()
        _os.environ.pop(NET_SCHEDULE_ENV, None)
        CONFIG.reset()


def run_node_loss_smoke(steps: int = 8, kill_at: int = 3) -> dict:
    """Node-loss survivability invariant (tier-1 guard for ISSUE 7):

    One scheduled node kill mid-run (SIGKILL the node's workers + drop
    its store, the in-process equivalent of killing a node agent).  The
    job must complete with exact results, every get inside its timeout:
    replicated puts restore from the surviving holder, sealed outputs
    reconstruct from lineage, and the recovery counters prove both
    actually happened (>= 1 replica restore, >= 1 reconstruction).
    """
    import numpy as np

    import ray_tpu
    from ray_tpu._private.config import CONFIG
    from ray_tpu._private.recovery import (recovery_stats,
                                           reset_recovery_stats)
    from ray_tpu.cluster_utils import Cluster
    from ray_tpu.util.scheduling_strategies import (
        NodeAffinitySchedulingStrategy,
    )

    reset_recovery_stats()
    ray_tpu.init(num_cpus=2, object_store_memory=256 * 1024**2,
                 ignore_reinit_error=True,
                 _system_config={"object_durability": "replicate:2"})
    try:
        head = ray_tpu._head
        cluster = Cluster(initialize_head=False)
        node2 = cluster.add_node(num_cpus=2,
                                 object_store_memory=256 * 1024**2)
        aff = NodeAffinitySchedulingStrategy(node2, soft=True)

        @ray_tpu.remote(max_retries=4)
        def make_put(i):
            return ray_tpu.put(np.full(300_000, i, dtype=np.int64))

        @ray_tpu.remote(max_retries=4)
        def make_out(i):
            return np.full(200_000, i, dtype=np.int64)

        put_refs, out_refs = [], []
        killed = False
        for step in range(steps):
            if step == kill_at:
                # Outputs so far are sealed-but-unread: the kill forces
                # real reconstructions, not in-flight retries only.
                ray_tpu.wait(out_refs, num_returns=len(out_refs),
                             timeout=60)
                ray_tpu.wait(put_refs, num_returns=len(put_refs),
                             timeout=60)
                # At-least-one-replica-acked before the kill (same gate
                # as the node-agent chaos test): the async durability
                # worker must drain, not merely have started.
                assert head.durability_quiesce(timeout=30)
                head.kill_node(node2)
                killed = True
            put_refs.append(
                make_put.options(scheduling_strategy=aff).remote(step))
            out_refs.append(
                make_out.options(scheduling_strategy=aff).remote(step))
        # Recovery is worth a few task re-runs: one timeout for all of it.
        inner, ok1 = _get_within(put_refs, timeout=60)
        puts, ok2 = _get_within(inner or [], timeout=60)
        outs, ok3 = _get_within(out_refs, timeout=60)
        no_hang = ok1 and ok2 and ok3
        exact = no_hang and all(
            v[0] == i and v[-1] == i and len(v) == 300_000
            for i, v in enumerate(puts)) and all(
            v[0] == i and len(v) == 200_000 for i, v in enumerate(outs))
        st = recovery_stats()
        out = {
            "steps": steps,
            "killed": killed,
            "exact_results": exact,
            "node_deaths": st["node_deaths"],
            "objects_replicated": st["objects_replicated"],
            "objects_restored": st["objects_restored"],
            "objects_reconstructed": st["objects_reconstructed"],
            "objects_lost": st["objects_lost"],
            "no_hang": no_hang,
        }
        out["ok"] = bool(out["killed"] and out["exact_results"]
                         and out["node_deaths"] >= 1
                         and out["objects_restored"] >= 1
                         and out["objects_reconstructed"] >= 1
                         and out["objects_lost"] == 0
                         and out["no_hang"])
        return out
    finally:
        ray_tpu.shutdown()
        CONFIG.reset()


# ---- elastic gang smoke (module-level fns: pickled by reference) ----
def _elastic_loss_fn(params, mb):
    import jax.numpy as jnp

    h = jnp.tanh(mb["x"] @ params["w1"] + params["b1"])
    return jnp.mean(((h @ params["w2"])[:, 0] - mb["y"]) ** 2)


def _elastic_params():
    import jax.numpy as jnp
    import numpy as np

    rng = np.random.default_rng(7)
    return {"w1": jnp.asarray(rng.normal(size=(3, 8)).astype(np.float32)),
            "b1": jnp.zeros((8,), jnp.float32),
            "w2": jnp.asarray(rng.normal(size=(8, 1)).astype(np.float32))}


def _elastic_tx():
    import optax

    return optax.adam(1e-2)


def _elastic_batch(step_idx):
    import numpy as np

    rng = np.random.default_rng(20_000 + step_idx)
    x = rng.normal(size=(4, 2, 3)).astype(np.float32)
    return {"x": x, "y": x.sum(axis=-1).astype(np.float32)}


def run_elastic_smoke(steps_per_phase: int = 2) -> dict:
    """Elastic-gang lifecycle invariants (tier-1 guard for the elastic
    data-parallel plane, ray_tpu/parallel/elastic.py):

    1. **Grow** 1 -> 2 hosts at a step boundary (scripted spare-capacity
       offer), **notice shrink** 2 -> 1 on a preemption notice — both
       land without losing a step.
    2. **One versioned weight broadcast per incarnation**: weight_puts
       == gang version after two resizes.
    3. **Bitwise parity**: the grown-then-shrunk run's final params are
       bit-identical to an uninterrupted in-process world-1 run — the
       slot-deterministic step contract, end to end through real
       actors.
    """
    import numpy as np

    import ray_tpu
    from ray_tpu.parallel.elastic import (ElasticMeshGroup,
                                          reference_trajectory)

    total = 3 * steps_per_phase
    ray_tpu.init(num_cpus=4, object_store_memory=256 * 1024**2,
                 ignore_reinit_error=True)
    try:
        emg = ElasticMeshGroup(_elastic_loss_fn, _elastic_params,
                               _elastic_tx, _elastic_batch,
                               num_hosts=(1, 2), initial_hosts=1,
                               platform="cpu", local_device_count=2,
                               slots=4)
        try:
            losses = emg.run(steps_per_phase)
            emg.offer_capacity(1)           # autoscaler found a spare host
            losses += emg.run(steps_per_phase)
            emg.preemption_notice(rank=1)   # ... and is now reclaiming it
            losses += emg.run(steps_per_phase)
            stats = emg.stats()
            params = emg.params_host()
        finally:
            emg.shutdown()
    finally:
        ray_tpu.shutdown()
    ref = reference_trajectory(_elastic_loss_fn, _elastic_params,
                               _elastic_tx, _elastic_batch,
                               steps=total, slots=4, world=1)
    bitwise = (
        sorted(params) == sorted(ref["params"])
        and all(np.array_equal(np.asarray(params[k]),
                               np.asarray(ref["params"][k]))
                for k in params)
        and np.array_equal(np.asarray(losses, dtype=np.float64),
                           ref["losses"]))
    out = {
        "steps": stats["step"],
        "hosts_final": stats["hosts"],
        "grows": stats["elastic_grows_total"],
        "notice_shrinks": stats["elastic_notice_shrinks_total"],
        "steps_lost": stats["elastic_steps_lost_total"],
        "weight_puts": stats["elastic_weight_puts_total"],
        "version": stats["version"],
        "bitwise_parity": bool(bitwise),
    }
    out["ok"] = bool(stats["step"] == total
                     and stats["hosts"] == 1
                     and stats["elastic_grows_total"] == 1
                     and stats["elastic_notice_shrinks_total"] == 1
                     and stats["elastic_steps_lost_total"] == 0
                     and stats["elastic_weight_puts_total"]
                     == stats["version"]
                     and bitwise)
    return out


def _zero_step(state, step_i):
    """Worker-side ZeRO train step (built lazily on a 4-way virtual data
    mesh inside the MeshGroup worker): one compiled shard_map program per
    process, re-dispatched per pipeline step.  Returns the jit cache size
    so the driver can assert the step never recompiles across
    admissions of new step indices."""
    import jax
    import jax.numpy as jnp
    import optax

    if "step" not in state:
        from ray_tpu.rllib.utils.mesh import data_mesh
        from ray_tpu.train.jax import compile_zero_step

        world = min(4, len(jax.devices()))
        mesh = data_mesh(world)
        key = jax.random.PRNGKey(0)
        params = {"w1": jax.random.normal(key, (64, 33)),
                  "b1": jnp.zeros((33,)),
                  "w2": jax.random.normal(key, (33, 1))}
        tx = optax.adam(1e-2)

        def grad_fn(p, batch):
            def loss(p):
                h = jnp.tanh(batch["x"] @ p["w1"] + p["b1"])
                return jnp.mean((h @ p["w2"] - batch["y"]) ** 2)

            return jax.value_and_grad(loss)(p)

        step, opt, info = compile_zero_step(
            grad_fn, tx, params, mesh, zero_sharding="opt+grads",
            quantized_collectives="int8", donate=False)
        x = jax.random.normal(key, (8 * world, 64))
        state.update(step_fn=step, params=params, opt=opt, info=info,
                     batch={"x": x, "y": jnp.sum(x, 1, keepdims=True)},
                     world=world)
    state["params"], state["opt"], loss = state["step_fn"](
        state["params"], state["opt"], state["batch"])
    return {"cache_size": int(state["step_fn"]._cache_size()),
            "world": state["world"],
            "zero_opt_bytes": state["info"]["zero_opt_bytes_per_replica"],
            "replicated_opt_bytes": state["info"]["replicated_opt_bytes"]}


def run_zero_smoke(steps: int = STEPS, depth: int = DEPTH) -> dict:
    """ZeRO update-plane invariants (tier-1 guard for ISSUE 9):

    1. **1/N optimizer memory**: the per-replica optimizer-state bytes of
       the sharded plan are <= 1/world + remainder slack of the
       replicated baseline (exact accounting, no timing).
    2. **Rides the pipeline with zero extra driver syncs**: driving the
       ZeRO+int8 step through MeshGroup.pipeline keeps
       driver_sync_count() flat and preserves the dispatch-before-drain
       overlap — sharding the update must not reintroduce lockstep.
    3. **No recompiles**: the compiled step's jit cache size stays 1
       across all steps (fresh shapes/layouts would silently multiply
       compile time at scale).
    """
    import ray_tpu
    from ray_tpu._private import profiling
    from ray_tpu.util import tracing
    from ray_tpu.parallel import MeshGroup, mesh_group

    ray_tpu.init(num_cpus=4, object_store_memory=256 * 1024**2,
                 ignore_reinit_error=True)
    mg = MeshGroup(num_hosts=1, platform="cpu", local_device_count=4,
                   pipeline_depth=depth)
    try:
        tracing.enable_tracing()  # recorded_spans reads the span ring
        profiling.clear_recorded_spans()
        syncs_before = mesh_group.driver_sync_count()
        with mg.pipeline(depth=depth, metrics_interval=1) as pipe:
            for i in range(steps):
                pipe.submit(_zero_step, i)
            results = pipe.flush()
        syncs = mesh_group.driver_sync_count() - syncs_before

        violations = _lockstep_steps(steps, depth)
        # Pipeline results are (step_idx, [per-rank metrics]) pairs.
        per_step = [res[0] if isinstance(res, (list, tuple)) else res
                    for _, res in results]
        last = per_step[-1]
        world = last["world"]
        ratio = (last["zero_opt_bytes"]
                 / max(1, last["replicated_opt_bytes"]))
        out = {
            "steps": steps,
            "depth": depth,
            "world": world,
            "results_ok": len(results) == steps,
            "driver_syncs": syncs,
            "overlap_violations": violations,
            "overlap_ok": not violations,
            "opt_bytes_ratio": round(ratio, 4),
            # 1/N + remainder/replicated-scalar slack
            "opt_bytes_ok": ratio <= 1.0 / world + 0.05,
            "cache_sizes": sorted({r["cache_size"] for r in per_step}),
            "no_recompile": all(r["cache_size"] == 1 for r in per_step),
        }
        out["ok"] = bool(out["results_ok"] and out["overlap_ok"]
                         and syncs == 0 and out["opt_bytes_ok"]
                         and out["no_recompile"])
        return out
    finally:
        tracing.disable_tracing()
        mg.shutdown()
        ray_tpu.shutdown()


def run_mpmd_smoke(steps: int = 6, microbatches: int = 4) -> dict:
    """MPMD pipeline invariants (tier-1 guard for ISSUE 10; tiny 2-stage
    MLP pipeline, no timing thresholds):

    1. **1F1B order on every stage**: the forward and backward ops each
       stage ran in the last step, in the order it ran them, are
       ``stage_schedule``'s: stage 0 runs its warm-up forward of
       microbatch m+1 before the backward of m, which is what lets it
       work while stage 1 holds m.  Whether two CPU workers' intervals
       then overlapped on a loaded host is the scheduler's doing, not the
       pipeline's, and is not asked.
    2. **Zero driver syncs in steady state**: the streamed submit_step
       path leaves mpmd_driver_sync_count() untouched (the driver only
       wires refs; activations never visit it).
    3. **Constant jit cache**: every stage's fwd/bwd/apply compile
       exactly once — no per-microbatch retrace, ever.
    4. **1F1B residual bound**: no stage ever holds more than
       (num_stages - stage) microbatches of residuals.
    """
    import numpy as np

    import ray_tpu
    from ray_tpu.parallel import mpmd_pipeline as mp

    ray_tpu.init(num_cpus=4, object_store_memory=256 * 1024**2,
                 ignore_reinit_error=True)
    try:
        import jax.numpy as jnp
        import optax

        def _stage0(params, x):
            import jax.numpy as jnp

            return jnp.tanh(x @ params["w0"])

        def _stage1_loss(params, h, target):
            import jax.numpy as jnp

            return jnp.mean((h @ params["w1"] - target) ** 2)

        rng = np.random.default_rng(0)
        p0 = {"w0": jnp.asarray(rng.normal(0, 0.3, (32, 64)), jnp.float32)}
        p1 = {"w1": jnp.asarray(rng.normal(0, 0.3, (64, 8)), jnp.float32)}
        x = rng.normal(size=(64, 32)).astype(np.float32)
        t = rng.normal(size=(64, 8)).astype(np.float32)

        pipe = mp.MPMDPipeline(
            [_stage0, _stage1_loss], [p0, p1],
            optimizer=optax.sgd(0.05), num_microbatches=microbatches,
            step_window=2, drain_timeout=120.0)
        syncs_before = mp.mpmd_driver_sync_count()
        caches, peaks = [], {}
        for _ in range(steps):
            pipe.submit_step(x, t)
            rep = pipe.last_step_report()
            if rep is None:
                continue
            caches.append(rep["jit_cache"])
        syncs = mp.mpmd_driver_sync_count() - syncs_before
        results = pipe.flush()
        # Tail reports (flush drains the window).
        rep = pipe.last_step_report()
        caches.append(rep["jit_cache"])

        # Each stage appends an op to its list as it finishes it, so the
        # list's order is the order the stage worked in.
        ran = {int(k): [(o["kind"], o["mb"]) for o in ops
                        if o["kind"] in ("F", "B")]
               for k, ops in rep["ops"].items()}
        planned = {k: [(kind, mb) for kind, _chunk, mb in mp.stage_schedule(
            pipe.schedule, pipe.num_stages, microbatches, k)]
            for k in range(pipe.num_stages)}
        for k, peak in rep["peak_inflight"].items():
            peaks[int(k)] = int(peak)
        pipe.stop()
        out = {
            "steps": steps,
            "microbatches": microbatches,
            "results_ok": len(results) == steps,
            "driver_syncs_steady": syncs,
            "stage_ops": ran,
            "schedule_order_ok": pipe.schedule == "1f1b"
            and ran == planned,
            "jit_cache_constant": caches[0] == caches[-1] and all(
                size == 1 for st in caches[-1].values()
                for size in st.values()),
            "peak_inflight": peaks,
            "inflight_bound_ok": all(
                peak <= pipe.num_stages - k for k, peak in peaks.items()),
        }
        out["ok"] = bool(out["results_ok"]
                         and out["driver_syncs_steady"] == 0
                         and out["schedule_order_ok"]
                         and out["jit_cache_constant"]
                         and out["inflight_bound_ok"])
        return out
    finally:
        ray_tpu.shutdown()


def run_3d_smoke(steps: int = 4, microbatches: int = 2) -> dict:
    """Composed 3D-parallelism invariants (tier-1 guard for ISSUE 12;
    tiny GQA Llama, 2 pipeline stages x 2-way intra-stage SPMD x ZeRO,
    interleaved virtual stages, int8 inter-stage wire — no timing
    thresholds):

    1. **Zero mid-step driver syncs**: the streamed submit_step path
       leaves mpmd_driver_sync_count() untouched even with every plane
       composed (SPMD shard_map apply + ZeRO + interleaving + wire
       quantization must not reintroduce lockstep).
    2. **Constant jit caches**: each stage compiles exactly one
       fwd/bwd/apply per owned chunk (= virtual_per_rank) and never
       retraces across steps.
    3. **int8 wire >= 3x**: `mpmd_wire_bytes` (actually shipped) is at
       least 3x below the logical fp32 activation bytes when
       wire_dtype=int8 — the EQuARX block format's envelope at the
       model's hidden size.
    4. **Numerics**: the int8-wire loss tracks the fp32-wire loss within
       the quantization envelope, and ZeRO's optimizer state is
       genuinely 1/N per device.
    """
    import numpy as np

    import ray_tpu
    from ray_tpu.parallel import mpmd_pipeline as mp

    ray_tpu.init(num_cpus=4, object_store_memory=256 * 1024**2,
                 ignore_reinit_error=True)
    try:
        import jax.numpy as jnp
        import optax

        from ray_tpu.models.llama import LlamaConfig, split_stages

        cfg = LlamaConfig.tiny(dtype=jnp.float32)
        S, v = 2, 2
        stage_fns, init_fns = split_stages(cfg, S, virtual_per_rank=v)
        rng = np.random.default_rng(0)
        ids = rng.integers(0, cfg.vocab_size, size=(8, 16)).astype(np.int32)
        tx = optax.adamw(1e-3)

        def run_leg(wire):
            pipe = mp.MPMDPipeline(
                stage_fns, init_fns, optimizer=tx,
                num_microbatches=microbatches, virtual_per_rank=v,
                wire_dtype=wire, step_window=2, drain_timeout=300.0,
                gang_hosts=1, gang_platform="cpu",
                gang_local_device_count=2,
                stage_options=[
                    {"spmd_devices": 2, "zero_sharding": "opt+grads"},
                    {"spmd_devices": 2, "zero_sharding": "opt+grads"}])
            syncs0 = mp.mpmd_driver_sync_count()
            caches = []
            for _ in range(steps):
                pipe.submit_step(ids, ids)
                rep = pipe.last_step_report()
                if rep is not None:
                    caches.append(rep["jit_cache"])
            results = pipe.flush()
            syncs = mp.mpmd_driver_sync_count() - syncs0
            rep = pipe.last_step_report()
            caches.append(rep["jit_cache"])
            stats = pipe.stats()
            stage0 = ray_tpu.get(
                pipe._handles[0].submit("stats", [()])[0])
            pipe.stop()
            return {
                "losses": [l for _, l in sorted(results)],
                "driver_syncs": syncs,
                "caches": caches,
                "stats": stats,
                "zero_ratio": stage0["zero_opt_bytes_per_replica"]
                / max(1, stage0["replicated_opt_bytes"]),
            }

        fp32 = run_leg("fp32")
        i8 = run_leg("int8")

        def leg_cache_ok(leg):
            # Constant across steps (no per-step/microbatch retrace).
            # fwd/apply compile exactly once per owned chunk; bwd may
            # compile twice per chunk under SPMD (the first call's fresh
            # zero-accumulator carries a different committed sharding
            # than the steady-state loop-carried one) — warmup-bounded,
            # never per-step.
            if leg["caches"][0] != leg["caches"][-1]:
                return False
            for st in leg["caches"][-1].values():
                if st["fwd"] != v or st["apply"] != v:
                    return False
                if not v <= st["bwd"] <= 2 * v:
                    return False
            return True

        cache_ok = leg_cache_ok(fp32) and leg_cache_ok(i8)
        wire_ratio = i8["stats"]["wire_reduction_vs_fp32"]
        loss_gap = max(abs(a - b) for a, b in zip(fp32["losses"],
                                                  i8["losses"]))
        out = {
            "steps": steps,
            "microbatches": microbatches,
            "virtual_per_rank": v,
            "results_ok": len(fp32["losses"]) == steps
            and len(i8["losses"]) == steps,
            "driver_syncs_steady": fp32["driver_syncs"]
            + i8["driver_syncs"],
            "jit_cache_constant": cache_ok,
            "wire_reduction_vs_fp32": round(wire_ratio, 2),
            "wire_ok": wire_ratio >= 3.0,
            "int8_loss_gap": round(loss_gap, 4),
            "loss_envelope_ok": loss_gap < 0.05,
            "zero_opt_bytes_ratio": round(i8["zero_ratio"], 3),
            "zero_ok": i8["zero_ratio"] <= 0.5 + 0.05,
        }
        out["ok"] = bool(out["results_ok"]
                         and out["driver_syncs_steady"] == 0
                         and out["jit_cache_constant"] and out["wire_ok"]
                         and out["loss_envelope_ok"] and out["zero_ok"])
        return out
    finally:
        ray_tpu.shutdown()


def run_serving_smoke(max_new: int = 10) -> dict:
    """Continuous-batching inference invariants (tier-1 guard for
    ISSUE 8; one in-process engine "replica", no timing assertions):

    1. **Token identity**: concurrent requests of mixed prompt lengths
       decoded through the paged KV cache produce EXACTLY the tokens of
       per-request full-context greedy decode (fp32 tiny GPT-2).
    2. **Token-boundary admission**: at least one request was admitted
       while another was mid-decode (``admitted_mid_batch >= 1``) — the
       batch never drained to let a newcomer in.
    3. **Fixed-slot compile**: the decode step compiled exactly once
       across all admissions/retirements.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import GPT2, GPT2Config
    from ray_tpu.serve.llm_engine import LLMEngine, NaiveLM

    cfg = GPT2Config.tiny(dtype=jnp.float32)
    model = GPT2(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    eng = LLMEngine(model, params, max_slots=4, page_size=8, max_ctx=64,
                    chunk_tokens=2)
    naive = NaiveLM(model, params, width=64)
    try:
        rng = np.random.default_rng(0)
        # Mixed lengths within ONE prefill bucket (<= 8): the smoke pays
        # exactly two engine compiles (prefill + decode) — tier-1 cheap.
        sizes = (4, 6, 8)
        prompts = [list(map(int, rng.integers(0, cfg.vocab_size, size=n)))
                   for n in sizes]
        # Provably-mid-flight admission: start the first request, wait for
        # a streamed chunk (it is decoding), then submit the rest.
        rid0 = eng.submit(prompts[0], max_new_tokens=2 * max_new)
        stream = eng.stream(rid0, timeout=60)
        next(stream)
        rids = [eng.submit(p, max_new_tokens=max_new) for p in prompts[1:]]
        outs = [eng.result(r, timeout=120) for r in rids]
        out0 = eng.result(rid0, timeout=120)
        refs = [naive.generate(p, max_new) for p in prompts[1:]]
        ref0 = naive.generate(prompts[0], 2 * max_new)
        st = eng.stats()
        out = {
            "requests": len(prompts),
            "prompt_sizes": list(sizes),
            "token_identical": bool(outs == refs and out0 == ref0),
            "admitted_mid_batch": st["admitted_mid_batch"],
            "decode_cache_size": st.get("decode_cache_size", 1),
            "avg_batch_occupancy": round(st["avg_batch_occupancy"], 3),
            "pages_leaked": st["pages_in_use"],
        }
        out["ok"] = bool(out["token_identical"]
                         and out["admitted_mid_batch"] >= 1
                         and out["decode_cache_size"] == 1
                         and out["pages_leaked"] == 0)
    finally:
        eng.close()

    # ---- serving tier (ISSUE 13): prefix cache, speculative decode,
    # disaggregated prefill — each gate is cheap and deterministic.
    from ray_tpu.serve.sampling import SamplingParams

    rng2 = np.random.default_rng(1)
    shared = list(map(int, rng2.integers(0, cfg.vocab_size, size=16)))
    p1 = shared + [1, 2, 3]
    p2 = shared + [4]

    # 4. **Prefix cache skips prefill**: the second shared-prefix
    # request adopts cached pages and prefills only the tail, with
    # token identity intact.
    eng = LLMEngine(model, params, max_slots=2, page_size=8, max_ctx=64,
                    prefix_cache=True)
    try:
        o1 = eng.result(eng.submit(p1, max_new), timeout=120)
        t1 = eng.stats()["prefill_tokens"]
        o2 = eng.result(eng.submit(p2, max_new), timeout=120)
        st = eng.stats()
        out["prefix_hit_pages"] = st["prefix_hit_pages"]
        out["prefill_tokens_saved"] = st["prefill_tokens_saved"]
        out["prefix_tail_tokens"] = st["prefill_tokens"] - t1
        out["prefix_token_identical"] = bool(
            o1 == naive.generate(p1, max_new)
            and o2 == naive.generate(p2, max_new))
        out["ok"] = bool(out["ok"] and out["prefix_token_identical"]
                         and st["prefix_hit_pages"] >= 1
                         and out["prefix_tail_tokens"] < len(p2)
                         and st["pages_in_use"] == 0)
    finally:
        eng.close()

    # 5. **Speculative decoding**: self-draft acceptance is total, the
    # sampled stream is bitwise the plain sampled stream.
    sp = SamplingParams(temperature=0.8, top_p=0.9, seed=7)
    eng = LLMEngine(model, params, max_slots=2, page_size=8, max_ctx=64,
                    draft_model=model, draft_params=params, spec_tokens=3)
    try:
        o = eng.result(eng.submit(p1, max_new, sampling=sp), timeout=120)
        st = eng.stats()
        out["spec_accepted"] = st["spec_accepted"]
        out["spec_acceptance_rate"] = round(st["spec_acceptance_rate"], 3)
        out["spec_token_identical"] = bool(
            o == naive.generate(p1, max_new, sampling=sp))
        out["ok"] = bool(out["ok"] and out["spec_token_identical"]
                         and st["spec_accepted"] >= 1
                         and st["pages_in_use"] == 0)
    finally:
        eng.close()

    # 6. **Disaggregated prefill**: KV pages stream worker→engine over
    # the object plane (put_many refs → get_many), outputs identical,
    # zero KV pages leaked after the handoff.
    import ray_tpu
    from ray_tpu.serve.prefill import PrefillWorker

    ray_tpu.init(num_cpus=2, object_store_memory=128 * 1024**2)
    try:
        worker = PrefillWorker("gpt2", {"tiny": True, "dtype": "float32"},
                               0, page_size=8, use_object_plane=True)
        eng = LLMEngine(model, params, max_slots=2, page_size=8,
                        max_ctx=64, prefill=worker, prefill_min_tokens=8)
        try:
            o1 = eng.result(eng.submit(p1, max_new), timeout=120)
            o2 = eng.result(eng.submit(p2, max_new), timeout=120)
            st = eng.stats()
            out["prefill_offloaded"] = st["prefill_offloaded"]
            out["disagg_wire_bytes"] = st["wire_bytes"]
            out["disagg_pages_leaked"] = st["pages_in_use"]
            out["disagg_token_identical"] = bool(
                o1 == naive.generate(p1, max_new)
                and o2 == naive.generate(p2, max_new))
            out["ok"] = bool(out["ok"] and out["disagg_token_identical"]
                             and st["prefill_offloaded"] >= 2
                             and st["wire_bytes"] > 0
                             and st["prefill_inflight"] == 0
                             and st["pages_in_use"] == 0)
        finally:
            eng.close()
    finally:
        ray_tpu.shutdown()
    return out


def run_rlhf_smoke(steps: int = 3) -> dict:
    """RLHF close-the-loop invariants (tier-1 guard for ISSUE 14):

    1. **Generation/SGD overlap**: the rollout producer is a flow.Stage
       worker, so while the learner runs SGD on batch i the engine
       decodes batch i+1 — proven by the engine's count of decode steps
       going up between the start and the end of a step's SGD.
    2. **Hot swap stays compiled**: >= 2 ``swap_weights`` applied with
       ``decode_cache_size == 1`` throughout, zero requests
       dropped/errored (every rollout at full length), zero leaked
       pages.
    3. **Logprob capture parity**: the behavior logprobs the engine
       stamped during generation match a full-context forward pass's
       log-softmax at the emitted tokens.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import GPT2, GPT2Config, GPT2WithValue
    from ray_tpu.rllib.algorithms.rlhf import (RLHFConfig, RLHFLoop,
                                               target_token_reward)
    from ray_tpu.serve.llm_engine import LLMEngine

    cfg = GPT2Config.tiny(dtype=jnp.float32, vocab_size=64, num_layers=2,
                          hidden_size=32, num_heads=2,
                          max_position_embeddings=64)
    acm = GPT2WithValue(cfg)
    params = acm.init(jax.random.PRNGKey(0),
                      jnp.zeros((1, 8), jnp.int32))["params"]
    model = GPT2(cfg)
    eng = LLMEngine(model, params["lm"], max_slots=8, page_size=8,
                    max_ctx=64)
    rng = np.random.default_rng(0)
    prompts = [list(map(int, rng.integers(0, 64, size=4)))
               for _ in range(4)]
    loop = RLHFLoop(
        eng, acm, params, prompts, target_token_reward(7),
        RLHFConfig(rollouts_per_step=16, max_new_tokens=24, lr=1e-3,
                   num_sgd_iter=1, seed=0))
    try:
        hist = loop.run(steps)
        # Logprob parity on a fresh greedy rollout under the CURRENT
        # (post-swap) weights — capture must track the live version.
        rec = eng.generate_rollouts([prompts[0]], max_new_tokens=8)[0]
        seq = rec["prompt"] + rec["tokens"]
        logits = model.apply({"params": loop.learner.lm_params},
                             jnp.asarray([seq], jnp.int32))
        lp = jax.nn.log_softmax(logits[0], axis=-1)
        p = len(rec["prompt"])
        ref = [float(lp[p - 1 + i, t])
               for i, t in enumerate(rec["tokens"])]
        logp_err = float(np.max(np.abs(np.asarray(ref)
                                       - np.asarray(rec["logprobs"]))))
        overlap_windows = sum(
            1 for m in hist if m["decode_steps_during_sgd"] >= 1)
        st = eng.stats()
        out = {
            "steps": steps,
            "overlap_windows": overlap_windows,
            "swaps": st["swaps"],
            "decode_cache_size": st.get("decode_cache_size", -1),
            "pages_leaked": st["pages_in_use"],
            "rollouts_full": all(m["response_tokens"] == 16 * 24
                                 for m in hist),
            "stale_batches_dropped": loop.stale_batches_dropped,
            "logp_parity_err": logp_err,
            "final_version": loop.weight_version,
        }
        out["ok"] = bool(out["overlap_windows"] >= 1
                         and out["swaps"] >= 2
                         and out["decode_cache_size"] == 1
                         and out["pages_leaked"] == 0
                         and out["rollouts_full"]
                         and out["logp_parity_err"] < 1e-3)
    finally:
        loop.close()
        eng.close()
    print(json.dumps({"rlhf": out}))
    return out


def _flow_smoke_reader(path, columns):
    """Synthetic source for run_flow_smoke: the path encodes the block
    index."""
    import numpy as _np

    from ray_tpu.data.block import block_from_numpy

    rows = 512
    base = int(path) * rows
    return block_from_numpy({
        "id": _np.arange(base, base + rows, dtype=_np.int64)})


def run_flow_smoke(blocks: int = 6, window: int = 2) -> dict:
    """Streaming-Dataset-on-flow invariants (tier-1 guard for ISSUE 11):

    1. **Read→map→consume overlap**: driving a lazy read→map plan through
       the windowed flow executor, the reads of LATER source blocks are
       submitted before the consumer is handed an EARLIER block, and no
       further ahead than the window: the stream fills its window, then
       yields (peak_in_flight == window >= 2, every block submitted and
       emitted once) — streaming execution, neither a stage barrier nor
       one read at a time.
    2. **Bounded residency**: the flow RefStream never holds more than
       ``window`` output blocks in flight (peak_in_flight ≤ window).
    3. **Exact results**: the streamed rows are exactly the eager
       engine's rows (byte-identical ids, in order).
    4. **Zero driver syncs**: the steady consume loop leaves
       mesh_group.driver_sync_count() untouched (the executor only
       chains refs — no lockstep dispatch path is ever touched).
    """
    import numpy as np

    import ray_tpu
    from ray_tpu.data.block import block_to_numpy
    from ray_tpu.data.dataset import Dataset
    from ray_tpu.parallel import mesh_group

    ray_tpu.init(num_cpus=4, object_store_memory=256 * 1024**2,
                 ignore_reinit_error=True)
    try:
        ds = Dataset(
            [("read", _flow_smoke_reader, str(i), None)
             for i in range(blocks)]
        ).map_batches(lambda b: dict(b, id=b["id"] * 3))
        ex = ds._executor(window=window, name="flow_smoke")
        syncs_before = mesh_group.driver_sync_count()
        ids = []
        for ref in ex.iter_block_refs():
            ids.append(block_to_numpy(ray_tpu.get(ref))["id"])
            del ref
        syncs = mesh_group.driver_sync_count() - syncs_before
        st = ex.last_stream_stats or {}
        got = np.concatenate(ids)
        want = np.arange(blocks * 512, dtype=np.int64) * 3
        out = {
            "blocks": blocks,
            "window": window,
            "exact_results": bool(np.array_equal(got, want)),
            "peak_in_flight": st.get("peak_in_flight", -1),
            "residency_ok": 0 < st.get("peak_in_flight", -1) <= window,
            "produce_consume_overlap": bool(
                window >= 2 and st.get("peak_in_flight") == window
                and st.get("submitted") == blocks
                and st.get("items_out") == blocks),
            "driver_syncs": syncs,
        }
        out["ok"] = bool(out["exact_results"] and out["residency_ok"]
                         and out["produce_consume_overlap"]
                         and syncs == 0)
        return out
    finally:
        ray_tpu.shutdown()


def run_locality_smoke(mb: int = 8) -> dict:
    """Locality-aware scheduling invariants (tier-1 guard for ISSUE 17):

    Two real node-agent subprocesses (distinct hosts/stores) join the
    head; a producer pinned to host A seals an ``mb``-MiB array there.

    1. **Local case — compute follows the bytes**: a DEFAULT-strategy
       consumer of that ref must land on host A (the arg-locality score
       outranks utilization packing) and read its arg with ZERO demand
       wire bytes (``sched_locality_wire_bytes_total`` stays flat) —
       same-host zero-copy segment attach, no transfer-plane pull.
    2. **Remote case — prefetch overlaps the queue**: a consumer pinned
       hard to host B forces a miss; the head must start a store-to-store
       prefetch of the arg into B WHILE the task is still queued (one
       prefetch started, and its record names that task: the head starts
       it at the task's placement, before it hands the task to a worker),
       complete it, and the worker must again find the bytes already
       local (wire counter still flat: no demand pull was needed).
    """
    import numpy as np

    import ray_tpu
    from ray_tpu.util.scheduling_strategies import (
        NodeAffinitySchedulingStrategy,
    )
    from ray_tpu.util.testing import start_node_agent, wait_for_condition

    n = mb * 1024 * 1024 // 8
    # Headless head (0 CPUs): every task must run on a real agent.
    ray_tpu.init(num_cpus=0, object_store_memory=256 * 1024**2,
                 ignore_reinit_error=True)
    agents = []
    try:
        head = ray_tpu._head
        base = len(head.raylets)
        agents.append(start_node_agent(head, num_cpus=2,
                                       resources={"hostA": 1.0}))
        agents.append(start_node_agent(head, num_cpus=2,
                                       resources={"hostB": 1.0}))
        wait_for_condition(lambda: len(head.raylets) >= base + 2,
                           timeout=30)
        with head._lock:
            node_a = next(nid for nid, st in head.scheduler.nodes.items()
                          if "hostA" in st.total)
            node_b = next(nid for nid, st in head.scheduler.nodes.items()
                          if "hostB" in st.total)

        def counters():
            c = head.locality_stats()["counters"]
            return (c.get("sched_locality_wire_bytes_total", 0.0),
                    c.get("sched_locality_hits_total", 0.0),
                    c.get("sched_locality_prefetch_started_total", 0.0))

        @ray_tpu.remote(resources={"hostA": 0.01})
        def produce():
            return np.arange(n, dtype=np.int64)

        @ray_tpu.remote
        def consume(arr):
            import ray_tpu as rt

            return {"sum": int(arr[:64].sum()),
                    "node": rt.get_runtime_context().get_node_id()}

        ref = produce.remote()
        # Wait for the seal through the directory — a driver-side get()
        # would copy the bytes onto the head host and blur the signal.
        wait_for_condition(
            lambda: (lambda e: e is not None and e.locations)(
                head.gcs.object_lookup(ref.id)), timeout=30)

        # --- local case ---
        w0, h0, _ = counters()
        got = ray_tpu.get(consume.remote(ref), timeout=60)
        w1, h1, _ = counters()
        with head._lock:
            host_of = dict(head.node_host)
        local_on_a = host_of.get(
            ray_tpu.NodeID.from_hex(got["node"])) == host_of.get(node_a)
        local_wire = w1 - w0
        local_hit = h1 - h0

        # --- remote case ---
        w2, _, p2 = counters()
        aff = NodeAffinitySchedulingStrategy(node_b, soft=False)
        ref_b = consume.options(scheduling_strategy=aff).remote(ref)
        got_b = ray_tpu.get(ref_b, timeout=60)
        # The agent acks the prefetch asynchronously; let it land before
        # reading the record (the task itself already proved the bytes).
        wait_for_condition(
            lambda: any(r["oid"] == ref.id.hex() and r["ok"]
                        for r in head.locality_stats()["prefetch"]),
            timeout=15)
        w3, _, p3 = counters()
        recs = [r for r in head.locality_stats()["prefetch"]
                if r["oid"] == ref.id.hex() and r["node"] == node_b.hex()]
        rec = recs[-1] if recs else None
        out = {
            "arg_mb": mb,
            "local_on_producer_host": bool(local_on_a),
            "local_wire_bytes": local_wire,
            "local_hit_counted": local_hit == 1,
            "remote_on_b": host_of.get(ray_tpu.NodeID.from_hex(
                got_b["node"])) == host_of.get(node_b),
            "remote_wire_bytes": w3 - w2,
            "prefetch_completed": bool(rec and rec["ok"]
                                       and rec["done"] is not None),
            "prefetch_overlapped_queue": bool(
                rec and p3 - p2 == 1
                and rec["task"] == ref_b.id.task_id().hex()),
            "values_ok": got["sum"] == got_b["sum"] == 2016,
        }
        out["ok"] = bool(out["local_on_producer_host"]
                         and out["local_wire_bytes"] == 0
                         and out["local_hit_counted"]
                         and out["remote_on_b"]
                         and out["remote_wire_bytes"] == 0
                         and out["prefetch_completed"]
                         and out["prefetch_overlapped_queue"]
                         and out["values_ok"])
        return out
    finally:
        import contextlib

        for a in agents:
            with contextlib.suppress(Exception):
                a.kill()
        for a in agents:
            with contextlib.suppress(Exception):
                a.wait(timeout=10)
        ray_tpu.shutdown()


def run_replay_smoke(frag_len: int = 512, dim: int = 512,
                     batches: int = 4, batch_size: int = 64,
                     steady_inserts: int = 4) -> dict:
    """Distributed replay plane invariants (no timing thresholds —
    tier-1 safe):

    1. **Zero-copy insert / eviction = ref release**: fragment columns
       are store-resident pooled-segment objects; once the shard rings
       are full, every further insert evicts one fragment and its
       segments recycle — steady-state inserts create NO new shm
       segments (``pool_created`` flat, ``pool_hits`` climbing).
    2. **One gather per batch**: K sampled batches issue exactly K
       batched ``get_many`` resolves (``plane.gather_calls``), never
       per-transition gets.
    3. **Gather/SGD overlap**: with the flow prefetcher on, the gather
       of batch i+1 is issued while batch i is still with the consumer —
       after every batch handed over, the plane's gather count gets (at
       least) one ahead of the batches consumed without the consumer
       asking for another.
    """
    import time

    import numpy as np

    import ray_tpu
    from ray_tpu.rllib.execution.replay_plane import ReplayPlane

    ray_tpu.init(num_cpus=4, object_store_memory=256 * 1024**2,
                 ignore_reinit_error=True)
    try:
        from ray_tpu._private.worker import global_worker as gw

        store = gw.transport.head.raylets[gw.node_id].store
        out = {}
        # 2 shards x 3 slots; obs/next_obs are frag_len*dim float32
        # (1 MiB at the defaults) — at the segment pool's MIN_CLASS, so
        # fragments land in pooled shm segments, not dedicated ones.
        plane = ReplayPlane(capacity=6 * frag_len, num_shards=2,
                            alpha=0.0, seed=0)
        rng = np.random.default_rng(0)

        def frag():
            return {
                "obs": rng.standard_normal((frag_len, dim))
                .astype(np.float32),
                "actions": rng.integers(0, 4, frag_len).astype(np.int64),
                "rewards": rng.standard_normal(frag_len)
                .astype(np.float32),
                "next_obs": rng.standard_normal((frag_len, dim))
                .astype(np.float32),
                "dones": np.zeros(frag_len, np.float32),
            }

        def settled_created():
            """pool_created once pending eviction releases land (the
            shard's release notify races the insert ack by a hair)."""
            last = store.stats().get("pool_created", -1)
            for _ in range(40):
                time.sleep(0.05)
                cur = store.stats().get("pool_created", -1)
                if cur == last:
                    return cur
                last = cur
            return last

        for _ in range(7):   # fill both rings + first eviction (warmup)
            plane.insert(frag())
        assert plane.size == 6 * frag_len
        created_before = settled_created()
        hits_before = store.stats().get("pool_hits", 0)
        for _ in range(steady_inserts):   # every insert now evicts
            plane.insert(frag())
        _ = plane.size                    # barrier: all acks harvested
        out["segments_created_steady"] = (settled_created()
                                          - created_before)
        out["pool_hits_steady"] = (store.stats().get("pool_hits", 0)
                                   - hits_before)
        out["zero_copy_ok"] = (out["segments_created_steady"] == 0
                               and out["pool_hits_steady"] > 0)

        # --- one batched gather per sampled batch ---
        g0 = plane.gather_calls
        for _ in range(batches):
            b = plane.sample(batch_size)
            assert b["obs"].shape == (batch_size, dim)
        out["gathers_per_batch"] = (plane.gather_calls - g0) / batches
        out["gather_ok"] = plane.gather_calls - g0 == batches

        # --- gather/SGD overlap via the flow prefetcher ---
        from ray_tpu.util.testing import wait_for_condition

        g1 = plane.gather_calls
        stage = plane.prefetch(batch_size, depth=2)
        ahead = 0
        for consumed in range(1, batches + 2):
            next(stage)                   # batch i, now with the consumer
            try:                          # ... and i+1 gathers meanwhile
                wait_for_condition(
                    lambda: plane.gather_calls - g1 > consumed, timeout=10)
                ahead += 1
            except TimeoutError:
                break
        stage.close()
        out["gathers_ahead_of_consumer"] = ahead
        out["overlap_ok"] = ahead == batches + 1
        plane.close()
        out["ok"] = bool(out["zero_copy_ok"] and out["gather_ok"]
                         and out["overlap_ok"])
        return out
    finally:
        ray_tpu.shutdown()


def run_tracing_smoke(batch: int = 300, batches: int = 5) -> dict:
    """Tracing-plane invariants (tier-1 guard for the observability PR):

    1. **Off = free**: with tracing off (the default), the instrumented
       put/submit paths record ZERO spans, before and after an
       enable→exercise→disable cycle, and the cycle leaves nothing
       behind that the off path would pay for: the switch reads off and
       this thread carries no trace context.
    2. **On = assembled**: with tracing on, ONE driver boundary span
       over tasks pinned to two virtual nodes produces a single trace
       whose spans come from >= 3 distinct processes on >= 2 nodes,
       and the chrome dump json-round-trips with >= 1 cross-process
       flow edge.
    """
    import json as _json

    import numpy as np

    import ray_tpu
    from ray_tpu import observability as obs
    from ray_tpu.util import tracing

    def small_puts():
        from ray_tpu._private.worker import global_worker as gw

        data = np.arange(64, dtype=np.int64)  # small: the inline path
        for _ in range(batches):
            refs = [ray_tpu.put(data) for _ in range(batch)]
            del refs
            gw._drain_ref_gc_queue()

    out = {}
    # --- phase 1: tracing OFF is free ---
    ray_tpu.init(num_cpus=2, object_store_memory=256 * 1024**2,
                 ignore_reinit_error=True)
    try:
        obs.drain_spans()  # what an earlier traced run left in the ring
        small_puts()
        out["off_zero_spans"] = obs.drain_spans() == []
        # Enable, record through every layer, then disable: the cycle
        # must leave no residue on the off path.
        tracing.enable_tracing()
        with tracing.span("tracing_smoke.warm"):
            ray_tpu.get(ray_tpu.put(1))
        tracing.disable_tracing()
        obs.drain_spans()
        tracing.pop_local_spans()
        out["off_path_restored"] = bool(
            not tracing.tracing_enabled() and not obs.on()
            and obs.get_context() is None)
        small_puts()
        out["off_still_zero_spans"] = obs.drain_spans() == []
    finally:
        ray_tpu.shutdown()

    # --- phase 2: tracing ON assembles one cross-process trace ---
    tracing.enable_tracing()
    try:
        ray_tpu.init(num_cpus=2, object_store_memory=256 * 1024**2,
                     ignore_reinit_error=True)
        from ray_tpu import state
        from ray_tpu._private.worker import global_worker as gw
        from ray_tpu.cluster_utils import Cluster
        from ray_tpu.observability.timeline import trace_stats
        from ray_tpu.util.scheduling_strategies import (
            NodeAffinitySchedulingStrategy,
        )
        from ray_tpu.util.testing import wait_for_condition

        cluster = Cluster(initialize_head=False)
        node2 = cluster.add_node(num_cpus=2,
                                 object_store_memory=128 * 1024**2)

        @ray_tpu.remote
        def work(x):
            _t = __import__("time")
            _t.sleep(0.05)
            return x + 1

        with tracing.span("tracing_smoke.root"):
            ctx = obs.get_context()
            refs = [
                work.options(scheduling_strategy=NodeAffinitySchedulingStrategy(
                    nid, soft=False)).remote(i)
                for i, nid in enumerate((gw.node_id, node2))
            ]
            vals = ray_tpu.get(refs, timeout=60)
        tid = ctx[0]

        def assembled():
            tl = state.get_timeline(tid)
            procs = {s["proc"] for s in tl["spans"]}
            nodes = {s["node"] for s in tl["spans"] if s["node"]}
            return len(procs) >= 3 and len(nodes) >= 2

        wait_for_condition(assembled, timeout=30)
        events = ray_tpu.timeline(trace_id=tid)
        st = trace_stats(events)
        rows = [r for r in state.list_traces() if r["trace_id"] == tid]
        out.update({
            "values_ok": vals == [1, 2],
            "trace_id": tid,
            "trace_listed": bool(rows),
            "procs": st["procs"],
            "nodes": st["nodes"],
            "flow_edges": st["flow_edges"],
            "chrome_events": st["events"],
            "chrome_json_ok": isinstance(
                _json.loads(_json.dumps(events)), list),
        })
        out["assembled_ok"] = bool(st["procs"] >= 3 and st["nodes"] >= 2
                                   and st["flow_edges"] >= 1
                                   and st["events"] > 0)
    finally:
        ray_tpu.shutdown()
        tracing.disable_tracing()
    out["ok"] = bool(out["off_zero_spans"] and out["off_path_restored"]
                     and out["off_still_zero_spans"] and out["values_ok"]
                     and out["trace_listed"] and out["chrome_json_ok"]
                     and out["assembled_ok"])
    return out


def run_broadcast_smoke(receivers: int = 3, mb: int = 24) -> dict:
    """Cooperative-broadcast invariant (tier-1 guard for ISSUE 20):

    One driver put, ``receivers`` real node-agent subprocesses (distinct
    host keys → every read is a wire pull) demand-pull the same object
    at a synchronized instant.  The pulls must stripe (multi-range
    scheduling engaged), at least one chunk range must be served by a
    NON-OWNER peer (the dissemination tree formed — receivers fed each
    other instead of all draining the owner), every copy must be
    byte-identical, and the owner's store must create zero new segments
    (serving is zero-copy out of the existing one).
    """
    import hashlib
    import time as _time

    import numpy as np

    import ray_tpu
    from ray_tpu._private.config import CONFIG
    from ray_tpu.util.testing import start_node_agent, wait_for_condition

    saved = {k: os.environ.get(k) for k in
             ("RAY_TPU_TRANSFER_STRIPE_MIN_BYTES",
              "RAY_TPU_TRANSFER_CHUNK_BYTES",
              "RAY_TPU_TRANSFER_STRIPE_RANGES")}
    # Small chunks + many ranges: plenty of stealable scheduling units
    # even on a loopback wire fast enough to finish a pull in ~100ms.
    os.environ["RAY_TPU_TRANSFER_STRIPE_MIN_BYTES"] = str(1 << 20)
    os.environ["RAY_TPU_TRANSFER_CHUNK_BYTES"] = str(256 * 1024)
    os.environ["RAY_TPU_TRANSFER_STRIPE_RANGES"] = "12"
    CONFIG.reset()
    ray_tpu.init(num_cpus=2, object_store_memory=256 * 1024**2,
                 ignore_reinit_error=True)
    agents = []
    try:
        head = ray_tpu._head
        baseline = len(head.raylets)
        agents = [start_node_agent(head, num_cpus=1,
                                   resources={f"bc{i}": 1},
                                   store_capacity=128 * 1024**2)
                  for i in range(receivers)]
        wait_for_condition(
            lambda: len(head.raylets) >= baseline + receivers, timeout=60)

        payload = np.random.default_rng(0).integers(
            0, 256, size=mb * 1024 * 1024, dtype=np.uint8)
        want = hashlib.sha256(payload.tobytes()).hexdigest()
        ref = ray_tpu.put(payload)

        import ray_tpu._private.worker as worker_mod

        gw = worker_mod.global_worker
        owner_store = gw.transport.head.raylets[gw.node_id].store
        seg_before = owner_store.stats()["segments_created_total"]

        @ray_tpu.remote
        def pull(oid_hex, start_at):
            import hashlib as _h
            import time as _t

            from ray_tpu._private import transfer
            from ray_tpu._private.ids import ObjectID
            from ray_tpu.object_ref import ObjectRef

            r = ObjectRef(ObjectID(bytes.fromhex(oid_hex)))
            while _t.time() < start_at:
                _t.sleep(0.005)
            v = ray_tpu.get(r)
            digest = _h.sha256(np.asarray(v).tobytes()).hexdigest()
            return digest, transfer.transfer_stats()

        # The id rides as a STRING so the scheduler cannot prefetch the
        # bytes ahead of the synchronized demand pulls — the smoke needs
        # the pulls to RACE to form the dissemination tree.
        start_at = _time.time() + 2.0
        futs = [pull.options(resources={f"bc{i}": 1}).remote(
            ref.hex(), start_at) for i in range(receivers)]
        res, no_hang = _get_within(futs, timeout=90)
        res = res or []
        seg_after = owner_store.stats()["segments_created_total"]

        out = {
            "receivers": receivers,
            "payload_mb": mb,
            "byte_identity": bool(res) and all(d == want for d, _ in res),
            "striped_pulls": sum(
                int(s.get("striped_pulls", 0)) for _, s in res),
            "ranges_from_partial": sum(
                int(s.get("ranges_from_partial", 0)) for _, s in res),
            "peer_served_ranges": sum(
                int(s.get("served_partial_ranges", 0)) for _, s in res),
            "owner_new_segments": seg_after - seg_before,
            "no_hang": no_hang,
        }
        out["ok"] = bool(out["byte_identity"]
                         and out["striped_pulls"] >= receivers
                         and out["ranges_from_partial"] >= 1
                         and out["peer_served_ranges"] >= 1
                         and out["owner_new_segments"] == 0
                         and out["no_hang"])
        return out
    finally:
        for a in agents:
            try:
                a.kill()
            except Exception:
                pass
        for a in agents:
            try:
                a.wait(timeout=10)
            except Exception:
                pass
        ray_tpu.shutdown()
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        CONFIG.reset()


def main() -> int:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    out = run_smoke()
    obj = run_object_plane_smoke()
    out["object_plane"] = obj
    ckpt = run_checkpoint_smoke()
    out["checkpoint"] = ckpt
    roll = run_rollout_smoke()
    out["rollout"] = roll
    rpc = run_rpc_chaos_smoke()
    out["rpc_chaos"] = rpc
    nl = run_node_loss_smoke()
    out["node_loss"] = nl
    el = run_elastic_smoke()
    out["elastic"] = el
    sv = run_serving_smoke()
    out["serving"] = sv
    zr = run_zero_smoke()
    out["zero"] = zr
    mpmd = run_mpmd_smoke()
    out["mpmd"] = mpmd
    fl = run_flow_smoke()
    out["flow"] = fl
    td = run_3d_smoke()
    out["threed"] = td
    rl = run_rlhf_smoke()
    out["rlhf"] = rl
    loc = run_locality_smoke()
    out["locality"] = loc
    rp = run_replay_smoke()
    out["replay"] = rp
    tr = run_tracing_smoke()
    out["tracing"] = tr
    bc = run_broadcast_smoke()
    out["broadcast"] = bc
    out["ok"] = bool(out["ok"] and obj["ok"] and ckpt["ok"] and roll["ok"]
                     and rpc["ok"] and nl["ok"] and el["ok"] and sv["ok"]
                     and zr["ok"] and mpmd["ok"] and fl["ok"] and td["ok"]
                     and rl["ok"] and loc["ok"] and rp["ok"] and tr["ok"]
                     and bc["ok"])
    print(json.dumps(out))
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
