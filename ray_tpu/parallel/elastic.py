"""Elastic data parallelism: grow/shrink a live ZeRO DP gang mid-run.

Every piece of the elasticity story exists in isolation in this repo —
N→M resharded opt-state restore (``zero.reshard_opt_state`` +
``place_opt_state``), budgeted gang restarts (MeshGroup), node-death
detection, and an autoscaler that already scales serve replicas.  This
module composes them into a *training* plane whose world size can change
between steps without losing one:

- :class:`ElasticMeshGroup` drives a MeshGroup-hosted DP run whose host
  count floats inside ``num_hosts=(min, max)``.  A **grow** (autoscaler
  offers capacity) and a **notice shrink** (``preemption_notice``) both
  land at a step boundary: the gang snapshots, is rebuilt at the new
  size, receives ONE versioned ``ray_tpu.put`` weight broadcast, and the
  ZeRO optimizer shards re-partition N→M through the assembled
  ``(total,)`` form — no disk round trip.  A **lease expiry** (SIGKILL,
  no notice) surfaces as a MeshGroupError; the survivors' size is fitted,
  the gang rebuilds from the last boundary snapshot, and any steps since
  are *replayed* deterministically — ``steps_lost == 0`` by construction.

- The step itself (:func:`build_elastic_step`) is **slot-deterministic**:
  the global batch is a fixed number of ``slots`` microbatches regardless
  of world size, each slot's gradient is computed by an identical
  per-slot program, and the combine is an all_gather into global slot
  order followed by a fixed-length ordered sum.  Every rank computes the
  identical full gradient; only the optimizer chunk it *applies* depends
  on its rank.  All cross-rank collectives are pure data movement, so the
  parameter trajectory is **bitwise identical for any world size that
  divides ``slots``** — which is what lets a chaos test assert that a
  gang SIGKILLed at lease expiry finishes bitwise-equal to an unkilled
  run at the surviving size (the in-process
  :func:`reference_trajectory` IS that run).

Note ``zero.zero_clip_by_global_norm`` reconstructs the norm with a psum
whose operand layout depends on the world size; elastic steps that clip
use the ``grad_clip`` argument here instead (a fixed-length norm over the
unpadded gradient), which is world-invariant.
"""
from __future__ import annotations

import logging
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

import ray_tpu
from ray_tpu import exceptions as exc
from ray_tpu._private import chaos
from ray_tpu.parallel import mesh_group as mg
from ray_tpu.parallel import zero
from ray_tpu.parallel.zero import DATA_AXIS

logger = logging.getLogger(__name__)


# ---- the slot-deterministic step ----
def build_elastic_step(loss_fn: Callable, tx, sharder: "zero.ZeroSharder",
                       *, slots: int, world: Optional[int] = None,
                       axis: str = DATA_AXIS,
                       grad_clip: Optional[float] = None) -> Callable:
    """ZeRO DP step for use inside a shard_map body whose parameter
    trajectory is bitwise-invariant to the mesh size.

    The local batch is ``slots/world`` microbatch slots; each slot runs an
    identical ``value_and_grad`` + flatten program (``jax.lax.map``, so
    the per-slot HLO does not depend on the local count), the per-slot
    flat gradients are all_gathered into GLOBAL slot order (rank-major ==
    slot order because the batch is placed ``P(axis)`` on its leading
    dim), and the mean is one fixed-length ordered sum over ``slots``
    computed identically on every rank.  The optimizer update then runs
    per LANE at a fixed lane width: ``sharder`` is built at lane
    granularity (``sharder.world`` lanes — the same count at every gang
    size) and each rank ``lax.map``s ``tx.update`` over the lanes it
    owns.  An elementwise update compiled at a world-dependent chunk
    shape picks up shape-dependent codegen (fusion/vector width) and can
    drift by 1 ulp; per-lane mapping keeps the compiled update program —
    like the per-slot grad program — independent of ``world``.
    ``grad_clip`` applies a world-invariant global-norm clip over the
    unpadded gradient."""
    import jax
    import jax.numpy as jnp
    import optax

    world = sharder.world if world is None else int(world)
    lanes = sharder.world
    if slots % world:
        raise ValueError(f"slots={slots} not divisible by world={world}")
    if lanes % world:
        raise ValueError(
            f"lane count {lanes} not divisible by world={world}")

    def step(params, opt_block, batch):
        def slot_grad(mb):
            loss, g = jax.value_and_grad(loss_fn)(params, mb)
            flat, repl = sharder.split(g)
            return loss, flat, repl

        losses, flats, repls = jax.lax.map(slot_grad, batch)
        if world > 1:
            losses = jax.lax.all_gather(losses, axis).reshape(slots)
            flats = jax.lax.all_gather(flats, axis).reshape(
                slots, sharder.padded)
            repls = tuple(
                jax.lax.all_gather(r, axis).reshape((slots,) + r.shape[1:])
                for r in repls)
        # Ordered chain of binary adds over the slot axis, NOT jnp.sum:
        # XLA's reduce may lower with a layout-dependent association
        # (local-partial-then-combine after an all_gather), which breaks
        # the bitwise world-invariance contract.  A static chain of adds
        # in global slot order is associated identically everywhere.
        def slot_sum(stacked):
            acc = stacked[0]
            for s in range(1, slots):
                acc = acc + stacked[s]
            return acc

        loss = slot_sum(losses) / np.float32(slots)
        g_full = slot_sum(flats) / np.float32(slots)
        g_repl = tuple(slot_sum(r) / np.float32(slots) for r in repls)
        if grad_clip is not None:
            sq = jnp.sum(jnp.square(
                g_full[: sharder.total].astype(jnp.float32)))
            for r in g_repl:
                sq = sq + jnp.sum(jnp.square(r.astype(jnp.float32)))
            norm = jnp.sqrt(sq)
            scale = jnp.where(norm < np.float32(grad_clip),
                              jnp.float32(1.0), np.float32(grad_clip) / norm)
            g_full = (g_full.astype(jnp.float32) * scale).astype(g_full.dtype)
            g_repl = tuple((r.astype(jnp.float32) * scale).astype(r.dtype)
                           for r in g_repl)
        k = lanes // world
        idx = jax.lax.axis_index(axis) if world > 1 else 0
        g_rows = jax.lax.dynamic_slice_in_dim(
            sharder.rows(g_full.astype(sharder.dtype)), idx * k, k, 0)
        p_flat, p_repl = sharder.split(params)
        p_rows = jax.lax.dynamic_slice_in_dim(
            sharder.rows(p_flat), idx * k, k, 0)
        # Lane-replicated view of the opt state: shard leaves arrive as
        # this rank's [k, lane] block; everything else (counts, state for
        # replicated leaves) is broadcast so lax.map can carry it.
        opt_lanes = jax.tree_util.tree_map_with_path(
            lambda kp, x: x if (zero._is_shard_path(kp)
                                and getattr(x, "ndim", 0) >= 2)
            else jnp.broadcast_to(x, (k,) + jnp.shape(x)), opt_block)

        def lane_update(lane):
            g_l, p_l, o_l = lane
            c_grads = {"shard": g_l, "repl": g_repl}
            c_params = {"shard": p_l, "repl": p_repl}
            updates, o_out = tx.update(c_grads, o_l, c_params)
            return optax.apply_updates(c_params, updates), o_out

        new_c, opt_stack = jax.lax.map(lane_update,
                                       (g_rows, p_rows, opt_lanes))
        # Un-stack what lax.map replicated: per-lane shard state keeps
        # its [k, lane] block shape; everything else was advanced
        # identically in every lane, so lane 0's copy is THE copy.
        opt_out = jax.tree_util.tree_map_with_path(
            lambda kp, x: x if (zero._is_shard_path(kp)
                                and getattr(x, "ndim", 0) >= 2) else x[0],
            opt_stack)
        new_repl = tuple(r[0] for r in new_c["repl"])
        if world > 1:
            new_rows = jax.lax.all_gather(new_c["shard"], axis, tiled=True)
        else:
            new_rows = new_c["shard"]
        return (sharder.merge(new_rows.reshape(sharder.padded), new_repl),
                opt_out, loss)

    return step


# ---- placement / assembly helpers (host <-> mesh) ----
def _place_tree(tree: Any, mesh, spec, multihost: bool) -> Any:
    """Place a host pytree on ``mesh`` with one PartitionSpec for every
    leaf (``P()`` replicated, ``P(DATA_AXIS)`` leading-dim sharded).
    ``multihost`` routes through ``make_array_from_callback`` so each
    process materializes only its addressable shards."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding

    sh = NamedSharding(mesh, spec)

    def place(x):
        arr = np.asarray(x)
        if not multihost:
            return jax.device_put(jnp.asarray(arr), sh)
        return jax.make_array_from_callback(
            arr.shape, sh, lambda idx, _a=arr: _a[idx])

    return jax.tree_util.tree_map(place, tree)


def _assemble_opt(host_opt: Any, total: int) -> Any:
    """Collapse a replicated-layout host opt state into the world-agnostic
    *assembled* form: shard leaves become unpadded ``(total,)`` vectors
    (what ``ZeroSharder.reshard_opt_state`` re-chunks onto any world)."""
    import jax

    def pick(kp, x):
        a = np.asarray(x)
        if zero._is_shard_path(kp) and a.ndim >= 2:
            return a.reshape(-1)[:total]
        return a

    return jax.tree_util.tree_map_with_path(pick, host_opt)


def _build_engine(spec: Dict[str, Any], params_host: Any, mesh,
                  multihost: bool) -> Dict[str, Any]:
    """The per-incarnation compiled machinery — shared verbatim by the
    gang workers and the in-process LocalElastic reference so both run
    the identical program."""
    import jax
    from jax.sharding import PartitionSpec as P

    world = int(np.prod(list(mesh.shape.values())))
    tx = spec["tx_factory"]()
    # Lane-granularity sharder: a FIXED lane count regardless of gang
    # size, so the opt layout ([lanes, lane] leaves, each rank owning
    # lanes/world of them) and the compiled per-lane update are identical
    # at every world — the bitwise-invariance contract.  2x slots keeps
    # every rank at >= 2 lanes even at the largest world (= slots): a
    # trip-count-1 lax.map is inlined by XLA's while-loop simplifier and
    # the re-fused body compiles differently from the looped one.
    sharder = zero.ZeroSharder(params_host, 2 * spec["slots"],
                               should_shard=spec.get("should_shard"))
    opt_specs = sharder.opt_specs(tx)
    step = build_elastic_step(spec["loss_fn"], tx, sharder,
                              slots=spec["slots"], world=world,
                              grad_clip=spec.get("grad_clip"))
    stepj = jax.jit(jax.shard_map(step, mesh=mesh,
                                  in_specs=(P(), opt_specs, P(DATA_AXIS)),
                                  out_specs=(P(), opt_specs, P()),
                                  check_vma=False))
    return {"tx": tx, "sharder": sharder, "opt_specs": opt_specs,
            "stepj": stepj, "world": world}


def _restore_state(spec, params_host, opt_assembled, mesh, multihost):
    """(params_dev, opt_dev, engine): place a snapshot (or fresh init when
    ``opt_assembled`` is None) onto ``mesh`` under the ZeRO layout."""
    import jax
    from jax.sharding import PartitionSpec as P

    engine = _build_engine(spec, params_host, mesh, multihost)
    sharder, tx = engine["sharder"], engine["tx"]
    params = _place_tree(params_host, mesh, P(), multihost)
    if opt_assembled is None:
        host_opt = jax.device_get(sharder.init_opt_state(tx, params_host))
    else:
        host_opt = jax.device_get(sharder.reshard_opt_state(opt_assembled))
    opt = zero.place_opt_state(host_opt, mesh, engine["opt_specs"],
                               multihost=multihost)
    return params, opt, engine


# ---- worker-side functions (module-level: pickled by reference) ----
def _elastic_setup(state, spec, params_host, opt_assembled, step0, version):
    """Build/rebuild a rank's elastic engine from the driver snapshot.
    Runs on every rank via ``run_stateful``; ``params_host`` and
    ``opt_assembled`` arrive as ONE ``ray_tpu.put`` ref each (the
    versioned one-put broadcast — the object store fans out, not the
    driver)."""
    import jax
    from jax.sharding import Mesh

    multihost = jax.process_count() > 1
    mesh = Mesh(np.asarray(jax.devices()), (DATA_AXIS,))
    params, opt, engine = _restore_state(spec, params_host, opt_assembled,
                                         mesh, multihost)
    state.clear()
    state.update(engine)
    state.update(
        rank=jax.process_index(), mesh=mesh, multihost=multihost,
        spec=spec, params=params, opt=opt, step=int(step0),
        version=int(version))
    return {"rank": state["rank"], "world": engine["world"],
            "step": int(step0), "version": int(version)}


def _elastic_step_fn(state, step_idx):
    """One global step at index ``step_idx`` (the driver replays indices
    after a recovery; ``batch_fn(step_idx)`` makes replay deterministic).
    The ``elastic_step`` chaos op fires HERE — a SIGKILL at this point is
    the no-notice lease-expiry drill."""
    import jax
    from jax.sharding import PartitionSpec as P

    chaos.maybe_die("elastic_step", state["rank"])
    batch = state["spec"]["batch_fn"](int(step_idx))
    batch_dev = _place_tree(batch, state["mesh"], P(DATA_AXIS),
                            state["multihost"])
    params, opt, loss = state["stepj"](state["params"], state["opt"],
                                       batch_dev)
    state["params"], state["opt"] = params, opt
    state["step"] = int(step_idx) + 1
    return float(jax.device_get(loss))


def _elastic_snapshot_fn(state):
    """Boundary snapshot: replicate the sharded opt state (a collective —
    EVERY rank participates, which is how survivors obtain a doomed
    rank's chunk over the transfer plane), then rank 0 assembles the
    world-agnostic form and returns it with the params."""
    import jax

    repl_opt = zero.replicate_opt_state(state["opt"], state["mesh"])
    if state["rank"] != 0:
        return None
    host_opt = jax.device_get(repl_opt)
    return {"step": state["step"],
            "params": jax.device_get(state["params"]),
            "opt": _assemble_opt(host_opt, state["sharder"].total)}


def _elastic_params_host(state):
    import jax

    return jax.device_get(state["params"])


# ---- in-process reference runner ----
class LocalElastic:
    """The elastic engine on in-process virtual devices — the *reference
    implementation* the gang is bitwise-compared against.  ``resize``
    runs the exact snapshot→assemble→reshard→place protocol the gang
    uses, just without actors."""

    def __init__(self, loss_fn: Callable, params_factory: Callable,
                 tx_factory: Callable, batch_fn: Callable, *,
                 slots: int = 4, world: int = 1,
                 grad_clip: Optional[float] = None,
                 should_shard: Optional[Callable] = None):
        self.spec = {"loss_fn": loss_fn, "tx_factory": tx_factory,
                     "batch_fn": batch_fn, "slots": slots,
                     "grad_clip": grad_clip, "should_shard": should_shard}
        self._params_host = params_factory()
        self.step_idx = 0
        self.losses: List[float] = []
        self._mount(world, opt_assembled=None)

    def _mount(self, world: int, opt_assembled):
        import jax
        from jax.sharding import Mesh

        devs = jax.devices()
        if world > len(devs):
            raise ValueError(f"world={world} > {len(devs)} local devices")
        self.mesh = Mesh(np.asarray(devs[:world]), (DATA_AXIS,))
        self.params, self.opt, engine = _restore_state(
            self.spec, self._params_host, opt_assembled, self.mesh,
            multihost=False)
        self.sharder = engine["sharder"]
        self._stepj = engine["stepj"]
        self.world = world

    def step(self) -> float:
        import jax
        from jax.sharding import PartitionSpec as P

        batch = self.spec["batch_fn"](self.step_idx)
        batch_dev = _place_tree(batch, self.mesh, P(DATA_AXIS), False)
        self.params, self.opt, loss = self._stepj(self.params, self.opt,
                                                  batch_dev)
        self.step_idx += 1
        loss = float(jax.device_get(loss))
        self.losses.append(loss)
        return loss

    def resize(self, world: int):
        """Snapshot → assembled opt form → remount at ``world``."""
        import jax

        if world == self.world:
            return
        host_opt = jax.device_get(
            zero.replicate_opt_state(self.opt, self.mesh))
        assembled = _assemble_opt(host_opt, self.sharder.total)
        self._params_host = jax.device_get(self.params)
        self._mount(world, opt_assembled=assembled)

    def params_host(self) -> Any:
        import jax

        return jax.device_get(self.params)


def reference_trajectory(loss_fn: Callable, params_factory: Callable,
                         tx_factory: Callable, batch_fn: Callable, *,
                         steps: int, slots: int = 4, world: int = 1,
                         grad_clip: Optional[float] = None,
                         resize_plan: Optional[Dict[int, int]] = None
                         ) -> Dict[str, Any]:
    """Run ``steps`` elastic steps in-process and return ``{"params",
    "losses"}``.  ``resize_plan={step: new_world}`` reshards mid-run at
    the given step boundaries — by slot-determinism the final params are
    bitwise-independent of the plan (the property the elastic tests pin
    down)."""
    le = LocalElastic(loss_fn, params_factory, tx_factory, batch_fn,
                      slots=slots, world=world, grad_clip=grad_clip)
    for s in range(steps):
        if resize_plan and s in resize_plan:
            le.resize(resize_plan[s])
        le.step()
    return {"params": le.params_host(),
            "losses": np.asarray(le.losses, dtype=np.float64)}


# ---- the driver-side elastic gang ----
class ElasticMeshGroup:
    """A data-parallel training gang whose host count floats inside
    ``num_hosts=(min, max)`` without ever losing a step.

    Resizes are full gang rebuilds at a step boundary (a jax.distributed
    world is fixed-size): the driver keeps a boundary snapshot
    ``{step, params, assembled opt}``, broadcasts it as one versioned
    ``ray_tpu.put`` per tree, and the new gang re-chunks the opt state
    onto its world via the ``reshard_opt_state``/``place_opt_state``
    path.  Grows and notice-shrinks snapshot first (graceful — the
    doomed rank still participates in the snapshot collective); a lease
    expiry (rank SIGKILLed with no notice) is caught as a
    MeshGroupError, the surviving count is fitted to an allowed size,
    and the missed steps are replayed deterministically from
    ``batch_fn`` — ``elastic_steps_lost_total`` stays 0 by construction.
    Transport aborts (the gloo TCP race) rebuild at the SAME size under
    their own budget and are not counted as shrinks."""

    def __init__(self, loss_fn: Callable, params_factory: Callable,
                 tx_factory: Callable, batch_fn: Callable, *,
                 num_hosts: Tuple[int, int] = (1, 2),
                 initial_hosts: Optional[int] = None,
                 platform: Optional[str] = None,
                 local_device_count: Optional[int] = None,
                 slots: int = 4, grad_clip: Optional[float] = None,
                 should_shard: Optional[Callable] = None,
                 snapshot_interval: int = 1,
                 resources_per_host: Optional[Dict[str, float]] = None,
                 bootstrap_timeout: float = 120.0,
                 transport_restart_budget: int = 2):
        if isinstance(num_hosts, int):
            num_hosts = (num_hosts, num_hosts)
        lo, hi = int(num_hosts[0]), int(num_hosts[1])
        if not (1 <= lo <= hi):
            raise ValueError(f"bad num_hosts range {num_hosts}")
        ldc = int(local_device_count or 1)
        self.allowed_hosts = [h for h in range(lo, hi + 1)
                              if slots % (h * ldc) == 0]
        if not self.allowed_hosts:
            raise ValueError(
                f"no host count in [{lo}, {hi}] divides slots={slots} "
                f"with local_device_count={ldc}")
        self.min_hosts, self.max_hosts = lo, hi
        self.slots = slots
        self.snapshot_interval = max(1, int(snapshot_interval))
        self.transport_restart_budget = int(transport_restart_budget)
        self._mg_kwargs = dict(platform=platform,
                               local_device_count=local_device_count,
                               resources_per_host=resources_per_host,
                               bootstrap_timeout=bootstrap_timeout,
                               max_group_restarts=0)
        self.spec = {"loss_fn": loss_fn, "tx_factory": tx_factory,
                     "batch_fn": batch_fn, "slots": slots,
                     "grad_clip": grad_clip, "should_shard": should_shard}
        self._step = 0          # global steps completed
        self._gang_step = 0     # next index the live gang will execute
        self._gang_calls = 0    # elastic_step invocations this incarnation
        self._version = 0
        self._snapshot = {"step": 0, "params": params_factory(),
                          "opt": None}
        self._pending_resize: Optional[int] = None
        self._notices: List[Tuple[int, float]] = []
        self._pending_steps = 0
        self.counters: Dict[str, float] = {
            "elastic_grows_total": 0, "elastic_shrinks_total": 0,
            "elastic_notice_shrinks_total": 0,
            "elastic_expiry_shrinks_total": 0,
            "elastic_transport_rebuilds_total": 0,
            "elastic_reshard_seconds_total": 0.0,
            "elastic_replayed_steps_total": 0,
            "elastic_steps_lost_total": 0,
            "elastic_weight_puts_total": 0,
        }
        self.hosts = self._fit(initial_hosts if initial_hosts is not None
                               else self.allowed_hosts[-1])
        self.group = mg.MeshGroup(num_hosts=self.hosts, **self._mg_kwargs)
        self._setup_gang()

    # ---- sizing ----
    def _fit(self, target: int) -> int:
        """Largest allowed host count <= target (floor: the smallest
        allowed size — a gang never dissolves below min)."""
        ok = [h for h in self.allowed_hosts if h <= target]
        return ok[-1] if ok else self.allowed_hosts[0]

    # ---- gang (re)build ----
    def _setup_gang(self):
        snap = self._snapshot
        self._version += 1
        # One put per rebuild; the N gang ranks resolve these refs
        # concurrently, which the transfer plane turns into a striped
        # cooperative broadcast (receivers serve each other's landed
        # ranges) — rebuild cost stays ~O(snapshot/BW) as the gang grows.
        params_ref = ray_tpu.put(snap["params"])
        opt_ref = ray_tpu.put(snap["opt"]) if snap["opt"] is not None \
            else None
        self.counters["elastic_weight_puts_total"] += 1
        self.group.run_stateful(_elastic_setup, self.spec, params_ref,
                                opt_ref, snap["step"], self._version)
        self._gang_step = snap["step"]
        self._gang_calls = 0

    def _resize_to(self, n: int):
        t0 = time.monotonic()
        self.group.resize(n)
        self.hosts = n
        self._setup_gang()
        self.counters["elastic_reshard_seconds_total"] += \
            time.monotonic() - t0
        self._export_metrics()

    def _refresh_snapshot(self, force: bool = False):
        if not force and self._step % self.snapshot_interval:
            return
        out = self.group.run_stateful(_elastic_snapshot_fn)
        snap = next(s for s in out if s is not None)
        self._snapshot = snap

    # ---- elasticity signals ----
    def request_resize(self, target: int):
        """Ask for a new size; applied at the next step boundary."""
        self._pending_resize = self._fit(int(target))

    def offer_capacity(self, spare_hosts: int):
        """Autoscaler hook: grow into ``spare_hosts`` extra hosts."""
        if spare_hosts > 0:
            self.request_resize(self.hosts + int(spare_hosts))

    def preemption_notice(self, rank: int, deadline_s: float = 30.0):
        """A host will disappear in ``deadline_s``: shrink gracefully at
        the next step boundary (the doomed rank still participates in
        the boundary snapshot — survivors get its opt chunk for free)."""
        self._notices.append((int(rank), time.monotonic() + deadline_s))

    def arm_lease_expiry(self, rank: int, after_steps: int):
        """The no-notice drill: schedule a SIGKILL of ``rank`` at the
        ``after_steps``-th future elastic step via the chaos plane (spot
        reclaim with zero warning — recovery must come from the
        snapshot + replay path, not a goodbye collective)."""
        # Chaos invocation counts start from zero when a schedule is
        # (re)armed, so nth counts elastic steps from NOW.
        spec = f"elastic_step:{rank}:{int(after_steps)}:*"
        ray_tpu.get(self.group.workers[rank].setup_env.remote(
            {chaos.KILL_SCHEDULE_ENV: spec}))

    def pending_steps(self) -> int:
        """Steps queued behind the gang (the autoscaler gang policy's
        scale signal)."""
        return self._pending_steps

    # ---- the step loop ----
    def step(self) -> float:
        """Advance the run by exactly one global step, absorbing any
        pending resize (boundary) and any gang failure (recovery +
        deterministic replay) along the way."""
        self._apply_pending()
        target = self._step + 1
        loss = None
        while True:
            try:
                while self._gang_step < target:
                    idx = self._gang_step
                    loss = self.group.run_stateful(_elastic_step_fn, idx)[0]
                    if idx < self._step:
                        self.counters["elastic_replayed_steps_total"] += 1
                    self._gang_step += 1
                    self._gang_calls += 1
                break
            except exc.MeshGroupError as e:
                self._recover(e)
        self._step = target
        self._refresh_snapshot()
        return loss

    def run(self, steps: int) -> List[float]:
        losses = []
        for _ in range(steps):
            self._pending_steps = steps - len(losses)
            losses.append(self.step())
        self._pending_steps = 0
        return losses

    def _apply_pending(self):
        if self._notices:
            doomed = {r for r, _ in self._notices}
            self._notices = []
            self._refresh_snapshot(force=True)
            n = self._fit(self.hosts - len(doomed))
            if n < self.hosts:
                self.counters["elastic_shrinks_total"] += 1
                self.counters["elastic_notice_shrinks_total"] += 1
                logger.info("elastic: notice shrink %d -> %d hosts",
                            self.hosts, n)
                self._resize_to(n)
            self._pending_resize = None
            return
        if self._pending_resize is not None:
            n, self._pending_resize = self._pending_resize, None
            if n == self.hosts:
                return
            self._refresh_snapshot(force=True)
            if n > self.hosts:
                self.counters["elastic_grows_total"] += 1
                logger.info("elastic: grow %d -> %d hosts", self.hosts, n)
            else:
                self.counters["elastic_shrinks_total"] += 1
                self.counters["elastic_notice_shrinks_total"] += 1
            self._resize_to(n)

    def _recover(self, err: exc.MeshGroupError):
        """A gang failure mid-step: transport aborts rebuild at the same
        size (bounded); real rank death shrinks to the surviving fit.
        Either way the gang restarts from the boundary snapshot and the
        driver replays the missed indices — nothing is lost."""
        if mg.is_transport_abort(err):
            if self.counters["elastic_transport_rebuilds_total"] >= \
                    self.transport_restart_budget:
                raise err
            self.counters["elastic_transport_rebuilds_total"] += 1
            logger.warning("elastic: transport abort, rebuilding %d-host "
                           "gang in place: %s", self.hosts, err)
            self._resize_to(self.hosts)
            return
        # Peers of a dead rank surface as transport-classified TaskErrors
        # (their collective was poisoned); only non-transport failures are
        # actual corpses when sizing the surviving gang.
        ranks = getattr(err, "failed_ranks", None) or {}
        dead = [r for r, e in ranks.items()
                if not mg.is_transport_abort(e)] or list(ranks) or [0]
        failed = len(dead)
        survivors = max(self.hosts - failed, 0)
        n = self._fit(survivors)
        self.counters["elastic_shrinks_total"] += 1
        self.counters["elastic_expiry_shrinks_total"] += 1
        logger.warning("elastic: lease expiry (%d rank(s) dead), shrink "
                       "%d -> %d hosts: %s", failed, self.hosts, n, err)
        self._resize_to(n)

    # ---- introspection ----
    def params_host(self) -> Any:
        return self.group.run_rank_stateful(0, _elastic_params_host)

    def stats(self) -> Dict[str, Any]:
        return {"hosts": self.hosts, "step": self._step,
                "version": self._version, **self.counters}

    def _export_metrics(self):
        try:
            from ray_tpu.util.metrics import Counter, Gauge

            for name, val in self.counters.items():
                if name.endswith("_total"):
                    c = Counter(name, "elastic gang lifecycle")
                    delta = val - c.value()
                    if delta > 0:
                        c.inc(delta)
            Gauge("elastic_gang_hosts", "current elastic gang size").set(
                self.hosts)
        except Exception:  # driver not connected / kv unavailable
            pass

    def shutdown(self):
        self._export_metrics()
        self.group.shutdown()
