"""MPMD pipeline parallelism: model stages owned by separate process
groups, activations flowing store-to-store, driven by an async 1F1B
schedule (the ROADMAP's "billion-parameter training across gangs" plane).

Reference papers: "Scaling Deep Learning Training with MPMD Pipeline
Parallelism" (arxiv 2412.14374) — stage-per-process-group pipelines with
1F1B schedules reach near-SPMD MFU at multi-billion scale — GPipe
(arxiv 1811.06965) for the microbatch decomposition, Megatron-LM's
interleaved virtual-stage schedule for the bubble shrink, and EQuARX
(arxiv 2506.17615) for the block-scaled int8 wire format.  The first
pipeline form (intra-mesh SPMD GPipe via shard_map/ppermute) is
parallel/pipeline.py; this module is the cross-gang form.

Design — the composed 3D plane (pipeline x SPMD x ZeRO), four legs:

1. **Compiled stage workers.**  Each pipeline stage precompiles donated
   fwd/bwd/apply steps per owned model chunk (:class:`StageCore`).  The
   forward runs under ``jax.vjp`` *inside* jit and returns the pullback
   as a ``jax.tree_util.Partial`` — a pytree whose leaves are the VJP
   residuals, so residuals stay ON-DEVICE between the separately-compiled
   forward and backward with zero recompute and zero per-microbatch
   retrace (jit cache sizes are constant after step one; ``stats()``
   proves it).  A stage is optionally *internally SPMD*:
   ``spmd_devices=N`` places its params replicated and its microbatch
   sharded over an N-device ``data`` mesh, and ``zero_sharding`` composes
   the per-stage optimizer with ``parallel/zero.py`` (1/N optimizer
   state per device).

2. **Multi-host stage gangs.**  With ``gang_hosts=G`` each stage is a
   :class:`~ray_tpu.parallel.mesh_group.MeshGroup` gang of G worker
   processes forming ONE ``jax.distributed`` SPMD world (the MPMD
   paper's deployment shape): the stage's params are replicated across
   the gang, each microbatch is sharded over every gang device, grads
   all-reduce inside the compiled backward, and ZeRO shards the
   optimizer 1/(G*devices) — the stage's internal SPMD/ZeRO genuinely
   spans hosts.  Rank r of stage k ships its *slice* of the activation
   store-to-store to rank r of stage k+1 (cotangents chain back the
   same edges), so the ref chain crosses hosts over the transfer plane
   and a gang-rank death exercises the real node-death path.  Stage ops
   ride the MeshWorker pipeline sequence gate, so every rank executes
   the identical schedule in the identical order — compiled collectives
   can never interleave across microbatches.

3. **Async interleaved 1F1B schedule.**  The driver never touches
   tensors: chunk c's forward output *ref* is chunk c+1's input (and
   cotangent refs chain back), so activations move store-to-store while
   the driver only wires the DAG.  ``virtual_per_rank=v`` assigns v
   non-contiguous model chunks to each physical stage (chunk c lives on
   stage ``c % S``) and the per-stage op order interleaves them
   (Megatron's interleaved 1F1B), cutting the pipeline bubble from
   ``(S-1)/(M+S-1)`` toward the ``1/(v*M)`` envelope —
   :func:`simulate_schedule` predicts it analytically and the
   ``mpmd_bubble_fraction`` gauge measures it.  ``v=1`` keeps the exact
   textbook 1F1B order (warmup ``S-1-k`` → steady alternation →
   cooldown; at most ``S-k`` residual sets per stage).

4. **Quantized inter-stage wire.**  ``wire_dtype="int8"`` serializes
   activations AND cotangents through the EQuARX block-scaled int8
   format (``ops/collectives.py``): the producer quantizes inside its
   compiled step (one f32 scale per block, block auto-sized to divide
   the hidden dim so no padding ships), int8 payloads + scales ride the
   same ref the fp32 wire used, and the consumer dequantizes inside its
   compiled step — wire bytes drop ~4x on the slowest link of the
   pipeline.  ``wire_dtype="fp32"`` (default) is the bit-stable
   fallback; the ``mpmd_wire_bytes`` meter counts actual shipped bytes
   vs the logical fp32 bytes either way.

Step streaming + fault tolerance are unchanged from the single-actor
plane: ``submit_step`` keeps ``step_window`` whole steps in flight,
``max_restarts > 0`` arms store-resident snapshots and a stage (or gang
rank) death tears down ALL stages, respawns with a generation bump,
restores from the confirmed snapshot and re-dispatches every step since
IN ORDER.
"""
from __future__ import annotations

import collections
import time
from typing import Any, Callable, Dict, List, Optional, Sequence

import numpy as np

import ray_tpu
from ray_tpu import exceptions as exc
from ray_tpu.parallel.flow import Window as InflightWindow
from ray_tpu.parallel.mesh_group import MeshGroup, gang_get

# Blocking driver↔stage syncs on the LOCKSTEP dispatch paths
# (train_step / get_params).  The async streaming path — submit_step +
# windowed drains — must leave it untouched: backpressure drains overlap
# with already-queued work, exactly like mesh_group.StepPipeline.
_MPMD_SYNCS = {"count": 0}


def mpmd_driver_sync_count() -> int:
    """Blocking per-step driver syncs performed by the lockstep MPMD
    paths since process start.  The async 1F1B stream adds zero."""
    return _MPMD_SYNCS["count"]


def _note_sync() -> None:
    _MPMD_SYNCS["count"] += 1


# ---------------------------------------------------------------------------
# Schedules
# ---------------------------------------------------------------------------

def stage_schedule(schedule: str, num_stages: int, num_microbatches: int,
                   stage: int, virtual_per_rank: int = 1) -> List[tuple]:
    """Per-stage op order ``[("F"|"B", chunk, mb), ...]``.

    ``chunk`` is the GLOBAL virtual-stage index in ``[0, S*v)``; physical
    stage ``k`` owns the non-contiguous chunks ``{k, k+S, ..., k+(v-1)S}``
    (Megatron's interleaved assignment — for ``v=1`` chunk == stage).

    ``"1f1b"``, ``v=1``: textbook warmup of ``S - 1 - stage`` forwards,
    strict one-forward-one-backward alternation, backward cooldown — at
    most ``S - stage`` microbatches ever hold residuals on this stage.
    ``v>1``: the interleaved schedule — microbatches advance in groups of
    S through each chunk slot, warmup is ``2*(S-1-k) + (v-1)*S`` forward
    ops, then strict 1F1B alternation; requires ``M % S == 0`` (the
    Megatron constraint — groups must tile the microbatch count).
    ``"gpipe"``: all forwards (chunks ascending) then all backwards
    (descending) — the naive baseline; holds every residual."""
    S, M, k, v = num_stages, num_microbatches, stage, virtual_per_rank
    if v < 1:
        raise ValueError(f"virtual_per_rank must be >= 1, got {v}")
    if schedule == "gpipe":
        ops = [("F", slot * S + k, m) for slot in range(v) for m in range(M)]
        ops += [("B", slot * S + k, m) for slot in reversed(range(v))
                for m in range(M)]
        return ops
    if schedule != "1f1b":
        raise ValueError(f"schedule must be 1f1b|gpipe, got {schedule!r}")
    if v == 1:
        warm = min(S - 1 - k, M)
        ops = [("F", k, m) for m in range(warm)]
        f, b = warm, 0
        while b < M:
            if f < M:
                ops.append(("F", k, f))
                f += 1
            ops.append(("B", k, b))
            b += 1
        return ops
    if M % S != 0:
        raise ValueError(
            f"interleaved schedule (virtual_per_rank={v}) requires "
            f"num_microbatches % num_stages == 0, got M={M}, S={S}")
    total = M * v

    def f_op(i: int) -> tuple:
        grp, within = divmod(i, S * v)
        slot, moff = divmod(within, S)
        return ("F", slot * S + k, grp * S + moff)

    def b_op(i: int) -> tuple:
        grp, within = divmod(i, S * v)
        slot = (v - 1) - within // S
        return ("B", slot * S + k, grp * S + within % S)

    warm = min(2 * (S - 1 - k) + (v - 1) * S, total)
    ops = [f_op(i) for i in range(warm)]
    f, b = warm, 0
    while b < total:
        if f < total:
            ops.append(f_op(f))
            f += 1
        ops.append(b_op(b))
        b += 1
    return ops


def simulate_schedule(schedule: str, num_stages: int, num_microbatches: int,
                      virtual_per_rank: int = 1, *, cost_f: float = 1.0,
                      cost_b: float = 2.0) -> dict:
    """Event-driven unit-cost simulation of a pipeline schedule.

    Validates feasibility (raises on deadlock — an op whose producer can
    never run) and returns the analytic envelope the real run should
    approach: ``makespan``, per-stage busy time, and ``bubble_fraction``
    = ``1 - sum(busy) / (S * makespan)``.  Used by tests to assert the
    interleaved schedule strictly beats the non-interleaved one at equal
    (S, M) without timing-sensitive measurements, and by docs for the
    when-to-interleave guidance."""
    S, M, v = num_stages, num_microbatches, virtual_per_rank
    C = S * v
    queues = [collections.deque(
        stage_schedule(schedule, S, M, k, v)) for k in range(S)]
    total = sum(len(q) for q in queues)
    done: Dict[tuple, float] = {}   # (op, chunk, mb) -> finish time
    free = [0.0] * S
    busy = [0.0] * S
    while total:
        progressed = False
        for k in range(S):
            q = queues[k]
            while q:
                op, c, m = q[0]
                if op == "F":
                    dep = None if c == 0 else ("F", c - 1, m)
                else:
                    dep = None if c == C - 1 else ("B", c + 1, m)
                if dep is not None and dep not in done:
                    break
                ready = done.get(dep, 0.0) if dep is not None else 0.0
                cost = cost_f if op == "F" else cost_b
                start = max(free[k], ready)
                done[(op, c, m)] = start + cost
                free[k] = start + cost
                busy[k] += cost
                q.popleft()
                total -= 1
                progressed = True
        if not progressed:
            raise RuntimeError(
                f"{schedule} schedule deadlocked (S={S}, M={M}, v={v}): "
                f"{total} ops can never run")
    makespan = max(free)
    return {
        "makespan": makespan,
        "busy": busy,
        "bubble_fraction": 1.0 - sum(busy) / (S * makespan)
        if makespan > 0 else 0.0,
    }


# ---------------------------------------------------------------------------
# StageCore: the in-process stage engine (shared by the solo actor and
# the multi-host gang ranks)
# ---------------------------------------------------------------------------

class StageCore:
    """One pipeline stage's compiled programs + schedule state, for the
    v model chunks this physical stage owns.

    ``chunk_fns[slot]`` is the fn of global chunk ``slot * S + stage_id``
    — ``fn(params, x)`` for middle chunks, ``loss_fn(params, x, target)``
    for the last global chunk.  ``chunk_params[slot]`` may be pytrees or
    zero-arg factories executed here (XL-scale params never round-trip
    through the driver).

    Mesh layout: ``gang_size > 1`` means this process is rank
    ``gang_rank`` of a ``jax.distributed`` world — the mesh spans EVERY
    device of the gang (multi-host SPMD; microbatch slices arrive/leave
    per rank).  Otherwise ``spmd_devices=N`` builds a local N-device
    data mesh (single-host SPMD), and 0 runs single-device.

    ``wire_dtype="int8"``: non-first inputs and non-last outputs cross
    the stage boundary as block-scaled int8 (quantize/dequantize INSIDE
    the compiled steps; the block is auto-sized to divide the trailing
    dim so no padding ships).  Cotangents use the producing edge's
    format symmetrically."""

    def __init__(self, chunk_fns: Sequence[Callable],
                 chunk_params: Sequence[Any], optimizer=None, *,
                 stage_id: int = 0, num_stages: int = 1,
                 virtual_per_rank: int = 1, wire_dtype: str = "fp32",
                 wire_block: int = 256, spmd_devices: int = 0,
                 zero_sharding: str = "off", gang_rank: int = 0,
                 gang_size: int = 1, restore_from: Any = None):
        import jax
        import jax.numpy as jnp
        import optax

        self._jax = jax
        self._jnp = jnp
        self.stage_id = int(stage_id)
        self.num_stages = int(num_stages)
        self.v = int(virtual_per_rank)
        self.num_chunks = self.num_stages * self.v
        self.gang_rank = int(gang_rank)
        self.gang_size = int(gang_size)
        if wire_dtype not in ("fp32", "int8"):
            raise ValueError(f"wire_dtype must be fp32|int8, "
                             f"got {wire_dtype!r}")
        self.wire_dtype = wire_dtype
        self.wire_block = int(wire_block)
        if len(chunk_fns) != self.v or len(chunk_params) != self.v:
            raise ValueError(
                f"stage {stage_id} expected {self.v} chunk fns/params, "
                f"got {len(chunk_fns)}/{len(chunk_params)}")
        self.fns = list(chunk_fns)
        self.tx = optimizer or optax.sgd(1e-2)

        # --- mesh: gang-global > local SPMD > single device ---
        self._mesh = None
        self._repl = None
        self._batched = None
        if self.gang_size > 1:
            from jax.sharding import Mesh, NamedSharding
            from jax.sharding import PartitionSpec as P

            devs = jax.devices()  # spans the gang post-bootstrap
            self._mesh = Mesh(np.array(devs), ("data",))
            self._repl = NamedSharding(self._mesh, P())
            self._batched = NamedSharding(self._mesh, P("data"))
        elif spmd_devices and spmd_devices > 1:
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as P

            from ray_tpu.rllib.utils.mesh import data_mesh

            self._mesh = data_mesh(int(spmd_devices))
            self._repl = NamedSharding(self._mesh, P())
            self._batched = NamedSharding(self._mesh, P("data"))
        elif zero_sharding != "off":
            raise ValueError(
                "zero_sharding requires spmd_devices > 1 or gang_hosts > 1 "
                "(the optimizer shards over the stage's data mesh)")
        self.params = [self._put_repl(p() if callable(p) else p)
                       for p in chunk_params]

        # --- compiled steps, one triplet per owned chunk ---
        donate = jax.default_backend() != "cpu"  # cpu: donation unimplemented
        self._zeros = jax.jit(
            lambda p: jax.tree_util.tree_map(jnp.zeros_like, p))
        self._fwd: List[Any] = []
        self._bwd: List[Any] = []
        self._apply: List[Any] = []
        self._zero = [None] * self.v
        self._zero_info = [None] * self.v
        self.opt_state: List[Any] = []
        for slot in range(self.v):
            self._build_chunk(slot, donate, zero_sharding)
        if restore_from is not None:
            self.restore(restore_from)

        # --- schedule state ---
        self._resid: Dict[tuple, tuple] = {}  # (slot, mb) -> (vjp, w, step)
        self._acc: List[Any] = [None] * self.v
        self._step_count = 0
        # --- per-step observability ---
        self._ops: List[dict] = []
        self._peak_inflight = 0
        self._act_bytes = 0    # logical fp32 boundary bytes
        self._wire_bytes = 0   # bytes actually shipped through the store

    # ---- chunk program construction ----
    def _global_chunk(self, slot: int) -> int:
        return slot * self.num_stages + self.stage_id

    def _is_last_chunk(self, slot: int) -> bool:
        return self._global_chunk(slot) == self.num_chunks - 1

    def _wire_block_for(self, n: int) -> int:
        """Largest block <= wire_block that divides n: the quantized
        payload then pads nothing — bytes on the wire are exactly
        ``n + 4 * n/block`` per fp32 element row."""
        wb = max(1, self.wire_block)
        if n <= wb:
            return n
        for d in range(wb, 0, -1):
            if n % d == 0:
                return d
        return n

    def _build_chunk(self, slot: int, donate: bool, zero_sharding: str):
        jax, jnp = self._jax, self._jnp
        from ray_tpu.ops import collectives as coll

        gc = self._global_chunk(slot)
        first = gc == 0
        last = self._is_last_chunk(slot)
        in_wire = (not first) and self.wire_dtype == "int8"
        out_wire = (not last) and self.wire_dtype == "int8"
        fn = self.fns[slot]
        core = self

        def dequant(q, s):
            return coll.dequantize_block_int8(q, s, q.shape[-1], jnp.float32)

        def quant(y):
            blk = core._wire_block_for(y.shape[-1])
            q, s = coll.quantize_block_int8(y, blk)
            return {"q": q, "s": s}

        def fwd_impl(params, *args):
            if in_wire:
                x, extra = dequant(args[0], args[1]), args[2:]
            else:
                x, extra = args[0], args[1:]
            y, vjp = jax.vjp(lambda p, xx: fn(p, xx, *extra), params, x)
            if out_wire:
                return quant(y), vjp
            return y, vjp

        def bwd_impl(vjp, acc, *dyargs):
            dy = dequant(dyargs[0], dyargs[1]) if out_wire else dyargs[0]
            dparams, dx = vjp(dy)
            acc = jax.tree_util.tree_map(jnp.add, acc, dparams)
            if first:
                return acc, jnp.zeros((), jnp.int32)
            if in_wire:
                return acc, quant(dx)
            return acc, dx

        def apply_impl(params, opt_state, acc, scale):
            import optax as _optax

            grads = jax.tree_util.tree_map(lambda g: g * scale, acc)
            updates, opt_state = self.tx.update(grads, opt_state, params)
            return _optax.apply_updates(params, updates), opt_state

        n_dy = 2 if out_wire else 1
        self._fwd.append(jax.jit(fwd_impl))
        self._bwd.append(jax.jit(
            bwd_impl, donate_argnums=tuple(range(2 + n_dy)) if donate
            else ()))
        if zero_sharding != "off":
            self._build_zero_apply(slot, zero_sharding, donate)
        else:
            self._apply.append(jax.jit(
                apply_impl, donate_argnums=(0, 1, 2) if donate else ()))
            self.opt_state.append(self.tx.init(self.params[slot]))

    def _build_zero_apply(self, slot: int, zero_sharding: str, donate: bool):
        """Per-chunk ZeRO optimizer (parallel/zero.py): state sharded 1/N
        over the stage's data mesh (which spans the whole gang when
        gang_size > 1); grads enter the shard_map body replicated — the
        cross-device mean already happened in the compiled backward — so
        the reduce-scatter degenerates to a mean of identical rows."""
        import jax
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        from ray_tpu.parallel import zero as zero_mod

        world = dict(self._mesh.shape).get("data", 1)
        zu = zero_mod.build_zero_update(
            jax.eval_shape(lambda: self.params[slot]), self.tx, world,
            zero_sharding=zero_sharding, axis_name="data")
        self._zero[slot] = zu
        self._zero_info[slot] = zero_mod.export_zero_metrics(
            zu.sharder, self.tx, zero_sharding=zero_sharding,
            quantized="off")

        def body(params, opt_block, acc, scale):
            grads = jax.tree_util.tree_map(lambda g: g * scale, acc)
            params, opt_block = zu.update(grads, opt_block, params)
            return params, opt_block

        mapped = jax.shard_map(body, mesh=self._mesh,
                               in_specs=(P(), zu.opt_specs, P(), P()),
                               out_specs=(P(), zu.opt_specs),
                               check_vma=False)
        self._apply.append(jax.jit(
            mapped, donate_argnums=(0, 1, 2) if donate else ()))
        opt_sh = jax.tree_util.tree_map(
            lambda s: NamedSharding(self._mesh, s), zu.opt_specs,
            is_leaf=lambda s: isinstance(s, P))
        self.opt_state.append(jax.jit(zu.init_opt, out_shardings=opt_sh)(
            self.params[slot]))

    # ---- host<->device plumbing (gang-aware) ----
    def _put_repl(self, tree):
        """Place a host pytree replicated on the stage mesh.  Multi-host:
        ``make_array_from_callback`` materializes only this process's
        addressable shards (every rank feeds identical host values)."""
        jax, jnp = self._jax, self._jnp
        if self._mesh is None:
            return jax.tree_util.tree_map(jnp.asarray, tree)
        if self.gang_size > 1:
            def put(a):
                host = np.asarray(a)
                return jax.make_array_from_callback(
                    host.shape, self._repl, lambda idx, _h=host: _h[idx])

            return jax.tree_util.tree_map(put, tree)
        return jax.device_put(
            jax.tree_util.tree_map(jnp.asarray, tree), self._repl)

    def _to_batched(self, x):
        """Host microbatch (this rank's slice) -> device, sharded over
        the stage's data axis.  Multi-host: the local slice becomes this
        process's rows of ONE global array."""
        jax, jnp = self._jax, self._jnp
        x = np.asarray(x)
        if self._mesh is None or x.ndim < 1:
            return jnp.asarray(x)
        if self.gang_size > 1:
            return jax.make_array_from_process_local_data(self._batched, x)
        return jax.device_put(jnp.asarray(x), self._batched)

    def _to_host(self, arr):
        """Device array -> this rank's host view: full array when fully
        addressable, the rank's concatenated row shards otherwise (the
        per-rank activation slice that ships downstream)."""
        jax = self._jax
        if self.gang_size <= 1 or getattr(arr, "is_fully_addressable", True):
            return np.asarray(jax.device_get(arr))
        seen: Dict[tuple, np.ndarray] = {}
        for s in arr.addressable_shards:
            key = tuple((sl.start or 0, sl.stop or -1) for sl in s.index)
            seen.setdefault(key, np.asarray(s.data))
        parts = [seen[k] for k in sorted(seen)]
        return parts[0] if len(parts) == 1 else np.concatenate(parts, axis=0)

    def _wire_in(self, payload, first: bool):
        """Host wire payload -> device args tuple for the compiled fwd."""
        if isinstance(payload, dict) and "q" in payload:
            return (self._to_batched(payload["q"]),
                    self._to_batched(payload["s"]))
        return (self._to_batched(payload),)

    def _wire_out(self, y):
        """Device boundary value -> host wire payload + byte accounting."""
        if isinstance(y, dict) and "q" in y:
            q = self._to_host(y["q"])
            s = self._to_host(y["s"])
            self._act_bytes += q.size * 4          # logical fp32 bytes
            self._wire_bytes += q.nbytes + s.nbytes
            return {"q": q, "s": s}
        out = self._to_host(y)
        self._act_bytes += out.nbytes
        self._wire_bytes += out.nbytes
        return out

    def _record(self, kind: str, step: int, mb: int, t0: float, t1: float):
        self._ops.append({"kind": kind, "stage": self.stage_id,
                          "step": step, "mb": mb, "start": t0, "end": t1})

    def _block(self, tree):
        self._jax.tree_util.tree_leaves(tree)[0].block_until_ready()

    # ---- schedule ops (driver-dispatched, executed in strict order) ----
    def fwd(self, step: int, slot: int, mb: int, x, target=None,
            weight: float = 1.0):
        """Forward one microbatch through chunk ``slot``; the pullback
        (residuals) stays here.  Middle chunks return the (possibly
        int8-packed) activation slice; the last chunk its scalar loss."""
        from ray_tpu._private import chaos

        chaos.maybe_die("mpmd_fwd", self.stage_id)
        gc = self._global_chunk(slot)
        last = self._is_last_chunk(slot)
        t_in0 = time.time()
        xargs = self._wire_in(x, first=gc == 0)
        extra = ()
        if last:
            if target is None:
                raise ValueError("last chunk forward requires a target")
            extra = (self._to_batched(target),)
        t0 = time.time()
        y, vjp = self._fwd[slot](self.params[slot], *xargs, *extra)
        self._block(y)
        t1 = time.time()
        self._resid[(slot, mb)] = (vjp, float(weight), step)
        self._peak_inflight = max(self._peak_inflight, len(self._resid))
        self._record("X", step, mb, t_in0, t0)
        self._record("F", step, mb, t0, t1)
        if last:
            return float(self._to_host(y))
        out = self._wire_out(y)
        self._record("X", step, mb, t1, time.time())
        return out

    def bwd(self, step: int, slot: int, mb: int, dy=None):
        """Backward one microbatch on chunk ``slot``: consume the stored
        pullback, fold dparams into the chunk's accumulator, ship the
        input cotangent upstream (chunk 0 returns a token)."""
        from ray_tpu._private import chaos

        chaos.maybe_die("mpmd_bwd", self.stage_id)
        vjp, weight, fwd_step = self._resid.pop((slot, mb))
        if fwd_step != step:
            raise RuntimeError(
                f"stage {self.stage_id}: bwd(step={step}, slot={slot}, "
                f"mb={mb}) found residuals of step {fwd_step} — schedule "
                "corrupted")
        gc = self._global_chunk(slot)
        t_in0 = time.time()
        if dy is None:
            # Last chunk: d(loss)/d(loss), scaled by this microbatch's
            # weight (its true row share of the global batch) so ragged
            # microbatches accumulate EXACT full-batch gradients.
            dyargs = (self._jnp.asarray(weight, self._jnp.float32),)
        elif isinstance(dy, dict) and "q" in dy:
            dyargs = (self._to_batched(dy["q"]), self._to_batched(dy["s"]))
        else:
            dyargs = (self._to_batched(dy),)
        if self._acc[slot] is None:
            self._acc[slot] = self._zeros(self.params[slot])
        t0 = time.time()
        self._acc[slot], dx = self._bwd[slot](vjp, self._acc[slot], *dyargs)
        self._block(self._acc[slot])
        t1 = time.time()
        self._record("X", step, mb, t_in0, t0)
        self._record("B", step, mb, t0, t1)
        if gc == 0:
            return mb
        out = self._wire_out(dx)
        self._record("X", step, mb, t1, time.time())
        return out

    def apply_grads(self, scale: float = 1.0) -> dict:
        """Optimizer step on every owned chunk's accumulated grads;
        returns this step's observability payload."""
        from ray_tpu._private import chaos

        chaos.maybe_die("mpmd_apply", self.stage_id)
        if self._resid:
            raise RuntimeError(
                f"stage {self.stage_id}: apply with {len(self._resid)} "
                "unconsumed residuals — schedule corrupted")
        t0 = time.time()
        scale_dev = self._jnp.asarray(scale, self._jnp.float32)
        for slot in range(self.v):
            self.params[slot], self.opt_state[slot] = self._apply[slot](
                self.params[slot], self.opt_state[slot], self._acc[slot],
                scale_dev)
            self._acc[slot] = None
        self._block(self.params[0])
        t1 = time.time()
        self._step_count += 1
        self._record("A", self._step_count - 1, -1, t0, t1)
        out = self.stats()
        self._ops = []
        self._peak_inflight = 0
        return out

    def stats(self) -> dict:
        caches = {
            "fwd": sum(int(f._cache_size()) for f in self._fwd),
            "bwd": sum(int(f._cache_size()) for f in self._bwd),
            "apply": sum(int(f._cache_size()) for f in self._apply),
        }
        out = {
            "stage": self.stage_id,
            "rank": self.gang_rank,
            "steps": self._step_count,
            "peak_inflight": self._peak_inflight,
            "act_bytes": self._act_bytes,
            "wire_bytes": self._wire_bytes,
            "ops": list(self._ops),
            "busy_s": sum(o["end"] - o["start"] for o in self._ops
                          if o["kind"] in ("F", "B", "A")),
            "jit_cache": caches,
        }
        if self._zero_info[0] is not None:
            out["zero_opt_bytes_per_replica"] = sum(
                zi["zero_opt_bytes_per_replica"] for zi in self._zero_info)
            out["replicated_opt_bytes"] = sum(
                zi["replicated_opt_bytes"] for zi in self._zero_info)
        return out

    # ---- lifecycle / fault tolerance ----
    def reset(self):
        """Drop partial schedule state after a failed step — stale grad
        accumulations must not leak into the next optimizer update."""
        self._resid.clear()
        self._acc = [None] * self.v
        self._ops = []
        self._peak_inflight = 0
        return True

    def snapshot(self):
        """Host copy of (per-chunk params, per-chunk opt state, step).
        ZeRO-sharded opt state is all-gathered to replicated first
        (``zero.replicate_opt_state``) so every gang rank snapshots the
        same bytes and any rank's ref can restore any future rank."""
        params = [self._jax.tree_util.tree_map(self._to_host, p)
                  for p in self.params]
        opts = []
        for slot in range(self.v):
            opt = self.opt_state[slot]
            if self._zero[slot] is not None:
                from ray_tpu.parallel import zero as zero_mod

                opt = zero_mod.replicate_opt_state(opt, self._mesh)
            opts.append(self._jax.tree_util.tree_map(self._to_host, opt))
        return (params, opts, self._step_count)

    def restore(self, snap):
        params, opts, step_count = snap
        if not isinstance(params, list):  # single-chunk legacy snapshot
            params, opts = [params], [opts]
        for slot in range(self.v):
            self.params[slot] = self._put_repl(params[slot])
            if self._zero[slot] is not None:
                from ray_tpu.parallel import zero as zero_mod

                self.opt_state[slot] = zero_mod.place_opt_state(
                    opts[slot], self._mesh, self._zero[slot].opt_specs,
                    multihost=self.gang_size > 1)
            else:
                self.opt_state[slot] = self._put_repl(opts[slot])
        self._step_count = int(step_count)
        return True

    def get_params(self):
        """Host params; the per-chunk list for v > 1, the bare pytree for
        v == 1 (the pre-interleaving contract)."""
        out = [self._jax.tree_util.tree_map(self._to_host, p)
               for p in self.params]
        return out[0] if self.v == 1 else out


@ray_tpu.remote
class PipelineStage:
    """One single-process pipeline stage: a :class:`StageCore` behind an
    actor boundary (the ``gang_hosts=1`` deployment).  Methods execute
    in strict submission order — the actor is single-threaded — which is
    what makes the driver-side schedule an execution order."""

    def __init__(self, chunk_fns, chunk_params, optimizer=None, *,
                 stage_id: int = 0, num_stages: int = 1,
                 virtual_per_rank: int = 1, generation: int = 0,
                 wire_dtype: str = "fp32", wire_block: int = 256,
                 spmd_devices: int = 0, zero_sharding: str = "off",
                 restore_from: Any = None):
        import os

        from ray_tpu._private import chaos

        os.environ[chaos.GENERATION_ENV] = str(generation)
        if not isinstance(chunk_fns, (list, tuple)):
            chunk_fns, chunk_params = [chunk_fns], [chunk_params]
        self.core = StageCore(
            list(chunk_fns), list(chunk_params), optimizer,
            stage_id=stage_id, num_stages=num_stages,
            virtual_per_rank=virtual_per_rank, wire_dtype=wire_dtype,
            wire_block=wire_block, spmd_devices=spmd_devices,
            zero_sharding=zero_sharding, restore_from=restore_from)
        self.stage_id = self.core.stage_id

    def fwd(self, step, slot, mb, x, target=None, weight: float = 1.0):
        return self.core.fwd(step, slot, mb, x, target, weight)

    def bwd(self, step, slot, mb, dy=None):
        return self.core.bwd(step, slot, mb, dy)

    def apply_grads(self, scale: float = 1.0) -> dict:
        return self.core.apply_grads(scale)

    def stats(self) -> dict:
        return self.core.stats()

    def ping(self) -> int:
        return self.stage_id

    def reset(self):
        return self.core.reset()

    def snapshot(self):
        return self.core.snapshot()

    def restore(self, snap):
        return self.core.restore(snap)

    def get_params(self):
        return self.core.get_params()


# ---- gang-rank entry points (run inside MeshWorker.pipeline_step with
# the worker's state dict: importable module functions, never closures) ----

def _gang_stage_setup(state, kwargs: dict, restore_snap=None):
    from ray_tpu.parallel.mpmd_pipeline import StageCore

    state["mpmd_core"] = StageCore(restore_from=restore_snap, **kwargs)
    return True


def _gang_stage_op(state, op: str, *args, **kwargs):
    return getattr(state["mpmd_core"], op)(*args, **kwargs)


# ---------------------------------------------------------------------------
# Driver-side stage handles
# ---------------------------------------------------------------------------

class _SoloStage:
    """Driver handle for a single-actor stage (width 1)."""

    width = 1

    def __init__(self, actor):
        self.actor = actor

    def submit(self, op: str, per_rank_args: Sequence[tuple],
               **kwargs) -> List[Any]:
        return [getattr(self.actor, op).remote(*per_rank_args[0], **kwargs)]

    def ping_refs(self) -> List[Any]:
        return [self.actor.ping.remote()]

    def resync(self) -> None:
        pass  # solo actors have no sequence gate to clear

    def kill(self) -> None:
        try:
            ray_tpu.kill(self.actor)
        except Exception:
            pass


class _GangStage:
    """Driver handle for a multi-host stage gang: every op is one gated
    ``MeshWorker.pipeline_step`` per rank at the next sequence position,
    so all ranks execute the identical op order — the property that
    keeps each rank's compiled collectives matched with its peers'."""

    def __init__(self, group: MeshGroup):
        self.group = group
        self.width = group.num_hosts
        self._seq = 0

    def submit(self, op: str, per_rank_args: Sequence[tuple],
               **kwargs) -> List[Any]:
        args_per_rank = [(_gang_stage_op, op) + tuple(a)
                         for a in per_rank_args]
        refs = self.group.submit_ordered(self._seq, args_per_rank,
                                         kwargs=kwargs)
        self._seq += 1
        return refs

    def setup(self, kwargs_base: dict, restore: Optional[List[Any]],
              timeout: float) -> None:
        self.group.seek_ranks(0)
        self._seq = 0
        per_rank = []
        for r in range(self.width):
            kw = dict(kwargs_base, gang_rank=r, gang_size=self.width)
            per_rank.append((_gang_stage_setup, kw,
                             None if restore is None else restore[r]))
        refs = self.group.submit_ordered(self._seq, per_rank)
        self._seq += 1
        gang_get(refs, timeout=timeout)

    def ping_refs(self) -> List[Any]:
        return [w.ping.remote() for w in self.group.workers]

    def resync(self) -> None:
        """Clear a poisoned sequence gate (a failed op fails every later
        queued op on its rank) so post-abort dispatch can resume."""
        self.group.seek_ranks(self._seq)

    def kill(self) -> None:
        try:
            self.group.shutdown()
        except Exception:
            pass


class _StepRec:
    """One submitted step: the host microbatches (for replay), the refs
    the driver drains, and bookkeeping flags.  ``aux_refs`` pins every
    intermediate activation/cotangent ref until the step drains —
    dropping them at dispatch would let ref-gc free a store-resident
    activation before its consumer stage resolved it."""
    __slots__ = ("idx", "xs", "ts", "weights", "loss_refs", "apply_refs",
                 "aux_refs", "snap", "drained", "trace_ctx")

    def __init__(self, idx, xs, ts, weights, snap):
        self.idx = idx
        self.xs = xs
        self.ts = ts
        self.weights = weights
        self.loss_refs: List[Any] = []
        self.apply_refs: List[Any] = []
        self.aux_refs: List[Any] = []
        self.snap = snap
        self.drained = False
        # One distributed trace per step (minted at dispatch, reused for
        # replay re-dispatch and the mpmd_stage_* spans at drain).
        self.trace_ctx = None


def _mpmd_metrics():
    """Lazy metric handles (internal_kv needs a connected driver)."""
    from ray_tpu.util.metrics import Counter, Gauge, Meter

    return {
        "bubble": Gauge("mpmd_bubble_fraction",
                        "1 - busy/(stages*wall) of the last drained step"),
        "steps": Counter("mpmd_steps_total", "pipeline train steps drained"),
        "replays": Counter("mpmd_replays_total",
                           "gang restarts absorbed by schedule replay"),
        "act_bytes": Meter("mpmd_activation_bytes",
                           "logical fp32 activation/cotangent bytes at "
                           "the stage boundaries"),
        "wire": Meter("mpmd_wire_bytes",
                      "activation/cotangent bytes actually shipped "
                      "through the object store (int8 wire shrinks "
                      "these ~4x vs mpmd_activation_bytes)"),
        "idle": Gauge("mpmd_stage_idle_frac",
                      "per-stage idle fraction of the last drained step",
                      tag_keys=("stage",)),
        "inflight": Gauge("mpmd_peak_inflight_microbatches",
                          "peak microbatches holding residuals on any "
                          "stage in the last drained step"),
    }


class MPMDPipeline:
    """Driver-side async (interleaved) 1F1B schedule over compiled stage
    actors or multi-host stage gangs.

    ``stage_fns``: ``num_stages * virtual_per_rank`` chunk callables in
    GLOBAL chunk order; the last must be ``loss_fn(params, x, target) ->
    scalar``.  Chunk c is owned by physical stage ``c % num_stages``
    (the interleaved assignment).  ``init_params``: per-chunk pytrees OR
    zero-arg factories (run on the stage).  ``stage_options``: per-stage
    StageCore kwargs (``spmd_devices``, ``zero_sharding``).

    3D composition knobs:

    - ``virtual_per_rank=v`` — interleaved virtual stages (v model
      chunks per physical stage; bubble shrinks toward ``1/(v*M)``).
    - ``wire_dtype="int8"`` — EQuARX block-scaled int8 activations AND
      cotangents on the inter-stage wire (~4x fewer bytes; fp32 is the
      bit-stable default).
    - ``gang_hosts=G`` — every stage becomes a G-process MeshGroup gang
      forming one jax.distributed SPMD world (with
      ``gang_local_device_count`` virtual/real devices per process);
      microbatches shard across the whole gang and ZeRO shards the
      optimizer across every gang device.

    Lockstep use (drop-in)::

        pipe = MPMDPipeline([f0, loss_fn], [p0, p1], num_microbatches=4)
        loss = pipe.train_step(x, t)        # one blocking sync per step

    Streaming use (the zero-sync hot path)::

        for x, t in batches:
            pipe.submit_step(x, t)          # <= step_window in flight
        losses = pipe.flush()               # [(step_idx, loss), ...]

    Fault tolerance: ``max_restarts > 0`` arms snapshotting (every
    ``snapshot_interval`` steps, store-resident) and replay — a stage or
    gang-rank death respawns every stage from the latest confirmed
    snapshot and re-dispatches every step since, in order."""

    def __init__(self, stage_fns: Sequence[Callable],
                 init_params: Sequence[Any], optimizer=None,
                 num_microbatches: int = 4,
                 stage_options: Optional[List[dict]] = None, *,
                 schedule: str = "1f1b", virtual_per_rank: int = 1,
                 wire_dtype: str = "fp32", wire_block: int = 256,
                 gang_hosts: int = 1, gang_platform: Optional[str] = None,
                 gang_local_device_count: Optional[int] = None,
                 step_window: int = 2, max_restarts: int = 0,
                 snapshot_interval: int = 1,
                 drain_timeout: Optional[float] = None,
                 bootstrap_timeout: float = 180.0,
                 export_metrics: bool = True):
        v = max(1, int(virtual_per_rank))
        if len(stage_fns) % v != 0:
            raise ValueError(
                f"{len(stage_fns)} chunk fns do not tile "
                f"virtual_per_rank={v}")
        n = len(stage_fns) // v
        if len(init_params) != len(stage_fns):
            raise ValueError("one params pytree per chunk fn")
        if schedule not in ("1f1b", "gpipe"):
            raise ValueError(f"schedule must be 1f1b|gpipe, got {schedule!r}")
        if v > 1 and int(num_microbatches) % n != 0:
            raise ValueError(
                f"interleaved schedule needs num_microbatches divisible "
                f"by num_stages ({num_microbatches} % {n} != 0)")
        if wire_dtype not in ("fp32", "int8"):
            raise ValueError(f"wire_dtype must be fp32|int8, "
                             f"got {wire_dtype!r}")
        self.num_stages = n
        self.virtual_per_rank = v
        self.num_chunks = n * v
        self.num_microbatches = int(num_microbatches)
        self.schedule = schedule
        self.wire_dtype = wire_dtype
        self.wire_block = int(wire_block)
        self.gang_hosts = max(1, int(gang_hosts))
        self.gang_platform = gang_platform
        self.gang_local_device_count = gang_local_device_count
        # The MeshGroup deployment also serves gang_hosts=1 when the
        # stage processes need a platform/device-count bootstrap BEFORE
        # their first jax import (virtual devices for intra-stage SPMD
        # on boxes whose env doesn't pre-set XLA flags).
        self._use_gang = (self.gang_hosts > 1 or gang_platform is not None
                          or gang_local_device_count is not None)
        self.step_window = max(1, int(step_window))
        self.max_restarts = int(max_restarts)
        self.snapshot_interval = max(1, int(snapshot_interval))
        self.drain_timeout = drain_timeout
        self.bootstrap_timeout = bootstrap_timeout
        self.restart_count = 0
        self._stage_fns = list(stage_fns)
        self._init_params = list(init_params)
        self._optimizer = optimizer
        self._stage_opts = list(stage_options or [{} for _ in range(n)])
        if len(self._stage_opts) != n:
            raise ValueError(f"stage_options must have one entry per "
                             f"PHYSICAL stage ({n}), got "
                             f"{len(self._stage_opts)}")
        self._generation = 0
        self.stages: List[Any] = []       # solo actor handles (width 1)
        self._gangs: List[MeshGroup] = []  # stage gangs (width > 1)
        self._handles: List[Any] = []
        self._spawn_stages(restore_refs=None)

        self._window: InflightWindow = InflightWindow(self.step_window)
        self._replay: collections.deque = collections.deque()  # _StepRec
        self._results: List[tuple] = []
        self._next_idx = 0
        self._snap: Optional[tuple] = None          # (idx, [[refs]/stage])
        self._pending_snap: Optional[tuple] = None
        self._last_report: Optional[dict] = None
        self._act_bytes_total = 0
        self._wire_bytes_total = 0
        self._busy_total = 0.0
        self._wall_total = 0.0
        self._peak_window = 0
        self._metrics = None
        if export_metrics:
            try:
                self._metrics = _mpmd_metrics()
            except Exception:
                self._metrics = None

    # ---- stage fn / param assignment ----
    def _chunks_of(self, k: int) -> List[int]:
        return [slot * self.num_stages + k
                for slot in range(self.virtual_per_rank)]

    def _stage_kwargs(self, k: int) -> dict:
        return dict(
            stage_id=k, num_stages=self.num_stages,
            virtual_per_rank=self.virtual_per_rank,
            wire_dtype=self.wire_dtype, wire_block=self.wire_block,
            **self._stage_opts[k])

    # ---- gang lifecycle ----
    def _spawn_stages(self, restore_refs) -> None:
        n = self.num_stages
        fns = [[self._stage_fns[c] for c in self._chunks_of(k)]
               for k in range(n)]
        params = [[self._init_params[c] for c in self._chunks_of(k)]
                  for k in range(n)]
        if not self._use_gang:
            self.stages = [
                PipelineStage.remote(
                    fns[k], params[k], self._optimizer,
                    generation=self._generation,
                    restore_from=None if restore_refs is None
                    else restore_refs[k][0],
                    **self._stage_kwargs(k))
                for k in range(n)
            ]
            self._handles = [_SoloStage(a) for a in self.stages]
            return
        # Multi-host: one MeshGroup gang per stage.  Spawn every gang
        # first (placement + jax.distributed rendezvous are the slow
        # part and independent), then fan the setups out.
        self.stages = []
        self._gangs = [
            MeshGroup(self.gang_hosts, platform=self.gang_platform,
                      local_device_count=self.gang_local_device_count,
                      bootstrap_timeout=self.bootstrap_timeout)
            for _ in range(n)
        ]
        self._handles = [_GangStage(g) for g in self._gangs]
        for k, h in enumerate(self._handles):
            kw = dict(self._stage_kwargs(k), chunk_fns=fns[k],
                      chunk_params=params[k], optimizer=self._optimizer)
            h.setup(kw, None if restore_refs is None else restore_refs[k],
                    timeout=self.bootstrap_timeout)

    def _teardown_stages(self) -> None:
        for h in self._handles:
            h.kill()
        self.stages = []
        self._gangs = []
        self._handles = []

    def _dead_stages(self, deadline: float = 15.0) -> List[int]:
        """Bounded ping fan-out over every rank of every stage; returns
        the stage ids with any dead/unresponsive rank."""
        refs, owner = [], []
        for k, h in enumerate(self._handles):
            for r in h.ping_refs():
                refs.append(r)
                owner.append(k)
        try:
            gang_get(refs, timeout=deadline)
            return []
        except exc.MeshGroupError as e:
            return sorted({owner[i] for i in e.failed_ranks})
        except Exception:
            return list(range(self.num_stages))

    # ---- batch slicing ----
    def _rank_split(self, arr: np.ndarray, width: int) -> List[np.ndarray]:
        if width == 1:
            return [arr]
        return np.split(arr, width)

    # ---- schedule dispatch (pure ref wiring — no tensors, no waits) ----
    def _dispatch_step(self, rec: _StepRec) -> None:
        from ray_tpu import observability as obs

        minted = False
        if rec.trace_ctx is None and obs.enabled():
            # Join the caller's trace when one is live (e.g. a learner
            # update_async boundary); mint a fresh per-step root else.
            rec.trace_ctx = obs.get_context()
            if rec.trace_ctx is None:
                rec.trace_ctx = obs.mint_context()
                minted = True
        if rec.trace_ctx is not None:
            # Dispatch inside the step's trace: every stage-actor submit
            # below inherits it, so one training step assembles into one
            # cross-process timeline.
            import time as _time

            from ray_tpu._private import profiling

            t0 = _time.perf_counter()
            with obs.use_context(rec.trace_ctx):
                self._dispatch_step_inner(rec)
            # A freshly minted step records its dispatch AS the trace
            # root: the stage actors' execute spans parent to the root
            # id, and flow arrows need that span to exist.
            profiling.record_span("mpmd_step_dispatch", t0,
                                  _time.perf_counter(), step=rec.idx,
                                  _trace_ctx=rec.trace_ctx, _root=minted)
            return
        self._dispatch_step_inner(rec)

    def _dispatch_step_inner(self, rec: _StepRec) -> None:
        if rec.snap:
            refs = [h.submit("snapshot", [() for _ in range(h.width)])
                    for h in self._handles]
            self._pending_snap = (rec.idx, refs)
        S, M, v = self.num_stages, len(rec.xs), self.virtual_per_rank
        C = self.num_chunks
        queues = [collections.deque(
            stage_schedule(self.schedule, S, M, k, v)) for k in range(S)]
        acts: Dict[tuple, List[Any]] = {}
        cots: Dict[tuple, List[Any]] = {}
        classic = self.schedule == "1f1b" and v == 1
        window = InflightWindow(S if classic else M)
        rec.loss_refs, rec.apply_refs = [], []
        aux: List[Any] = []
        remaining = sum(len(q) for q in queues)
        while remaining:
            progressed = False
            for k in range(S):
                q = queues[k]
                h = self._handles[k]
                while q:
                    op, c, m = q[0]
                    slot = c // S
                    if op == "F":
                        if c == 0:
                            srcs = self._rank_split(rec.xs[m], h.width)
                        else:
                            srcs = acts.get((c - 1, m))
                            if srcs is None:
                                break
                        if c == 0:
                            window.append(m)
                            self._peak_window = max(self._peak_window,
                                                    len(window))
                            if classic and window.over_depth:
                                raise RuntimeError(
                                    "1F1B scheduler admitted more than "
                                    f"{window.depth} microbatches")
                        if c == C - 1:
                            tgt = self._rank_split(rec.ts[m], h.width)
                            refs = h.submit(
                                "fwd",
                                [(rec.idx, slot, m, srcs[r], tgt[r],
                                  float(rec.weights[m]))
                                 for r in range(h.width)])
                            rec.loss_refs.append(refs[0])
                            aux += refs[1:]
                        else:
                            refs = h.submit(
                                "fwd",
                                [(rec.idx, slot, m, srcs[r])
                                 for r in range(h.width)])
                            acts[(c, m)] = refs
                    else:  # "B"
                        if c == C - 1:
                            dys: Optional[List[Any]] = None
                        else:
                            dys = cots.get((c + 1, m))
                            if dys is None:
                                break
                        if c == 0:
                            window.remove(m)
                        refs = h.submit(
                            "bwd",
                            [(rec.idx, slot, m,
                              None if dys is None else dys[r])
                             for r in range(h.width)])
                        cots[(c, m)] = refs
                    q.popleft()
                    remaining -= 1
                    progressed = True
            if not progressed:
                raise RuntimeError(
                    f"{self.schedule} schedule deadlocked with "
                    f"{remaining} ops pending (S={S}, M={M}, v={v})")
        for h in self._handles:
            rec.apply_refs += h.submit("apply_grads",
                                       [() for _ in range(h.width)])
        rec.aux_refs = aux + [r for refs in list(acts.values())
                              + list(cots.values()) for r in refs]

    def _split_batch(self, x, target):
        M = self.num_microbatches
        if len(x) < M:
            raise ValueError(
                f"batch of {len(x)} rows cannot fill num_microbatches={M} "
                "(an empty microbatch means a NaN loss, not an error)")
        if len(x) != len(target):
            raise ValueError("x and target row counts differ")
        width = self._handles[0].width if self._handles else 1
        if width > 1 and len(x) % (M * width) != 0:
            raise ValueError(
                f"gang mode needs batch % (num_microbatches * gang_hosts) "
                f"== 0 so every rank gets an equal slice; got "
                f"{len(x)} % ({M} * {width}) != 0")
        xs = np.array_split(x, M)
        ts = np.array_split(target, M)
        # True per-microbatch weights: grad accumulation and the reported
        # loss weight each microbatch by its ACTUAL row share, so ragged
        # splits (len(x) % M != 0) match the single-process full-batch
        # gradients exactly.
        weights = np.asarray([len(xb) for xb in xs], np.float64) / len(x)
        return xs, ts, weights

    # ---- streaming API (the zero-sync hot path) ----
    def submit_step(self, x: np.ndarray, target: np.ndarray) -> int:
        """Dispatch one full schedule asynchronously; blocks (draining
        the oldest step) only once more than ``step_window`` steps are in
        flight.  Returns the step index."""
        xs, ts, weights = self._split_batch(x, target)
        idx = self._next_idx
        self._next_idx += 1
        snap = self.max_restarts > 0 and (
            self._snap is None and self._pending_snap is None
            or (self._pending_snap is None
                and idx - self._snap[0] >= self.snapshot_interval))
        rec = _StepRec(idx, xs, ts, weights, snap)
        self._dispatch_step(rec)
        self._replay.append(rec)
        self._window.append(rec)
        while self._window.over_depth:
            self._drain_one()
        return idx

    def flush(self) -> List[tuple]:
        """Drain every in-flight step; returns all accumulated
        ``(step_idx, loss)`` pairs (destructive read)."""
        while self._window:
            self._drain_one()
        out, self._results = self._results, []
        return out

    def train_step(self, x: np.ndarray, target: np.ndarray) -> float:
        """Lockstep step (compat API): submit + drain everything, return
        THIS step's weighted mean microbatch loss."""
        _note_sync()
        idx = self.submit_step(x, target)
        drained = dict(self.flush())
        return drained[idx]

    # ---- drain + recovery ----
    def _drain_one(self) -> None:
        rec = self._window.peek()
        while True:
            try:
                vals = gang_get(rec.loss_refs + rec.apply_refs,
                                timeout=self.drain_timeout)
                break
            except exc.MeshGroupError as e:
                self._recover(e)
            except exc.RayTpuError:
                # A user exception — or a task poisoned by an upstream
                # stage death (surfaces as a TaskError, not an actor
                # error).  Disambiguate with a bounded ping fan-out.
                dead = self._dead_stages()
                if dead:
                    self._recover(exc.MeshGroupError(
                        f"pipeline stage(s) {dead} died mid-step",
                        failed_ranks={d: exc.ActorDiedError(
                            f"stage {d} unresponsive") for d in dead}))
                    continue
                self._abort()
                raise
        M = len(rec.loss_refs)
        losses, rank_stats = vals[:M], vals[M:]
        loss = float(np.dot(rec.weights, np.asarray(losses, np.float64)))
        self._window.popleft()
        rec.drained = True
        rec.aux_refs = []  # consumers finished: release the pins
        self._results.append((rec.idx, loss))
        self._ingest_stats(rec, rank_stats)
        # Snapshot confirmation: this step drained, so every op queued
        # before it — including the snapshot — executed.
        if self._pending_snap is not None and \
                rec.idx >= self._pending_snap[0]:
            self._snap = self._pending_snap
            self._pending_snap = None
            while self._replay and self._replay[0].idx < self._snap[0]:
                self._replay.popleft()
        elif self.max_restarts == 0:
            while self._replay and self._replay[0].drained:
                self._replay.popleft()

    def _recover(self, cause: exc.MeshGroupError) -> None:
        """All-or-nothing gang restart + in-order schedule replay."""
        from ray_tpu import observability as obs

        obs.flight_record(f"mpmd_gang_restart: {cause}")
        if self.restart_count >= self.max_restarts:
            cause.restarts = self.restart_count
            self._abort(teardown=False)
            raise cause
        self.restart_count += 1
        self._generation += 1
        self._teardown_stages()
        # The fresh stage gangs resolve these snapshot refs concurrently
        # during setup — a cooperative striped broadcast on the transfer
        # plane, so restart time doesn't grow with gang width.
        restore = [list(refs) for refs in self._snap[1]] \
            if self._snap is not None else None
        self._pending_snap = None  # its refs died with the old gang
        self._spawn_stages(restore_refs=restore)
        for rec in self._replay:
            if rec.snap and self._snap is not None \
                    and rec.idx <= self._snap[0]:
                rec.snap = False  # already restored from this snapshot
            self._dispatch_step(rec)
        if self._metrics is not None:
            try:
                self._metrics["replays"].inc()
            except Exception:
                pass

    def _abort(self, teardown: bool = False) -> None:
        """Drop in-flight schedule state after an unrecoverable error so
        a retry doesn't double-apply; stages reset their accumulators."""
        self._window.clear()
        self._replay.clear()
        self._pending_snap = None
        if teardown:
            self._teardown_stages()
            return
        for h in self._handles:
            try:
                h.resync()
                gang_get(h.submit("reset", [() for _ in range(h.width)]),
                         timeout=30.0)
            except Exception:
                pass

    # ---- observability ----
    def _merge_rank_stats(self, rank_stats: Sequence[dict]) -> List[dict]:
        """Fold per-rank apply payloads into one dict per stage: rank 0
        carries the spans/watermarks (ranks run the identical schedule),
        boundary bytes sum across ranks (each ships its own slice)."""
        width = self._handles[0].width if self._handles else 1
        out = []
        for k in range(self.num_stages):
            group = list(rank_stats[k * width:(k + 1) * width])
            st = dict(group[0])
            st["act_bytes"] = sum(g["act_bytes"] for g in group)
            st["wire_bytes"] = sum(g["wire_bytes"] for g in group)
            out.append(st)
        return out

    def _ingest_stats(self, rec: _StepRec, rank_stats: Sequence[dict]):
        try:
            stage_stats = self._merge_rank_stats(rank_stats)
            ops = [o for st in stage_stats for o in st["ops"]]
            wall = (max(o["end"] for o in ops)
                    - min(o["start"] for o in ops)) if ops else 0.0
            busy = [st["busy_s"] for st in stage_stats]
            bubble = 1.0 - sum(busy) / (self.num_stages * wall) \
                if wall > 0 else 0.0
            act_bytes = sum(st["act_bytes"] for st in stage_stats) \
                - self._act_bytes_total
            wire_bytes = sum(st["wire_bytes"] for st in stage_stats) \
                - self._wire_bytes_total
            self._act_bytes_total += act_bytes
            self._wire_bytes_total += wire_bytes
            self._busy_total += sum(busy)
            self._wall_total += wall
            self._last_report = {
                "step": rec.idx,
                "bubble_fraction": bubble,
                "wall_s": wall,
                "busy_s": busy,
                "peak_inflight": {st["stage"]: st["peak_inflight"]
                                  for st in stage_stats},
                "jit_cache": {st["stage"]: st["jit_cache"]
                              for st in stage_stats},
                "act_bytes": act_bytes,
                "wire_bytes": wire_bytes,
                "ops": {st["stage"]: st["ops"] for st in stage_stats},
            }
            from ray_tpu._private import profiling

            for o in ops:
                profiling.record_span(
                    {"F": "mpmd_stage_fwd", "B": "mpmd_stage_bwd",
                     "A": "mpmd_stage_apply", "X": "mpmd_stage_transfer"}
                    [o["kind"]], o["start"], o["end"], stage=o["stage"],
                    step=o["step"], mb=o["mb"], _trace_ctx=rec.trace_ctx)
            if self._metrics is not None:
                m = self._metrics
                m["bubble"].set(bubble)
                m["steps"].inc()
                m["act_bytes"].mark(float(act_bytes))
                m["wire"].mark(float(wire_bytes))
                m["inflight"].set(float(max(
                    st["peak_inflight"] for st in stage_stats)))
                for st, b in zip(stage_stats, busy):
                    idle = 1.0 - b / wall if wall > 0 else 0.0
                    m["idle"].set(idle, tags={"stage": str(st["stage"])})
        except Exception:
            pass  # observability is best-effort, never the step path

    def last_step_report(self) -> Optional[dict]:
        """Observability payload of the most recently drained step."""
        return self._last_report

    def stats(self) -> dict:
        rep = self._last_report or {}
        return {
            "num_stages": self.num_stages,
            "virtual_per_rank": self.virtual_per_rank,
            "num_microbatches": self.num_microbatches,
            "schedule": self.schedule,
            "wire_dtype": self.wire_dtype,
            "gang_hosts": self.gang_hosts,
            "steps_submitted": self._next_idx,
            "steps_inflight": len(self._window),
            "restarts": self.restart_count,
            "bubble_fraction": rep.get("bubble_fraction"),
            "peak_inflight": rep.get("peak_inflight"),
            "jit_cache": rep.get("jit_cache"),
            "activation_bytes": self._act_bytes_total,
            "wire_bytes": self._wire_bytes_total,
            "wire_reduction_vs_fp32": (
                self._act_bytes_total / self._wire_bytes_total
                if self._wire_bytes_total else 1.0),
            "act_gb_per_s": (self._act_bytes_total / self._wall_total / 1e9
                             if self._wall_total > 0 else 0.0),
            "driver_peak_window": self._peak_window,
        }

    # ---- params access (lockstep paths) ----
    def get_params(self) -> List[Any]:
        """Host params per GLOBAL chunk (length ``num_stages * v``; for
        v=1 that is the familiar one-pytree-per-stage list).  Gang mode
        reads rank 0 (params are replicated across the gang)."""
        _note_sync()
        self.flush()
        per_stage = gang_get(
            [h.submit("get_params", [() for _ in range(h.width)])[0]
             for h in self._handles])
        out = []
        for c in range(self.num_chunks):
            k, slot = c % self.num_stages, c // self.num_stages
            got = per_stage[k]
            out.append(got[slot] if self.virtual_per_rank > 1 else got)
        return out

    def stop(self):
        try:
            if self._window:
                self.flush()
        except Exception:
            pass
        self._teardown_stages()

    def __enter__(self) -> "MPMDPipeline":
        return self

    def __exit__(self, exc_type, exc_val, tb) -> None:
        if exc_type is not None:
            self._abort(teardown=True)
        else:
            self.stop()
