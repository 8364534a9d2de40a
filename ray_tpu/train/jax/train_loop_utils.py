"""In-loop helpers (reference: python/ray/train/torch/train_loop_utils.py —
prepare_model DDP wrap, prepare_data_loader).  The TPU equivalents don't
wrap modules; they build the mesh and place arrays.
"""
from __future__ import annotations

from typing import Any, Optional

from ray_tpu.parallel.mesh import MeshSpec, make_mesh
from ray_tpu.parallel.sharding import ShardingRules, batch_sharding, shard_params


def get_mesh(spec: Optional[MeshSpec] = None):
    """Mesh over all devices visible to this training group.

    After jax.distributed.initialize (multi-host), jax.devices() spans the
    whole group, so the same call yields the global mesh on every worker."""
    return make_mesh(spec or MeshSpec({"data": -1}))


def prepare_train_state(params: Any, mesh, annotations=None,
                        rules: Optional[ShardingRules] = None):
    """Place params on the mesh (replicated or by logical-axis annotation) —
    the moral equivalent of prepare_model's DDP wrap."""
    return shard_params(params, mesh, rules, annotations)


def prepare_batch(batch: Any, mesh):
    """Shard a host batch's leading dim over the data axes."""
    import jax

    def place(x):
        return jax.device_put(x, batch_sharding(mesh, getattr(x, "ndim", 1)))

    return jax.tree_util.tree_map(place, batch)


def compile_donated_step(step_fn, carry_argnums=(0,), batch_argnums=(),
                         donate_batch: bool = False, **jit_kwargs):
    """jit a training step with the carry (params/opt state) — and
    optionally the batch buffers — donated, so XLA updates weights
    in-place instead of allocating a second copy per step (the hot-path
    half of the zero-sync pipeline; see docs/PERFORMANCE.md).

    ``step_fn(carry..., batch...) -> (carry..., metrics)``: the caller
    must not reuse donated arguments after the call (donation invalidates
    their buffers) — keep ``donate_batch=False`` when the same host batch
    is fed to several steps (e.g. synthetic-data benches).

    What comes back is the jitted step with its first call inside a
    ``train.compile`` lifecycle span (``jax_env.FirstCallSpan``)."""
    import jax

    from ray_tpu._private import jax_env

    jax_env.ensure_compile_listener()
    donate = tuple(carry_argnums)
    if donate_batch:
        donate = donate + tuple(batch_argnums)
    return jax_env.FirstCallSpan(
        jax.jit(step_fn, donate_argnums=donate, **jit_kwargs),
        "train.compile", getattr(step_fn, "__name__", "step"))


class AsyncMetrics:
    """Every-N async metrics fetch for step loops.

    ``push(step, metrics)`` keeps the (lazy, device-resident) metrics of
    the latest step and only converts them to host floats every
    ``interval`` steps — so the loop never blocks on a per-step
    device_get round trip.  ``last`` holds
    the most recent host copy; ``flush()`` forces a final fetch (and is
    the loop-end barrier the bench pattern needs)."""

    def __init__(self, interval: int = 10):
        self.interval = max(1, int(interval))
        self._pending = None
        self._pending_step = None
        self.last: Optional[dict] = None
        self.last_step: Optional[int] = None

    def push(self, step: int, metrics: Any) -> Optional[dict]:
        self._pending = metrics
        self._pending_step = step
        if step % self.interval == 0:
            return self.flush()
        return None

    def flush(self) -> Optional[dict]:
        if self._pending is None:
            return self.last
        import jax

        host = jax.device_get(self._pending)
        self.last = {k: (float(v) if hasattr(v, "__float__") else v)
                     for k, v in host.items()} \
            if isinstance(host, dict) else host
        self.last_step = self._pending_step
        self._pending = None
        return self.last


def compile_zero_step(grad_fn, tx, params, mesh=None, *,
                      zero_sharding: str = "opt+grads",
                      quantized_collectives: str = "off",
                      should_shard=None, donate: bool = True):
    """Build a ZeRO data-parallel train step for the Train JAX loop
    (arxiv 2004.13336 + EQuARX int8 collectives; see
    ray_tpu.parallel.zero and docs/PERFORMANCE.md).

    ``grad_fn(params, batch) -> (loss, grads)`` on a LOCAL batch shard
    (e.g. ``jax.value_and_grad`` of the model loss).  Returns
    ``(step, opt_state, info)`` where ``step(params, opt_state, batch) ->
    (params, opt_state, loss)`` is one jitted shard_map program over the
    mesh's ``data`` axis: batch sharded, params replicated, optimizer
    state sharded 1/N per replica, gradients reduce-scattered (int8 when
    ``quantized_collectives="int8"``), fresh params all-gathered, loss
    pmean'd.  ``opt_state`` is the globally-sharded initial state
    (already placed); ``info`` is the memory/wire envelope
    (``zero_opt_bytes_per_replica``, ``grad_comm_bytes``, ...).

    ``tx`` must be elementwise (adam/adamw/sgd/...); for gradient-norm
    clipping chain ``zero.zero_clip_by_global_norm`` instead of
    ``optax.clip_by_global_norm`` — the shard-local norm would otherwise
    be wrong.  The carry is donated by default (in-place weight update,
    same contract as ``compile_donated_step``)."""
    import jax
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from ray_tpu.parallel import zero as zero_mod

    if mesh is None:
        mesh = get_mesh()
    axis = zero_mod.DATA_AXIS
    world = dict(mesh.shape).get(axis, 1)
    zu = zero_mod.build_zero_update(
        jax.eval_shape(lambda: params), tx, world,
        zero_sharding=zero_sharding, quantized=quantized_collectives,
        axis_name=axis, should_shard=should_shard)
    info = zero_mod.export_zero_metrics(
        zu.sharder, tx, zero_sharding=zero_sharding,
        quantized=quantized_collectives)

    def body(params, opt_block, batch):
        loss, grads = grad_fn(params, batch)
        loss = jax.lax.pmean(loss, axis) if world > 1 else loss
        params, opt_block = zu.update(grads, opt_block, params)
        return params, opt_block, loss

    mapped = jax.shard_map(body, mesh=mesh,
                           in_specs=(P(), zu.opt_specs, P(axis)),
                           out_specs=(P(), zu.opt_specs, P()),
                           check_vma=False)
    step = jax.jit(mapped, donate_argnums=(0, 1) if donate else ())
    opt_sh = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), zu.opt_specs,
        is_leaf=lambda s: isinstance(s, P))
    opt_state = jax.jit(zu.init_opt, out_shardings=opt_sh)(params)
    return step, opt_state, info


def prepare_device_iterator(host_batches, mesh=None, sharding=None,
                            prefetch: int = 2):
    """Wrap any host-batch iterable in the background device prefetcher,
    sharded over the mesh's data axes when ``mesh`` is given — the Train
    JAX loop's ingest hot path (same machinery as
    Dataset.iter_device_batches; see ray_tpu.data.prefetch)."""
    from ray_tpu.data.prefetch import DevicePrefetcher

    place_fn = None
    if mesh is not None and sharding is None:
        place_fn = lambda b: prepare_batch(b, mesh)  # noqa: E731
    return DevicePrefetcher(host_batches, sharding=sharding,
                            prefetch=prefetch, place_fn=place_fn)
