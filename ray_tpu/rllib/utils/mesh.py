"""Data-parallel mesh plumbing for the anakin train step.

Reference shape: the learner-group DDP fan-out in
rllib/core/rl_trainer/trainer_runner.py:75-90 and the multi-GPU tower
loop in rllib/execution/train_ops.py:82 — one replica per device, grads
all-reduced.  TPU-first redesign: there are no towers and no NCCL
buckets; the whole train step (env rollout + GAE + SGD) is ONE SPMD
program `shard_map`-ed over a `data` mesh axis.  Envs live sharded on
the axis, parameters are replicated, and the only communication is a
`psum`/`pmean` over gradients (and episode counters) that XLA lowers to
an ICI all-reduce.
"""
from __future__ import annotations

from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

DATA_AXIS = "data"


def data_mesh(num_devices: int) -> Mesh:
    """A 1-D `data` mesh over the first `num_devices` local devices."""
    devs = jax.devices()
    if num_devices > len(devs):
        raise ValueError(
            f"num_devices={num_devices} but only {len(devs)} jax devices "
            "are visible (set XLA_FLAGS=--xla_force_host_platform_device_"
            "count=N for a virtual CPU mesh)")
    return Mesh(np.asarray(devs[:num_devices]), (DATA_AXIS,))


def pmean_if(x, sharded: bool):
    return jax.lax.pmean(x, DATA_AXIS) if sharded else x


def psum_if(x, sharded: bool):
    return jax.lax.psum(x, DATA_AXIS) if sharded else x


def normalize_global(x, sharded: bool, eps: float = 1e-8):
    """Mean/std normalization over the GLOBAL batch: local moments are
    pmean'd across the data axis so the sharded update matches the
    single-device one at equal global batch."""
    import jax.numpy as jnp

    m = pmean_if(x.mean(), sharded)
    var = pmean_if(jnp.mean((x - m) ** 2), sharded)
    return (x - m) / (jnp.sqrt(var) + eps)


def state_sharding(mesh: Mesh, state_specs):
    """Pytree-prefix of NamedShardings matching a pytree-prefix of
    PartitionSpecs (for jit out_shardings on the init fn)."""
    return jax.tree.map(lambda s: NamedSharding(mesh, s), state_specs,
                        is_leaf=lambda s: isinstance(s, P))


def shard_train_step(step_fn, mesh: Mesh, state_specs, donate: bool = False):
    """jit(shard_map(...)) for a `state -> (state, metrics)` train step.

    `state_specs` is a pytree prefix of PartitionSpecs for the state;
    metrics are replicated (the step body must pmean/psum them)."""
    mapped = jax.shard_map(step_fn, mesh=mesh, in_specs=(state_specs,),
                           out_specs=(state_specs, P()), check_vma=False)
    return jax.jit(mapped, donate_argnums=(0,) if donate else ())


def zero_train_step(step_fn, mesh: Mesh, state_specs, donate: bool = False):
    """`shard_train_step` for a ZeRO-sharded state (drop-in; see
    ray_tpu.parallel.zero).

    Identical compilation contract — the difference is carried by
    `state_specs`: the optimizer-state subtree is the per-leaf spec pytree
    from `ZeroSharder.opt_specs` (``[world, chunk]`` leaves P(data),
    scalars replicated) instead of a blanket P(), so each replica's state
    block is 1/N and the step body's reduce-scatter/all-gather pair (built
    by `zero.make_update_fn`) is the only cross-replica traffic."""
    return shard_train_step(step_fn, mesh, state_specs, donate=donate)


def build_update_plan(config, lr, grad_clip, params_template, D, sharded):
    """The gradient-application recipe every anakin algorithm shares,
    resolved from ``config.zero_sharding`` / ``config.quantized_collectives``
    — one copy so PPO and IMPALA cannot drift.

    Returns ``(update_fn, opt_init, opt_specs)``:
    ``update_fn(grads, opt_state, params) -> (params, opt_state)`` runs
    INSIDE the shard_map body (grads are the local, un-reduced values);
    ``opt_init(params)`` builds the (possibly globally sharded) optimizer
    state; ``opt_specs`` is its PartitionSpec pytree (a bare ``P()`` on the
    replicated paths).

    - default: ``pmean`` grads + replicated optax update (today's math),
    - ``quantized_collectives=int8``: the block-scaled int8 all-reduce
      from ``ray_tpu.ops.collectives`` in place of the fp32 pmean,
    - ``zero_sharding=opt|opt+grads``: the ZeRO plane from
      ``ray_tpu.parallel.zero`` — 1/N optimizer state per replica,
      reduce-scattered grads, all-gathered fresh params (grad_clip maps
      to ``zero_clip_by_global_norm`` so the clip stays exactly global).

    Both knobs require the SPMD path: without ``num_devices`` there is no
    mesh axis to shard or quantize over, and silently ignoring the
    request is the worst failure — so it raises."""
    import optax

    zero_mode = getattr(config, "zero_sharding", "off") or "off"
    quant = getattr(config, "quantized_collectives", "off") or "off"
    if zero_mode not in ("off", "opt", "opt+grads"):
        raise ValueError(f"zero_sharding must be off|opt|opt+grads, "
                         f"got {zero_mode!r}")
    if quant not in ("off", "int8"):
        raise ValueError(f"quantized_collectives must be off|int8, "
                         f"got {quant!r}")
    if (zero_mode != "off" or quant != "off") and not sharded:
        raise ValueError(
            "zero_sharding/quantized_collectives require the SPMD path: "
            "set resources(num_devices=...) (1 is valid)")

    if zero_mode == "off":
        parts = [optax.clip_by_global_norm(grad_clip)] if grad_clip else []
        tx = optax.chain(*parts, optax.adam(lr))
        if quant == "int8":
            from ray_tpu.ops import collectives

            def update_fn(grads, opt_state, params):
                grads = collectives.quantized_pmean(grads, DATA_AXIS, D)
                updates, opt_state = tx.update(grads, opt_state, params)
                return optax.apply_updates(params, updates), opt_state
        else:
            def update_fn(grads, opt_state, params):
                grads = pmean_if(grads, sharded)
                updates, opt_state = tx.update(grads, opt_state, params)
                return optax.apply_updates(params, updates), opt_state
        return update_fn, tx.init, P()

    from ray_tpu.parallel import zero as zero_mod

    zparts = [zero_mod.zero_clip_by_global_norm(grad_clip, DATA_AXIS)] \
        if grad_clip else []
    tx = optax.chain(*zparts, optax.adam(lr))
    zu = zero_mod.build_zero_update(params_template, tx, D,
                                    zero_sharding=zero_mode,
                                    quantized=quant, axis_name=DATA_AXIS)
    return zu.update, zu.init_opt, zu.opt_specs


def resolve_num_devices(config_num_devices: Optional[int]) -> Optional[int]:
    """None → legacy jit path; int → SPMD path.  Validates only; if the
    count exceeds the visible devices, data_mesh raises at build time."""
    if config_num_devices is None:
        return None
    n = int(config_num_devices)
    if n < 1:
        raise ValueError(f"num_devices must be >= 1, got {n}")
    return n


def setup_data_mesh(config, num_envs: int):
    """Shared anakin data-mesh wiring: returns (D, sharded, mesh) from
    ``config.num_devices``, enforcing env divisibility.  One copy so the
    divisibility error and mesh construction cannot drift between
    algorithms (PPO/IMPALA both call this)."""
    D = resolve_num_devices(getattr(config, "num_devices", None))
    if D is None:
        return None, False, None
    if num_envs % D:
        raise ValueError(f"num_envs={num_envs} not divisible by "
                         f"num_devices={D}")
    return D, True, data_mesh(D)


def reject_data_mesh(config, path: str) -> None:
    """Paths that have no shard_map implementation must refuse a
    num_devices request loudly — silently running single-device while the
    user believes they are N-way data-parallel is the worst failure."""
    if getattr(config, "num_devices", None) is not None:
        raise NotImplementedError(
            f"resources(num_devices=...) is not implemented for {path}; "
            "the data-parallel anakin step currently covers feedforward "
            "PPO and IMPALA/APPO")
    if getattr(config, "zero_sharding", "off") != "off" or \
            getattr(config, "quantized_collectives", "off") != "off":
        raise NotImplementedError(
            f"zero_sharding/quantized_collectives are not implemented for "
            f"{path}; they ride the shard_map data-parallel step")


def split_rng(rng, D: Optional[int], sharded: bool):
    """State rng leaf: per-device key rows [D, 2] when sharded."""
    import jax

    return jax.random.split(rng, D) if sharded else rng


def unwrap_rng(state_rng, sharded: bool):
    """Inside shard_map the [1, 2] local block unwraps to this device's
    key; wrap_rng re-wraps for the output state."""
    return state_rng[0] if sharded else state_rng


def wrap_rng(rng, sharded: bool):
    return rng[None] if sharded else rng
