"""Pipeline parallelism: GPipe-style microbatch schedule inside one SPMD
program (shard_map over a `pipe` mesh axis, stage hand-off via ppermute).

The reference has no pipeline parallelism (SURVEY.md §2.4) — only the
substrate (placement groups + collective send/recv between actors).  The
TPU-native design runs the whole pipeline *inside one compiled program*:
every device holds one stage's weights, activations rotate along the ring,
and XLA overlaps the ppermute with the next microbatch's compute.  Autodiff
through the scan+ppermute yields the reversed-ring backward schedule
automatically.  MPMD pipelines across *meshes* (per PAPERS.md's MPMD
pipeline paper) layer on top via the actor runtime; this module is the
intra-mesh SPMD form.

Constraint: all stages share one activation shape [mb, ...] (uniform-stack
transformer assumption).
"""
from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp


def stack_stage_params(per_stage_params: list) -> Any:
    """Stack per-stage pytrees along a new leading 'stage' axis (shard it
    over the pipe mesh axis with logical axis name "stage")."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *per_stage_params)


def _pipeline_body(stacked_params, x_micro, *, stage_fn, axis_name, n_stages,
                   n_micro, remat):
    """Inside shard_map. stacked_params leaves: [1, ...] (this device's
    stage); x_micro: [n_micro, mb, ...] (replicated along pipe)."""
    params = jax.tree_util.tree_map(lambda p: p[0], stacked_params)
    idx = jax.lax.axis_index(axis_name)
    perm = [(j, (j + 1) % n_stages) for j in range(n_stages)]
    total_steps = n_micro + n_stages - 1

    fn = jax.checkpoint(stage_fn) if remat else stage_fn

    mb_shape = x_micro.shape[1:]
    state = jnp.zeros(mb_shape, x_micro.dtype)
    outputs = jnp.zeros((n_micro,) + mb_shape, x_micro.dtype)

    def step(carry, t):
        state, outputs = carry
        # Stage 0 injects microbatch t (clamped; masked when t >= n_micro).
        inject = x_micro[jnp.minimum(t, n_micro - 1)]
        state = jnp.where(idx == 0, inject, state)
        out = fn(params, state)
        # Last stage records finished microbatch (t - (n_stages-1)).
        widx = t - (n_stages - 1)
        valid = jnp.logical_and(idx == n_stages - 1, widx >= 0)
        upd = jax.lax.dynamic_update_slice(
            outputs, out[None].astype(outputs.dtype),
            (jnp.maximum(widx, 0),) + (0,) * len(mb_shape))
        outputs = jnp.where(valid, upd, outputs)
        state = jax.lax.ppermute(out, axis_name, perm)
        return (state, outputs), None

    (state, outputs), _ = jax.lax.scan(step, (state, outputs),
                                       jnp.arange(total_steps))
    # Only the last stage holds real outputs; broadcast them along the ring
    # so the result is replicated over `pipe`.
    mask = (idx == n_stages - 1).astype(outputs.dtype)
    return jax.lax.psum(outputs * mask, axis_name)


def pipeline_apply(stage_fn: Callable[[Any, jax.Array], jax.Array],
                   stacked_params: Any, x_micro: jax.Array, mesh,
                   axis: str = "pipe", remat: bool = True) -> jax.Array:
    """Run `stage_fn` as an n-stage pipeline over the mesh's `pipe` axis.

    stage_fn(params_i, x: [mb, ...]) -> [mb, ...]
    stacked_params: pytree with leading stage axis == mesh.shape[axis]
    x_micro: [n_micro, mb, ...] microbatched input
    Returns [n_micro, mb, ...] outputs (replicated over `pipe`).
    """
    from jax.sharding import PartitionSpec as P

    if axis not in mesh.axis_names:
        # No pipe axis: run stages sequentially (single-device fallback).
        n_stages = jax.tree_util.tree_leaves(stacked_params)[0].shape[0]

        def seq(x):
            for i in range(n_stages):
                p_i = jax.tree_util.tree_map(lambda p: p[i], stacked_params)
                x = stage_fn(p_i, x)
            return x

        return jax.vmap(seq)(x_micro)

    n_stages = mesh.shape[axis]
    n_micro = x_micro.shape[0]
    param_spec = jax.tree_util.tree_map(lambda _: P(axis), stacked_params)
    body = functools.partial(_pipeline_body, stage_fn=stage_fn,
                             axis_name=axis, n_stages=n_stages,
                             n_micro=n_micro, remat=remat)
    return jax.shard_map(body, mesh=mesh,
                         in_specs=(param_spec, P()), out_specs=P(),
                         check_vma=False)(stacked_params, x_micro)


def microbatch(x: jax.Array, n_micro: int) -> jax.Array:
    """[B, ...] → [n_micro, B/n_micro, ...]"""
    if x.shape[0] % n_micro:
        raise ValueError(f"batch {x.shape[0]} not divisible by {n_micro}")
    return x.reshape((n_micro, x.shape[0] // n_micro) + x.shape[1:])
