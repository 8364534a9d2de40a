"""Mixture-of-Experts: top-k routing + expert-parallel dispatch/combine.

Net-new TPU scope (SURVEY §2.4 EP row — the reference has no MoE or expert
parallelism; its substrate is just placement groups + collectives).  Two
interchangeable formulations of the same math:

- ``moe_apply`` — dense dispatch/combine einsums (GShard/Switch style with
  static capacity).  Pure jnp: runs anywhere under jit, and under pjit the
  one-hot dispatch einsums partition cleanly when the expert dim of the
  weights is sharded over the ``expert`` mesh axis (XLA inserts the
  all_to_all itself — the GSPMD-idiomatic path).
- ``moe_apply_expert_parallel`` — explicit shard_map version: tokens are
  sharded over the ``expert`` axis, dispatch runs locally, and
  ``lax.all_to_all`` exchanges token groups so each device computes only
  its resident experts.  Byte-equivalent to running ``moe_apply`` on each
  token shard (tests/test_moe.py asserts this on an 8-device CPU mesh).

Routing is top-k with probabilities renormalized over the selected experts
and a static per-expert capacity ``C = ceil(k * N * capacity_factor / E)``;
overflowing tokens drop (standard Switch semantics — the residual stream
carries them unchanged).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    num_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 1.25
    dtype: Any = jnp.bfloat16

    def capacity(self, num_tokens: int) -> int:
        import math

        return max(1, int(math.ceil(
            self.top_k * num_tokens * self.capacity_factor
            / self.num_experts)))


def router_probs(x: jax.Array, w_router: jax.Array):
    """x: [N, d] tokens, w_router: [d, E] → (probs [N, E] fp32)."""
    logits = jnp.einsum("nd,de->ne", x.astype(jnp.float32),
                        w_router.astype(jnp.float32))
    return jax.nn.softmax(logits, axis=-1)


def dispatch_combine_masks(probs: jax.Array, cfg: MoEConfig, capacity: int):
    """Top-k dispatch (one-hot [N, E, C]) + combine weights [N, E, C].

    Position-in-expert bookkeeping follows the GShard construction: for
    each of the k choices in priority order, a token takes the next free
    slot of its expert; tokens past capacity drop.
    """
    n, e = probs.shape
    top_p, top_i = lax.top_k(probs, cfg.top_k)              # [N, k]
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

    dispatch = jnp.zeros((n, e, capacity), probs.dtype)
    combine = jnp.zeros((n, e, capacity), probs.dtype)
    # Slots already taken per expert, accumulated across the k passes.
    base = jnp.zeros((e,), jnp.int32)
    for j in range(cfg.top_k):
        onehot = jax.nn.one_hot(top_i[:, j], e, dtype=jnp.int32)  # [N, E]
        pos = jnp.cumsum(onehot, axis=0) - 1 + base[None, :]      # [N, E]
        pos_t = jnp.sum(pos * onehot, axis=1)                     # [N]
        keep = pos_t < capacity
        slot = jax.nn.one_hot(pos_t, capacity, dtype=probs.dtype)
        d_j = (onehot.astype(probs.dtype)[:, :, None] * slot[:, None, :])
        d_j = d_j * keep[:, None, None].astype(probs.dtype)
        dispatch = dispatch + d_j
        combine = combine + d_j * top_p[:, j][:, None, None]
        base = base + jnp.sum(onehot, axis=0)
    return dispatch, combine


def moe_ffn(expert_inputs: jax.Array, w_in: jax.Array, w_out: jax.Array,
            act=jax.nn.gelu) -> jax.Array:
    """Per-expert MLP. expert_inputs [E, C, d], w_in [E, d, f], w_out
    [E, f, d] → [E, C, d]."""
    h = act(jnp.einsum("ecd,edf->ecf", expert_inputs, w_in))
    return jnp.einsum("ecf,efd->ecd", h, w_out)


def moe_apply(x: jax.Array, w_router, w_in, w_out, cfg: MoEConfig,
              capacity: Optional[int] = None) -> jax.Array:
    """Dense-dispatch MoE on a flat token batch x [N, d] → [N, d]."""
    n = x.shape[0]
    capacity = capacity or cfg.capacity(n)
    probs = router_probs(x, w_router)
    dispatch, combine = dispatch_combine_masks(probs, cfg, capacity)
    expert_inputs = jnp.einsum("nec,nd->ecd", dispatch.astype(x.dtype), x)
    out = moe_ffn(expert_inputs, w_in.astype(x.dtype), w_out.astype(x.dtype))
    return jnp.einsum("nec,ecd->nd", combine.astype(x.dtype), out)


def moe_apply_expert_parallel(x, w_router, w_in_local, w_out_local,
                              cfg: MoEConfig, capacity: int,
                              axis_name: str = "expert") -> jax.Array:
    """shard_map body: explicit all_to_all dispatch/combine.

    Runs per-device with x [N_local, d] (tokens sharded over `axis_name`),
    w_in_local/w_out_local [E_local, d, f]/[E_local, f, d] (experts sharded
    over the same axis), w_router replicated.  Semantics == moe_apply on
    each token shard with the full expert set.
    """
    ep = lax.psum(1, axis_name)
    probs = router_probs(x, w_router)
    dispatch, combine = dispatch_combine_masks(probs, cfg, capacity)
    # Local token→expert groups: [E, C, d].
    expert_inputs = jnp.einsum("nec,nd->ecd", dispatch.astype(x.dtype), x)
    # all_to_all: trade expert groups so each device holds ITS experts'
    # tokens from every peer: [E, C, d] → [E/ep, ep*C, d].
    expert_inputs = lax.all_to_all(expert_inputs, axis_name,
                                   split_axis=0, concat_axis=1, tiled=True)
    out = moe_ffn(expert_inputs, w_in_local.astype(x.dtype),
                  w_out_local.astype(x.dtype))
    # Inverse all_to_all: send results back to the owning token shards.
    out = lax.all_to_all(out, axis_name, split_axis=1, concat_axis=0,
                         tiled=True)
    return jnp.einsum("nec,ecd->nd", combine.astype(x.dtype), out)


def make_expert_parallel_moe(mesh, cfg: MoEConfig, num_tokens_per_shard: int,
                             axis_name: str = "expert"):
    """Wraps moe_apply_expert_parallel in shard_map over `mesh`.

    Returns fn(x, w_router, w_in, w_out) with x [N, d] sharded over
    `axis_name` on dim 0 and the expert dim of w_in/w_out sharded over the
    same axis; w_router replicated."""
    from jax.sharding import PartitionSpec as P

    capacity = cfg.capacity(num_tokens_per_shard)
    body = functools.partial(moe_apply_expert_parallel, cfg=cfg,
                             capacity=capacity, axis_name=axis_name)
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(axis_name, None), P(), P(axis_name, None, None),
                  P(axis_name, None, None)),
        out_specs=P(axis_name, None))


def init_moe_params(key, d_model: int, d_ff: int, cfg: MoEConfig):
    k1, k2, k3 = jax.random.split(key, 3)
    scale = 0.02
    return {
        "w_router": jax.random.normal(k1, (d_model, cfg.num_experts),
                                      jnp.float32) * scale,
        "w_in": jax.random.normal(k2, (cfg.num_experts, d_model, d_ff),
                                  jnp.float32) * scale,
        "w_out": jax.random.normal(k3, (cfg.num_experts, d_ff, d_model),
                                   jnp.float32) * scale,
    }
