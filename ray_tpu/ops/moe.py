"""Mixture-of-Experts: top-k routing, and two ways to run the experts.

Net-new TPU scope (SURVEY §2.4 EP row — the reference has no MoE or expert
parallelism; its substrate is just placement groups + collectives).

**Dropless** (``moe_dropless`` = ``route_topk`` + ``experts_dropless``):
every token reaches every expert it chose, whatever the load; SwiGLU
experts; the softmax over all experts, the chosen weights renormalised only
on request.  What a published sparse model computes, so what serving runs:
``models/llama.py`` with ``num_experts`` set (OLMoE through ``LLMServer``).
Two exact forms, chosen from the static row count alone: few rows compute
every expert on every row and mask (a decode step is bound by the weights
it streams, and the masked einsum is one pass over them); many rows are
sorted by expert and multiplied group by group (``lax.ragged_dot``, which
the TPU compiler turns into a grouped-matmul kernel of its own).

**Capacity-factor** (``moe_apply`` and its expert-parallel twin
``moe_apply_expert_parallel``): GShard/Switch dense dispatch/combine
einsums with a static per-expert capacity ``C = ceil(k * N *
capacity_factor / E)``; overflowing tokens DROP (the residual stream
carries them unchanged), weights renormalised over the chosen, two-matrix
GELU experts.  Used by ``GPT2Config.moe_tiny`` (``models/gpt2.py``), the
multichip dry run and ``tests/test_moe.py``.  Under pjit the one-hot
einsums partition cleanly when the expert dim of the weights is sharded
over the ``expert`` mesh axis; the ``shard_map`` twin makes the
``all_to_all`` explicit and is byte-equivalent on each token shard.  It
goes when a training cell takes the dropless op over the ``expert`` axis
(ROADMAP D3).
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


# ---------------------------------------------------------------------------
# Dropless MoE (serving: logits must equal the reference's, so no capacity)
# ---------------------------------------------------------------------------

# Rows up to which every expert is computed on every row.  The masked form
# does E/k times the needed arithmetic but reads each weight once with no
# sort; the grouped kernel works in tiles of 512 rows an expert, so for few
# rows it multiplies more padding than the masked form multiplies zeros.
# One OLMoE layer (64 experts of 2048 x 1024, top-8) on the v5e, ms: masked
# 1.25 up to 256 rows (the weights' stream), 2.33 at 512, 4.53 at 1024;
# grouped 2.6-2.8 up to 256, 3.10 at 512, 3.74 at 1024 (PERF.md, PR 27).
DENSE_MAX_ROWS = 512


def route_topk(x: jax.Array, w_router: jax.Array, top_k: int,
               norm_topk_prob: bool = False):
    """x [N, d], w_router [d, E] → (weights [N, k] fp32, experts [N, k]
    int32): softmax in fp32 over ALL experts, then the k largest.  The
    weights are the softmax's own values (they sum to under 1) unless
    ``norm_topk_prob`` rescales them to sum to 1."""
    top_p, top_i = lax.top_k(router_probs(x, w_router), top_k)
    if norm_topk_prob:
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    return top_p, top_i.astype(jnp.int32)


def expert_rows(experts: jax.Array, num_experts: int) -> jax.Array:
    """experts [..., k] → rows assigned to each expert, [E] int32."""
    return jnp.zeros((num_experts,), jnp.int32).at[
        experts.reshape(-1)].add(1)


def experts_dropless(x: jax.Array, weights: jax.Array, experts: jax.Array,
                     w_gate: jax.Array, w_up: jax.Array,
                     w_down: jax.Array) -> jax.Array:
    """sum_j weights[n, j] * down_e(silu(gate_e(x_n)) * up_e(x_n)) with
    e = experts[n, j]: x [N, d], w_gate / w_up [E, d, f], w_down [E, f, d]
    → [N, d] in x's dtype.  Products in x's dtype, sums in fp32.  N (a
    static shape) picks the form; both are exact, no token is dropped."""
    n, d = x.shape
    e, k = w_gate.shape[0], experts.shape[1]
    f32 = jnp.float32
    w_gate, w_up, w_down = (w.astype(x.dtype) for w in (w_gate, w_up, w_down))
    if n <= DENSE_MAX_ROWS:
        # [N, E] combine weights, zero where an expert was not chosen.
        combine = jnp.zeros((n, e), f32).at[
            jnp.arange(n)[:, None], experts].add(weights)
        g = jnp.einsum("nd,edf->enf", x, w_gate, preferred_element_type=f32)
        u = jnp.einsum("nd,edf->enf", x, w_up, preferred_element_type=f32)
        h = (jax.nn.silu(g) * u * combine.T[:, :, None]).astype(x.dtype)
        out = jnp.einsum("enf,efd->nd", h, w_down,
                         preferred_element_type=f32)
        return out.astype(x.dtype)
    # Sort the N*k (row, expert) assignments by expert; each expert then
    # owns one contiguous group of rows, of any size.
    order = jnp.argsort(experts.reshape(-1), stable=True)
    sizes = expert_rows(experts, e)
    xs = x[order // k]
    g = lax.ragged_dot(xs, w_gate, sizes, preferred_element_type=f32)
    u = lax.ragged_dot(xs, w_up, sizes, preferred_element_type=f32)
    y = lax.ragged_dot((jax.nn.silu(g) * u).astype(x.dtype), w_down, sizes,
                       preferred_element_type=f32)
    # Back to (row, choice) order by a gather, then the weighted sum.
    back = jnp.zeros_like(order).at[order].set(
        jnp.arange(n * k, dtype=order.dtype))
    y = y[back].reshape(n, k, d) * weights[:, :, None]
    return jnp.sum(y, axis=1).astype(x.dtype)


def moe_dropless(x: jax.Array, w_router: jax.Array, w_gate: jax.Array,
                 w_up: jax.Array, w_down: jax.Array, top_k: int,
                 norm_topk_prob: bool = False):
    """Dropless top-k MoE on a flat token batch: x [N, d] → ([N, d], rows
    per expert [E] int32)."""
    weights, experts = route_topk(x, w_router, top_k, norm_topk_prob)
    out = experts_dropless(x, weights, experts, w_gate, w_up, w_down)
    return out, expert_rows(experts, w_router.shape[1])
