"""Mixture-of-Experts: top-k routing, and two ways to run the experts.

Net-new TPU scope (SURVEY §2.4 EP row — the reference has no MoE or expert
parallelism; its substrate is just placement groups + collectives).

**Dropless** (``moe_dropless`` = ``route_topk`` + ``experts_dropless``):
every token reaches every expert it chose, whatever the load; SwiGLU
experts; the softmax over all experts, the chosen weights renormalised only
on request.  What a published sparse model computes, so what serving runs:
``models/llama.py`` with ``num_experts`` set (OLMoE through ``LLMServer``).
Two exact forms, chosen from the static row count alone:

- up to ``DENSE_MAX_ROWS`` rows (a decode step: one row a slot; the shorter
  prefill buckets) the Pallas kernel ``moe_hit`` runs over a list, made in
  the same program, of the experts that some live row chose, and fetches
  those experts' weights and no other's.  A decode step is bound by the
  weights it streams, and few rows choose few experts: 4 live rows of
  OLMoE's choose 8 of 64 each and hit ~29 of them.  The caller may say
  which rows are live (``active``: a free slot of the serve engine chooses
  nothing).  With every expert hit it is one pass over all the weights;
- above, the rows are sorted by expert and multiplied group by group
  (``lax.ragged_dot``, which the TPU compiler turns into a grouped-matmul
  kernel of its own).

**A share of the experts** (``route_sigmoid_topk`` + ``experts_held_relu2``:
``models/nemotron_h.py``'s latent expert layer).  The router is a float32
sigmoid over ALL experts of the layer that chooses by score plus a bias
and weighs by the score; the experts have two matrices and ``relu^2``.  The
op is told which experts it holds (H of them from ``expert_offset`` on: one
chip's share under expert parallelism), keeps the choices that land on
them and computes their part of the result; the other chips' parts add to
it.  The same two forms: ``moe_hit_relu2`` (``moe_hit``'s grid and block
look-up, shared in ``_hit_call``) over the held experts that a live row
hit, and the grouped form over the held experts, the choices that landed
elsewhere sorted last and in no group.  ``route_group_sigmoid_topk`` +
``experts_held_swiglu`` (``models/ling_linear.py``) are the same share for a
router limited to groups of experts and SwiGLU experts of three matrices,
through ``moe_hit``.

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
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


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

# Rows up to which the experts run through ``moe_hit``, the kernel that
# fetches only the experts some live row chose; above, the rows are grouped.
# One OLMoE layer (64 experts of 2048 x 1024, top-8) on the v5e, ms
# (PERF.md, PR 42): ``moe_hit`` at 16 rows 0.158 with 8 experts hit, 0.289
# with 16, 0.617 with 36, 0.814 with 48, 1.075 with all 64 (0.03 + 0.0164
# an expert: its 12.6 MB at 767 GB/s); with all 64 hit 1.08 at 32, 64 and
# 128 rows, 1.12 at 256, 2.15 at 512, where an expert's three matmuls
# take longer than its weights' copy.  The grouped kernel works in tiles
# of 512 rows an expert: 2.6-2.8 up to 256 rows, 3.10 at 512, 3.74 at 1024
# (PR 27).  Until PR 42 the rows up to here computed every expert on every
# row and masked, one pass over all the weights whatever was hit: 1.17 up
# to 64 rows, 1.30 at 128, 1.45 at 256, 2.43 at 512; that form is now
# ``tests/test_moe.py``'s reference.
DENSE_MAX_ROWS = 512

# Bytes of one weight tile of a ``moe_hit`` grid step (hidden x a tile of
# the expert's width).  A step holds three, twice over (the pipeline
# fetches the next step's while this one's are multiplied): 12.6 MB of VMEM
# at OLMoE's hidden 2048, in tiles of 512 columns.  With all 64 hit, tiles
# of 256 columns read 1.150 ms, 512 read 1.079, the whole 1024 read 1.080.
HIT_TILE_BYTES = 2 << 20
_SUBLANES = 16  # rows of a bfloat16 tile: x and the accumulator are padded


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


def _sigmoid_scores(x: jax.Array, w_router: jax.Array) -> jax.Array:
    """sigmoid(x W_r) in fp32 over ALL experts, [N, E].  float32 in
    earnest: at the default precision the chip would round both operands
    to bfloat16, and the last chosen and the first unchosen score lie
    close."""
    return jax.nn.sigmoid(jnp.einsum(
        "nd,de->ne", x.astype(jnp.float32), w_router.astype(jnp.float32),
        precision=lax.Precision.HIGHEST))


def _score_weights(scores, top_i, norm_topk_prob: bool, scaling: float):
    """The chosen experts' weights: the score at each, divided by their sum
    where ``norm_topk_prob``, times ``scaling``."""
    top_s = jnp.take_along_axis(scores, top_i, axis=-1)
    if norm_topk_prob:
        top_s = top_s / (jnp.sum(top_s, axis=-1, keepdims=True) + 1e-20)
    return top_s * scaling, top_i.astype(jnp.int32)


def route_sigmoid_topk(x: jax.Array, w_router: jax.Array, bias: jax.Array,
                       top_k: int, norm_topk_prob: bool, scaling: float):
    """x [N, d], w_router [d, E], bias [E] → (weights [N, k] fp32, experts
    [N, k] int32): ``s = sigmoid(x W_r)`` in fp32 over ALL experts; the k
    largest of ``s + bias`` are chosen (the bias steers the choice and
    weighs nothing: ``e_score_correction_bias``); the weights are ``s`` at
    the chosen, divided by their sum where ``norm_topk_prob``, times
    ``scaling``.  A limit on groups of experts with one group is no
    limit, and is not here (``route_group_sigmoid_topk`` has it)."""
    scores = _sigmoid_scores(x, w_router)
    _, top_i = lax.top_k(scores + bias.astype(jnp.float32), top_k)
    return _score_weights(scores, top_i, norm_topk_prob, scaling)


def route_group_sigmoid_topk(x: jax.Array, w_router: jax.Array,
                             bias: jax.Array, top_k: int, n_group: int,
                             topk_group: int, norm_topk_prob: bool,
                             scaling: float):
    """``route_sigmoid_topk`` with DeepSeek-V3's limit on groups: the E
    experts are ``n_group`` groups of neighbours; a group's score is the sum
    of its 2 largest ``s + bias``; the ``topk_group`` best groups are kept
    and the ``top_k`` largest ``s + bias`` inside them chosen.  A token's
    experts then lie in at most ``topk_group`` groups (under expert
    parallelism: on that share of the chips).  Weights as there."""
    scores = _sigmoid_scores(x, w_router)
    n, e = scores.shape
    grouped = (scores + bias.astype(jnp.float32)).reshape(
        n, n_group, e // n_group)
    group_score = jnp.sum(lax.top_k(grouped, 2)[0], axis=-1)
    _, best = lax.top_k(group_score, topk_group)
    kept = jnp.any(best[:, :, None] == jnp.arange(n_group), axis=1)
    _, top_i = lax.top_k(jnp.where(
        kept[:, :, None], grouped, -jnp.inf).reshape(n, e), top_k)
    return _score_weights(scores, top_i, norm_topk_prob, scaling)


def expert_rows(experts: jax.Array, num_experts: int) -> jax.Array:
    """experts [..., k] → rows assigned to each expert, [E] int32."""
    return jnp.zeros((num_experts,), jnp.int32).at[
        experts.reshape(-1)].add(1)


def hit_order(chosen: jax.Array):
    """chosen [N, E] bool (row n chose expert e, and is live) → (order [E]
    int32, n_hit [1] int32): the experts some row chose, ascending, in the
    first ``n_hit`` places of ``order`` and zeros behind them.  Compares
    and sums over [E, E], no sort and no scatter."""
    e = chosen.shape[1]
    hit = jnp.any(chosen, axis=0)
    place = jnp.cumsum(hit) - 1  # a hit expert's position in the order
    ids = jnp.arange(e, dtype=jnp.int32)
    at = hit[None, :] & (place[None, :] == ids[:, None])  # [position, e]
    order = jnp.sum(jnp.where(at, ids[None, :], 0), axis=1)
    return order.astype(jnp.int32), jnp.sum(hit, dtype=jnp.int32)[None]


def _moe_hit_kernel(order_ref, n_hit_ref,  # SMEM
                    x_ref, combine_ref, w_gate_ref, w_up_ref, w_down_ref,
                    out_ref):
    """Grid step (p, j): tile j of the width of expert ``order[p]``.
    x_ref [N, d] and combine_ref [N, E] whole; w_gate_ref / w_up_ref
    [d, tile], w_down_ref [tile, d] of that expert; out_ref [N, d] float32,
    the same block at every step: the accumulator, written back once."""
    p, j = pl.program_id(0), pl.program_id(1)

    @pl.when((p == 0) & (j == 0))
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(p < n_hit_ref[0])  # past the list: nothing fetched, nothing done
    def _():
        x = x_ref[...]
        f32 = jnp.float32
        g = jnp.dot(x, w_gate_ref[...], preferred_element_type=f32)
        u = jnp.dot(x, w_up_ref[...], preferred_element_type=f32)
        # This expert's column of the combine weights, [N, 1].
        lane = lax.broadcasted_iota(jnp.int32, combine_ref.shape, 1)
        c = jnp.sum(jnp.where(lane == order_ref[p], combine_ref[...], 0.0),
                    axis=1, keepdims=True)
        h = (g * jax.nn.sigmoid(g) * u * c).astype(x.dtype)
        out_ref[...] += jnp.dot(h, w_down_ref[...],
                                preferred_element_type=f32)


def _tile_of(f: int, most: int) -> int:
    """The widest tile of whole 128-lane registers that divides ``f`` and
    is at most ``most`` (or is one register); the whole width where there
    is none."""
    for tile in range(min(max(most, 128), f) // 128 * 128, 0, -128):
        if f % tile == 0:
            return tile
    return f


def _moe_hit_relu2_kernel(order_ref, n_hit_ref,  # SMEM
                          x_ref, combine_ref, w_up_ref, w_down_ref, out_ref):
    """``_moe_hit_kernel`` for experts of two matrices and no gate:
    relu(x Wu)^2 in place of silu(x Wg) * (x Wu).  ``order`` and the
    columns of ``combine_ref`` count the experts held here."""
    p, j = pl.program_id(0), pl.program_id(1)

    @pl.when((p == 0) & (j == 0))
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(p < n_hit_ref[0])
    def _():
        x = x_ref[...]
        f32 = jnp.float32
        u = jnp.maximum(
            jnp.dot(x, w_up_ref[...], preferred_element_type=f32), 0.0)
        lane = lax.broadcasted_iota(jnp.int32, combine_ref.shape, 1)
        c = jnp.sum(jnp.where(lane == order_ref[p], combine_ref[...], 0.0),
                    axis=1, keepdims=True)
        h = (u * u * c).astype(x.dtype)
        out_ref[...] += jnp.dot(h, w_down_ref[...],
                                preferred_element_type=f32)


def _hit_call(kernel, name: str, x: jax.Array, combine: jax.Array,
              order: jax.Array, n_hit: jax.Array, w_ins: tuple,
              w_down: jax.Array) -> jax.Array:
    """``kernel`` over the grid (place in ``order``, tile of the experts'
    width): ``w_ins`` are the experts' [E, d, f] matrices (gate and up, or
    up alone), ``w_down`` [E, f, d].  The block of the weights a grid step
    works on is looked up in ``order``, so the pipeline copies the listed
    experts' tiles from HBM and no other's; places at or past ``n_hit``
    name the last real step's block again, which is not fetched twice, and
    compute nothing."""
    n, d = x.shape
    e, f = w_down.shape[:2]
    size = x.dtype.itemsize
    tile = _tile_of(f, HIT_TILE_BYTES // (d * size))
    tiles = f // tile
    pad = -n % _SUBLANES
    x = jnp.pad(x, ((0, pad), (0, 0)))
    combine = jnp.pad(combine, ((0, pad), (0, 0)))
    rows = n + pad
    mats = len(w_ins) + 1

    def block(p, j, order_ref, n_hit_ref):
        last = jnp.maximum(n_hit_ref[0], 1) - 1
        return (order_ref[jnp.minimum(p, last)],
                jnp.where(p < n_hit_ref[0], j, tiles - 1))

    def up_block(p, j, order_ref, n_hit_ref):
        expert, col = block(p, j, order_ref, n_hit_ref)
        return expert, 0, col

    def down_block(p, j, order_ref, n_hit_ref):
        expert, row = block(p, j, order_ref, n_hit_ref)
        return expert, row, 0

    whole = lambda shape: pl.BlockSpec(  # noqa: E731
        shape, lambda p, j, *_: (0, 0))
    # Two buffers a weight tile; x, combine and the accumulator; one
    # [rows, tile] float32 intermediate a matrix (g, u and h).
    vmem = (2 * mats * d * tile * size + 2 * rows * (d * (size + 4) + e * 4)
            + mats * rows * tile * 4)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(e, tiles),
            in_specs=[whole((rows, d)), whole((rows, e)),
                      *[pl.BlockSpec((None, d, tile), up_block)
                        for _ in w_ins],
                      pl.BlockSpec((None, tile, d), down_block)],
            out_specs=whole((rows, d))),
        out_shape=jax.ShapeDtypeStruct((rows, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem + (8 << 20)),
        name=name,
        interpret=jax.default_backend() == "cpu",
    )(order, n_hit, x, combine.astype(jnp.float32), *w_ins, w_down)
    return out[:n]


def moe_hit(x: jax.Array, combine: jax.Array, order: jax.Array,
            n_hit: jax.Array, w_gate: jax.Array, w_up: jax.Array,
            w_down: jax.Array) -> jax.Array:
    """sum over the first ``n_hit`` experts e of ``order`` of
    (silu(x Wg[e]) * (x Wu[e]) * combine[:, e]) Wd[e] → [N, d] float32
    (``_hit_call``)."""
    return _hit_call(_moe_hit_kernel, "moe_hit", x, combine, order, n_hit,
                     (w_gate, w_up), w_down)


def moe_hit_relu2(x: jax.Array, combine: jax.Array, order: jax.Array,
                  n_hit: jax.Array, w_up: jax.Array,
                  w_down: jax.Array) -> jax.Array:
    """sum over the first ``n_hit`` experts e of ``order`` of
    (relu(x Wu[e])^2 * combine[:, e]) Wd[e] → [N, d] float32: ``moe_hit``
    for experts of two matrices (``_hit_call``)."""
    return _hit_call(_moe_hit_relu2_kernel, "moe_hit_relu2", x, combine,
                     order, n_hit, (w_up,), w_down)


def experts_dropless(x: jax.Array, weights: jax.Array, experts: jax.Array,
                     w_gate: jax.Array, w_up: jax.Array, w_down: jax.Array,
                     active: Optional[jax.Array] = None):
    """sum_j weights[n, j] * down_e(silu(gate_e(x_n)) * up_e(x_n)) with
    e = experts[n, j]: x [N, d], w_gate / w_up [E, d, f], w_down [E, f, d]
    → ([N, d] in x's dtype, the experts whose weights were streamed, int32).
    Products in x's dtype, sums in fp32.  ``active`` [N] bool (default: all)
    marks the live rows: any other row weighs nothing, gets zeros and
    counts for no expert.  N (a static shape) picks the form; both are
    exact, no token is dropped."""
    n, d = x.shape
    e, k = w_gate.shape[0], experts.shape[1]
    f32 = jnp.float32
    w_gate, w_up, w_down = (w.astype(x.dtype) for w in (w_gate, w_up, w_down))
    if active is not None:
        weights = jnp.where(active[:, None], weights, 0.0)
    if n <= DENSE_MAX_ROWS:
        # [N, E] combine weights, zero where an expert was not chosen.
        chose = experts[:, :, None] == jnp.arange(e, dtype=experts.dtype)
        combine = jnp.sum(jnp.where(chose, weights[:, :, None], 0.0), axis=1)
        chosen = jnp.any(chose, axis=1)
        if active is not None:
            chosen &= active[:, None]
        order, n_hit = hit_order(chosen)
        out = moe_hit(x, combine, order, n_hit, w_gate, w_up, w_down)
        return out.astype(x.dtype), n_hit[0]
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
    # Which tiles the compiler's grouped kernel fetches for an expert with
    # no row has not been looked at: counted as every expert.
    return jnp.sum(y, axis=1).astype(x.dtype), jnp.asarray(e, jnp.int32)


def _experts_held(x, weights, experts, w_ins, w_down, expert_offset, active,
                  hit_kernel, hidden):
    """One chip's share of a dropless layer (``experts_held_relu2``,
    ``experts_held_swiglu``): ``w_ins`` the held experts' [H, d, f]
    matrices, ``hidden`` what an expert does with a row's products by them
    (float32), ``hit_kernel`` the ``_hit_call`` kernel that does the same
    over the held experts hit."""
    n, d = x.shape
    held, k = w_down.shape[0], experts.shape[1]
    f32 = jnp.float32
    w_ins = tuple(w.astype(x.dtype) for w in w_ins)
    w_down = w_down.astype(x.dtype)
    local = experts - expert_offset
    here = (local >= 0) & (local < held)
    if active is not None:
        here &= active[:, None]
    weights = jnp.where(here, weights, 0.0)
    landed = jnp.sum(here, dtype=jnp.int32)
    if n <= DENSE_MAX_ROWS:
        chose = here[:, :, None] & (
            local[:, :, None] == jnp.arange(held, dtype=local.dtype))
        combine = jnp.sum(jnp.where(chose, weights[:, :, None], 0.0), axis=1)
        order, n_hit = hit_order(jnp.any(chose, axis=1))
        out = hit_kernel(x, combine, order, n_hit, *w_ins, w_down)
        return out.astype(x.dtype), n_hit[0], landed
    # Sorted by held expert, the choices that landed elsewhere last and in
    # no group: the grouped products leave their rows alone.
    key = jnp.where(here, local, held).reshape(-1)
    order = jnp.argsort(key, stable=True)
    sizes = expert_rows(key, held + 1)[:held]
    xs = x[order // k]
    mid = hidden(*[lax.ragged_dot(xs, w, sizes, preferred_element_type=f32)
                   for w in w_ins])
    y = lax.ragged_dot(mid.astype(x.dtype), w_down, sizes,
                       preferred_element_type=f32)
    back = jnp.zeros_like(order).at[order].set(
        jnp.arange(n * k, dtype=order.dtype))
    # A row of no group holds whatever the grouped kernel left there.
    y = jnp.where(here[:, :, None],
                  y[back].reshape(n, k, d) * weights[:, :, None], 0.0)
    return (jnp.sum(y, axis=1).astype(x.dtype), jnp.asarray(held, jnp.int32),
            landed)


def _relu2(u):
    u = jnp.maximum(u, 0.0)
    return u * u


def experts_held_relu2(x: jax.Array, weights: jax.Array, experts: jax.Array,
                       w_up: jax.Array, w_down: jax.Array,
                       expert_offset: int = 0,
                       active: Optional[jax.Array] = None):
    """One chip's share of a dropless layer of two-matrix experts:
    sum over the choices j of row n that land on an expert held here
    (``expert_offset <= experts[n, j] < expert_offset + H``) of
    weights[n, j] * relu(x_n Wu[e])^2 Wd[e], e counted from
    ``expert_offset``: x [N, d], ``experts`` [N, k] ids over ALL experts of
    the layer, w_up [H, d, f], w_down [H, f, d] the H held
    → ([N, d] in x's dtype, held experts whose weights were streamed, the
    live rows' choices that landed here; both int32).  What the absent
    experts would add is left out: the shares of all chips add up to the
    whole layer.  ``active`` and the two exact forms are
    ``experts_dropless``'s; no capacity, no choice of a held expert is
    dropped."""
    return _experts_held(x, weights, experts, (w_up,), w_down, expert_offset,
                         active, moe_hit_relu2, _relu2)


def experts_held_swiglu(x: jax.Array, weights: jax.Array, experts: jax.Array,
                        w_gate: jax.Array, w_up: jax.Array, w_down: jax.Array,
                        expert_offset: int = 0,
                        active: Optional[jax.Array] = None):
    """``experts_held_relu2`` for SwiGLU experts of three matrices
    (``experts_dropless``'s, told which it holds): the choices that land
    here weigh ``silu(x Wg[e]) * (x Wu[e])`` through ``Wd[e]``; up to
    ``DENSE_MAX_ROWS`` rows through ``moe_hit`` over the held experts
    hit."""
    return _experts_held(
        x, weights, experts, (w_gate, w_up), w_down, expert_offset, active,
        moe_hit, lambda g, u: jax.nn.silu(g) * u)


def moe_dropless(x: jax.Array, w_router: jax.Array, w_gate: jax.Array,
                 w_up: jax.Array, w_down: jax.Array, top_k: int,
                 norm_topk_prob: bool = False):
    """Dropless top-k MoE on a flat token batch: x [N, d] → ([N, d], rows
    per expert [E] int32)."""
    weights, experts = route_topk(x, w_router, top_k, norm_topk_prob)
    out, _ = experts_dropless(x, weights, experts, w_gate, w_up, w_down)
    return out, expert_rows(experts, w_router.shape[1])
