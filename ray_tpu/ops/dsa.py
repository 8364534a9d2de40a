"""Learned sparse attention (DeepSeek sparse attention, DSA, as
DeepSeek-V3.2-Exp publishes it: ``inference/model.py``, class ``Indexer``):
a small *indexer* scores every earlier row for each query, the ``topk``
best are kept, and the layer's softmax runs over them alone.

    I[t, s] = sum_j w[t, j] * relu(q_idx[t, j] . k_idx[s])     (s <= t)
    S_t     = the min(topk, t + 1) rows s of largest I[t, s]
              (ties: the lower s, ``lax.top_k``'s rule)

one set a token a layer, shared by every head.  ``q_idx`` [J heads, D],
``k_idx`` [D] (ONE key a row, what a cache holds) and ``w`` [J] float32 are
the model's projections; the sums are float32.

Two forms, which select the same rows for the same inputs:

- ``dsa_prefill_attention``: a whole context.  Scores, the selection (an
  exact mask, ``topk_mask``) and the attention run in blocks of query rows:
  ``[L, L]`` float32 at 16k rows is 1 GB a layer, the per-head products 32
  times that.  A block's attention is the flash recurrence over the key
  blocks up to its causal frontier, masked by the selection; a row at
  position ``< topk`` keeps every earlier row, so there it is plain causal
  attention.  Dense in the work it does, exact in what it computes: a
  sparse kernel is a later matter (ROADMAP).
- ``sparse_paged_attention``: one new token a slot against the serve
  engine's page pool.  The slot's cached index keys are read through the
  page table where they lie (they ride the V row, ``ops/mla.py::
  index_rows``; the kernel ``dsa_index`` copies the key's columns of the
  live pages and no other byte) and scored, the new token's own with them,
  the ``topk`` best taken (a stable sort, ``lax.top_k``'s, that carries
  each row's place in the pool), and the selected rows, and no others,
  gathered BY ROW from the K pool: the first read of the pool at
  token granularity (``ops/paged_attention.py`` reads a contiguous run of
  pages).  The row is a latent row (``ops/mla.py``): its first ``rank``
  columns are the values.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.attention import NEG_INF

F32 = jnp.float32

# The selection over a context runs in blocks of this many query rows, whose
# flash recurrence takes this many key rows a step (a context shorter than a
# block is one block): shapes of this program, no published key.
BLOCK_Q = 256
BLOCK_K = 1024


def index_scores(q_idx: jax.Array, w_idx: jax.Array,
                 k_idx: jax.Array) -> jax.Array:
    """q_idx [..., T, J, D], w_idx [..., T, J] float32, k_idx [..., S, D] →
    I [..., T, S] float32: ``sum_j w[t, j] relu(q_idx[t, j] . k_idx[s])``.
    Products in the operands' dtype, every sum in float32.  Both forms call
    this and nothing else for a score, so that a row's scores, and with
    them its selection, do not depend on the form."""
    dots = jnp.einsum("...tjd,...sd->...tjs", q_idx, k_idx,
                      preferred_element_type=F32)
    return jnp.sum(jax.nn.relu(dots) * w_idx.astype(F32)[..., None], axis=-2)


def _ordered_keys(scores: jax.Array) -> jax.Array:
    """float32 → uint32 whose unsigned order is the floats' total order
    (``-0.0`` below ``0.0``, as a sort has it; no NaN): negatives have
    every bit flipped, the others their sign bit set."""
    bits = lax.bitcast_convert_type(scores, jnp.uint32)
    return bits ^ jnp.where(bits >> 31 == 1, jnp.uint32(0xFFFFFFFF),
                            jnp.uint32(0x80000000))


def kth_largest_key(keys: jax.Array, k: int) -> jax.Array:
    """keys [T, S] uint32 → [T, 1]: each row's k-th largest (k <= S), found
    bit by bit from the top: the largest value that at least k of the row
    reach.  32 passes that compare and count, where a sort of 16,384
    (value, index) pairs is a hundred that exchange them."""
    def bit(i, found):
        tried = found | (jnp.uint32(1) << (jnp.uint32(31) - i.astype(
            jnp.uint32)))
        enough = jnp.sum(keys >= tried, axis=-1, keepdims=True,
                         dtype=jnp.int32) >= k
        return jnp.where(enough, tried, found)

    return lax.fori_loop(0, 32, bit,
                         jnp.zeros(keys.shape[:-1] + (1,), jnp.uint32))


def topk_mask(scores: jax.Array, topk: int) -> jax.Array:
    """scores [T, S] float32, ``-inf`` where s is no candidate → bool
    [T, S]: the candidates among the ``topk`` largest of each row, ties at
    the last place to the lower s: the set ``lax.top_k`` returns the
    indices of, as a mask, with no sort and no scatter.  A row with
    ``topk`` candidates or fewer keeps them all."""
    k = min(topk, scores.shape[-1])
    keys = _ordered_keys(scores)
    last = kth_largest_key(keys, k)
    above = keys > last
    at = (keys == last) & (last > _ordered_keys(jnp.float32(-jnp.inf)))
    need = k - jnp.sum(above, axis=-1, keepdims=True, dtype=jnp.int32)
    # Rows at the last place's value beyond the places left: only then does
    # the order among them matter (a running count, which random scores
    # never pay for).
    tied = jnp.any(jnp.sum(at, axis=-1, keepdims=True, dtype=jnp.int32)
                   > need)
    at = lax.cond(
        tied, lambda: at & (jnp.cumsum(at, axis=-1, dtype=jnp.int32) <= need),
        lambda: at)
    return above | at


def _flash_step(q_nope, q_rope, kv_blk, kr_blk, mask, carry, sm_scale):
    """One key block of the flash recurrence for one sequence: q_nope
    [Q, H, N], q_rope [Q, H, P]; kv_blk [K, H, N + V] the block's rows
    expanded a head (``[k_nope | v]``), kr_blk [K, P] their rope key, which
    every head shares; mask [Q, K]; carry (o [Q, H, V], l and m [H, Q]),
    float32."""
    o, l, m = carry
    nope = q_nope.shape[-1]
    s = (jnp.einsum("qhd,khd->hqk", q_nope, kv_blk[..., :nope],
                    preferred_element_type=F32)
         + jnp.einsum("qhd,kd->hqk", q_rope, kr_blk,
                      preferred_element_type=F32)) * sm_scale
    s = jnp.where(mask[None], s, NEG_INF)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    corr = jnp.exp(m - m_new)
    p = jnp.where(mask[None], jnp.exp(s - m_new[..., None]), 0.0)
    l_new = l * corr + jnp.sum(p, axis=-1)
    pv = jnp.einsum("hqk,khv->qhv", p.astype(kv_blk.dtype),
                    kv_blk[..., nope:], preferred_element_type=F32)
    return o * corr.T[..., None] + pv, l_new, m_new


def _prefill_one(q_nope, q_rope, kv, k_rope, q_idx, w_idx, k_idx, first,
                 real, *, topk, sm_scale, keep):
    """``dsa_prefill_attention`` for one sequence (no batch dimension)."""
    n_q, h, nope = q_nope.shape
    length = kv.shape[0]
    vd = kv.shape[-1] - nope
    bq = min(BLOCK_Q, -(-n_q // 8) * 8)
    bk = max(bq, min(BLOCK_K, -(-length // bq) * bq) // bq * bq)
    padded = -(-length // bk) * bk
    q_rows = -(-n_q // bq) * bq

    def pad(a, rows):
        return jnp.pad(a, ((0, rows - a.shape[0]),)
                       + ((0, 0),) * (a.ndim - 1))

    q_nope, q_rope, q_idx, w_idx = (pad(a, q_rows) for a in (
        q_nope, q_rope, q_idx, w_idx))
    kv, k_rope, k_idx = (pad(a, padded) for a in (kv, k_rope, k_idx))
    cols = jnp.arange(padded)

    def scored(t0):
        """Query rows t0 .. t0 + bq: their index scores over every column,
        ``-inf`` above the diagonal."""
        take = lambda a: lax.dynamic_slice_in_dim(a, t0, bq)  # noqa: E731
        causal = cols[None] <= (first + t0 + jnp.arange(bq))[:, None]
        return jnp.where(causal, index_scores(
            take(q_idx), take(w_idx), k_idx), -jnp.inf)

    def attended(t0):
        chosen = topk_mask(scored(t0), topk)
        qn = lax.dynamic_slice_in_dim(q_nope, t0, bq)
        qr = lax.dynamic_slice_in_dim(q_rope, t0, bq)

        def k_block(j, carry):
            at = lambda a: lax.dynamic_slice_in_dim(  # noqa: E731
                a, j * bk, bk)
            return _flash_step(
                qn, qr, at(kv), at(k_rope),
                lax.dynamic_slice_in_dim(chosen, j * bk, bk, axis=1), carry,
                sm_scale)

        # key blocks up to the block's causal frontier, no further
        o, l, _ = lax.fori_loop(
            0, jnp.minimum((first + t0 + bq + bk - 1) // bk, padded // bk),
            k_block,
            (jnp.zeros((bq, h, vd), F32), jnp.zeros((h, bq), F32),
             jnp.full((h, bq), NEG_INF, F32)))
        out = (o / l.T[..., None]).astype(q_nope.dtype)
        return (out, chosen) if keep else out

    def q_block(i):
        t0 = i * bq
        if real is None:
            return attended(t0)
        # a block of nothing but padding is not computed (a bucket's rows
        # past the prompt: the blocks with the longest causal frontier)
        nothing = jnp.zeros((bq, h, vd), q_nope.dtype)
        return lax.cond(
            first + t0 < real, lambda: attended(t0),
            lambda: (nothing, jnp.zeros((bq, padded), bool)) if keep
            else nothing)

    got = lax.map(q_block, jnp.arange(q_rows // bq))
    if not keep:
        return got.reshape(q_rows, h, vd)[:n_q]
    out, chosen = got
    return (out.reshape(q_rows, h, vd)[:n_q],
            chosen.reshape(q_rows, padded)[:n_q, :length])


def dsa_prefill_attention(q_nope: jax.Array, q_rope: jax.Array,
                          kv: jax.Array, k_rope: jax.Array, q_idx: jax.Array,
                          w_idx: jax.Array, k_idx: jax.Array, topk: int,
                          sm_scale: float, first=0, real=None,
                          keep: bool = False):
    """Latent attention in its expanded form (``ops/mla.py``) over a whole
    context, every query row over its own ``S_t``: q_nope [B, Q, H, N],
    q_rope [B, Q, H, P] (rope applied) the queries of rows ``first ..
    first + Q`` of the context (``first`` may be traced: a caller that
    projects its queries a stretch of rows at a time calls once a stretch);
    kv [B, L, H, N + V] every row expanded a head (``[k_nope | v]``, as
    ``W_kvb`` gives them: neither half is copied out of it), k_rope [B, L,
    P] the rope key all heads share; q_idx [B, Q, J, D], w_idx [B, Q, J]
    float32, k_idx [B, L, D] → [B, Q, H, V] in q's dtype.  ``real`` (a
    scalar, may be traced): the context's rows from there on are padding,
    in every sequence; a block of query rows that holds nothing else is
    skipped and comes back as zeros.  ``keep``, for a comparison: also the
    selection [B, Q, L] bool."""
    one = functools.partial(_prefill_one, first=first, real=real, topk=topk,
                            sm_scale=sm_scale, keep=keep)
    return jax.vmap(one)(q_nope, q_rope, kv, k_rope, q_idx, w_idx, k_idx)


# Pages of index keys copied and scored per step of the kernel's loop: a
# 16-token page's keys are 4 KB; 32 of them are 512 rows, four MXU passes.
INDEX_PAGES_PER_STEP = 32


def _index_kernel(layer_ref, table_ref, lengths_ref,  # SMEM
                  q_ref, w_ref, v_hbm, out_ref, k_buf, sems, *, page_size,
                  pages_per_step, first_col, width):
    """One slot: the index scores of its cached rows.  q_ref [J, D] the new
    token's index queries, w_ref [J, 1] float32 their weights, v_hbm the
    whole V pool, left in HBM; out_ref [1, ctx] float32, ``-inf`` from the
    slot's length on; k_buf [2, pages_per_step * page_size, D] VMEM."""
    slot = pl.program_id(0)
    layer = layer_ref[0]
    rows = lengths_ref[slot]
    n_pages = (rows + page_size - 1) // page_size
    n_steps = (n_pages + pages_per_step - 1) // pages_per_step
    span = pages_per_step * page_size

    def for_live_pages(step, buf, what):
        # a page past the slot's length is neither started nor waited for
        for i in range(pages_per_step):
            @pl.when(step * pages_per_step + i < n_pages)
            def _():
                page = table_ref[slot, step * pages_per_step + i]
                what(pltpu.make_async_copy(
                    v_hbm.at[layer, page, :, pl.ds(first_col, width)],
                    k_buf.at[buf, pl.ds(i * page_size, page_size)],
                    sems.at[buf]))

    out_ref[...] = jnp.full(out_ref.shape, -jnp.inf, out_ref.dtype)

    @pl.when(n_steps > 0)
    def _():
        for_live_pages(0, 0, lambda c: c.start())

    q, w = q_ref[...], w_ref[...]

    def body(step, _):
        buf = step % 2

        @pl.when(step + 1 < n_steps)
        def _():
            for_live_pages(step + 1, 1 - buf, lambda c: c.start())

        for_live_pages(step, buf, lambda c: c.wait())
        dots = lax.dot_general(q, k_buf[buf], (((1,), (1,)), ((), ())),
                               preferred_element_type=F32)   # [J, span]
        score = jnp.sum(jnp.maximum(dots, 0.0) * w, axis=0, keepdims=True)
        live = step * span + lax.broadcasted_iota(
            jnp.int32, score.shape, 1) < rows
        out_ref[:, pl.ds(pl.multiple_of(step * span, span), span)] = \
            jnp.where(live, score, -jnp.inf)
        return 0

    lax.fori_loop(0, n_steps, body, 0)


# jit of its own, with the layer as an argument, as ``_paged_attention``:
# a model's layers share one trace and one lowering of the kernel.
@functools.partial(jax.jit, static_argnames=("first_col",))
def cached_index_scores(q_idx, w_idx, v_pool, layer, table, lengths, *,
                        first_col):
    """The index scores of every slot's cached rows, the keys read through
    the page table where they lie: q_idx [slots, J, D], w_idx [slots, J]
    float32, v_pool [layers, pages, page_size, width] whose columns
    ``first_col .. first_col + D`` hold a row's index key, table [slots,
    pages a slot], lengths [slots] → [slots, pages a slot * page_size]
    float32, ``-inf`` from a slot's length on.  Only pages that hold live
    rows are read, and of them only the key's columns.  ``index_scores``'
    numbers (bfloat16 products, float32 sums), by a kernel."""
    slots, j, d = q_idx.shape
    ps = v_pool.shape[2]
    ctx = table.shape[1] * ps
    per_step = INDEX_PAGES_PER_STEP
    while ctx % (per_step * ps):  # a toy context: fewer pages a step
        per_step //= 2
    kernel = functools.partial(
        _index_kernel, page_size=ps, pages_per_step=per_step,
        first_col=first_col, width=d)
    per_slot = lambda *shape: pl.BlockSpec(  # noqa: E731
        (None,) + shape, lambda s, *_: (s, 0, 0))
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(slots,),
            in_specs=[per_slot(j, d), per_slot(j, 1),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=per_slot(1, ctx),
            scratch_shapes=[pltpu.VMEM((2, per_step * ps, d), v_pool.dtype),
                            pltpu.SemaphoreType.DMA((2,))]),
        out_shape=jax.ShapeDtypeStruct((slots, 1, ctx), F32),
        name="dsa_index",
        interpret=jax.default_backend() == "cpu",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), table.astype(jnp.int32),
      lengths.astype(jnp.int32), q_idx.astype(v_pool.dtype),
      w_idx.astype(F32)[..., None], v_pool)[:, 0]


def sparse_paged_attention(q: jax.Array, k_new: jax.Array, v_new: jax.Array,
                           *, k_pool: jax.Array, v_pool: jax.Array, layer,
                           table: jax.Array, lengths: jax.Array, index,
                           topk: int, sm_scale: float, rank: int,
                           first_page: Optional[jax.Array] = None,
                           keep: bool = False):
    """One new token a slot over the ``topk`` rows its indexer selects
    among the slot's cached rows and itself (``paged_attention``'s
    arguments; ``first_page`` has no meaning here and must be None).

    q [slots, 1, H, W] the absorbed query (``ops/mla.py``), k_new / v_new
    [slots, 1, 1, W] the new token's latent row, at position
    ``lengths[s]`` and not in the pool yet; k_pool / v_pool [layers, pages,
    page_size, width >= W]; ``index`` = (q_idx [slots, 1, J, D], w_idx
    [slots, 1, J], k_idx [slots, 1, D]): the new token's indexer
    projections.  The cached index keys are columns ``rank .. rank + D`` of
    the V pool's rows (``ops/mla.py::index_rows``), the values the first
    ``rank`` columns of the K pool's.

    → (out [slots, 1, H, rank] in q's dtype, rows [slots] int32: how many
    rows each slot's softmax ran over, ``min(lengths + 1, topk)``, of which
    all but the token's own were gathered from the pool).  Of the K pool
    only the selected rows are read.  ``keep``, for a comparison: also the
    selection, [slots, min(topk, ctx + 1)] int32 positions in the order of
    their scores (``lengths[s]``: the token itself), -1 past the rows there
    are: a third operand the sort carries, so another program."""
    if first_page is not None:
        raise ValueError("a window of pages and a learned selection are two "
                         "answers to one question: first_page must be None")
    q_idx, w_idx, k_idx = index
    slots, t, h, w = q.shape
    if t != 1:
        raise ValueError(f"one new token a slot, got {t}")
    _, n_pages, ps, width = k_pool.shape
    ctx = table.shape[1] * ps
    cached = cached_index_scores(
        q_idx[:, 0], w_idx[:, 0], v_pool, jnp.asarray(layer, jnp.int32),
        table, lengths, first_col=rank)
    own = index_scores(q_idx, w_idx, k_idx)[:, 0]          # [slots, 1]
    # Where each cached position's row lies in the pool addressed as rows,
    # carried through the sort as its payload (looked up after it, 2,048
    # scalars a slot are a gather of their own); -1: the token's own row,
    # which is last, the highest position, so that the order among equal
    # scores is the order of positions (a stable sort).
    where = ((jnp.asarray(layer, jnp.int32) * n_pages + table)[:, :, None]
             * ps + jnp.arange(ps, dtype=jnp.int32)).reshape(slots, ctx)
    where = jnp.concatenate(
        [where, jnp.full((slots, 1), -1, jnp.int32)], axis=-1)
    k = min(topk, ctx + 1)
    # ascending by the negated score: what ``lax.top_k`` does, with a
    # payload of our choosing in the place of its iota
    carried = (-jnp.concatenate([cached, own], axis=-1), where)
    if keep:  # each row's position too; the token's own, last, at its length
        at = jnp.arange(ctx + 1, dtype=jnp.int32)[None]
        carried += (jnp.where(at < ctx, at, lengths[:, None].astype(
            jnp.int32)),)
    lowest, row, *places = lax.sort(carried, dimension=-1, is_stable=True,
                                    num_keys=1)
    valid = lowest[:, :k] < jnp.inf
    row = row[:, :k]
    from_pool = valid & (row >= 0)
    own_in = jnp.any(valid & (row < 0), axis=-1)
    row = jnp.where(from_pool, row, 0)                 # page 0: scratch
    got = k_pool.reshape(-1, width)[row]               # [slots, K, width]
    # 0 * NaN is NaN: what the scratch page holds must not reach the MXU
    got = jnp.where(from_pool[..., None], got, jnp.zeros_like(got))
    q1, k1, v1 = q[:, 0], k_new[:, 0, 0], v_new[:, 0, 0]
    s = jnp.einsum("shw,skw->shk", q1, got[..., :w],
                   preferred_element_type=F32) * sm_scale
    s = jnp.where(from_pool[:, None], s, NEG_INF)
    s_own = jnp.einsum("shw,sw->sh", q1, k1,
                       preferred_element_type=F32) * sm_scale
    s_own = jnp.where(own_in[:, None], s_own, NEG_INF)
    m = jnp.maximum(jnp.max(s, axis=-1), s_own)
    p = jnp.where(from_pool[:, None], jnp.exp(s - m[..., None]), 0.0)
    p_own = jnp.where(own_in[:, None], jnp.exp(s_own - m), 0.0)
    out = jnp.einsum("shk,skr->shr", p.astype(got.dtype), got[..., :rank],
                     preferred_element_type=F32)
    out = out + p_own[..., None] * v1[:, None, :rank].astype(F32)
    out = out / (jnp.sum(p, axis=-1) + p_own)[..., None]
    counted = jnp.sum(valid, axis=-1, dtype=jnp.int32)
    if keep:
        return (out[:, None].astype(q.dtype), counted,
                jnp.where(valid, places[0][:, :k], -1))
    return out[:, None].astype(q.dtype), counted
