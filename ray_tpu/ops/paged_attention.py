"""Decode attention that reads the serve engine's page pool in place.

``serve/llm_engine.py`` keeps K and V in fixed-size pages of one pool,
``[layers, pages, page_size, kv_heads * head_dim]`` (the last rounded up
to whole lane registers, ``pool_width``), and finds a slot's
pages through its row of the page table.  The kernel here follows that
row: for each slot it copies the pages that hold live rows, and only
those, from the pool in HBM into VMEM, several pages a step and the next
step's pages while this step's are multiplied, and keeps the flash
recurrence (running max, denominator, accumulator, all float32) over
them.  Nothing the size of the pool, or of ``slots * max_ctx`` rows, is
ever built.

A page is one lane-dense ``[page_size, kv_heads * head_dim]`` tile, so
the heads are not sliced out of it (a head of 64 is half a lane
register).  The queries are laid out block-diagonally instead — row
``(t, h)`` holds ``q[t, h]`` in the columns of its kv head and zeros
elsewhere — and one matmul against the page gives every head's scores;
the accumulator carries all columns and the caller keeps each row's own
head.  The MXU multiplies ``kv_heads`` times more than it needs to, which
decode attention, bound by the bytes of K and V, does not notice.

The new tokens' own K and V are not in the pool when attention runs (the
engine writes them once, after the last layer, so that the pool is only
read here and updated in place there): ``paged_attention`` folds their
causal block in with the recurrence ``ops/attention.py`` already has.
On a CPU backend the same kernel runs interpreted.

At 32 KV heads of 128 (``models/eva_decoder.py``, the widest pool so far) a
page is 16 rows x 4,096 columns, 128 KB a pool; ``PAGES_PER_STEP`` 8 stands
there: a step's K and V buffers, held twice, are 4 MiB of VMEM, under the
compiler's default of scoped memory (no limit of its own is asked for:
``tests/test_flash_compile_tpu.py``), and a step copies 2 MB, long enough
for the next step's copies to hide behind.  The block-diagonal queries are
32 rows x 4,096 columns: the MXU multiplies 32 times what the heads need,
32 FLOP for every byte of K and V, still under the v5e's ridge (~240), and
the kernel reads 80-86% of its memory roofline there (PERF.md section 5,
PR 61).  The table row the kernel follows need not be the engine's own: a
model whose cached rows are not its tokens hands it a row composed on the
device (``ops/eva.py::EvaCacheMap.read_table``: summary pages, then a ring
of window pages) with the count of live rows in it.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.attention import (NEG_INF, blockwise_update,
                                   finalize_blockwise)

# Pages copied and multiplied per step of the kernel's loop: a 16-token
# page of GPT-2 medium is 32 KB, too little for one DMA round to hide
# behind; 8 of them are 128 rows, one MXU pass.
PAGES_PER_STEP = 8
# The latent form: 64 heads read ONE row of 640 columns, so a step of 128
# rows is 160 KB and done in a fraction of a microsecond; more pages a step
# mean fewer turns of the loop for a context of thousands of rows.
LATENT_PAGES_PER_STEP = 16
_LANES = 128


def pool_width(kv_heads: int, head_dim: int) -> int:
    """Columns of a pool row: ``kv_heads * head_dim`` rounded up to whole
    128-lane registers, which is what the kernel's page copies need on the
    chip (a toy model's 64 columns are padded; a real model's are not)."""
    return -(-kv_heads * head_dim // _LANES) * _LANES


def _paged_kernel(layer_ref, table_ref, lengths_ref, first_ref,  # SMEM
                  q_ref, *refs, page_size, pages_per_step, sm_scale,
                  rank=None):
    """One slot: the flash recurrence over its live pages.

    q_ref [R, kv_heads*head_dim] block-diagonal queries; k_hbm / v_hbm the
    whole pools, left in HBM; acc_ref [R, kv_heads*head_dim], m_ref and
    l_ref [R, 128] (the value in every lane), all float32 and not
    normalised; k_buf / v_buf [2, pages_per_step*page_size, ...] VMEM.

    ``rank`` (the latent form: a pool of rows ``[c | rope(k_r)]``,
    ``ops/mla.py``): there is no V pool and no V buffer; a page is copied
    once, and the values are the first ``rank`` columns of the block the
    keys are; acc_ref is [R, rank]."""
    if rank is None:
        k_hbm, v_hbm, acc_ref, m_ref, l_ref, k_buf, v_buf, sems = refs
    else:
        k_hbm, acc_ref, m_ref, l_ref, k_buf, sems = refs
    slot = pl.program_id(0)
    layer = layer_ref[0]
    first = first_ref[slot]
    rows = lengths_ref[slot] - first * page_size  # live rows to attend to
    n_pages = (rows + page_size - 1) // page_size
    n_steps = (n_pages + pages_per_step - 1) // pages_per_step
    span = pages_per_step * page_size

    def page_copies(step, buf, i):
        page = table_ref[slot, first + step * pages_per_step + i]
        dst = pl.ds(i * page_size, page_size)
        k_copy = pltpu.make_async_copy(k_hbm.at[layer, page],
                                       k_buf.at[buf, dst], sems.at[0, buf])
        if rank is not None:
            return (k_copy,)
        return (k_copy,
                pltpu.make_async_copy(v_hbm.at[layer, page],
                                      v_buf.at[buf, dst], sems.at[1, buf]))

    def for_live_pages(step, buf, what):
        # A page past the slot's length is neither started nor waited
        # for: its rows of the buffer keep what an earlier step left.
        for i in range(pages_per_step):
            @pl.when(step * pages_per_step + i < n_pages)
            def _():
                for copy in page_copies(step, buf, i):
                    what(copy)

    @pl.when(n_steps > 0)
    def _():
        for_live_pages(0, 0, lambda c: c.start())

    q = q_ref[...]

    def body(step, carry):
        m, l, acc = carry
        buf = step % 2

        @pl.when(step + 1 < n_steps)
        def _():
            for_live_pages(step + 1, 1 - buf, lambda c: c.start())

        for_live_pages(step, buf, lambda c: c.wait())
        k = k_buf[buf]
        v = v_buf[buf] if rank is None else k[:, :rank]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale  # [R, span]
        live = step * span + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1) < rows
        s = jnp.where(live, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        corr = jnp.exp(m - m_new)
        p = jnp.where(live, jnp.exp(s - m_new), 0.0)
        l_new = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        # 0 * NaN is NaN: rows past the length must not reach the MXU.
        v_live = step * span + jax.lax.broadcasted_iota(
            jnp.int32, v.shape, 0) < rows
        v = jnp.where(v_live, v, jnp.zeros_like(v))
        acc_new = acc * corr + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    r = q.shape[0]
    m, l, acc = jax.lax.fori_loop(
        0, n_steps, body,
        (jnp.full((r, 1), NEG_INF, jnp.float32),
         jnp.zeros((r, 1), jnp.float32),
         jnp.zeros(acc_ref.shape, jnp.float32)))
    acc_ref[...] = acc
    m_ref[...] = jnp.broadcast_to(m, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l, l_ref.shape)


def _paged_partial(q_bd, k_pool, v_pool, layer, table, lengths, first_page,
                   sm_scale, rank=None):
    """(acc, m, l) of the block-diagonal queries ``q_bd [slots, R, HD]``
    over each slot's cached rows.  ``rank``: the latent form
    (``_paged_kernel``), of a kernel named ``latent_paged_attn``; ``v_pool``
    is not looked at and acc is [slots, R, rank]."""
    slots, r, hd = q_bd.shape
    page_size = k_pool.shape[2]
    latent = rank is not None
    pages_per_step = LATENT_PAGES_PER_STEP if latent else PAGES_PER_STEP
    span = pages_per_step * page_size
    kernel = functools.partial(
        _paged_kernel, page_size=page_size, pages_per_step=pages_per_step,
        sm_scale=sm_scale, rank=rank)
    per_slot = lambda width: pl.BlockSpec(  # noqa: E731
        (None, r, width), lambda s, *_: (s, 0, 0))
    pools = (k_pool,) if latent else (k_pool, v_pool)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(slots,),
            in_specs=[per_slot(hd)]
            + [pl.BlockSpec(memory_space=pl.ANY)] * len(pools),
            out_specs=[per_slot(rank if latent else hd), per_slot(_LANES),
                       per_slot(_LANES)],
            scratch_shapes=[pltpu.VMEM((2, span, hd), pool.dtype)
                            for pool in pools]
            + [pltpu.SemaphoreType.DMA((len(pools), 2))]),
        out_shape=[jax.ShapeDtypeStruct(
            (slots, r, rank if latent else hd), jnp.float32),
                   jax.ShapeDtypeStruct((slots, r, _LANES), jnp.float32),
                   jax.ShapeDtypeStruct((slots, r, _LANES), jnp.float32)],
        name="latent_paged_attn" if latent else "paged_attn",
        interpret=jax.default_backend() == "cpu",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), table.astype(jnp.int32),
      lengths.astype(jnp.int32), first_page.astype(jnp.int32),
      q_bd, *pools)


def paged_attention(q: jax.Array, k_new: jax.Array, v_new: jax.Array,
                    k_pool: jax.Array, v_pool: jax.Array, layer,
                    table: jax.Array, lengths: jax.Array,
                    first_page: Optional[jax.Array] = None,
                    sm_scale: Optional[float] = None) -> jax.Array:
    """T new tokens of every slot attend to the slot's cached rows, read
    from the page pool through the page table, plus themselves (causally).

    q: [slots, T, H, D]; k_new, v_new: [slots, T, Hkv, D], the new tokens'
    projections at absolute positions ``lengths[s] + t`` (not in the pool
    yet).  k_pool, v_pool: [layers, pages, page_size, pool_width(Hkv, D)]
    (columns past Hkv * D are ignored) and ``layer`` the index to read
    (the pool goes to the kernel whole: a slice of it would be a copy).
    table: [slots, pages_per_slot] page ids; lengths: [slots] cached rows,
    0 for a lane with no request, which reads nothing.  first_page:
    [slots] index into the slot's table row of the first page to attend to
    (None: 0) — a sliding window is the same kernel started later.  Only
    pages ``first_page .. ceil(lengths / page_size) - 1`` of a row are
    read.

    What ``cached_attention`` computes on a gathered dense view, with its
    numerics: float32 scores and softmax over masked rows, probabilities
    in the values' dtype for the second matmul, output in q's dtype."""
    if first_page is None:
        first_page = jnp.zeros(lengths.shape, jnp.int32)
    scale = sm_scale if sm_scale is not None else q.shape[-1] ** -0.5
    return _paged_attention(q, k_new, v_new, k_pool, v_pool,
                            jnp.asarray(layer, jnp.int32), table, lengths,
                            first_page, sm_scale=scale)


# jit of its own, with the layer as an argument: a model's layers then
# share one trace and one lowering of the kernel (24 separate ones added
# 8 s to the serve cell's set-up), and the compiler inlines the calls.
@functools.partial(jax.jit, static_argnames=("sm_scale",))
def _paged_attention(q, k_new, v_new, k_pool, v_pool, layer, table, lengths,
                     first_page, *, sm_scale):
    slots, t, h, d = q.shape
    hkv = k_new.shape[2]
    # Row (t, h) of the block-diagonal queries: q[t, h] in the columns of
    # kv head h // (H / Hkv).  Rows padded to the dtype's sublane tile,
    # columns to the pool's.
    own = jnp.arange(h)[:, None] // (h // hkv) == jnp.arange(hkv)[None]
    q_bd = jnp.where(own[None, None, :, :, None], q[:, :, :, None, :], 0)
    q_bd = q_bd.reshape(slots, t * h, hkv * d).astype(k_pool.dtype)
    q_bd = jnp.pad(q_bd, ((0, 0), (0, -(t * h) % 16),
                          (0, k_pool.shape[-1] - hkv * d)))
    acc, m, l = _paged_partial(q_bd, k_pool, v_pool, layer, table, lengths,
                               first_page, sm_scale)
    acc = acc[:, :t * h, :hkv * d].reshape(slots, t, h, hkv, d)
    o = jnp.sum(jnp.where(own[None, None, :, :, None], acc, 0.0), axis=3)
    m = m[:, :t * h, 0].reshape(slots, t, h).transpose(0, 2, 1)
    l = l[:, :t * h, 0].reshape(slots, t, h).transpose(0, 2, 1)
    if hkv != h:  # GQA: the new tokens' few rows, expanded to query heads
        k_new = jnp.repeat(k_new, h // hkv, axis=2)
        v_new = jnp.repeat(v_new, h // hkv, axis=2)
    causal = jnp.tril(jnp.ones((t, t), bool))
    o, l, _ = blockwise_update(q, k_new, v_new, o, l, m, mask=causal,
                               sm_scale=sm_scale)
    return finalize_blockwise(o, l).astype(q.dtype)


def latent_paged_attention(q: jax.Array, row_new: jax.Array, v_new,
                           k_pool: jax.Array, v_pool, layer,
                           table: jax.Array, lengths: jax.Array,
                           first_page: Optional[jax.Array] = None,
                           sm_scale: Optional[float] = None, *,
                           rank: int) -> jax.Array:
    """``paged_attention`` over a pool of latent rows (``ops/mla.py``'s
    absorbed form, one row a token): q [slots, T, H, R + P] every head's
    query against ONE shared row ``[c | rope(k_r)]`` (row_new [slots, T, 1,
    R + P]: the new tokens'; k_pool [layers, pages, page_size,
    pool_width(1, R + P)]: the cached ones), whose first ``rank`` (R)
    columns are also its value.  A page is copied once and multiplied
    twice, as K whole and as V by its first R columns; ``v_new`` and
    ``v_pool`` are not looked at (the engine's hook hands over what it has:
    None and a pool with no page).  → [slots, T, H, R] in q's dtype, with
    ``paged_attention``'s numerics."""
    if first_page is None:
        first_page = jnp.zeros(lengths.shape, jnp.int32)
    scale = sm_scale if sm_scale is not None else q.shape[-1] ** -0.5
    return _latent_paged_attention(
        q, row_new, k_pool, jnp.asarray(layer, jnp.int32), table, lengths,
        first_page, sm_scale=scale, rank=rank)


@functools.partial(jax.jit, static_argnames=("sm_scale", "rank"))
def _latent_paged_attention(q, row_new, k_pool, layer, table, lengths,
                            first_page, *, sm_scale, rank):
    slots, t, h, d = q.shape
    # one KV head: every (t, h) row of the queries is whole, no diagonal
    q_rows = q.reshape(slots, t * h, d).astype(k_pool.dtype)
    q_rows = jnp.pad(q_rows, ((0, 0), (0, -(t * h) % 16),
                              (0, k_pool.shape[-1] - d)))
    acc, m, l = _paged_partial(q_rows, k_pool, None, layer, table, lengths,
                               first_page, sm_scale, rank=rank)
    o = acc[:, :t * h].reshape(slots, t, h, rank)
    m = m[:, :t * h, 0].reshape(slots, t, h).transpose(0, 2, 1)
    l = l[:, :t * h, 0].reshape(slots, t, h).transpose(0, 2, 1)
    k_new = jnp.repeat(row_new, h, axis=2)
    causal = jnp.tril(jnp.ones((t, t), bool))
    o, l, _ = blockwise_update(q, k_new, k_new[..., :rank], o, l, m,
                               mask=causal, sm_scale=sm_scale)
    return finalize_blockwise(o, l).astype(q.dtype)
