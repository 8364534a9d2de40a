"""Attention ops: reference XLA implementation, online-softmax block update
(shared with ring attention), and a Pallas TPU flash-attention kernel.

The reference framework has no attention kernels at all — its models call
torch; the closest analogue is RLlib's GTrXL attention_net
(rllib/models/torch/attention_net.py), which is plain torch ops.  Here
attention is a first-class fused kernel because on TPU the HBM-bandwidth win
of not materializing the [L, L] score matrix is the difference between MXU-
bound and memory-bound.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

NEG_INF = -1e30  # large-negative instead of -inf: keeps exp() NaN-free


def mha_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                  causal: bool = True, sm_scale: Optional[float] = None,
                  use_flash: Optional[bool] = None, mesh=None) -> jax.Array:
    """Multi-head attention. q,k,v: [B, L, H, D] → [B, L, H, D].

    Dispatches to the Pallas flash kernel on real TPU backends for long
    sequences, XLA reference otherwise.  On a v5e, forward and backward
    of [8, 1024, 16, 64] bf16 causal as a function of a fused projection,
    its split and the gradient's concatenation included: the kernels 1.23
    ms reading column blocks of ``[B, L, H * D]`` (1.74 head-major, with a
    transpose each way, as they read until PR 49; 1.04 out of the fused
    array itself, ``mha_attention_qkv``, and 0.85 since PR 54 cut the
    masked tiles: ``_auto_blocks``); this XLA path 4.44 (PR 39's and PR
    49's chip runs; PERF.md section 6).  Below 1k ctx the XLA
    path is still chosen, as it was when the kernels were 2.2 times slower
    than now; at [16, 512, 16, 64] they read 1.17 ms against XLA's 2.06,
    so the crossover lies lower than this rule puts it, and nobody has
    looked for it.  A kernel that fails to trace or compile is the
    failure: there is no fallback to the XLA path.

    ``mesh``: pass it when the call sits under a plain ``jit`` whose arrays
    are sharded over that mesh (``prepare_batch`` / ``prepare_train_state``).
    The compiler will not split a Mosaic kernel by itself ("Mosaic kernels
    cannot be automatically partitioned. Please wrap the call in a
    shard_map" — seen on a four-chip v5e host), and at trace time the
    inputs' shardings cannot be read, so the caller has to name the mesh:
    the call is then shard_mapped by the repo's logical-axis rules, batch
    over the data axes and heads over ``model``, every device running the
    kernel on its own rows.  Inside a shard_map body arrays are already
    per-device: leave it None there."""
    if mesh is not None:
        from ray_tpu.parallel.sharding import ShardingRules

        spec = ShardingRules().spec_for(("batch", None, "heads", None), mesh)
        local = functools.partial(mha_attention, causal=causal,
                                  sm_scale=sm_scale, use_flash=use_flash)
        return jax.shard_map(local, mesh=mesh, in_specs=(spec, spec, spec),
                             out_specs=spec, check_vma=False)(q, k, v)
    b, lq, h, _ = q.shape
    if use_flash is None:
        use_flash = _flash_by_default(b, lq, k.shape[1], h, q.dtype, causal)
    if use_flash:
        return flash_attention(q, k, v, causal=causal, sm_scale=sm_scale)
    return _xla_attention(q, k, v, causal, sm_scale)


def mha_attention_qkv(qkv: jax.Array, num_heads: int, causal: bool = True,
                      sm_scale: Optional[float] = None,
                      use_flash: Optional[bool] = None) -> jax.Array:
    """``mha_attention`` on a fused projection, for a caller that has q, k
    and v as the columns of one array (GPT-2's ``attn_qkv``): qkv ``[B, L,
    3 * H * D]`` → ``[B, L, H * D]``, the same dispatch.  On the kernels'
    side nothing is split or concatenated (``flash_attention_qkv``)."""
    b, l, columns = qkv.shape
    if use_flash is None:
        use_flash = _flash_by_default(b, l, l, num_heads, qkv.dtype, causal)
    if use_flash:
        return flash_attention_qkv(qkv, num_heads, causal=causal,
                                   sm_scale=sm_scale)
    q, k, v = (x.reshape(b, l, num_heads, columns // (3 * num_heads))
               for x in jnp.split(qkv, 3, axis=-1))
    return _xla_attention(q, k, v, causal, sm_scale).reshape(
        b, l, columns // 3)


def _flash_by_default(b, lq, lk, h, dtype, causal) -> bool:
    # [B, H, Lq, Lk] score-matrix footprint the XLA path materializes.
    score_bytes = b * h * lq * lk * dtype.itemsize
    return (jax.default_backend() not in ("cpu",)
            and lq % 128 == 0 and lk % 128 == 0
            # From 1k ctx on (mha_attention's docstring has the times);
            # memory can force flash even earlier: per-layer score matrices
            # past ~512MB OOM real training steps on a 16G chip.
            and (lq >= 1024 or score_bytes > 512 * 1024 * 1024)
            # Flash's causal mask is diagonal-aligned (self-attention); the
            # XLA path's is bottom-right-aligned for lq != lk (decode), so
            # only lq == lk may auto-dispatch.
            and (not causal or lq == lk))


def _xla_attention(q, k, v, causal, sm_scale):
    *_, d = q.shape
    scale = sm_scale if sm_scale is not None else d ** -0.5
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        lq, lk = q.shape[1], k.shape[1]
        mask = jnp.tril(jnp.ones((lq, lk), dtype=bool), k=lk - lq)
        s = jnp.where(mask[None, None], s, NEG_INF)
    p = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def cached_attention(q: jax.Array, k_new: jax.Array, v_new: jax.Array,
                     k_cache: jax.Array, v_cache: jax.Array,
                     cache_lengths: jax.Array,
                     sm_scale: Optional[float] = None) -> jax.Array:
    """Attention for the incremental-decode path: T new tokens attend to a
    per-sequence cached prefix plus themselves (causally).

    q, k_new, v_new: [B, T, H(q/kv), D] projections of the new tokens,
    occupying absolute positions ``cache_lengths[b] + t``.
    k_cache, v_cache: [B, S, Hkv, D]; only the first ``cache_lengths[b]``
    rows of each sequence are valid — the rest (pool pages past the
    write head) is masked out, so callers can pass padded/gathered
    caches without zeroing them.  With Hkv < H the key/value heads are
    expanded GQA-style after concatenation.  S == 0 degenerates to plain
    causal self-attention (the prefill case).  Numerics match
    ``_xla_attention`` (fp32 softmax over masked scores), so greedy
    decode through a cache is token-identical to a full-context forward
    pass in fp32.
    """
    b, t, h, d = q.shape
    s = k_cache.shape[1]
    k = jnp.concatenate([k_cache, k_new], axis=1) if s else k_new
    v = jnp.concatenate([v_cache, v_new], axis=1) if s else v_new
    if k.shape[2] != h:  # GQA: expand kv heads to query heads
        rep = h // k.shape[2]
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    scale = sm_scale if sm_scale is not None else d ** -0.5
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale  # [B, H, T, S+T]
    j = jnp.arange(s + t)
    i = jnp.arange(t)
    # Key j is visible to query i when it's a valid cache row (j < len[b])
    # or a causally-earlier new token (j - S <= i).
    mask = jnp.where(j[None, None, :] < s,
                     j[None, None, :] < cache_lengths[:, None, None],
                     (j[None, None, :] - s) <= i[None, :, None])
    scores = jnp.where(mask[:, None], scores, NEG_INF)
    p = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


# ---------------------------------------------------------------------------
# Online-softmax block update (the flash recurrence), shared by ring
# attention: numerically safe when a block is fully masked.
# ---------------------------------------------------------------------------
def blockwise_update(q, k_blk, v_blk, o, l, m, mask=None,
                     sm_scale: Optional[float] = None
                     ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One step of the flash-attention recurrence.

    q: [B, Lq, H, D]; k_blk/v_blk: [B, Lk, H, D]
    o: [B, Lq, H, D] unnormalized accumulator
    l: [B, H, Lq] running denominator; m: [B, H, Lq] running max
    mask: optional [Lq, Lk] bool (True = attend) applied on top of nothing.
    """
    d = q.shape[-1]
    scale = sm_scale if sm_scale is not None else d ** -0.5
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k_blk).astype(jnp.float32) * scale
    if mask is not None:
        s = jnp.where(mask[None, None], s, NEG_INF)
    m_blk = jnp.max(s, axis=-1)  # [B,H,Lq]
    m_new = jnp.maximum(m, m_blk)
    # Fully-masked-so-far rows keep m = NEG_INF; corrections stay 0.
    corr = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new[..., None])
    p = jnp.where(s <= NEG_INF / 2, 0.0, p)
    l_new = l * corr + jnp.sum(p, axis=-1)
    pv = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v_blk.dtype), v_blk)
    o_new = o * corr.transpose(0, 2, 1)[..., None].astype(o.dtype) + pv
    return o_new, l_new, m_new


def finalize_blockwise(o, l):
    """Normalize the accumulator; fully-masked rows return zeros."""
    denom = l.transpose(0, 2, 1)[..., None]
    return jnp.where(denom > 0, o / denom.astype(o.dtype), 0.0)


# ---------------------------------------------------------------------------
# Pallas TPU flash attention, forward + backward (custom VJP).  Grid over
# (batch*heads, blocks); K/V streamed through VMEM.  The forward emits
# per-row log-sum-exp residuals so the backward recomputes P blockwise —
# neither pass ever materializes the [L, L] score matrix, which is what
# keeps training MXU-bound instead of HBM-bound (and is why the XLA
# reference path OOMs at batch 32 / 1024 ctx on a 16G chip while this
# doesn't).
#
# The causal schedule.  The [lq, lk] square is cut into (block_q, block_k)
# tiles.  A tile whose every column lies past its every row is skipped, one
# whose every column lies at or before its every row runs with no mask, and
# only the tiles the diagonal crosses pay for the iota/compare/select.  The
# two helpers below give those bounds from either side (a q tile's range of
# k tiles, a k tile's range of q tiles); the kernels' loops and
# ``causal_tile_schedule`` (the number a test and PERF.md quote) both read
# them, so what is counted is what runs.
#
# Inside a masked tile (PR 54).  A square tile that the diagonal crosses
# corner to corner is half zeros, so it is cut into chunks of ``_CHUNK``
# columns (``_diagonal_chunks``) and a chunk is multiplied only with the
# rows that reach it: of a (512, 512) tile's 16 sub-blocks of 128 x 128 the
# ten at or under the diagonal go to the MXU.  The cut runs along the
# operand the MXU holds still (a chunk of keys in a q tile, a chunk of
# queries in a k tile), so that the other operand's rows stream past it in
# one long run: cut the other way, into bands of rows that each meet all
# their columns, the same sub-blocks took as long as the whole tile (my
# chip runs, PR 54; ``_auto_blocks`` has the times).  The softmax's row
# maximum and sum still need a row's scores side by side, so the forward
# puts the sub-blocks of a band of rows together again; every such cut and
# join is static and lies on a multiple of 128 rows or lanes, so nothing
# moves.  A row meets the tile once: no rescale, no maximum is added.
# ---------------------------------------------------------------------------
# lse and delta lie along the lanes of one float32 tile of 8 sublanes a
# column block, ``[B, blocks, 8, L]``: TPU block shapes need the last two
# dims (sublane, lane)-tiled, and a lane dim of 1 would pad 128x in HBM.
# The block's heads share the tile, head j of g on sublanes j * 8 / g and
# the 8 / g - 1 after it (``_pack_rows``; one head: all eight).  The
# forward's result is the residual as it is and the backward reads it with
# nothing in between.  (One sublane a head, ``[B, H, 1, L]``, compiles and is
# compact, and its single-sublane stores made the forward 30% slower: my
# chip run, PR 49.)
_LSE_SUBLANES = 8


def _clamp(x, hi):
    """min(x, hi) for a Python int (a static schedule) or a traced value."""
    return min(x, hi) if isinstance(x, int) else jnp.minimum(x, hi)


def _k_tile_bounds(q_off, block_q, block_k, num_k_blocks):
    """For the q tile starting at row ``q_off``: k tiles ``[0, full)`` run
    unmasked, ``[full, end)`` masked, the rest are skipped.  Clamped to
    ``num_k_blocks``: with lq > lk the tail query rows sit entirely past
    the last K block and an unclamped bound would read past K/V.  Works on
    Python ints and on traced values."""
    full = _clamp(q_off // block_k, num_k_blocks)
    end = _clamp((q_off + block_q + block_k - 1) // block_k, num_k_blocks)
    return full, end


def _q_tile_bounds(k_off, block_k, block_q, num_q_blocks):
    """For the k tile starting at column ``k_off``: q tiles ``[0, first)``
    are skipped (they lie above the diagonal), ``[first, full)`` run
    masked, ``[full, num_q_blocks)`` unmasked."""
    first = _clamp(k_off // block_q, num_q_blocks)
    full = _clamp((k_off + block_k + block_q - 1) // block_q, num_q_blocks)
    return first, full


_CHUNK = 128  # columns a chunk of a masked tile: one lane tile, the width
#               of the MXU's still operand (``_auto_blocks`` has the times)


def _diagonal_chunks(block_q: int, block_k: int):
    """(g, the chunks' first columns) of a masked (block_q, block_k) tile
    that the kernels cut: the chunk of g columns from ``first`` on meets the
    rows that reach it and no others (in a q tile the rows from ``first``
    on, in a k tile's transposed one the rows before ``first + g``).  (0,
    []) where the tile is multiplied whole.  Square tiles only: there every
    masked tile starts on the diagonal (k_off == q_off, in the rolled
    kernels too, where the offsets are traced), so the cut is static; an
    oblong tile's mask starts at an offset that differs tile by tile."""
    if block_q != block_k or block_q % _CHUNK or block_q < 2 * _CHUNK:
        return 0, []
    return _CHUNK, list(range(0, block_q, _CHUNK))


def _sum_bands(parts, g: int):
    """``parts``: (first row, value) each, a chunk's product lying on some
    of a tile's rows.  Their sum, as the list of the tile's bands of g
    rows: every band covered by some part, static slices of g rows."""
    last = max(first + x.shape[0] for first, x in parts)
    return [functools.reduce(jnp.add, [
        x[r - first:r - first + g] for first, x in parts
        if first <= r < first + x.shape[0]]) for r in range(0, last, g)]


def causal_tile_schedule(lq: int, lk: int, block_q: int, block_k: int
                         ) -> dict:
    """What the causal kernels visit at these tile sizes: tiles ``visited``
    (``masked`` of them under the iota/select), ``skipped``, ``total``, and
    ``visited_share`` of the lq x lk square; ``multiplied_share`` of it
    reaches the MXU, a masked tile counted by its ``_diagonal_chunks`` of
    ``chunk`` columns (0: masked tiles are multiplied whole, and the two
    shares are one).  The mask itself needs 1/2 + 1/(2 * lq) of the square
    when lq == lk."""
    nq, nk = lq // block_q, lk // block_k
    visited = masked = 0
    for i in range(nq):
        full, end = _k_tile_bounds(i * block_q, block_q, block_k, nk)
        visited += end
        masked += end - full
    g, chunks = _diagonal_chunks(block_q, block_k)
    in_masked = (sum(g * (block_q - first) for first in chunks) if g
                 else block_q * block_k)
    multiplied = (visited - masked) * block_q * block_k + masked * in_masked
    return {"total": nq * nk, "visited": visited, "masked": masked,
            "skipped": nq * nk - visited,
            "visited_share": visited / (nq * nk),
            "chunk": g,
            "multiplied_share": multiplied / (lq * lk)}


def _dot_nt(a, b):
    """a @ b.T with float32 accumulation (the MXU takes the transposed
    right operand as it is)."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _fold_scale(q, sm_scale):
    """(q', s_scale): where the softmax scale is a power of two (d = 64:
    1/8) it goes onto the [block, d] query tile, which is exact in every
    binary float format, and the [block_q, block_k] score tile is left
    alone (s_scale None); any other scale stays a float32 multiply on the
    scores."""
    if math.frexp(sm_scale)[0] == 0.5:
        return q * jnp.asarray(sm_scale, q.dtype), None
    return q, sm_scale


def _loop(lo, hi, body, carry):
    """``fori_loop``, unrolled in Python where both bounds are static (the
    whole-head kernels; a non-causal call's tiles): the compiler then
    schedules one tile's VPU work under the next one's matmuls, which a
    rolled loop with a traced trip count forbids."""
    if isinstance(lo, int) and isinstance(hi, int):
        for i in range(lo, hi):
            carry = body(i, carry)
        return carry
    return jax.lax.fori_loop(lo, hi, body, carry)


def _tile_rel(rows: int, cols: int, transposed: bool = False):
    """row - col of a [rows, cols] tile at the origin (col - row of the
    transposed tile): the tile at (q_off, k_off) shows the entries with
    rel >= k_off - q_off."""
    r = jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 1)
    return c - r if transposed else r - c


def _head_lanes(x, j: int, heads: int):
    """x [rows, heads * d] with every lane outside head ``j``'s set to zero:
    a product that contracts all the lanes with it is head j's alone, and
    one that it multiplies from the left lands on head j's lanes only.  A
    mask, not a slice: nothing moves across lanes.  One head: x."""
    if heads == 1:
        return x
    d = x.shape[1] // heads
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    return jnp.where((lane >= j * d) & (lane < (j + 1) * d), x,
                     jnp.zeros_like(x))


def _merge_heads(parts):
    """``parts[j]`` [rows, heads * d] is right on head j's lanes and holds
    anything elsewhere: the array that is right on every lane."""
    heads = len(parts)
    if heads == 1:
        return parts[0]
    d = parts[0].shape[1] // heads
    lane = jax.lax.broadcasted_iota(jnp.int32, parts[0].shape, 1)
    out = parts[-1]
    for j in range(heads - 2, -1, -1):
        out = jnp.where(lane < (j + 1) * d, parts[j], out)
    return out


def _row_of(j: int, heads: int) -> int:
    """The first sublane of head j's row in a block's tile of rows."""
    return j * (_LSE_SUBLANES // heads)


def _pack_rows(rows):
    """One ``[n]`` row a head → the ``[8, n]`` tile that holds them all,
    head j's on sublanes ``_row_of(j, heads)`` and after: whole tiles are
    stored, no single sublane."""
    heads, n = len(rows), rows[0].shape[0]
    if heads == 1:
        return jnp.broadcast_to(rows[0][None, :], (_LSE_SUBLANES, n))
    sub = jax.lax.broadcasted_iota(jnp.int32, (_LSE_SUBLANES, n), 0)
    out = rows[-1][None, :]
    for j in range(heads - 2, -1, -1):
        out = jnp.where(sub < _row_of(j + 1, heads), rows[j][None, :], out)
    return out


def _fwd_q_tile(q, q_off, k_ref, v_ref, causal, sm_scale, block_k):
    """One q tile against its k tiles: (o [block_q, lanes] float32,
    normalised; lse, a ``[rows]`` piece for each band of the q tile's rows,
    one under the other: one piece where masked tiles are not cut).
    ``q_off`` is a Python int in the unrolled kernel and a traced value
    where the grid walks the q tiles.  Where the block holds several heads,
    ``q`` is one head's (``_head_lanes``) and o is that head's on its own
    lanes."""
    import jax.experimental.pallas as pl

    # Inputs stay in their storage dtype (bf16 on the training path): the
    # MXU multiplies natively and accumulates f32 via
    # preferred_element_type — casting blocks to f32 up front would force
    # full-precision MXU passes and halve throughput.
    q, s_scale = _fold_scale(q, sm_scale)
    block_q, d = q.shape
    num_k_blocks = k_ref.shape[0] // block_k
    g, chunks = _diagonal_chunks(block_q, block_k) if causal else (0, [])

    def scores(q, keys):
        s = _dot_nt(q, k_ref[keys, :])
        return s if s_scale is None else s * s_scale

    def softmax_step(m, l, s):
        """(m', l', the rescale of o, p) of rows that meet the scores s."""
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        corr = jnp.exp(m - m_new)
        # No second select on p: rows >= cols shows column 0 to every row
        # and the loop starts there, so m_new is finite from a row's first
        # tile on and exp(NEG_INF - m_new) is exactly 0.
        p = jnp.exp(s - m_new[:, None])
        return m_new, l * corr + jnp.sum(p, axis=-1), corr, p

    def whole(kb, carry, shown=None):
        m, l, o = carry
        keys = pl.ds(kb * block_k, block_k)
        s = scores(q, keys)
        if shown is not None:
            s = jnp.where(shown, s, NEG_INF)
        m, l, corr, p = softmax_step(m, l, s)
        return m, l, o * corr[:, None] + jnp.dot(
            p.astype(v_ref.dtype), v_ref[keys, :],
            preferred_element_type=jnp.float32)

    def cut(kb, carry):
        """A masked tile on the diagonal, ``carry`` a band of rows each: both
        matmuls run a chunk of keys at a time, against the rows from the
        chunk's first on, and the softmax a band at a time."""
        keys = [pl.ds(kb * block_k + first, g) for first in chunks]
        by_chunk = [scores(q[first:], key)        # [block_q - first, g]
                    for first, key in zip(chunks, keys)]
        shown = _tile_rel(g, g) >= 0
        stepped, by_band = [], []
        for r, (m, l, o) in enumerate(carry):
            s = jnp.concatenate(
                [by_chunk[j][(r - j) * g:(r - j + 1) * g] for j in range(r)]
                + [jnp.where(shown, by_chunk[r][:g], NEG_INF)], axis=1)
            m, l, corr, p = softmax_step(m, l, s)
            stepped.append((m, l, o * corr[:, None]))
            by_band.append(p.astype(v_ref.dtype))
        pv = _sum_bands([
            (first, jnp.dot(
                jnp.concatenate([p[:, first:first + g] for p in by_band[j:]],
                                axis=0),
                v_ref[key, :], preferred_element_type=jnp.float32))
            for j, (first, key) in enumerate(zip(chunks, keys))], g)
        return [(m, l, o + pv_r) for (m, l, o), pv_r in zip(stepped, pv)]

    carry = (jnp.full((block_q,), NEG_INF, jnp.float32),
             jnp.zeros((block_q,), jnp.float32),
             jnp.zeros((block_q, d), jnp.float32))
    num_full, num_iter = (
        _k_tile_bounds(q_off, block_q, block_k, num_k_blocks) if causal
        else (num_k_blocks, num_k_blocks))
    carry = _loop(0, num_full, whole, carry)
    if g:
        # Each band goes on with its own rows of m, l and o, and ends with
        # its own piece of lse: one-dimensional values are cut and never put
        # together again, which Mosaic does not lower.
        bands = _loop(num_full, num_iter, cut, [
            tuple(x[first:first + g] for x in carry) for first in chunks])
    else:
        rel = _tile_rel(block_q, block_k) if causal else None
        bands = [_loop(num_full, num_iter, lambda kb, c: whole(
            kb, c, rel >= kb * block_k - q_off), carry)]
    outs, lses = [], []
    for m, l, o in bands:
        l_safe = jnp.maximum(l, 1e-30)
        outs.append(o / l_safe[:, None])
        lses.append(m + jnp.log(l_safe))
    return jnp.concatenate(outs, axis=0), lses


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, *maybe_lse_ref, causal,
                      sm_scale, block_q, block_k, unrolled, heads):
    """``unrolled``: one grid step is a whole block of ``heads`` heads, its
    q tiles walked here with static offsets; otherwise the grid's last axis
    walks them and ``q_ref`` is the tile."""
    import jax.experimental.pallas as pl

    if unrolled:
        offs = [i * block_q for i in range(q_ref.shape[0] // block_q)]
    else:
        offs = [pl.program_id(2) * block_q]
    for q_off in offs:
        rows = pl.ds(q_off, block_q) if unrolled else slice(None)
        q = q_ref[rows, :]
        parts = [_fwd_q_tile(_head_lanes(q, j, heads), q_off, k_ref, v_ref,
                             causal, sm_scale, block_k)
                 for j in range(heads)]
        o_ref[rows, :] = _merge_heads([o for o, _ in parts]).astype(
            o_ref.dtype)
        if maybe_lse_ref:  # omitted on the inference path: nothing reads it
            first = q_off if unrolled else 0
            for pieces in zip(*(lse for _, lse in parts)):  # a band's heads
                n = pieces[0].shape[0]
                maybe_lse_ref[0][:, pl.ds(first, n)] = _pack_rows(pieces)
                first += n


def _flash_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                     dq_ref, *, causal, sm_scale, block_k):
    """The two-kernel backward's dq half: one q tile against its k tiles."""
    import jax.experimental.pallas as pl

    q, s_scale = _fold_scale(q_ref[...], sm_scale)  # [block_q, d]
    do = do_ref[...]                   # [block_q, d]
    lse = lse_ref[0, :]                # [block_q] (sublane 0 of 8)
    delta = delta_ref[0, :]            # [block_q]
    block_q = q.shape[0]
    q_off = pl.program_id(2) * block_q
    num_k_blocks = k_ref.shape[0] // block_k
    g, chunks = _diagonal_chunks(block_q, block_k) if causal else (0, [])

    def product(first, keys, shown=None):
        """ds @ k of the q rows from ``first`` on and the keys ``keys``."""
        k_blk, v_blk = k_ref[keys, :], v_ref[keys, :]
        s = _dot_nt(q[first:], k_blk)
        if s_scale is not None:
            s = s * s_scale
        if shown is not None:
            s = jnp.where(shown, s, NEG_INF)
        # lse is finite (every row sees column 0), so a masked entry's
        # exp(NEG_INF - lse) is exactly 0 with no second select.
        p = jnp.exp(s - lse[first:][:, None])
        dp = _dot_nt(do[first:], v_blk)
        ds = (p * (dp - delta[first:][:, None])).astype(k_blk.dtype)
        return jnp.dot(ds, k_blk, preferred_element_type=jnp.float32)

    def whole(kb, dq, shown=None):
        return dq + product(0, pl.ds(kb * block_k, block_k), shown)

    def cut(kb, dq):
        """A masked tile on the diagonal, a chunk of keys at a time against
        the rows from the chunk's first on (``_fwd_q_tile``); lse is known,
        so the rows are never put side by side."""
        return dq + jnp.concatenate(_sum_bands([
            (first, product(first, pl.ds(kb * block_k + first, g),
                            _tile_rel(block_q - first, g) >= 0))
            for first in chunks], g), axis=0)

    dq = jnp.zeros((block_q, q.shape[-1]), jnp.float32)
    num_full, num_iter = (
        _k_tile_bounds(q_off, block_q, block_k, num_k_blocks) if causal
        else (num_k_blocks, num_k_blocks))
    dq = _loop(0, num_full, whole, dq)
    if g:
        dq = _loop(num_full, num_iter, cut, dq)
    else:
        rel = _tile_rel(block_q, block_k) if causal else None
        dq = _loop(num_full, num_iter, lambda kb, dq: whole(
            kb, dq, rel >= kb * block_k - q_off), dq)
    # ds = p * (dp - delta) * scale: the scale goes once onto the
    # [block_q, d] result, not onto every [block_q, block_k] tile.
    dq_ref[...] = (dq * sm_scale).astype(dq_ref.dtype)


def _bwd_k_tile(k_blk, v_blk, k_off, q_ref, do_ref, lse_ref, delta_ref,
                dq, causal, sm_scale, block_q, row=0):
    """One k tile against the q tiles at or under its diagonal: (dk, dv)
    float32, unscaled.  The tiles are [block_k, block_q], the transposed
    orientation: lse and delta then broadcast along sublanes as they are
    stored, and dk, dv need no transpose.  With ``dq`` (the unrolled
    kernel's list of float32 [block_q, lanes] sums, one a q tile) every
    tile also adds its dq there, so s, p, dp and ds are computed once a
    tile: 5 matmuls where the two-kernel form runs 7.  Where the block holds
    several heads, ``k_blk`` and ``v_blk`` are one head's (``_head_lanes``):
    dq lands on that head's lanes, and dk and dv are right on them;
    ``row`` is the sublane of the head's lse and delta."""
    import jax.experimental.pallas as pl

    block_k = k_blk.shape[0]
    num_q_blocks = q_ref.shape[0] // block_q
    k_s, s_scale = _fold_scale(k_blk, sm_scale)
    g, chunks = _diagonal_chunks(block_k, block_q) if causal else (0, [])

    def products(n, rows, shown=None):
        """(dk, dv, ds^T) of the first ``n`` k rows and the queries
        ``rows``: [n, lanes] float32 twice, and [n, rows] as the operands
        are stored."""
        q_blk, do_blk = q_ref[rows, :], do_ref[rows, :]
        st = _dot_nt(k_s[:n], q_blk)
        if s_scale is not None:
            st = st * s_scale
        if shown is not None:
            st = jnp.where(shown, st, NEG_INF)
        pt = jnp.exp(st - lse_ref[row:row + 1, rows])
        dv = jnp.dot(pt.astype(do_blk.dtype), do_blk,
                     preferred_element_type=jnp.float32)
        dpt = _dot_nt(v_blk[:n], do_blk)
        dst = (pt * (dpt - delta_ref[row:row + 1, rows])).astype(q_blk.dtype)
        return (jnp.dot(dst, q_blk, preferred_element_type=jnp.float32), dv,
                dst)

    def dq_product(dst, k_rows):
        return jax.lax.dot_general(dst, k_rows, (((0,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32)

    def whole(qb, carry, shown=None):
        dk, dv, dst = products(block_k, pl.ds(qb * block_q, block_q), shown)
        if dq is not None:
            dq[qb] = dq[qb] + dq_product(dst, k_blk)
        return carry[0] + dk, carry[1] + dv

    def cut(qb, carry):
        """A masked tile on the diagonal, a chunk of queries at a time
        against the k rows up to the chunk's last; dq, which contracts the
        k rows, a chunk of k rows at a time against the queries from its
        first on."""
        dks, dvs, dsts = zip(*(
            products(first + g, pl.ds(qb * block_q + first, g),
                     _tile_rel(first + g, g, transposed=True) >= -first)
            for first in chunks))
        if dq is not None:
            dq[qb] = dq[qb] + jnp.concatenate(_sum_bands([
                (first, dq_product(
                    jnp.concatenate([dst[first:first + g]
                                     for dst in dsts[j:]], axis=1),
                    k_blk[first:first + g]))
                for j, first in enumerate(chunks)], g), axis=0)
        return tuple(c + jnp.concatenate(_sum_bands(
            [(0, part) for part in parts], g), axis=0)
            for c, parts in zip(carry, (dks, dvs)))

    carry = (jnp.zeros(k_blk.shape, jnp.float32),
             jnp.zeros(v_blk.shape, jnp.float32))
    first, first_full = (
        _q_tile_bounds(k_off, block_k, block_q, num_q_blocks) if causal
        else (0, 0))
    if g:
        carry = _loop(first, first_full, cut, carry)
    else:
        rel = _tile_rel(block_k, block_q, transposed=True) if causal else None
        carry = _loop(first, first_full, lambda qb, c: whole(
            qb, c, rel >= k_off - qb * block_q), carry)
    return _loop(first_full, num_q_blocks, whole, carry)


def _flash_dkv_kernel(k_ref, v_ref, q_ref, do_ref, lse_ref, delta_ref,
                      dk_ref, dv_ref, *, causal, sm_scale, block_q):
    """The two-kernel backward's dk/dv half: the grid walks the k tiles."""
    import jax.experimental.pallas as pl

    k_off = pl.program_id(2) * k_ref.shape[0]
    dk, dv = _bwd_k_tile(k_ref[...], v_ref[...], k_off, q_ref, do_ref,
                         lse_ref, delta_ref, None, causal, sm_scale, block_q)
    # ds = p * (dp - delta) * scale: the scale goes once onto the
    # [block_k, d] result, not onto every tile of ds.
    dk_ref[...] = (dk * sm_scale).astype(dk_ref.dtype)
    dv_ref[...] = dv.astype(dv_ref.dtype)


def _flash_bwd_kernel(k_ref, v_ref, q_ref, do_ref, o_ref, lse_ref, dk_ref,
                      dv_ref, dq_ref, delta_ref, *, causal, sm_scale,
                      block_q, block_k, heads):
    """The fused backward: one grid step is a whole block of ``heads``
    heads, its k tiles walked here with static offsets, dq summed a q tile
    beside dk and dv (every head's on its own lanes of the one sum).

    delta_i = sum_d dO_i * O_i, the softmax-normalisation term of dS, is
    made here first, into the scratch ``delta_ref`` laid out as lse is: the
    blocks are in VMEM anyway, and outside the kernel a sum over 64 of a
    row's 1,024 columns cost a float32 product written out, a relayout
    and a reduction (3.4 ms of a 146.6 ms train step: my chip run, PR 49)."""
    import jax.experimental.pallas as pl

    lanes = q_ref.shape[1]
    num_q_blocks = q_ref.shape[0] // block_q
    for i in range(num_q_blocks):
        rows = pl.ds(i * block_q, block_q)
        prod = (do_ref[rows, :].astype(jnp.float32)
                * o_ref[rows, :].astype(jnp.float32))
        delta_ref[:, rows] = _pack_rows([
            jnp.sum(_head_lanes(prod, j, heads), axis=-1)
            for j in range(heads)])
    dq = [jnp.zeros((block_q, lanes), jnp.float32)
          for _ in range(num_q_blocks)]
    for k_off in range(0, k_ref.shape[0], block_k):
        cols = pl.ds(k_off, block_k)
        k_blk, v_blk = k_ref[cols, :], v_ref[cols, :]
        parts = [_bwd_k_tile(_head_lanes(k_blk, j, heads),
                             _head_lanes(v_blk, j, heads), k_off, q_ref,
                             do_ref, lse_ref, delta_ref, dq, causal,
                             sm_scale, block_q, row=_row_of(j, heads))
                 for j in range(heads)]
        dk = _merge_heads([dk for dk, _ in parts])
        dv = _merge_heads([dv for _, dv in parts])
        dk_ref[cols, :] = (dk * sm_scale).astype(dk_ref.dtype)
        dv_ref[cols, :] = dv.astype(dv_ref.dtype)
    for i, dq_i in enumerate(dq):
        dq_ref[pl.ds(i * block_q, block_q), :] = (dq_i * sm_scale).astype(
            dq_ref.dtype)


def _flash_bwd_kernel_one_result(k_ref, v_ref, q_ref, do_ref, o_ref, lse_ref,
                                 dqkv_ref, delta_ref, buf, sem, **kw):
    """The fused backward where q, k and v are columns of one array, and so
    are dq, dk and dv: a grid step may write one block of a result through
    its BlockSpec and this one writes three, so the result stays in HBM.
    The kernel puts the step's three pieces into ``buf[slot]`` and three
    copies carry them out under the next step's work; a slot is written
    again only after its copies of two steps before are through, and the
    last step waits for all.  (Grid steps run one after the other on a
    chip's one core, which this counts on.  A third grid axis that handed
    dk and dv over through the BlockSpec read 0.085 ms a layer more: its
    two steps had no work to hide their copy behind.  My chip run, PR 49.)"""
    import jax.experimental.pallas as pl
    import jax.experimental.pallas.tpu as pltpu

    b, n = pl.program_id(0), pl.program_id(1)
    blocks, lanes = pl.num_programs(1), q_ref.shape[1]
    step = b * blocks + n
    last = pl.num_programs(0) * blocks - 1
    slot = step % 2

    def copies(slot):
        # A wait needs the copy's size and semaphore only, so the steps
        # before are waited for through this step's descriptors.
        return [pltpu.make_async_copy(
            buf.at[slot, part],
            dqkv_ref.at[b, :, pl.ds(pl.multiple_of(
                (part * blocks + n) * lanes, lanes), lanes)],
            sem.at[slot, part]) for part in range(3)]

    @pl.when(step >= 2)
    def _():
        for copy in copies(slot):
            copy.wait()

    _flash_bwd_kernel(k_ref, v_ref, q_ref, do_ref, o_ref, lse_ref,
                      buf.at[slot, 1], buf.at[slot, 2], buf.at[slot, 0],
                      delta_ref, **kw)
    for copy in copies(slot):
        copy.start()

    @pl.when(step == last)
    def _():
        for copy in copies(slot):
            copy.wait()

    @pl.when((step == last) & (step >= 1))
    def _():
        for copy in copies(1 - slot):
            copy.wait()


# What a kernel may take in VMEM without asking for more (Mosaic's scoped
# default on v5e), and how many tile bodies the unrolled kernels may hold:
# the compile time grows with them (10 tiles 1-3 s a kernel, 136 tiles 14 s,
# compiled here for a described v5e) and is part of a train cell's set-up.
_VMEM_BUDGET = 16 * 1024 * 1024
_MAX_UNROLLED_TILES = 16
_LANES = 128  # of a vector register, and of a column block's tiling


def _whole_head_fits(lq: int, lk: int, d: int, itemsize: int, block_q: int,
                     block_k: int, causal: bool, heads: int = 1) -> bool:
    """Whether the kernels take a whole block of ``heads`` heads a grid
    step, their tile loops unrolled (the forward) and dq accumulated beside
    dk and dv (the fused backward).  The backward's residency decides: Q, K,
    V, dO in and dQ, dK, dV out whole, the lane dimension padded to 128 (so
    two heads of 64 take what one took) and every pipelined block held
    twice, lse and delta on 8 sublanes; half the budget is left to O (read once, for delta), the [block_k, block_q] float32 tiles
    (s, p, dp, ds and their casts), the float32 sums and the compiler's own
    scratch.  Every head of the block has tile bodies of its own; a masked
    tile that is cut into chunks (PR 54) is still one body, smaller than
    the uncut one: the compiler's time follows the elements a body holds,
    and the kernels compile for a described v5e in the time they took uncut
    ([8, 1024, 16, 64] forward and backward 1.8 s uncut, 1.5 s cut; [1,
    2048, 8, 128] 3.3 s either way).  With and without the cut, ms a layer
    on a v5e: ``_auto_blocks``."""
    tiles = (causal_tile_schedule(lq, lk, block_q, block_k)["visited"]
             if causal else (lq // block_q) * (lk // block_k))
    lanes = -(-heads * d // _LANES) * _LANES
    blocks = 2 * (3 * lq + 4 * lk) * lanes * itemsize
    rows = 2 * 2 * _LSE_SUBLANES * lq * 4
    return (tiles * heads <= _MAX_UNROLLED_TILES
            and blocks + rows <= _VMEM_BUDGET // 2)


def _heads_per_block(lq: int, lk: int, h: int, d: int, itemsize: int,
                     block_q: int, block_k: int, causal: bool) -> int:
    """How many heads a column block of ``[B, L, H * D]`` holds where the
    kernels can read the operands as the projection wrote them: the heads
    that fill 128 lanes (two of 64, four of 32), or one whose width is a
    multiple of 128.  0 where they cannot, and the operands go head-major
    (``[B * H, L, D]``, a transpose each way) as they always did: a head
    count or width that does not fill whole blocks of 128 lanes, a block of
    heads past the whole-head kernels' residency or tile count, and the
    rolled forms past 2,048 tokens."""
    if d % _LANES == 0:
        heads = 1
    elif _LANES % d == 0 and h % (_LANES // d) == 0:
        heads = _LANES // d
    else:
        return 0
    fits = _whole_head_fits(lq, lk, d, itemsize, block_q, block_k, causal,
                            heads)
    return heads if fits else 0


def _column_spec(rows: int, lanes: int, first: int):
    """The [rows, lanes] block of a ``[B, L, columns]`` array at (batch i,
    column block ``first + n``) of a grid whose first two axes are (i, n):
    ``first`` is where in the array the operand starts, 0 for an array of
    its own and H * D / lanes, twice that, for k and v inside a fused qkv."""
    import jax.experimental.pallas as pl

    return pl.BlockSpec((None, rows, lanes),
                        lambda i, n, *_: (i, 0, first + n))


def _tile_spec(rows: int, lanes: int):
    """The [rows, lanes] tile j of column block n: the rolled kernels'."""
    import jax.experimental.pallas as pl

    return pl.BlockSpec((None, rows, lanes), lambda i, n, j: (i, j, n))


def _row_spec(rows: int, tiled: bool = False):
    """lse and delta, ``[B, blocks, 8, L]``: a column block's tile of rows,
    whole or the tile j of the rolled kernels."""
    import jax.experimental.pallas as pl

    return pl.BlockSpec((None, None, _LSE_SUBLANES, rows),
                        (lambda i, n, j: (i, n, 0, j)) if tiled
                        else (lambda i, n, *_: (i, n, 0, 0)))


def _operands(ops, lanes: int):
    """(q, k, v, column blocks of one of them, the column block at which
    each starts) of the kernels' operands: three ``[B, L, C]`` arrays of
    their own, or one ``[B, L, 3 * C]`` that is all three side by side."""
    if len(ops) == 3:
        return ops + (ops[0].shape[2] // lanes, (0, 0, 0))
    blocks = ops[0].shape[2] // (3 * lanes)
    return ops * 3 + (blocks, (0, blocks, 2 * blocks))


# The kernels are jitted on their own so that the layers of a program share
# one trace and one lowering of each: a kernel's body is unrolled over its
# tiles and heads, and a program lowers again in every process that runs
# it, cached or not (trace and lowering of the 24-layer train step for a
# described v5e: PERF.md section 6, PR 49).  The compiler inlines the call.
@functools.partial(jax.jit, static_argnames=(
    "d", "heads", "whole", "causal", "sm_scale", "block_q", "block_k",
    "interpret", "with_lse"))
def _fwd_call(ops, *, d, heads, whole, causal, sm_scale, block_q, block_k,
              interpret, with_lse=True):
    """ops: (q, k, v) ``[B, L, C]`` or (qkv,) ``[B, L, 3 * C]``, C a
    multiple of the block's ``heads * d`` lanes → (out ``[B, Lq, C]``, lse
    ``[B, C / lanes, 8, Lq]`` float32 as ``_pack_rows`` lays it, or None).
    The two forms differ in where the index maps find k and v, and in
    nothing else.  ``whole``: the grid is (batch, column blocks) with whole
    blocks, the q tiles unrolled; otherwise (batch, column blocks, q
    tiles)."""
    import jax.experimental.pallas as pl

    lanes = heads * d
    q, k, v, blocks, first = _operands(ops, lanes)
    b, lq, lk = q.shape[0], q.shape[1], k.shape[1]
    kernel = functools.partial(_flash_fwd_kernel, causal=causal,
                               sm_scale=sm_scale, block_q=block_q,
                               block_k=block_k, unrolled=whole, heads=heads)
    if whole:
        grid = (b, blocks)
        q_spec = o_spec = _column_spec(lq, lanes, 0)
        lse_spec = _row_spec(lq)
    else:
        grid = (b, blocks, lq // block_q)
        q_spec = o_spec = _tile_spec(block_q, lanes)
        lse_spec = _row_spec(block_q, tiled=True)
    out_specs = [o_spec]
    out_shape = [jax.ShapeDtypeStruct((b, lq, blocks * lanes), q.dtype)]
    if with_lse:
        out_specs.append(lse_spec)
        out_shape.append(jax.ShapeDtypeStruct(
            (b, blocks, _LSE_SUBLANES, lq), jnp.float32))
    # K and V lie whole beside the q tiles, each held twice by the pipeline:
    # where that nears the compiler's default of 16 MiB of scoped VMEM (a
    # head of 192, padded to 256 lanes, over 8,192 rows is 16 MiB and over
    # 16,384 rows 32 MiB: neither compiles under the default) the call asks
    # for what it holds; every smaller call is compiled as it was.
    resident = 2 * 2 * lk * (-(-lanes // _LANES) * _LANES) * k.dtype.itemsize
    more = {}
    if resident > _VMEM_BUDGET * 3 // 4:
        import jax.experimental.pallas.tpu as pltpu

        more["compiler_params"] = pltpu.CompilerParams(
            vmem_limit_bytes=resident + _VMEM_BUDGET)
    res = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[q_spec, _column_spec(lk, lanes, first[1]),
                  _column_spec(lk, lanes, first[2])],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
        name="flash_fwd",
        **more,
    )(q, k, v)
    return res if with_lse else (res[0], None)


@functools.partial(jax.jit, static_argnames=(
    "d", "heads", "whole", "causal", "sm_scale", "block_q", "block_k",
    "interpret"))
def _bwd_call(ops, out, lse, do, *, d, heads, whole, causal, sm_scale,
               block_q, block_k, interpret):
    """The gradients of ``ops`` in their own form: (dq, dk, dv), or the one
    (dqkv,) of a fused qkv.  ``whole``: ``flash_bwd``, one kernel, a whole
    block of heads a grid step; otherwise ``flash_dkv`` and ``flash_dq``,
    each walking its tiles on the grid with K, V (or Q, dO) whole beside
    them (one head a block, three operands)."""
    import jax.experimental.pallas as pl
    import jax.experimental.pallas.tpu as pltpu

    lanes = heads * d
    q, k, v, blocks, first = _operands(ops, lanes)
    b, lq, lk = q.shape[0], q.shape[1], k.shape[1]
    q_whole, k_whole, v_whole = (
        _column_spec(x.shape[1], lanes, f) for x, f in zip((q, k, v), first))
    do_whole, row_whole = _column_spec(lq, lanes, 0), _row_spec(lq)
    like = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)
    statics = dict(causal=causal, sm_scale=sm_scale, block_q=block_q)
    if whole:
        call = functools.partial(
            pl.pallas_call, grid=(b, blocks),
            in_specs=[k_whole, v_whole, q_whole, do_whole, do_whole,
                      row_whole],
            interpret=interpret, name="flash_bwd")
        statics.update(block_k=block_k, heads=heads)
        delta = pltpu.VMEM((_LSE_SUBLANES, lq), jnp.float32)
        if len(ops) == 3:
            dk, dv, dq = call(
                functools.partial(_flash_bwd_kernel, **statics),
                out_specs=[_column_spec(lk, lanes, 0)] * 2 + [do_whole],
                out_shape=[like(k), like(v), like(q)],
                scratch_shapes=[delta],
            )(k, v, q, do, out, lse)
            return dq, dk, dv
        return (call(
            functools.partial(_flash_bwd_kernel_one_result, **statics),
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            out_shape=like(q),
            scratch_shapes=[delta, pltpu.VMEM((2, 3, lq, lanes), q.dtype),
                            pltpu.SemaphoreType.DMA((2, 3))],
        )(k, v, q, do, out, lse),)

    # delta_i = sum_d dO_i * O_i — the softmax-normalization term of dS —
    # as the kernels read lse (head-major operands: a sum over the minor
    # dimension, which the compiler fuses).
    delta = jnp.sum((do.astype(jnp.float32) * out.astype(jnp.float32))
                    .reshape(b, lq, blocks, d), axis=-1)
    delta = jnp.broadcast_to(delta.transpose(0, 2, 1)[:, :, None, :],
                             lse.shape)
    k_tile, q_tile = _tile_spec(block_k, lanes), _tile_spec(block_q, lanes)
    dk, dv = pl.pallas_call(
        functools.partial(_flash_dkv_kernel, **statics),
        grid=(b, blocks, lk // block_k),
        in_specs=[k_tile, k_tile, q_whole, do_whole, row_whole, row_whole],
        out_specs=[k_tile, k_tile],
        out_shape=[like(k), like(v)],
        interpret=interpret,
        name="flash_dkv",
    )(k, v, q, do, lse, delta)
    row_tile = _row_spec(block_q, tiled=True)
    dq = pl.pallas_call(
        functools.partial(_flash_dq_kernel, causal=causal, sm_scale=sm_scale,
                          block_k=block_k),
        grid=(b, blocks, lq // block_q),
        in_specs=[q_tile, k_whole, v_whole, q_tile, row_tile, row_tile],
        out_specs=q_tile,
        out_shape=like(q),
        interpret=interpret,
        name="flash_dq",
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


def _head_major(x, h: int):
    """[B, L, H * D] → [B * H, L, D]: a head is an array of one column."""
    b, l, c = x.shape
    return x.reshape(b, l, h, c // h).transpose(0, 2, 1, 3).reshape(
        b * h, l, c // h)


def _head_minor(x, h: int):
    """[B * H, L, D] → [B, L, H * D]."""
    bh, l, d = x.shape
    return x.reshape(bh // h, h, l, d).transpose(0, 2, 1, 3).reshape(
        bh // h, l, h * d)


def _form(lq, lk, h, d, dtype, causal, sm_scale, block_q, block_k, interpret):
    """(heads a column block, or 0 for head-major operands; the kernels'
    static arguments), read off the call's shapes."""
    heads = _heads_per_block(lq, lk, h, d, dtype.itemsize, block_q, block_k,
                             causal)
    whole = bool(heads) or _whole_head_fits(lq, lk, d, dtype.itemsize,
                                            block_q, block_k, causal)
    return heads, dict(
        d=d, heads=max(heads, 1), whole=whole, causal=causal,
        sm_scale=sm_scale if sm_scale is not None else d ** -0.5,
        block_q=block_q, block_k=block_k, interpret=interpret)


def _flash_forward(ops, h, causal, sm_scale, block_q, block_k, interpret,
                   with_lse):
    """(out [B, Lq, H * D], the VJP's residuals).  The residuals of the
    column-block form are the caller's own arrays and the result itself."""
    _, lq, columns = ops[0].shape
    d = columns // h // (3 if len(ops) == 1 else 1)
    heads, statics = _form(lq, ops[-1].shape[1], h, d, ops[0].dtype, causal,
                           sm_scale, block_q, block_k, interpret)
    if not heads:
        ops = tuple(_head_major(x, h) for x in ops)
    out, lse = _fwd_call(ops, with_lse=with_lse, **statics)
    return (out if heads else _head_minor(out, h)), (ops, out, lse)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4, 5, 6))
def _flash(ops, h, causal, sm_scale, block_q, block_k, interpret):
    """ops: (q, k, v), ``[B, L, H * D]`` each, or (qkv,) ``[B, L, 3 * H *
    D]`` where ``_heads_per_block`` holds it → ``[B, Lq, H * D]``."""
    # Primal (inference) path: skip the lse output entirely — nothing
    # reads it outside the VJP, and it costs an HBM write per call.
    return _flash_forward(ops, h, causal, sm_scale, block_q, block_k,
                          interpret, with_lse=False)[0]


def _flash_vjp_fwd(ops, h, causal, sm_scale, block_q, block_k, interpret):
    return _flash_forward(ops, h, causal, sm_scale, block_q, block_k,
                          interpret, with_lse=True)


def _flash_vjp_bwd(h, causal, sm_scale, block_q, block_k, interpret,
                   residuals, g):
    ops, out, lse = residuals
    _, lq, columns = g.shape
    heads, statics = _form(lq, ops[-1].shape[1], h, columns // h, g.dtype,
                           causal, sm_scale, block_q, block_k, interpret)
    if heads:
        return (_bwd_call(ops, out, lse, g, **statics),)
    grads = _bwd_call(ops, out, lse, _head_major(g, h), **statics)
    return (tuple(_head_minor(x, h) for x in grads),)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def _auto_blocks(lq: int, lk: int, d: int, causal: bool) -> Tuple[int, int]:
    """(block_q, block_k) by shape.  Causal calls from 1,024 keys on take
    (512, 512): no tile lies wholly above the diagonal at any length (at
    1,024 three of four tiles are visited, two of them masked), and up to
    2,048 a head's tiles are few enough for the unrolled forward and the
    fused backward (``_whole_head_fits``).  Measured on a v5e, bf16 causal,
    batch x heads x length = 131,072 rows (d = 64) or 65,536 (d = 128), ms
    a call, forward + backward (PR 39's chip runs; the parent's tiles are
    (256, 1024); ``*``: the form the code takes there is the rolled,
    two-kernel one):

        L, d       parent      256x256     256x512     512x512     512x1024
        1024,  64  0.89+2.21   0.48+0.95   0.49+0.97   0.49+0.96   0.58+1.17
        1024, 128  0.50+1.11   0.29+0.47   0.29+0.48   0.29+0.47   0.34+0.59
        2048,  64  0.62+1.61   0.83+1.59*  0.57+1.39*  0.40+0.69   0.42+0.79
        2048, 128  0.60+1.60   0.83+1.61*  0.58+1.40*  0.37+0.69   0.42+0.79
        4096,  64  0.85+2.49   1.36+2.77*  0.84+2.26*  0.85+1.89*  0.87+2.00*
        4096, 128  0.87+2.49   1.36+2.82*  0.85+2.31*  0.86+1.91*  0.90+2.01*

    At 4,096 the parent's (256, 1024) already skipped (62.5% of the square
    visited) and is level in the forward (0.83 with this code); the
    backward is where (512, 512) gains.  Rolled, small tiles lose to the
    loop's own cost (256 x 256 at 1,024: 1.04 + 2.02 against 0.49 + 0.95
    unrolled).  Non-causal calls keep (256, 1024): (512, 512) read the
    same there (1,024: 0.58 + 1.17 against 0.57 + 1.19).  Under 1,024 keys
    the choice is the one the code always made, (128, 128): not measured
    beyond [16, 512, 16, 64] (``mha_attention``).

    The table's operands were head-major arrays of their own.  Since PR 49
    the whole-head forms read column blocks of ``[B, L, H * D]`` where
    ``_heads_per_block`` allows; at these tiles, forward + backward of one
    layer as a function of a fused projection (its split and the gradient's
    concatenation included; ``tools/flash_layout_probe.py``, my chip run,
    PR 49), ms: head-major / column blocks / out of the fused array:

        [8, 1024, 16, 64]   1.74 / 1.23 / 1.04     (two heads a block)
        [8, 1024,  8, 128]  0.77 / 0.73 / 0.54     (a head a block)
        [4, 2048,  8, 128]  1.07 / 1.04 / 0.85
        [8, 1024, 32, 32]   3.28 / 2.22 / 2.04     (four heads a block)

    Since PR 54 a masked tile is cut (``_diagonal_chunks``) and multiplies
    only its sub-blocks at or under the diagonal.  The same probe with
    ``--chunks`` (my chip runs, PR 54), the form the shape rule gives, ms a
    layer, forward alone + forward and backward; the share of the square
    that is multiplied under each:

        chunk of columns     none         256          128 (taken)
        [8, 1024, 16, 64]    0.325 1.047  0.287 0.900  0.285 0.847
        [8, 1024,  8, 128]   0.208 0.548  0.209 0.485  0.207 0.463
        [4, 2048,  8, 128]   0.266 0.856  0.243 0.783  0.246 0.760
        [8, 1024, 32, 32]    0.642 2.040  0.574 1.763  0.575 1.654
        [2, 4096,  8, 128]*  0.825 2.753  0.787 2.636  0.794 2.641
        multiplied at 1,024  0.75         0.625        0.5625

    Which way a tile is cut decides whether the cut pays.  The MXU holds a
    128 x 128 block of one operand still and streams the other's rows past
    it, and a block costs about as much to load as 128 rows to stream.
    Bands of *rows* that each meet all their keys multiply the same
    sub-blocks with runs of 128 or 256 rows a block where the whole tile
    has 512, and read, at [8, 1024, 16, 64]: bands of 256 rows 0.330 and
    0.954, bands of 128 rows 0.356 and 0.972: the forward no faster than
    uncut.  Chunks of *columns* (of the operand that stands still) keep the
    runs as long as the mask allows, 512, 384, 256 and 128 rows, and are
    the numbers above.  The chunk is 128 columns, the block's own width:
    the most the mask lets one skip, and no slower than 256 anywhere.  The
    cut adds 0.2-0.3 s of lowering a program and nothing to its compile
    (1.2-3.3 s a kernel for a described v5e, with and without)."""
    def pick(l, target):
        b = target
        while b > 128 and l % b:
            b //= 2
        return b if l % b == 0 else 128

    if lk < 1024:
        return pick(lq, 128), pick(lk, 128)
    if causal:
        return pick(lq, 512), pick(lk, 512)
    return pick(lq, 256), pick(lk, 1024)


def _blocks(lq, lk, d, causal, block_q, block_k):
    auto_q, auto_k = _auto_blocks(lq, lk, d, causal)
    block_q = auto_q if block_q is None else block_q
    block_k = auto_k if block_k is None else block_k
    if lq % block_q or lk % block_k:
        raise ValueError(f"sequence lengths ({lq},{lk}) must be multiples of "
                         f"block sizes ({block_q},{block_k})")
    if causal and lq != lk:
        # The kernels' causal mask is rows >= cols (diagonal-aligned,
        # self-attention); the XLA reference bottom-right-aligns the
        # triangle for lq != lk.  Refuse rather than silently divergent.
        raise ValueError(f"causal flash attention requires lq == lk "
                         f"(got {lq} vs {lk}); use the XLA path for "
                         f"decode-style windows")
    return block_q, block_k


def flash_attention(q, k, v, causal: bool = True,
                    sm_scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: bool = False) -> jax.Array:
    """Fused attention on TPU via Pallas, differentiable (custom VJP
    recomputes P blockwise from the saved log-sum-exp — the flash
    backward). q,k,v: [B, L, H, D] → [B, L, H, D].

    Block sizes default to a measured per-length choice (_auto_blocks);
    pass them explicitly to override.  The kernels read the operands as
    ``[B, L, H * D]``, which costs nothing, where ``_heads_per_block`` says
    they can, and head-major otherwise."""
    b, lq, h, d = q.shape
    lk = k.shape[1]
    block_q, block_k = _blocks(lq, lk, d, causal, block_q, block_k)
    ops = (q.reshape(b, lq, h * d), k.reshape(b, lk, h * d),
           v.reshape(b, lk, h * d))
    return _flash(ops, h, causal, sm_scale, block_q, block_k,
                  interpret).reshape(b, lq, h, d)


def flash_attention_qkv(qkv, num_heads: int, causal: bool = True,
                        sm_scale: Optional[float] = None,
                        block_q: Optional[int] = None,
                        block_k: Optional[int] = None,
                        interpret: bool = False) -> jax.Array:
    """``flash_attention`` on a fused projection: qkv ``[B, L, 3 * H * D]``
    (q's columns, then k's, then v's, a head's D columns together) →
    ``[B, L, H * D]``.  The same kernels find q, k and v in the one array
    through their index maps, and the backward writes the one dqkv: no
    split before, no concatenation after.  Where the shapes do not allow
    it (``_heads_per_block``) the array is split and goes the other way."""
    b, l, columns = qkv.shape
    d = columns // (3 * num_heads)
    block_q, block_k = _blocks(l, l, d, causal, block_q, block_k)
    ops = (qkv,)
    if not _heads_per_block(l, l, num_heads, d, qkv.dtype.itemsize, block_q,
                            block_k, causal):
        ops = tuple(jnp.split(qkv, 3, axis=-1))
    return _flash(ops, num_heads, causal, sm_scale, block_q, block_k,
                  interpret)


def mha_attention_lse(q: jax.Array, k: jax.Array, v: jax.Array,
                      causal: bool = True, sm_scale: Optional[float] = None,
                      use_flash: Optional[bool] = None
                      ) -> Tuple[jax.Array, jax.Array]:
    """``mha_attention`` and every query row's log-sum-exp of its scaled
    scores, ``[B, H, Lq]`` float32: for a caller that goes on to merge the
    result with another softmax's part over further keys
    (``ops/eva.py::eva_prefill_attention``).  The same dispatch; the flash
    path is the forward kernel with the residual the backward reads (forward
    only: this function has no gradient of its own)."""
    b, lq, h, d = q.shape
    lk = k.shape[1]
    if use_flash is None:
        use_flash = _flash_by_default(b, lq, lk, h, q.dtype, causal)
    if not use_flash:
        scale = sm_scale if sm_scale is not None else d ** -0.5
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
        if causal:
            s = jnp.where(jnp.tril(jnp.ones((lq, lk), bool), k=lk - lq),
                          s, NEG_INF)
        lse = jax.nn.logsumexp(s, axis=-1)
        p = jnp.exp(s - lse[..., None]).astype(v.dtype)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v), lse
    block_q, block_k = _blocks(lq, lk, d, causal, None, None)
    ops = (q.reshape(b, lq, h * d), k.reshape(b, lk, h * d),
           v.reshape(b, lk, h * d))
    out, (_, _, lse) = _flash_forward(ops, h, causal, sm_scale, block_q,
                                      block_k, False, with_lse=True)
    # lse as ``_pack_rows`` lays it: [B, blocks, 8, Lq], a column block's
    # heads each on sublanes of their own; with head-major operands (no
    # column blocks) [B * H, 1, 8, Lq], a head on all eight
    heads, _ = _form(lq, lk, h, d, q.dtype, causal, sm_scale, block_q,
                     block_k, False)
    if heads:
        lse = lse.reshape(b, h // heads, heads, _LSE_SUBLANES // heads, lq)
        lse = lse[:, :, :, 0]
    else:
        lse = lse[:, 0, 0]
    return out.reshape(b, lq, h, d), lse.reshape(b, h, lq)
