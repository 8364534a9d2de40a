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
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

NEG_INF = -1e30  # large-negative instead of -inf: keeps exp() NaN-free


def mha_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                  causal: bool = True, sm_scale: Optional[float] = None,
                  use_flash: Optional[bool] = None, mesh=None) -> jax.Array:
    """Multi-head attention. q,k,v: [B, L, H, D] → [B, L, H, D].

    Dispatches to the Pallas flash kernel on real TPU backends for long
    sequences, XLA reference otherwise.  Below 1k ctx the XLA path is
    chosen because attention is a tiny FLOP fraction there and the d<128
    lane padding around the custom call costs more than the [L, L]
    materialization it avoids (speeds on the current installation: not
    measured).  A kernel that fails to trace or compile is the failure:
    there is no fallback to the XLA path.

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
    lk = k.shape[1]
    if use_flash is None:
        # [B, H, Lq, Lk] score-matrix footprint the XLA path materializes.
        score_bytes = b * h * lq * lk * q.dtype.itemsize
        use_flash = (jax.default_backend() not in ("cpu",)
                     and lq % 128 == 0 and lk % 128 == 0
                     # Speed crossover is ~1k ctx with the tuned block
                     # sizes; memory can force flash even earlier:
                     # per-layer score matrices past ~512MB OOM real
                     # training steps on a 16G chip.
                     and (lq >= 1024 or score_bytes > 512 * 1024 * 1024)
                     # Flash's causal mask is diagonal-aligned (self-
                     # attention); the XLA path's is bottom-right-aligned
                     # for lq != lk (decode), so only lq == lk may
                     # auto-dispatch.
                     and (not causal or lq == lk))
    if use_flash:
        return flash_attention(q, k, v, causal=causal, sm_scale=sm_scale)
    return _xla_attention(q, k, v, causal, sm_scale)


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
# ---------------------------------------------------------------------------
def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, *maybe_lse_ref, causal,
                      sm_scale, block_k, seq_len_k):
    import jax.experimental.pallas as pl

    # Inputs stay in their storage dtype (bf16 on the training path): the
    # MXU multiplies natively and accumulates f32 via
    # preferred_element_type — casting blocks to f32 up front would force
    # full-precision MXU passes and halve throughput.
    q = q_ref[...]  # [block_q, d] (batch*heads block squeezed)
    block_q = q.shape[0]
    q_off = pl.program_id(1) * block_q

    m = jnp.full((block_q,), NEG_INF, jnp.float32)
    l = jnp.zeros((block_q,), jnp.float32)
    o = jnp.zeros((block_q, q.shape[-1]), jnp.float32)

    num_k_blocks = seq_len_k // block_k

    def make_body(masked):
        def body(kb, carry):
            m, l, o = carry
            k_blk = k_ref[pl.ds(kb * block_k, block_k), :]
            v_blk = v_ref[pl.ds(kb * block_k, block_k), :]
            s = jnp.dot(q, k_blk.T,
                        preferred_element_type=jnp.float32) * sm_scale
            if masked:
                rows = q_off + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
                cols = kb * block_k + jax.lax.broadcasted_iota(
                    jnp.int32, s.shape, 1)
                s = jnp.where(rows >= cols, s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            corr = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new[:, None])
            if masked:
                p = jnp.where(s <= NEG_INF / 2, 0.0, p)
            l_new = l * corr + jnp.sum(p, axis=-1)
            o_new = o * corr[:, None] + jnp.dot(
                p.astype(v_blk.dtype), v_blk,
                preferred_element_type=jnp.float32)
            return m_new, l_new, o_new
        return body

    if causal:
        # Interior blocks (strictly below the diagonal band) skip the mask
        # entirely — the iota/select pair is pure VPU overhead there; only
        # the diagonal-crossing tail blocks mask.  Clamp to num_k_blocks:
        # with lq > lk the tail query rows sit entirely past the last K
        # block and an unclamped bound would read past K/V.
        num_full = jnp.minimum(q_off // block_k, num_k_blocks)
        last = (q_off + block_q + block_k - 1) // block_k
        num_iter = jnp.minimum(last, num_k_blocks)
        m, l, o = jax.lax.fori_loop(0, num_full, make_body(False), (m, l, o))
        m, l, o = jax.lax.fori_loop(num_full, num_iter, make_body(True),
                                    (m, l, o))
    else:
        m, l, o = jax.lax.fori_loop(0, num_k_blocks, make_body(False),
                                    (m, l, o))

    l_safe = jnp.maximum(l, 1e-30)
    o_ref[...] = (o / l_safe[:, None]).astype(o_ref.dtype)
    if maybe_lse_ref:  # omitted on the inference path — nothing reads it
        # lse is broadcast across an 8-sublane dim: TPU block shapes need
        # the last two dims (sublane, lane)-tiled; a lane dim of 1 would
        # pad 128x in HBM, blowing up the residuals kept for the backward.
        lse_ref = maybe_lse_ref[0]
        lse_ref[...] = jnp.broadcast_to((m + jnp.log(l_safe))[None, :],
                                        lse_ref.shape)


def _flash_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                     dq_ref, *, causal, sm_scale, block_k, seq_len_k):
    import jax.experimental.pallas as pl

    q = q_ref[...]                     # [block_q, d]
    do = do_ref[...]                   # [block_q, d]
    lse = lse_ref[0, :]                # [block_q] (sublane 0 of 8)
    delta = delta_ref[0, :]            # [block_q]
    block_q = q.shape[0]
    q_off = pl.program_id(1) * block_q
    num_k_blocks = seq_len_k // block_k

    def make_body(masked):
        def body(kb, dq):
            k_blk = k_ref[pl.ds(kb * block_k, block_k), :]
            v_blk = v_ref[pl.ds(kb * block_k, block_k), :]
            s = jnp.dot(q, k_blk.T,
                        preferred_element_type=jnp.float32) * sm_scale
            if masked:
                rows = q_off + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
                cols = kb * block_k + jax.lax.broadcasted_iota(
                    jnp.int32, s.shape, 1)
                s = jnp.where(rows >= cols, s, NEG_INF)
            p = jnp.exp(s - lse[:, None])
            if masked:
                p = jnp.where(s <= NEG_INF / 2, 0.0, p)
            dp = jnp.dot(do, v_blk.T, preferred_element_type=jnp.float32)
            ds = (p * (dp - delta[:, None]) * sm_scale).astype(k_blk.dtype)
            return dq + jnp.dot(ds, k_blk, preferred_element_type=jnp.float32)
        return body

    dq = jnp.zeros((block_q, q.shape[-1]), jnp.float32)
    if causal:
        # Same lq > lk clamp as the forward (see _flash_fwd_kernel).
        num_full = jnp.minimum(q_off // block_k, num_k_blocks)
        last = (q_off + block_q + block_k - 1) // block_k
        num_iter = jnp.minimum(last, num_k_blocks)
        dq = jax.lax.fori_loop(0, num_full, make_body(False), dq)
        dq = jax.lax.fori_loop(num_full, num_iter, make_body(True), dq)
    else:
        dq = jax.lax.fori_loop(0, num_k_blocks, make_body(False), dq)
    dq_ref[...] = dq.astype(dq_ref.dtype)


def _flash_dkv_kernel(k_ref, v_ref, q_ref, do_ref, lse_ref, delta_ref,
                      dk_ref, dv_ref, *, causal, sm_scale, block_q,
                      seq_len_q):
    import jax.experimental.pallas as pl

    k_blk = k_ref[...]                 # [block_k, d]
    v_blk = v_ref[...]                 # [block_k, d]
    block_k = k_blk.shape[0]
    k_off = pl.program_id(1) * block_k
    num_q_blocks = seq_len_q // block_q

    def make_body(masked):
        def body(qb, carry):
            dk, dv = carry
            q_blk = q_ref[pl.ds(qb * block_q, block_q), :]
            do_blk = do_ref[pl.ds(qb * block_q, block_q), :]
            lse = lse_ref[0, pl.ds(qb * block_q, block_q)]
            delta = delta_ref[0, pl.ds(qb * block_q, block_q)]
            s = jnp.dot(q_blk, k_blk.T,
                        preferred_element_type=jnp.float32) * sm_scale
            if masked:
                rows = qb * block_q + jax.lax.broadcasted_iota(
                    jnp.int32, s.shape, 0)
                cols = k_off + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
                s = jnp.where(rows >= cols, s, NEG_INF)
            p = jnp.exp(s - lse[:, None])
            if masked:
                p = jnp.where(s <= NEG_INF / 2, 0.0, p)
            dv = dv + jnp.dot(p.astype(do_blk.dtype).T, do_blk,
                              preferred_element_type=jnp.float32)
            dp = jnp.dot(do_blk, v_blk.T, preferred_element_type=jnp.float32)
            ds = (p * (dp - delta[:, None]) * sm_scale).astype(q_blk.dtype)
            dk = dk + jnp.dot(ds.T, q_blk, preferred_element_type=jnp.float32)
            return dk, dv
        return body

    dk = jnp.zeros(k_blk.shape, jnp.float32)
    dv = jnp.zeros(v_blk.shape, jnp.float32)
    if causal:
        # Only q blocks at or past this k block's diagonal contribute;
        # blocks fully below the diagonal band skip the mask.
        first = k_off // block_q
        first_full = (k_off + block_k + block_q - 1) // block_q
        first_full = jnp.minimum(first_full, num_q_blocks)
        dk, dv = jax.lax.fori_loop(first, first_full, make_body(True),
                                   (dk, dv))
        dk, dv = jax.lax.fori_loop(first_full, num_q_blocks,
                                   make_body(False), (dk, dv))
    else:
        dk, dv = jax.lax.fori_loop(0, num_q_blocks, make_body(False),
                                   (dk, dv))
    dk_ref[...] = dk.astype(dk_ref.dtype)
    dv_ref[...] = dv.astype(dv_ref.dtype)


_LSE_SUBLANES = 8  # minimum sublane tiling for an f32 operand


def _flash_fwd(q, k, v, causal, sm_scale, block_q, block_k, interpret,
               with_lse=True):
    import jax.experimental.pallas as pl

    b, lq, h, d = q.shape
    lk = k.shape[1]
    scale = sm_scale if sm_scale is not None else d ** -0.5
    # Fold batch and heads into the grid's first dimension.
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, lq, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * h, lk, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * h, lk, d)

    kernel = functools.partial(_flash_fwd_kernel, causal=causal,
                               sm_scale=scale, block_k=block_k,
                               seq_len_k=lk)
    out_specs = [pl.BlockSpec((None, block_q, d), lambda i, j: (i, j, 0))]
    out_shape = [jax.ShapeDtypeStruct((b * h, lq, d), q.dtype)]
    if with_lse:
        out_specs.append(pl.BlockSpec((None, _LSE_SUBLANES, block_q),
                                      lambda i, j: (i, 0, j)))
        out_shape.append(jax.ShapeDtypeStruct(
            (b * h, _LSE_SUBLANES, lq), jnp.float32))
    res = pl.pallas_call(
        kernel,
        grid=(b * h, lq // block_q),
        in_specs=[
            pl.BlockSpec((None, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, lk, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((None, lk, d), lambda i, j: (i, 0, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
        name="flash_fwd",
    )(qf, kf, vf)
    if not with_lse:
        return res[0], None, (qf, kf, vf)
    out, lse = res
    # Keep only sublane 0 as the residual: [bh, lq] is compact in HBM,
    # while the broadcast copy would be carried for every layer.
    return out, lse[:, 0, :], (qf, kf, vf)


def _flash_bwd(q, k, v, out, lse, do, causal, sm_scale, block_q, block_k,
               interpret):
    import jax.experimental.pallas as pl

    bh, lq, d = q.shape
    lk = k.shape[1]
    scale = sm_scale if sm_scale is not None else d ** -0.5
    # delta_i = sum_d dO_i * O_i — the softmax-normalization term of dS.
    delta2 = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                     axis=-1)  # [bh, lq]
    # Re-broadcast the row vectors across the 8-sublane tiling dim the
    # kernels read (transient, not a residual).
    lse8 = jnp.broadcast_to(lse[:, None, :], (bh, _LSE_SUBLANES, lq))
    delta8 = jnp.broadcast_to(delta2[:, None, :], (bh, _LSE_SUBLANES, lq))

    dq = pl.pallas_call(
        functools.partial(_flash_dq_kernel, causal=causal, sm_scale=scale,
                          block_k=block_k, seq_len_k=lk),
        grid=(bh, lq // block_q),
        in_specs=[
            pl.BlockSpec((None, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, lk, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((None, lk, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((None, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, _LSE_SUBLANES, block_q),
                         lambda i, j: (i, 0, j)),
            pl.BlockSpec((None, _LSE_SUBLANES, block_q),
                         lambda i, j: (i, 0, j)),
        ],
        out_specs=pl.BlockSpec((None, block_q, d), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, lq, d), q.dtype),
        interpret=interpret,
        name="flash_dq",
    )(q, k, v, do, lse8, delta8)

    dk, dv = pl.pallas_call(
        functools.partial(_flash_dkv_kernel, causal=causal, sm_scale=scale,
                          block_q=block_q, seq_len_q=lq),
        grid=(bh, lk // block_k),
        in_specs=[
            pl.BlockSpec((None, block_k, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, block_k, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, lq, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((None, lq, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((None, _LSE_SUBLANES, lq), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((None, _LSE_SUBLANES, lq), lambda i, j: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, block_k, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, block_k, d), lambda i, j: (i, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, lk, d), k.dtype),
            jax.ShapeDtypeStruct((bh, lk, d), v.dtype),
        ],
        interpret=interpret,
        name="flash_dkv",
    )(k, v, q, do, lse8, delta8)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, causal, sm_scale, block_q, block_k, interpret):
    # Primal (inference) path: skip the lse output entirely — nothing
    # reads it outside the VJP, and it costs an HBM write per call.
    out, _lse, _res = _flash_fwd(q, k, v, causal, sm_scale, block_q,
                                 block_k, interpret, with_lse=False)
    b, lq, h, d = q.shape
    return out.reshape(b, h, lq, d).transpose(0, 2, 1, 3)


def _flash_vjp_fwd(q, k, v, causal, sm_scale, block_q, block_k, interpret):
    out, lse, (qf, kf, vf) = _flash_fwd(q, k, v, causal, sm_scale,
                                        block_q, block_k, interpret)
    b, lq, h, d = q.shape
    return (out.reshape(b, h, lq, d).transpose(0, 2, 1, 3),
            (qf, kf, vf, out, lse))


def _flash_vjp_bwd(causal, sm_scale, block_q, block_k, interpret,
                   residuals, g):
    qf, kf, vf, out, lse = residuals
    bh, lq, d = qf.shape
    h = bh // g.shape[0]
    b = g.shape[0]
    gf = g.transpose(0, 2, 1, 3).reshape(bh, lq, d)
    dq, dk, dv = _flash_bwd(qf, kf, vf, out, lse, gf, causal, sm_scale,
                            block_q, block_k, interpret)
    lk = kf.shape[1]

    def unfold(x, l):
        return x.reshape(b, h, l, d).transpose(0, 2, 1, 3)

    return unfold(dq, lq), unfold(dk, lk), unfold(dv, lk)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def _auto_blocks(lq: int, lk: int) -> Tuple[int, int]:
    """Measured on v5e (GPT-2 heads, d=64, 4k ctx): (256, 1024) runs the
    fwd+bwd 2.1x faster than (128, 128) — bigger K tiles amortize the
    per-block loop/bookkeeping and keep the MXU fed; past ~(512, 2048)
    the f32 score/probability tiles blow the 16M VMEM scoped budget."""
    def pick(l, target):
        b = target
        while b > 128 and l % b:
            b //= 2
        return b if l % b == 0 else 128

    if lk >= 1024:
        return pick(lq, 256), pick(lk, 1024)
    return pick(lq, 128), pick(lk, 128)


def flash_attention(q, k, v, causal: bool = True,
                    sm_scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: bool = False) -> jax.Array:
    """Fused attention on TPU via Pallas, differentiable (custom VJP
    recomputes P blockwise from the saved log-sum-exp — the flash
    backward). q,k,v: [B, L, H, D] → [B, L, H, D].

    Block sizes default to a measured per-length choice (_auto_blocks);
    pass them explicitly to override."""
    b, lq, h, d = q.shape
    lk = k.shape[1]
    auto_q, auto_k = _auto_blocks(lq, lk)
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
    return _flash(q, k, v, causal, sm_scale, block_q, block_k, interpret)
