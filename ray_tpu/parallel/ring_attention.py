"""Sequence/context parallelism: ring attention and Ulysses all-to-all.

Absent from the reference entirely (SURVEY.md §5.7: no ring/ulysses/
sequence-parallel code exists there; it only provides the substrate —
placement groups + collective send/recv).  Here they are first-class:

- **Ring attention**: K/V shards rotate around the `sequence` mesh axis via
  `ppermute` (nearest-neighbour ICI hops on a TPU torus) while each device
  accumulates the flash-attention online-softmax recurrence for its local Q
  shard.  Peak memory per device is O(L/n · L/n) scores; no device ever
  holds the full sequence.  Autodiff flows through the scan+ppermute, so the
  backward pass is also a ring (reversed permutation), for free.
- **Ulysses**: all_to_all swaps the sharded axis from sequence to heads,
  computes exact local attention, and swaps back — cheaper at moderate L
  when heads ≥ mesh axis size.

Both run under shard_map over a named mesh axis.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from ray_tpu.ops.attention import blockwise_update, finalize_blockwise


def _ring_fwd(q, k, v, *, axis_name: str, axis_size: int, causal: bool,
              sm_scale: Optional[float]):
    """Per-device body (inside shard_map). q,k,v: [B, Lloc, H, D]."""
    b, lq, h, d = q.shape
    lk = k.shape[1]
    my_idx = jax.lax.axis_index(axis_name)
    perm = [(j, (j + 1) % axis_size) for j in range(axis_size)]

    o = jnp.zeros((b, lq, h, d), jnp.float32)
    l = jnp.zeros((b, h, lq), jnp.float32)
    m = jnp.full((b, h, lq), -1e30, jnp.float32)

    def step(carry, t):
        o, l, m, k_cur, v_cur = carry
        src_idx = (my_idx - t) % axis_size  # whose K/V block we now hold
        if causal:
            # Global positions decide the mask: full block, masked block, or
            # the diagonal block with a triangular mask.
            q_pos = my_idx * lq + jnp.arange(lq)
            k_pos = src_idx * lk + jnp.arange(lk)
            mask = q_pos[:, None] >= k_pos[None, :]
        else:
            mask = None
        o, l, m = blockwise_update(q, k_cur, v_cur, o, l, m, mask,
                                   sm_scale=sm_scale)
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        return (o, l, m, k_nxt, v_nxt), None

    (o, l, m, _, _), _ = jax.lax.scan(step, (o, l, m, k, v),
                                      jnp.arange(axis_size))
    return finalize_blockwise(o, l).astype(q.dtype)


def ring_attention(q, k, v, mesh, axis: str = "sequence",
                   causal: bool = True, sm_scale: Optional[float] = None,
                   batch_axes=("data", "fsdp")):
    """Ring attention over global arrays [B, L, H, D] sharded on L.

    Usable standalone or composed inside a larger pjit program; the shard_map
    boundary keeps the ppermute schedule explicit while XLA still fuses the
    local blockwise math."""
    from jax.sharding import PartitionSpec as P

    if axis not in mesh.axis_names:
        # Degenerate mesh (e.g. single chip): plain attention.
        from ray_tpu.ops.attention import mha_attention

        return mha_attention(q, k, v, causal=causal, sm_scale=sm_scale)

    axis_size = mesh.shape[axis]
    bax = tuple(a for a in batch_axes if a in mesh.axis_names)
    spec = P(bax if bax else None, axis, None, None)
    fn = functools.partial(_ring_fwd, axis_name=axis, axis_size=axis_size,
                           causal=causal, sm_scale=sm_scale)
    return jax.shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)


def _ulysses_fwd(q, k, v, *, axis_name: str, axis_size: int, causal: bool,
                 sm_scale: Optional[float]):
    from ray_tpu.ops.attention import mha_attention

    # [B, L/n, H, D] → all_to_all → [B, L, H/n, D]
    def swap_in(x):
        return jax.lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                                  tiled=True)

    def swap_out(x):
        return jax.lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                                  tiled=True)

    qh, kh, vh = swap_in(q), swap_in(k), swap_in(v)
    out = mha_attention(qh, kh, vh, causal=causal, sm_scale=sm_scale,
                        use_flash=False)
    return swap_out(out)


def ulysses_attention(q, k, v, mesh, axis: str = "sequence",
                      causal: bool = True, sm_scale: Optional[float] = None,
                      batch_axes=("data", "fsdp")):
    """Ulysses-style sequence parallelism: all_to_all head/sequence swap.

    Requires num_heads % axis_size == 0."""
    from jax.sharding import PartitionSpec as P

    if axis not in mesh.axis_names:
        from ray_tpu.ops.attention import mha_attention

        return mha_attention(q, k, v, causal=causal, sm_scale=sm_scale)

    axis_size = mesh.shape[axis]
    if q.shape[2] % axis_size:
        raise ValueError(
            f"num_heads {q.shape[2]} not divisible by axis size {axis_size}")
    bax = tuple(a for a in batch_axes if a in mesh.axis_names)
    spec = P(bax if bax else None, axis, None, None)
    fn = functools.partial(_ulysses_fwd, axis_name=axis, axis_size=axis_size,
                           causal=causal, sm_scale=sm_scale)
    return jax.shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)
