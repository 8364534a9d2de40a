"""The gated delta rule with a decay per channel (Kimi Delta Attention, KDA;
Kimi Linear, arXiv:2510.26692, section 3), in a one-token form and a
chunked form that computes the same numbers.

A head keeps a state ``S`` [K, V] (float32) and, for a token with key ``k``
and query ``q`` [K], value ``v`` [V], log-decay ``g`` [K] (``g <= 0``; the
decay is ``exp(g)``, one a CHANNEL, where ``ops/ssm.py``'s is one a head)
and step size ``beta``:

    S <- Diag(exp g) S
    S <- S + beta k (v - S^T k)^T        # the delta rule: what S holds for
    o  = S^T q                           # k is moved toward v

which is ``S_t = (I - beta k k^T) Diag(exp g) S_{t-1} + beta k v^T``.
``kda_step`` is that, a token a sequence, over the serve engine's per-slot
pool.  ``kda_chunked`` runs a whole context in chunks of at most 64 rows:
inside a chunk the pseudo-values ``u_t = beta_t (v_t - (Diag(exp g_t)
S_{t-1})^T k_t)`` solve a unit lower-triangular system (the UT transform;
its inverse is made by forward substitution, row by row over all chunks at
once), and only the chunks' starts are sequential.

**Decays inside a chunk are differences of cumulative logs between a pair
of rows, never a lone exp(+gamma).**  With ``gamma_t`` the sum of ``g`` up
to row t, the pair (t, i <= t) needs ``exp(gamma_t - gamma_i)`` per
channel, which does not factor into a matmul safely: at the gate's lower
bound of -5 a token, 64 tokens are e^-320 one way and e^+320 the other.
So a chunk is cut into sub-blocks of 16 rows.  Pairs inside a sub-block
take the difference itself, channel by channel ([16, 16, K], summed at
once).  A pair across sub-blocks goes through the cumulative log at the
row sub-block's start, ``exp(gamma_t - ref) * exp(ref - gamma_i)``: both
exponents are at most 0 (``i`` < start <= ``t``), and where one factor
underflows the pair's own decay is smaller still.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

F32 = jnp.float32
_HIGHEST = jax.lax.Precision.HIGHEST
CHUNK = 64
SUB = 16


def kda_gate(f: jax.Array, a_log: jax.Array, dt_bias: jax.Array,
             lower_bound: float) -> jax.Array:
    """The log-decay with a lower bound (``kda_safe_gate``): f [..., H, K]
    float32 (the gate projection), a_log [H], dt_bias [H, K] →
    ``lower_bound * sigmoid(exp(a_log) * (f + dt_bias))`` in
    ``(lower_bound, 0)``: a token can wipe a channel to ``exp(lower_bound)``
    of itself and no further."""
    return lower_bound * jax.nn.sigmoid(
        jnp.exp(a_log.astype(F32))[:, None] * (f.astype(F32) + dt_bias))


def kda_step(state: jax.Array, q: jax.Array, k: jax.Array, v: jax.Array,
             g: jax.Array, beta: jax.Array,
             active: Optional[jax.Array] = None):
    """One token a sequence.  state [B, H, K, V] float32; q, k, g
    [B, H, K]; v [B, H, V]; beta [B, H] → (state, o [B, H, V] float32).
    ``active`` [B] bool: any other row's state comes back as it was."""
    q, k, v, g, beta = (a.astype(F32) for a in (q, k, v, g, beta))
    s = jnp.exp(g)[..., None] * state
    held = jnp.einsum("bhkv,bhk->bhv", s, k, precision=_HIGHEST)
    s = s + (beta[..., None] * k)[..., None] * (v - held)[..., None, :]
    o = jnp.einsum("bhkv,bhk->bhv", s, q, precision=_HIGHEST)
    if active is not None:
        s = jnp.where(active[:, None, None, None], s, state)
    return s, o


def _decayed_products(a: jax.Array, b: jax.Array, gamma: jax.Array, sub: int,
                      strict: bool) -> jax.Array:
    """a, b, gamma [..., C, K] float32 (gamma: the cumulative log-decay of
    a chunk's rows) → M [..., C, C] with ``M[t, i] = sum_c a[t, c] b[i, c]
    exp(gamma[t, c] - gamma[i, c])`` for ``i < t`` (``strict``) or
    ``i <= t``, 0 elsewhere (the module's docstring says how)."""
    *lead, c, kdim = a.shape
    ns = c // sub
    blk = lambda x: x.reshape(*lead, ns, sub, kdim)  # noqa: E731
    a_b, b_b, g_b = blk(a), blk(b), blk(gamma)
    # inside a sub-block: the pair's own difference
    lower = jnp.tril(jnp.ones((sub, sub), bool), k=-1 if strict else 0)
    diff = g_b[..., :, None, :] - g_b[..., None, :, :]  # [.., ns, t, i, K]
    keep = lower[:, :, None]
    decay = jnp.where(keep, jnp.exp(jnp.where(keep, diff, 0.0)), 0.0)
    inside = jnp.sum(a_b[..., :, None, :] * b_b[..., None, :, :] * decay,
                     axis=-1)                           # [.., ns, sub, sub]
    if ns == 1:
        return inside.reshape(*lead, c, c)
    # across sub-blocks: through the cumulative log at the row block's start
    ref = jnp.concatenate([jnp.zeros_like(g_b[..., :1, 0, :]),
                           g_b[..., :-1, -1, :]], axis=-2)  # [.., ns, K]
    rows = a_b * jnp.exp(g_b - ref[..., :, None, :])
    earlier = (jnp.arange(c)[None, :] // sub
               < jnp.arange(ns)[:, None])[..., None]        # [ns, C, 1]
    gap = ref[..., :, None, :] - gamma[..., None, :, :]     # [.., ns, C, K]
    cols = jnp.where(earlier, b[..., None, :, :]
                     * jnp.exp(jnp.where(earlier, gap, 0.0)), 0.0)
    across = jnp.einsum("...ntk,...nik->...nti", rows, cols,
                        precision=_HIGHEST)                 # [.., ns, sub, C]
    eye = jnp.eye(ns, dtype=F32)
    placed = inside[..., :, :, None, :] * eye[:, None, :, None]
    return (across.reshape(*lead, c, c)
            + placed.reshape(*lead, c, c))


def _unit_lower_inverse(n: jax.Array) -> jax.Array:
    """(I + n)^-1 for n [..., C, C] strictly lower triangular, by forward
    substitution: row t of the inverse is ``e_t - n[t, :t] @ inverse[:t]``,
    C steps over all chunks at once, float32 sums.  (The Neumann series
    ends, n being nilpotent, and its product form (I - n)(I + n^2)(I +
    n^4)... is six matmuls; but where the keys of a chunk are alike, as
    they are behind a residual stream with a large common part, n's powers
    grow and cancel, and in float32 the product lost two to three digits on
    the chip: PERF.md, PR 47.)"""
    c = n.shape[-1]
    eye = jnp.eye(c, dtype=n.dtype)

    def row(t, inv):
        n_t = jax.lax.dynamic_index_in_dim(n, t, axis=-2, keepdims=False)
        new = eye[t] - jnp.sum(n_t[..., :, None] * inv, axis=-2)
        return jax.lax.dynamic_update_index_in_dim(inv, new, t, axis=-2)

    return jax.lax.fori_loop(0, c, row, jnp.zeros_like(n))


def kda_chunked(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
                beta: jax.Array, chunk: int = CHUNK, sub: int = SUB):
    """A whole context, from an empty state.  q, k, g [B, L, H, K]; v
    [B, L, H, V]; beta [B, L, H] → (o [B, L, H, V] float32, the state after
    row L - 1).  A row with
    ``g = 0`` and ``beta = 0`` advances nothing: a caller masks its padding
    so.  Everything here is float32 at the highest matmul precision: the
    state is float32 and the products that build it are not rounded to
    bfloat16 on the way.  Any ``chunk`` (a multiple of ``sub``) gives the
    same numbers up to rounding."""
    bsz, length, h, kdim = q.shape
    vdim = v.shape[-1]
    chunk = min(chunk, -(-length // sub) * sub)
    pad = -length % chunk
    q, k, v, g, beta = (a.astype(F32) for a in (q, k, v, g, beta))
    if pad:
        q, k, v, g, beta = (
            jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
            for a in (q, k, v, g, beta))
    nc = (length + pad) // chunk

    def chunks(a):  # [B, L, H, X] -> [B, H, nc, C, X]
        return a.reshape(bsz, nc, chunk, h, -1).transpose(0, 3, 1, 2, 4)

    q, k, v, g = chunks(q), chunks(k), chunks(v), chunks(g)
    beta = chunks(beta[..., None])                       # [B, H, nc, C, 1]
    gamma = jnp.cumsum(g, axis=-2)
    total = gamma[..., -1:, :]                           # [B, H, nc, 1, K]
    # u = t_mat (v - (k exp gamma) S_0): the unit lower-triangular solve
    a_mat = _decayed_products(k, k, gamma, sub, strict=True)
    t_mat = _unit_lower_inverse(beta * a_mat) * beta.swapaxes(-1, -2)
    b_mat = _decayed_products(q, k, gamma, sub, strict=False)
    from_start = jnp.exp(gamma)
    mm = lambda x, y: jnp.matmul(x, y, precision=_HIGHEST)  # noqa: E731
    t_k = mm(t_mat, k * from_start)                      # [B, H, nc, C, K]
    t_v = mm(t_mat, v)                                   # [B, H, nc, C, V]
    q_start = q * from_start
    k_end = k * jnp.exp(total - gamma)

    def carry(s, one):  # s [B, H, K, V]
        t_k, t_v, q_start, b_mat, k_end, total = one
        u = t_v - mm(t_k, s)
        o = mm(q_start, s) + mm(b_mat, u)
        s = jnp.exp(total).swapaxes(-1, -2) * s + mm(k_end.swapaxes(-1, -2),
                                                     u)
        return s, o

    per_chunk = lambda a: jnp.moveaxis(a, 2, 0)  # noqa: E731
    state, o = jax.lax.scan(carry, jnp.zeros((bsz, h, kdim, vdim), F32), tuple(
        per_chunk(a) for a in (t_k, t_v, q_start, b_mat, k_end, total)))
    o = jnp.moveaxis(o, 0, 2)                            # [B, H, nc, C, V]
    o = o.transpose(0, 2, 3, 1, 4).reshape(bsz, nc * chunk, h, vdim)
    return o[:, :length], state
