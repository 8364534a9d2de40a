"""Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434, section 2.1)
in the two forms a serve engine needs.  The head widths are the caller's
(128 + 64 / 128 in ``models/ling_linear.py`` and ``models/latent_moe.py``,
192 + 64 / 256 in ``models/glm_dsa.py``), and so are where the queries come
from (projected from the hidden state, or from a compressed query latent:
``compressed_query``, ``q_lora_rank``), the rope's frequencies and the
softmax scale (``sm_scale``: a YaRN-scaled rope multiplies it,
``ops/rope.py``).

What is cached for a token is one latent row ``[c | rope(k_r)]``: ``c``
[R] the RMSNorm'd compressed key/value, ``k_r`` [P] the rope part every
head shares.  Head h's key is ``[W_UK,h c | rope(k_r)]`` (N + P wide), its
value ``W_UV,h c`` (V wide); ``w_kvb`` [R, H, N + V] holds both.

- ``mla_expanded``: a whole context, k and v built from c, causal
  attention at N + P / V (a prefill, the plain forward).
- ``mla_absorbed``: against a cache of latent rows, through the caller's
  ``attend(q, k, v, sm_scale=)`` (the serve engine's paged kernel) with ONE
  KV head: ``q_nope . W_UK,h c = (W_UK,h^T q_nope) . c``, so the query
  presented for head h is ``[W_UK,h^T q_nope,h | rope(q_rope,h)]``, the K
  row ``[c | rope(k_r)]``, and ``W_UV,h`` acts on the R columns that come
  back.  The same numbers as the expanded form, up to rounding.  What the
  hook is handed as V is the cache's business:
  *one row* (``one_row``: ``models/latent_moe.py``, whose cache is ONE pool
  of latent rows): nothing; the values are the first R columns of the K
  row, which the latent form of the paged kernel reads out of the block it
  already holds (``ops/paged_attention.py::latent_paged_attention``);
  *two rows* (``models/ling_linear.py``): ``[c | 0]``, a row as wide as
  K's for a V pool of the K pool's shape; ``models/glm_dsa.py`` puts its
  indexer's key where the zeros are (``index_rows``), so its second row is
  not a copy.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ray_tpu.ops.attention import mha_attention


def latent_rows(c: jax.Array, k_rope: jax.Array):
    """c [B, L, R], k_rope [B, L, P] (rope applied) → the K row and the V
    row of the cache, [B, L, 1, R + P] each."""
    k_row = jnp.concatenate([c, k_rope], axis=-1)[:, :, None]
    v_row = jnp.concatenate([c, jnp.zeros_like(k_rope)], axis=-1)[:, :, None]
    return k_row, v_row


def index_rows(c: jax.Array, k_index: jax.Array) -> jax.Array:
    """The V row of a cache that also holds a learned-sparse-attention
    indexer's key (``ops/dsa.py``): ``[c | k_index]``, c [B, L, R], k_index
    [B, L, D] → [B, L, 1, R + D].  The key takes the columns that
    ``latent_rows``' V row leaves at zero and what the pool pads a row out
    with (R + D = 512 + 128 = 640 = ``pool_width(1, 576)``: no byte
    added); the values a reader wants are still the first R columns."""
    return jnp.concatenate([c, k_index.astype(c.dtype)], axis=-1)[:, :, None]


def compressed_query(cq: jax.Array, w_qb: jax.Array, heads: int, nope: int):
    """Query compression: cq [B, L, Q] the RMSNorm'd query latent
    (``W_qa h``, ``q_lora_rank`` wide), w_qb [Q, H * (N + P)] → (q_nope
    [B, L, H, N], q_rope [B, L, H, P] before rope)."""
    q = jnp.dot(cq, w_qb.astype(cq.dtype)).reshape(
        cq.shape[:2] + (heads, -1))
    return q[..., :nope], q[..., nope:]


def mla_expanded(q_nope: jax.Array, q_rope: jax.Array, c: jax.Array,
                 k_rope: jax.Array, w_kvb: jax.Array, nope: int,
                 sm_scale: float) -> jax.Array:
    """q_nope [B, L, H, N], q_rope [B, L, H, P], c [B, L, R], k_rope
    [B, L, P] → [B, L, H, V].  The values ride ``mha_attention`` padded to
    the keys' width (its kernels take one head size)."""
    h = q_nope.shape[2]
    kv = jnp.einsum("blr,rhx->blhx", c, w_kvb.astype(c.dtype))
    k_nope, v = kv[..., :nope], kv[..., nope:]
    k = jnp.concatenate([k_nope, jnp.broadcast_to(
        k_rope[:, :, None], k_rope.shape[:2] + (h, k_rope.shape[-1]))], -1)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    width = v.shape[-1]
    v = jnp.pad(v, ((0, 0),) * 3 + ((0, q.shape[-1] - width),))
    return mha_attention(q, k, v, causal=True, sm_scale=sm_scale)[..., :width]


def mla_absorbed(attend, q_nope: jax.Array, q_rope: jax.Array, c: jax.Array,
                 k_rope: jax.Array, w_kvb: jax.Array, nope: int,
                 sm_scale: float, one_row: bool = False):
    """The new tokens' q_nope / q_rope / c / k_rope as above, ``attend``
    the caller's cache hook → ([B, L, H, V], the (K row, V row) for the
    caller's cache; ``one_row``: the V row is None, and ``attend`` takes
    its values from the K rows' first R columns)."""
    rank = c.shape[-1]
    w = w_kvb.astype(c.dtype)
    q_lat = jnp.einsum("blhn,rhn->blhr", q_nope, w[..., :nope])
    k_row, v_row = latent_rows(c, k_rope)
    if one_row:
        v_row = None
    out = attend(jnp.concatenate([q_lat, q_rope], axis=-1), k_row, v_row,
                 sm_scale=sm_scale)[..., :rank]
    return jnp.einsum("blhr,rhv->blhv", out, w[..., nope:]), (k_row, v_row)
