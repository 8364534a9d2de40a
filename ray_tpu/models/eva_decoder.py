"""EVA decoder in flax (``model_type`` ``evabyte``: EvaByte's byte-level
language model): EVA attention (``ops/eva.py``) in EVERY layer, an exact
window of ``window_size`` bytes beside one pooled row for every
``chunk_size`` bytes before it, under one softmax.

Eighth LM family beside GPT-2, the Llama decoder, Falcon-H1, Nemotron-H,
Ling-linear, GLM-DSA and Latent-MoE, and built from their parts.  Pre-norm
blocks over a float32 residual stream (``fp32_skip_add``), ``h = x +
Attn(N(x))``, ``y = h + MLP(N(h))``:

- ``N`` is an RMSNorm whose learned scale is ``1 + g``, g starting at zero
  (``norm_add_unit_offset``), run in the activations' dtype.
- ``Attn``: 32 heads of 128 with as many KV heads, no bias, rotate-half rope
  at the absolute position (``models/llama.py::apply_rope``, the angles made
  from the positions), then EVA with two learned vectors a head, ``phi``
  (the values' pooling) and ``mu`` (the keys').
- ``MLP`` is a SwiGLU of ``intermediate_size``, in blocks of ``row_block``
  rows over a long context (``models/latent_moe.py::BlockedSwiGLU``).
- The head is ``num_pred_heads`` heads of ``vocab_size`` columns each, one
  untied matrix; head m at position i predicts byte i + 1 + m.  A serve
  program computes head 0's columns, the next byte's; the plain forward all.

**The cache's rows are not its tokens** (``cache_map``): a slot keeps the
rows of its open window in a ring of pages and one summary row for every
closed chunk (``ops/eva.py::EvaCacheMap``).  A prefill hands the engine what
the cache keeps, ``(ring k, ring v, summary k, summary v)`` a layer, and not
its every row; a decode step reads through ``ops/paged_attention.py`` as it
is, over the row the map composes, and hands back its new row; the engine
asks ``close_chunks`` for the summary of a chunk that row closes.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from ray_tpu.models.latent_moe import BlockedSwiGLU
from ray_tpu.models.ling_linear import _rope
from ray_tpu.models.nemotron_h import _dense, _drawn_in_float32, _kernel_init
from ray_tpu.ops.eva import (EvaCacheMap, eva_pool_chunks,
                             eva_prefill_attention, gather_pages)


@dataclasses.dataclass(frozen=True)
class EvaDecoderConfig:
    """Fields under the names of the published ``config.json``, plus the
    block of feed-forward rows and the two dtypes."""
    vocab_size: int = 320
    max_position_embeddings: int = 32768
    num_hidden_layers: int = 32
    hidden_size: int = 4096
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    intermediate_size: int = 11008
    rms_norm_eps: float = 1e-5
    rope_theta: float = 100000.0
    window_size: int = 2048
    chunk_size: int = 16
    num_pred_heads: int = 8
    # of a long context (no published key: a shape of this program)
    row_block: int = 2048
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    def __post_init__(self):
        if self.num_key_value_heads != self.num_attention_heads:
            raise ValueError(
                "EVA pools a chunk a head: num_key_value_heads "
                f"{self.num_key_value_heads} is not num_attention_heads "
                f"{self.num_attention_heads}")
        if self.window_size % self.chunk_size:
            raise ValueError(f"window_size {self.window_size} is not whole "
                             f"chunks of {self.chunk_size}")

    @classmethod
    def tiny(cls, **kw):  # test-sized: three windows are 192 rows
        for k, v in dict(
                vocab_size=96, max_position_embeddings=512,
                num_hidden_layers=2, hidden_size=64, num_attention_heads=4,
                num_key_value_heads=4, intermediate_size=96, window_size=64,
                chunk_size=8, num_pred_heads=3, row_block=64).items():
            kw.setdefault(k, v)
        return cls(**kw)

    # What the serve engine and the shared modules read off any LM config.
    @property
    def num_layers(self) -> int:
        return self.num_hidden_layers

    @property
    def num_heads(self) -> int:
        return self.num_attention_heads

    @property
    def num_kv_heads(self) -> int:
        return self.num_key_value_heads

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads


class OffsetRMSNorm(nn.Module):
    """``x / sqrt(mean(x^2) + eps) * (1 + g)`` in ``dtype``, the variance in
    float32; the learned g starts at zero."""
    eps: float
    dtype: Any
    param_dtype: Any

    @nn.compact
    def __call__(self, x):
        x = x.astype(self.dtype)
        var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1,
                       keepdims=True)
        g = self.param("scale", nn.initializers.zeros, (x.shape[-1],),
                       self.param_dtype)
        return x * jax.lax.rsqrt(var + self.eps).astype(x.dtype) * (
            1 + g.astype(x.dtype))


_pooling_init = _drawn_in_float32(nn.initializers.normal(1.0))


def _norm(c: EvaDecoderConfig, name: str) -> OffsetRMSNorm:
    return OffsetRMSNorm(c.rms_norm_eps, c.dtype, c.param_dtype, name=name)


class EvaAttention(nn.Module):
    config: EvaDecoderConfig

    @nn.compact
    def __call__(self, x, positions, kv=None, lengths=None):
        """x [B, L, d].  ``kv``: the caller's ``attend(q, k, v)`` over its
        cache, a decode step: → (out, (k, v)), the new rows.  Else the
        call's own rows are the context; with ``lengths`` [B] (a prefill of
        that many real rows) also what the cache keeps of them: → (out,
        (ring k, ring v, summary k, summary v)), the rows of the window the
        next position lies in, from that window's first row on, and every
        whole chunk's summary."""
        c = self.config
        bsz, length, _ = x.shape
        h, d = c.num_attention_heads, c.head_dim
        heads = lambda name: _dense(c, h * d, name)(x).reshape(  # noqa: E731
            bsz, length, h, d)
        q = _rope(heads("q_proj"), positions, c.rope_theta)
        k = _rope(heads("k_proj"), positions, c.rope_theta)
        v = heads("v_proj")
        # of unit deviation: a chunk's softmax then has scores of deviation
        # ~1, as the queries' have, and is not the chunk's mean
        phi, mu = (self.param(name, _pooling_init, (h, d), c.param_dtype)
                   for name in ("phi", "mu"))
        out_proj = _dense(c, c.hidden_size, "o_proj")
        if kv is not None:
            return out_proj(kv(q, k, v).reshape(bsz, length, h * d)), (k, v)
        chunks = length // c.chunk_size
        whole = lambda a: a[:, :chunks * c.chunk_size].reshape(  # noqa: E731
            bsz, chunks, c.chunk_size, h, d)
        k_sum, v_sum = eva_pool_chunks(whole(k), whole(v), phi, mu)
        out = eva_prefill_attention(q, k, v, k_sum, v_sum,
                                    window=c.window_size, chunk=c.chunk_size)
        out = out_proj(out.reshape(bsz, length, h * d))
        if lengths is None:
            return out, None
        kept = min(length, c.window_size)
        start = jnp.minimum(lengths // c.window_size * c.window_size,
                            length - kept)
        ring = lambda a: jax.vmap(  # noqa: E731
            lambda rows, at: jax.lax.dynamic_slice_in_dim(rows, at, kept))(
                a, start)
        return out, (ring(k), ring(v), k_sum, v_sum)


class EvaBlock(nn.Module):
    """What each part adds to the residual stream is sown into ``branches``
    (``attn_out``, ``mlp_out``)."""
    config: EvaDecoderConfig

    @nn.compact
    def __call__(self, x, positions, kv=None, lengths=None):
        c = self.config
        mixed, new_kv = EvaAttention(c, name="attn")(
            _norm(c, "attn_norm")(x), positions, kv=kv, lengths=lengths)
        self.sow("branches", "attn_out", mixed)
        x = x + mixed.astype(x.dtype)
        out = BlockedSwiGLU(c, c.intermediate_size, name="mlp")(
            _norm(c, "mlp_norm")(x))
        self.sow("branches", "mlp_out", out)
        return x + out.astype(x.dtype), new_kv


class EvaDecoder(nn.Module):
    config: EvaDecoderConfig

    # a prefill is told how many of its bucket's rows are real and takes
    # the head at the last real row alone
    prefill_lengths = True

    def cache_map(self, page_size: int, max_ctx: int) -> EvaCacheMap:
        """What a slot of the serve engine holds for this model, and where
        (``ops/eva.py::EvaCacheMap``)."""
        c = self.config
        return EvaCacheMap(c.window_size, c.chunk_size, page_size, max_ctx)

    def close_chunks(self, params, k_pages, v_pages, pages, new_k, new_v):
        """The summary rows of the chunks a decode step closes, out of the
        engine's pools [layers, pages, chunk, >= H * D]: ``pages`` [n] int32
        names the page that holds a chunk's earlier rows (a page is a chunk;
        0, the scratch page, for a lane that closes none: what comes back
        for it means nothing) and new_k, new_v [layers, n, H * D] are its
        last row, which the step has just made → (k~, v~) [layers, n,
        H * D], each layer's under its own ``phi`` and ``mu``."""
        c = self.config
        h, d = c.num_attention_heads, c.head_dim

        def chunks(pool, last):  # [layers, n, chunk, H, D]
            rows = jnp.concatenate([
                gather_pages(pool, pages)[:, :, :-1, :h * d],
                last[:, :, None]], axis=2)
            return rows.reshape(rows.shape[:3] + (h, d))

        vec = lambda name: jnp.stack([  # noqa: E731
            params[f"layer_{i}"]["attn"][name]
            for i in range(c.num_hidden_layers)])
        k_sum, v_sum = jax.vmap(eva_pool_chunks)(
            chunks(k_pages, new_k), chunks(v_pages, new_v), vec("phi"),
            vec("mu"))
        return (k_sum.reshape(k_sum.shape[:2] + (h * d,)),
                v_sum.reshape(v_sum.shape[:2] + (h * d,)))

    @nn.compact
    def __call__(self, input_ids: jax.Array, positions: jax.Array = None,
                 kv_caches=None, lengths=None, logits_at=None):
        """input_ids [B, L] → logits [B, L, num_pred_heads, V] float32, the
        plain forward.  With ``kv_caches`` (one hook a layer): (logits of
        head 0 [B, L, V], what each layer hands the cache): a prefill where
        ``lengths`` [B] says how many rows are real (the hooks are not
        called: the call's own rows are the context; ``logits_at`` [B]: that
        row alone, [B, 1, V]), else a decode step through the hooks."""
        c = self.config
        bsz, length = input_ids.shape
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(length)[None],
                                         (bsz, length))
        emb = nn.Embed(c.vocab_size, c.hidden_size, dtype=c.dtype,
                       param_dtype=c.param_dtype, name="embed",
                       embedding_init=_drawn_in_float32(
                           nn.initializers.variance_scaling(
                               1.0, "fan_in", "normal", out_axis=0)))
        x = emb(input_ids).astype(jnp.float32)  # fp32_skip_add
        cached = kv_caches is not None
        decode = cached and lengths is None
        new_kvs = []
        for i in range(c.num_hidden_layers):
            x, nkv = EvaBlock(c, name=f"layer_{i}")(
                x, positions, kv=kv_caches[i] if decode else None,
                lengths=lengths)
            if lengths is not None:
                # what a long prefill hands the cache is cut out of this
                # layer's keys and values HERE, so that they are let go
                # before the next layer makes its own
                x, nkv = jax.lax.optimization_barrier((x, nkv))
            new_kvs.append(nkv)
        if logits_at is not None:
            x = jnp.take_along_axis(x, logits_at[:, None, None], axis=1)
        x = _norm(c, "final_norm")(x)
        head = self.param("lm_head", _kernel_init,
                          (c.hidden_size, c.num_pred_heads * c.vocab_size),
                          c.param_dtype).astype(c.dtype)
        if cached:  # the next byte's head alone
            return jnp.dot(x, head[:, :c.vocab_size],
                           preferred_element_type=jnp.float32), new_kvs
        logits = jnp.dot(x, head, preferred_element_type=jnp.float32)
        return logits.reshape(bsz, -1, c.num_pred_heads, c.vocab_size)
