"""Latent-MoE decoder in flax (``model_type`` ``sarvam_mla``: Sarvam-105B's
language model; DeepSeek-V2's shape): latent attention (MLA) over the WHOLE
cache in EVERY layer under a YaRN-scaled rope, a leading dense layer, then
a sigmoid router's experts beside a shared one.

Seventh LM family beside GPT-2, the Llama decoder, Falcon-H1, Nemotron-H,
Ling-linear and GLM-DSA, and built from the last two's parts.  Pre-norm
blocks, ``h = x + Attn(norm(x))``, ``y = h + FFN_i(norm(h))``:

- ``Attn`` is ``models/ling_linear.py::MLAttention`` (``ops/mla.py``): no
  query compression, one learned RMSNorm scale over each head's whole query
  and the RMSNorm of the latent (``use_qk_norm``), a ``kv_lora_rank``-wide
  latent and a ``qk_rope_head_dim``-wide rope key all heads share;
  rotate-half rope whose frequencies are YaRN's blend (``rope_scaling``,
  type ``deepseek_yarn``: ``ops/rope.py``) and a softmax scale of
  ``qk_head_dim^-1/2 * mscale^2``.  Expanded over a context (``head_block``
  heads at a time past 2,048 rows), absorbed against the serve engine's
  cache.
- ``FFN_i`` is a SwiGLU of ``intermediate_size`` in the first
  ``first_k_dense_replace`` layers (in blocks of ``row_block`` rows over a
  long context) and, elsewhere, ``models/glm_dsa.py::SigmoidMoE``
  (``ops/moe.py``): a float32 sigmoid router over all ``num_experts``, the
  ``num_experts_per_tok`` best of score + bias (no groups), weighed by the
  score, normalised, times ``routed_scaling_factor``; routed SwiGLU experts
  plus ``num_shared_experts`` shared ones.

**A chip's share of the experts**, as ``models/nemotron_h.py``:
``experts_held`` / ``expert_offset`` say which experts this program holds;
the router keeps its width and what the absent ones would add is left out.

**The cache is ONE row a token a layer** (``latent_cache``): ``[c |
rope(k_r)]``, whose first ``kv_lora_rank`` columns are also the values.  A
layer hands the serve engine ``(row, None)``; the engine allocates no V
pool for such a model and reads the one pool through the latent form of
the paged kernel (``ops/paged_attention.py::latent_paged_attention``).
``config.num_kv_heads`` (1) and ``config.head_dim`` (576 at the published
widths) are what the cache holds, not a head of the model.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from ray_tpu.models.glm_dsa import SigmoidMoE
from ray_tpu.models.ling_linear import MLAttention, _norm
from ray_tpu.models.nemotron_h import _drawn_in_float32, _kernel_init
from ray_tpu.ops.paged_attention import latent_paged_attention
from ray_tpu.ops.rope import YarnScaling


@dataclasses.dataclass(frozen=True)
class LatentMoEConfig:
    """Fields under the names of the published ``config.json``
    (``rope_scaling``: its block, a dict or a ``YarnScaling``), plus the
    share, the two blocks and the two dtypes."""
    vocab_size: int = 262144
    max_position_embeddings: int = 131072
    num_hidden_layers: int = 32
    hidden_size: int = 4096
    rms_norm_eps: float = 1e-6
    num_attention_heads: int = 64
    # latent attention
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 10000.0
    rope_scaling: Optional[Any] = None
    # feed-forward
    first_k_dense_replace: int = 1
    intermediate_size: int = 16384
    num_experts: int = 128  # the router's width: every expert of a layer
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 2048
    num_shared_experts: int = 1
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True  # no published key: the family's convention
    # this program's share of every layer's experts (0: all of them)
    experts_held: int = 0
    expert_offset: int = 0
    # of a long context (no published key: shapes of this program): rows of
    # a feed-forward at a time, heads of the attention at a time
    row_block: int = 2048
    head_block: int = 16
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    # the serve engine's cache holds one row a token, no V row
    latent_cache = True

    def __post_init__(self):
        object.__setattr__(self, "rope_scaling",
                           YarnScaling.from_config(self.rope_scaling))
        if not self.experts_held:
            object.__setattr__(self, "experts_held", self.num_experts)
        if not 0 <= self.expert_offset <= \
                self.num_experts - self.experts_held:
            raise ValueError(
                f"experts_held {self.experts_held} from expert_offset "
                f"{self.expert_offset} on are not among the layer's "
                f"{self.num_experts}")
        if self.num_attention_heads % self.head_block:
            raise ValueError("head_block must divide num_attention_heads")

    @classmethod
    def tiny(cls, **kw):  # test-sized: YaRN binds past 32 positions
        for k, v in dict(
                vocab_size=256, max_position_embeddings=256,
                num_hidden_layers=3, hidden_size=64, num_attention_heads=4,
                kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
                v_head_dim=16, first_k_dense_replace=1, intermediate_size=96,
                num_experts=16, num_experts_per_tok=4,
                moe_intermediate_size=32, experts_held=4, expert_offset=4,
                row_block=16, head_block=2,
                rope_scaling=YarnScaling(
                    factor=8.0, original_max_position_embeddings=32,
                    beta_fast=4.0, beta_slow=1.0, mscale=1.0,
                    mscale_all_dim=1.0)).items():
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
        """Of the cache: every head reads the one latent row."""
        return 1

    @property
    def head_dim(self) -> int:
        """Of the cache: a latent row, ``[c | rope(k_r)]``."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    # ``SigmoidMoE``'s names for the two counts
    @property
    def n_routed_experts(self) -> int:
        return self.num_experts

    @property
    def n_shared_experts(self) -> int:
        return self.num_shared_experts

    def is_dense(self, i: int) -> bool:
        return i < self.first_k_dense_replace


class BlockedSwiGLU(nn.Module):
    """``down(silu(gate x) * up x)`` on x [B, L, d], ``row_block`` rows at a
    time where there are more: the hidden rows of a 16k-row context at a
    width of 16,384, three times over, are 1.6 GB beside an engine's
    pool."""
    config: LatentMoEConfig
    width: int

    @nn.compact
    def __call__(self, x):
        c = self.config
        d = c.hidden_size
        gate, up, down = (
            self.param(name, _kernel_init, shape, c.param_dtype).astype(
                c.dtype)
            for name, shape in (("gate_proj", (d, self.width)),
                                ("up_proj", (d, self.width)),
                                ("down_proj", (self.width, d))))
        one = lambda r: jnp.dot(  # noqa: E731
            nn.silu(jnp.dot(r, gate)) * jnp.dot(r, up), down)
        rows = x.reshape(-1, d).astype(c.dtype)
        n, blk = rows.shape[0], c.row_block
        if n <= blk:
            return one(rows).reshape(x.shape)
        padded = jnp.pad(rows, ((0, -n % blk), (0, 0))).reshape(-1, blk, d)
        return jax.lax.map(one, padded).reshape(-1, d)[:n].reshape(x.shape)


class LatentMoEBlock(nn.Module):
    """Layer ``index``.  What each part adds to the residual stream is sown
    into ``branches`` (``attn_out``; ``dense_out``, or the expert layer's
    two parts)."""
    config: LatentMoEConfig
    index: int

    @nn.compact
    def __call__(self, x, positions, kv=None, rows=False, lengths=None,
                 active=None):
        c = self.config
        mixed, new_kv = MLAttention(c, name="attn")(
            _norm(c, "attn_norm")(x), positions, kv=kv, rows=rows)
        self.sow("branches", "attn_out", mixed)
        x = x + mixed
        u = _norm(c, "ffn_norm")(x)
        if c.is_dense(self.index):
            out = BlockedSwiGLU(c, c.intermediate_size, name="mlp")(u)
            self.sow("branches", "dense_out", out)
        else:
            live = None  # a free lane, and a bucket's padding, choose nothing
            if active is not None:
                live = jnp.broadcast_to(active[:, None], u.shape[:2])
            if lengths is not None:
                real = jnp.arange(u.shape[1])[None] < lengths[:, None]
                live = real if live is None else live & real
            out = SigmoidMoE(c, name="moe")(u, live=live)
        return x + out, new_kv


class LatentMoE(nn.Module):
    config: LatentMoEConfig

    # a prefill is told how many of its bucket's rows are real (the padding
    # chooses no expert) and takes the head at the last real row alone
    prefill_lengths = True

    @property
    def latent_paged_attend(self):
        """The serve engine's cache hook for this family: the latent form
        of the paged kernel over the engine's one pool, the values a row's
        first ``kv_lora_rank`` columns."""
        return functools.partial(latent_paged_attention,
                                 rank=self.config.kv_lora_rank)

    @property
    def expert_layers(self) -> int:
        c = self.config
        return c.num_hidden_layers - min(c.first_k_dense_replace,
                                         c.num_hidden_layers)

    @nn.compact
    def __call__(self, input_ids: jax.Array, positions: jax.Array = None,
                 kv_caches=None, lengths=None, active=None, logits_at=None):
        """input_ids [B, L] → logits [B, L, V] float32 (``logits_at`` [B]:
        that row alone, [B, 1, V]).  With ``kv_caches`` (one hook a layer)
        also the new rows of every layer, ``(row, None)`` each: a prefill
        where ``lengths`` [B] says how many rows are real (the attention
        runs expanded over the call's own rows, whatever the hooks are, and
        the padding chooses no expert), else a decode step, absorbed
        through the hooks, where ``active`` [B] marks the slots that
        count."""
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
        x = emb(input_ids)
        cached = kv_caches is not None
        decode = cached and lengths is None
        new_kvs = []
        for i in range(c.num_hidden_layers):
            x, nkv = LatentMoEBlock(c, i, name=f"layer_{i}")(
                x, positions, kv=kv_caches[i] if decode else None,
                rows=cached, lengths=lengths, active=active)
            new_kvs.append(nkv)
        if logits_at is not None:
            x = jnp.take_along_axis(x, logits_at[:, None, None], axis=1)
        x = _norm(c, "final_norm")(x)
        head = self.param("lm_head", _kernel_init,
                          (c.hidden_size, c.vocab_size), c.param_dtype)
        logits = jnp.dot(x, head.astype(c.dtype),
                         preferred_element_type=jnp.float32)
        return (logits, new_kvs) if cached else logits
