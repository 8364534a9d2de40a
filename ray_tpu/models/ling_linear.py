"""Ling-linear decoder in flax (Ling-3.0-flash's language model): five
gated-delta-rule layers (Kimi Delta Attention, KDA) to one latent-attention
layer (MLA), a leading dense layer, group-routed experts beside a shared
one.

Fifth LM family beside GPT-2, the Llama decoder, Falcon-H1 and Nemotron-H.
Pre-norm blocks, ``h = x + Mix_i(norm(x))``, ``y = h + FFN_i(norm(h))``,
both by the layer's index:

- ``Mix_i`` is MLA where ``(i + 1) % layer_group_size == 0`` and KDA
  elsewhere.
  *KDA* (``ops/kda.py``): q, k, v through a causal depthwise convolution of
  ``short_conv_kernel_size`` and a SiLU, q and k L2-normalised a head; a
  log-decay per head AND channel, ``kda_lower_bound * sigmoid(exp(A_log) *
  (W_f u + dt_bias))`` (the safe gate, a full projection: no low rank);
  ``beta = sigmoid(W_beta u)``; the delta-rule recurrence on a [K, V] state
  a head; an RMSNorm a head and one sigmoid gate a head on the way out.
  *MLA* (``ops/mla.py``): no query compression, a 512-wide latent and a
  64-wide rope key all heads share; expanded over a context, absorbed
  against the serve engine's cache, where the model presents ONE KV head
  of ``kv_lora_rank + qk_rope_head_dim`` columns.
- ``FFN_i`` is a SwiGLU of ``intermediate_size`` in the first
  ``first_k_dense_replace`` layers and, elsewhere, routed experts plus one
  shared expert (``ops/moe.py``): a float32 sigmoid router over all
  ``num_experts``, chosen by score + bias inside the ``topk_group`` best of
  ``n_group`` groups, weighed by the score, normalised, times
  ``routed_scaling_factor``.

**A chip's share of the experts**, as ``models/nemotron_h.py``:
``experts_held`` / ``expert_offset`` say which experts this program holds;
the router keeps its width, the layer computes its own experts' part and
what the absent ones would add is left out.  The shared expert is what
every chip computes alike and counts once.

What a layer hands the serve engine: a KDA layer its recurrent state
(``slot_state``: ``S`` [heads, K, V] float32 and the convolution's last
rows), an MLA layer the new latent rows.  ``kv_layers``, ``state_layers``
and ``expert_layers`` count them; ``config.num_kv_heads`` (1) and
``config.head_dim`` (576 at the published widths) are what the cache
holds, not a head of the model.  Not built: the vision tower, the
multi-token-prediction head, the clamped SwiGLU of the published model's
last layers (``expert_swiglu_limit_list``).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from ray_tpu.models.llama import RMSNorm, apply_rope
from ray_tpu.models.nemotron_h import (_drawn_in_float32, _kernel_init,
                                       _score_bias_init, _stack_init)
from ray_tpu.models.falcon_h1 import _a_log_init, _dt_bias_init
from ray_tpu.ops.kda import kda_chunked, kda_gate, kda_step
from ray_tpu.ops.mla import latent_rows, mla_absorbed, mla_expanded
from ray_tpu.ops.moe import experts_held_swiglu, route_group_sigmoid_topk
from ray_tpu.ops.rope import cos_sin_mscale, inv_freq, softmax_mscale


@dataclasses.dataclass(frozen=True)
class LingLinearConfig:
    """Fields under the names of the published ``config.json`` (its
    ``head_dim`` is ``kda_head_dim`` here: ``head_dim`` is what the serve
    engine's cache holds), plus the share and the two dtypes."""
    vocab_size: int = 157184
    max_position_embeddings: int = 131072
    num_hidden_layers: int = 42
    hidden_size: int = 2560
    rms_norm_eps: float = 1e-6
    layer_group_size: int = 6
    num_attention_heads: int = 32
    # KDA layers
    kda_head_dim: int = 128
    short_conv_kernel_size: int = 4
    kda_lower_bound: float = -5.0
    # the chunked prefill's chunk (ops/kda.py's CHUNK); no published key:
    # only the tiny presets set it (16: a short test context crosses chunks)
    chunk_size: int = 64
    # MLA layers
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 6e6
    # feed-forward
    first_k_dense_replace: int = 2
    intermediate_size: int = 6144
    num_experts: int = 512  # the router's width: every expert of a layer
    num_experts_per_tok: int = 8
    n_group: int = 8
    topk_group: int = 4
    moe_intermediate_size: int = 768
    moe_shared_expert_intermediate_size: int = 768
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    # this program's share of every layer's experts (0: all of them)
    experts_held: int = 0
    expert_offset: int = 0
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    def __post_init__(self):
        if not self.experts_held:
            object.__setattr__(self, "experts_held", self.num_experts)
        if not 0 <= self.expert_offset <= \
                self.num_experts - self.experts_held:
            raise ValueError(
                f"experts_held {self.experts_held} from expert_offset "
                f"{self.expert_offset} on are not among the layer's "
                f"{self.num_experts}")
        if self.num_experts % self.n_group or not \
                0 < self.topk_group <= self.n_group:
            raise ValueError("num_experts must divide into n_group groups, "
                             "topk_group of them kept")

    @classmethod
    def tiny(cls, **kw):  # test-sized: every kind of layer, 4 of 16 held
        for k, v in dict(
                vocab_size=256, max_position_embeddings=128,
                num_hidden_layers=7, hidden_size=64, num_attention_heads=4,
                kda_head_dim=16, chunk_size=16, kv_lora_rank=32,
                qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
                first_k_dense_replace=1, intermediate_size=96,
                num_experts=16, num_experts_per_tok=4, n_group=4,
                topk_group=2, moe_intermediate_size=32,
                moe_shared_expert_intermediate_size=32, experts_held=4,
                expert_offset=4).items():
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

    @property
    def kda_dim(self) -> int:
        return self.num_attention_heads * self.kda_head_dim

    def is_latent(self, i: int) -> bool:
        """Whether layer i's mixer is the full (MLA) one: the last of each
        group of ``layer_group_size``."""
        return (i + 1) % self.layer_group_size == 0

    def is_dense(self, i: int) -> bool:
        return i < self.first_k_dense_replace


# A context up to this long is attended to with all heads at once, whatever
# a config's ``head_block`` says.
ROWS_ALL_HEADS = 2048


def _norm(c: LingLinearConfig, name: str) -> RMSNorm:
    return RMSNorm(c.rms_norm_eps, c.dtype, c.param_dtype, name=name)


def _dense(c: LingLinearConfig, feats: int, name: str) -> nn.Dense:
    return nn.Dense(feats, use_bias=False, dtype=c.dtype,
                    param_dtype=c.param_dtype, kernel_init=_kernel_init,
                    name=name)


def _rope(x, positions, theta: float, scaling=None):
    """Rotate-half rope over the whole of x's last dimension: x
    [B, L, H, P], positions [B, L] absolute.  The angles are made from the
    positions, no table to ``max_position_embeddings``.  ``scaling``: a
    ``YarnScaling`` (``ops/rope.py``), its blend of frequencies and its
    factor on cos and sin."""
    angles = positions.astype(jnp.float32)[..., None] * inv_freq(
        x.shape[-1], theta, scaling)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if scaling is not None and cos_sin_mscale(scaling) != 1.0:
        cos, sin = (a * cos_sin_mscale(scaling) for a in (cos, sin))
    return apply_rope(x, cos, sin)


class _Float32Out(nn.Module):
    """``_dense`` (the same ``kernel`` leaf, products in the activations'
    dtype) whose result keeps the float32 the products are summed in."""
    config: LingLinearConfig
    features: int

    @nn.compact
    def __call__(self, x):
        c = self.config
        kernel = self.param("kernel", _kernel_init,
                            (x.shape[-1], self.features), c.param_dtype)
        return jnp.dot(x.astype(c.dtype), kernel.astype(c.dtype),
                       preferred_element_type=jnp.float32)


class KDAMixer(nn.Module):
    """The KDA layer on the normed input ``u`` [B, L, d].  ``state`` None:
    a whole context from an empty state (``kda_chunked``); with ``lengths``
    ([B]: real rows; the rest is padding, which advances nothing) it also
    returns what the sequence carries on, ``{"S": [B, H, K, V] float32,
    "conv": [B, kernel - 1, 3 H K]}`` as they stand after row ``lengths -
    1``.  ``state`` given (one new token a sequence, a row a slot): the
    one-token recurrence; rows that ``active`` [B] does not mark keep their
    state."""
    config: LingLinearConfig

    @nn.compact
    def __call__(self, u, state=None, lengths=None, active=None):
        c = self.config
        f32 = jnp.float32
        bsz, length, _ = u.shape
        h, hd, kw = c.num_attention_heads, c.kda_head_dim, \
            c.short_conv_kernel_size
        w_conv = self.param("conv_kernel", _kernel_init, (kw, 3 * c.kda_dim),
                            c.param_dtype)
        a_log = self.param("A_log", _a_log_init, (h,), f32)
        dt_bias = self.param("dt_bias", _dt_bias_init, (h, hd), f32)
        norm_scale = self.param("o_norm_scale", nn.initializers.ones, (hd,),
                                c.param_dtype)

        qkv = _dense(c, 3 * c.kda_dim, "qkv_proj")(u)
        # causal depthwise convolution: out_t = sum_j w[j] in_{t-(k-1)+j},
        # the taps summed in float32
        before = (jnp.zeros((bsz, kw - 1, 3 * c.kda_dim), qkv.dtype)
                  if state is None else state["conv"].astype(qkv.dtype))
        window = jnp.concatenate([before, qkv], axis=1)
        conv = sum(window[:, j:j + length].astype(f32) * w_conv[j].astype(f32)
                   for j in range(kw))
        q, k, v = (a.reshape(bsz, length, h, hd) for a in
                   jnp.split(nn.silu(conv), 3, axis=-1))
        unit = lambda a: a * jax.lax.rsqrt(  # noqa: E731
            jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)
        q, k = unit(q) * hd ** -0.5, unit(k)
        # the gates' projections come out in float32: exp(A_log), up to 16,
        # multiplies what a bfloat16 result would round off
        g = kda_gate(_Float32Out(c, c.kda_dim, name="f_proj")(u).reshape(
            bsz, length, h, hd), a_log, dt_bias, c.kda_lower_bound)
        beta = jax.nn.sigmoid(_Float32Out(c, h, name="b_proj")(u))

        new_state = None
        if state is not None:
            with jax.named_scope("kda.step"):
                s, o = kda_step(state["S"], q[:, 0], k[:, 0], v[:, 0],
                                g[:, 0], beta[:, 0], active=active)
                o = o[:, None]
            new_state = {"S": s, "conv": jnp.where(
                active[:, None, None],
                window[:, 1:].astype(state["conv"].dtype), state["conv"])}
        else:
            with jax.named_scope("kda.scan"):
                if lengths is not None:  # padding advances nothing
                    real = jnp.arange(length)[None] < lengths[:, None]
                    g = jnp.where(real[..., None, None], g, 0.0)
                    beta = jnp.where(real[..., None], beta, 0.0)
                o, s = kda_chunked(q, k, v, g, beta, chunk=c.chunk_size,
                                   sub=min(16, c.chunk_size))
            if lengths is not None:
                # rows lengths-(k-1) .. lengths-1 of the convolution's input
                at = lengths[:, None] + jnp.arange(kw - 1)[None]
                new_state = {"S": s, "conv": jnp.take_along_axis(
                    window, at[..., None], axis=1)}
        # an RMSNorm a head, then one gate a head
        var = jnp.mean(jnp.square(o), axis=-1, keepdims=True)
        o = o * jax.lax.rsqrt(var + c.rms_norm_eps) * norm_scale.astype(f32)
        gate = jax.nn.sigmoid(_Float32Out(c, h, name="g_proj")(u))
        o = (o * gate[..., None]).astype(c.dtype)
        return _dense(c, c.hidden_size, "o_proj")(
            o.reshape(bsz, length, c.kda_dim)), new_state


class MLAttention(nn.Module):
    """The MLA layer on the normed input ``u``.  ``kv`` None: a whole
    context, expanded.  ``kv`` the caller's ``attend(q, k, v, sm_scale=)``
    over its cache of latent rows: absorbed.  ``rows`` True: expanded, and
    the latent rows come back for a cache that is empty yet (a prefill).

    Shared with ``models/latent_moe.py``, whose config has the same keys
    and three more, read here where a config has them: ``rope_scaling`` (a
    ``YarnScaling``: the rope's frequencies are blended and the softmax
    scale carries ``mscale^2``), ``latent_cache`` (the cache holds ONE row
    a token, ``[c | rope(k_r)]``: no V row is made) and ``head_block``
    (over a context of more than ``ROWS_ALL_HEADS`` rows that many heads
    at a time, from the query's projection to the output's)."""
    config: Any

    @nn.compact
    def __call__(self, u, positions, kv=None, rows=False):
        c = self.config
        bsz, length, _ = u.shape
        h, nope, rope_d = c.num_attention_heads, c.qk_nope_head_dim, \
            c.qk_rope_head_dim
        scaling = getattr(c, "rope_scaling", None)
        one_row = getattr(c, "latent_cache", False)
        rope = functools.partial(_rope, positions=positions,
                                 theta=c.rope_theta, scaling=scaling)
        kva = _dense(c, c.kv_lora_rank + rope_d, "kv_a_proj")(u)
        latent = _norm(c, "kv_norm")(kva[..., :c.kv_lora_rank])
        k_rope = rope(kva[..., None, c.kv_lora_rank:])[:, :, 0]
        w_kvb = self.param("kv_b_proj", _kernel_init,
                           (c.kv_lora_rank, h * (nope + c.v_head_dim)),
                           c.param_dtype).reshape(c.kv_lora_rank, h, -1)
        scale = c.qk_head_dim ** -0.5 * softmax_mscale(scaling)
        new_kv = None
        if kv is None and rows:
            new_kv = latent_rows(latent, k_rope)
            if one_row:
                new_kv = (new_kv[0], None)
        block = getattr(c, "head_block", 0)
        if kv is None and 0 < block < h and length > ROWS_ALL_HEADS:
            return self._by_head_blocks(u, rope, latent, k_rope, w_kvb,
                                        scale, block), new_kv
        q = _dense(c, h * c.qk_head_dim, "q_proj")(u).reshape(
            bsz, length, h, c.qk_head_dim)
        q = _norm(c, "q_norm")(q)  # one learned scale, every head's 192
        q_nope, q_rope = q[..., :nope], rope(q[..., nope:])
        if kv is not None:
            out, new_kv = mla_absorbed(kv, q_nope, q_rope, latent, k_rope,
                                       w_kvb, nope, scale, one_row=one_row)
        else:
            out = mla_expanded(q_nope, q_rope, latent, k_rope, w_kvb, nope,
                               scale)
        out = _dense(c, c.hidden_size, "o_proj")(
            out.reshape(bsz, length, h * c.v_head_dim))
        return out, new_kv

    def _by_head_blocks(self, u, rope, latent, k_rope, w_kvb, scale, block):
        """The expanded form ``block`` heads at a time, from ``q_proj`` to
        ``o_proj`` (whose products the blocks add up in float32): a context
        of 16,384 rows at 64 heads of 192 never holds its queries, keys and
        values of all heads at once (2 GB beside a serving engine's pool),
        and the flash kernel sees ``block`` heads a call.  The weights are
        the leaves the whole-context form made.

        Unrolled, and a block (its slices of the weights too) starts when
        the one before is done: inside a ``scan`` the compiler set every
        layer's sums aside at once, and left alone it re-lays every block's
        slice of every layer's ``q_proj`` out at the program's start and
        runs blocks side by side (a 16,384-row prefill's scratch: 3.1 GiB
        so, 1.4 GiB in order; compiled for a described v5e, PR 56)."""
        c = self.config
        bsz, length, _ = u.shape
        nope, qk, vd = c.qk_nope_head_dim, c.qk_head_dim, c.v_head_dim
        p = self.variables["params"]
        q_norm = _norm(c, None)
        w_q, w_o = p["q_proj"]["kernel"], p["o_proj"]["kernel"]
        acc = None
        for first in range(0, c.num_attention_heads, block):
            u, acc, w_q, w_kvb, w_o = jax.lax.optimization_barrier(
                (u, acc, w_q, w_kvb, w_o))
            q = jnp.dot(u.astype(c.dtype),
                        w_q[:, first * qk:(first + block) * qk].astype(
                            c.dtype)).reshape(bsz, length, block, qk)
            q = q_norm.apply({"params": p["q_norm"]}, q)
            out = mla_expanded(
                q[..., :nope], rope(q[..., nope:]), latent, k_rope,
                w_kvb[:, first:first + block], nope, scale).reshape(
                bsz, length, block * vd)
            part = jnp.dot(out, w_o[first * vd:(first + block) * vd].astype(
                c.dtype), preferred_element_type=jnp.float32)
            acc = part if acc is None else acc + part
        return acc.astype(c.dtype)


class SwiGLU(nn.Module):
    config: LingLinearConfig
    width: int

    @nn.compact
    def __call__(self, x):
        c = self.config
        gate = _dense(c, self.width, "gate_proj")(x)
        up = _dense(c, self.width, "up_proj")(x)
        return _dense(c, c.hidden_size, "down_proj")(nn.silu(gate) * up)


class GroupedMoE(nn.Module):
    """The expert layer on the normed input ``u`` [B, L, d]: the held
    experts' part of ``sum_j w_j SwiGLU_{e_j}(u)`` plus the shared expert.
    ``live`` [B, L] bool (default: all) marks the rows that count: any
    other row chooses nothing and reads no expert.  Sown into ``moe`` for a
    caller that asks, as ``models/nemotron_h.py::LatentMoE`` does
    (``expert_idx`` over all experts, ``experts_streamed``,
    ``local_choices``), and the two parts into ``branches``."""
    config: LingLinearConfig

    @nn.compact
    def __call__(self, u, live=None):
        c = self.config
        d, f, k = c.hidden_size, c.moe_intermediate_size, \
            c.num_experts_per_tok
        router = self.param("router", _kernel_init, (d, c.num_experts),
                            jnp.float32)
        bias = self.param("expert_bias", _score_bias_init, (c.num_experts,),
                          jnp.float32)
        w_gate = self.param("w_gate", _stack_init, (c.experts_held, d, f),
                            c.param_dtype)
        w_up = self.param("w_up", _stack_init, (c.experts_held, d, f),
                          c.param_dtype)
        w_down = self.param("w_down", _stack_init, (c.experts_held, f, d),
                            c.param_dtype)
        bsz, length, _ = u.shape
        rows = u.reshape(bsz * length, d).astype(c.dtype)
        with jax.named_scope("route"):
            weights, experts = route_group_sigmoid_topk(
                rows, router, bias, k, c.n_group, c.topk_group,
                c.norm_topk_prob, c.routed_scaling_factor)
        self.sow("moe", "expert_idx", experts.reshape(bsz, length, k))
        with jax.named_scope("experts"):
            routed, streamed, landed = experts_held_swiglu(
                rows, weights, experts, w_gate, w_up, w_down,
                c.expert_offset,
                active=None if live is None else live.reshape(-1))
        self.sow("moe", "experts_streamed", streamed)
        self.sow("moe", "local_choices", landed)
        routed = routed.reshape(u.shape)
        with jax.named_scope("shared"):
            shared = SwiGLU(c, c.moe_shared_expert_intermediate_size,
                            name="shared")(u)
        self.sow("branches", "routed_out", routed)
        self.sow("branches", "shared_out", shared)
        return routed + shared


class LingLinearBlock(nn.Module):
    """Layer ``index``.  What each part adds to the residual stream is sown
    into ``branches`` (``kda_out`` or ``mla_out``; ``dense_out``, or the
    expert layer's two parts)."""
    config: LingLinearConfig
    index: int

    @nn.compact
    def __call__(self, x, positions=None, kv=None, rows=False, state=None,
                 lengths=None, active=None):
        c = self.config
        u = _norm(c, "mix_norm")(x)
        new_kv = new_state = None
        if c.is_latent(self.index):
            mixed, new_kv = MLAttention(c, name="mla")(u, positions, kv=kv,
                                                       rows=rows)
            self.sow("branches", "mla_out", mixed)
        else:
            mixed, new_state = KDAMixer(c, name="kda")(
                u, state=state, lengths=lengths, active=active)
            self.sow("branches", "kda_out", mixed)
        x = x + mixed
        u = _norm(c, "ffn_norm")(x)
        if c.is_dense(self.index):
            out = SwiGLU(c, c.intermediate_size, name="mlp")(u)
            self.sow("branches", "dense_out", out)
        else:
            live = None  # a free lane, and a bucket's padding, choose nothing
            if active is not None:
                live = jnp.broadcast_to(active[:, None], u.shape[:2])
            if lengths is not None:
                real = jnp.arange(u.shape[1])[None] < lengths[:, None]
                live = real if live is None else live & real
            out = GroupedMoE(c, name="moe")(u, live=live)
        return x + out, new_kv, new_state


class LingLinear(nn.Module):
    config: LingLinearConfig

    @property
    def kv_layers(self) -> int:
        """Layers that write latent rows: the serve engine's pool has as
        many."""
        c = self.config
        return sum(c.is_latent(i) for i in range(c.num_hidden_layers))

    @property
    def state_layers(self) -> int:
        """Layers that carry ``slot_state``."""
        return self.config.num_hidden_layers - self.kv_layers

    @property
    def expert_layers(self) -> int:
        c = self.config
        return c.num_hidden_layers - min(c.first_k_dense_replace,
                                         c.num_hidden_layers)

    @property
    def slot_state(self) -> dict:
        """What one sequence carries from token to token in one KDA layer:
        name -> (shape, dtype)."""
        c = self.config
        return {"S": ((c.num_attention_heads, c.kda_head_dim,
                       c.kda_head_dim), jnp.float32),
                "conv": ((c.short_conv_kernel_size - 1, 3 * c.kda_dim),
                         c.dtype)}

    @nn.compact
    def __call__(self, input_ids: jax.Array, positions: jax.Array = None,
                 kv_caches=None, state=None, lengths=None, active=None,
                 logits_at=None):
        """``FalconH1.__call__``'s contract, with ``kv_caches`` one
        ``attend`` an MLA layer and ``state`` one set a KDA layer, each in
        the layers' order; ``new_kvs`` and ``new_state`` come back
        likewise.  A prefill (``kv_caches`` given, ``state`` None) runs the
        MLA layers expanded over its own rows, whatever the hooks are, and
        returns the latent rows; a decode step (``state`` given) runs them
        absorbed through the hooks."""
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
        new_kvs, new_state = [], []
        for i in range(c.num_hidden_layers):
            kw = {}
            if c.is_latent(i):
                if state is not None:
                    kw["kv"] = kv_caches[len(new_kvs)]
                kw["rows"] = cached
            elif state is not None:
                kw["state"] = state[len(new_state)]
            x, nkv, nst = LingLinearBlock(c, i, name=f"layer_{i}")(
                x, positions=positions, lengths=lengths, active=active, **kw)
            if c.is_latent(i):
                new_kvs.append(nkv)
            else:
                new_state.append(nst)
        if logits_at is not None:
            x = jnp.take_along_axis(x, logits_at[:, None, None], axis=1)
        x = _norm(c, "final_norm")(x)
        head = self.param("lm_head", _kernel_init,
                          (c.hidden_size, c.vocab_size), c.param_dtype)
        logits = jnp.dot(x, head.astype(c.dtype),
                         preferred_element_type=jnp.float32)
        if cached:
            return logits, new_kvs, new_state
        return logits
