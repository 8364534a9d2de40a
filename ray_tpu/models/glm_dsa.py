"""GLM-DSA decoder in flax (``model_type`` ``glm_moe_dsa``: GLM-5's language
model): latent attention with a compressed query (MLA) whose keys are
chosen a query by a learned indexer (DeepSeek sparse attention, DSA) in
EVERY layer; a leading dense layer, then a sigmoid router's experts beside a
shared one.

Sixth LM family beside GPT-2, the Llama decoder, Falcon-H1, Nemotron-H and
Ling-linear.  Pre-norm blocks, ``h = x + Attn(norm(x))``, ``y = h +
FFN_i(norm(h))``:

- ``Attn`` (``ops/mla.py``, ``ops/dsa.py``), u the normed input at
  position t:
  *query latent* ``cq = RMSNorm(W_qa u)`` (``q_lora_rank``), from which
  both the heads' queries ``W_qb cq`` (``qk_nope_head_dim`` |
  ``qk_rope_head_dim`` a head) and the indexer's come;
  *latent row* ``[c | k_r] = W_kva u``, ``c = RMSNorm(c)``, rope on
  ``k_r``; head j's key ``[W_UK,j c | rope(k_r)]``, value ``W_UV,j c``;
  *indexer* ``q_idx = W_qbI cq`` (``index_n_heads`` x ``index_head_dim``),
  ``k_idx = LayerNorm(W_kI u)`` (ONE key a row), rope on the first
  ``qk_rope_head_dim`` columns of both, ``w = W_wI u * index_n_heads^-1/2
  * index_head_dim^-1/2``; ``I[t, s] = sum_j w[t, j] relu(q_idx[t, j] .
  k_idx[s])`` in float32; the softmax of row t runs over ``S_t``, the
  ``min(index_topk, t + 1)`` rows of largest ``I[t, s]``, one set for every
  head.
  Rope is interleaved (``rope_interleave``, ``indexer_rope_interleave``):
  channels (2i, 2i + 1) are a pair.
  Over a context the attention is expanded, masked by the selection in
  blocks of query rows (plain causal where the context is no longer than
  ``index_topk``); against the serve engine's cache it is absorbed, and
  the engine's hook (``sparse_paged_attend``) scores the slot's cached
  index keys, takes the ``index_topk`` best and gathers those latent rows
  alone.  The cache holds ONE KV head of ``kv_lora_rank +
  qk_rope_head_dim`` columns; the index key rides the V row
  (``ops/mla.py::index_rows``).
- ``FFN_i`` is a SwiGLU of ``intermediate_size`` in the first
  ``first_k_dense_replace`` layers and, elsewhere, routed experts plus one
  shared expert (``ops/moe.py``): a float32 sigmoid router over all
  ``n_routed_experts``, the ``num_experts_per_tok`` best of score + bias
  (``n_group`` 1: no limit on groups), weighed by the score, normalised,
  times ``routed_scaling_factor``.  Above ``row_block`` rows the routed
  part runs in blocks of rows: the grouped form sorts every choice of
  every row, held here or not.

**A chip's share of the experts**, as ``models/nemotron_h.py``:
``experts_held`` / ``expert_offset`` say which experts this program holds;
the router keeps its width and what the absent ones would add is left out.

What a layer hands the serve engine: the new latent rows (K row ``[c |
rope(k_r)]``, V row ``[c | k_idx]``), and, sown into ``moe`` beside the
expert layer's counts, ``kv_rows_read``: how many rows the live slots'
softmax ran over.  ``config.num_kv_heads`` (1) and ``config.head_dim`` (576
at the published widths) are what the cache holds, not a head of the
model.  Not built: the multi-token-prediction module
(``num_nextn_predict_layers``), FP8 index keys and their Hadamard rotation
(orthogonal: it leaves every ``q . k`` as it is).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from ray_tpu.models.llama import RMSNorm
from ray_tpu.models.nemotron_h import (_drawn_in_float32, _kernel_init,
                                       _score_bias_init, _stack_init)
from ray_tpu.ops import dsa
from ray_tpu.ops.dsa import (dsa_prefill_attention, index_scores,
                             sparse_paged_attention)
from ray_tpu.ops.mla import (compressed_query, index_rows, latent_rows,
                             mla_absorbed, mla_expanded)
from ray_tpu.ops.moe import experts_held_swiglu, route_sigmoid_topk


@dataclasses.dataclass(frozen=True)
class GlmDsaConfig:
    """Fields under the names of the published ``config.json``
    (``rope_theta`` is its ``rope_parameters.rope_theta``), plus the share,
    the row block and the two dtypes."""
    vocab_size: int = 154880
    max_position_embeddings: int = 202752
    num_hidden_layers: int = 78
    hidden_size: int = 6144
    rms_norm_eps: float = 1e-5
    num_attention_heads: int = 64
    # latent attention
    q_lora_rank: int = 2048
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    rope_theta: float = 1e6
    # the indexer
    index_n_heads: int = 32
    index_head_dim: int = 128
    index_topk: int = 2048
    index_norm_eps: float = 1e-6  # the key's LayerNorm: no published key
    # feed-forward
    first_k_dense_replace: int = 3
    intermediate_size: int = 12288
    n_routed_experts: int = 256  # the router's width: every expert
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 2048
    n_shared_experts: int = 1
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    # this program's share of every layer's experts (0: all of them)
    experts_held: int = 0
    expert_offset: int = 0
    # rows of a context at a time: the attention's stretch of query rows
    # and the routed part's block (no published key: a shape of this program)
    row_block: int = 2048
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    def __post_init__(self):
        if not self.experts_held:
            object.__setattr__(self, "experts_held", self.n_routed_experts)
        if not 0 <= self.expert_offset <= \
                self.n_routed_experts - self.experts_held:
            raise ValueError(
                f"experts_held {self.experts_held} from expert_offset "
                f"{self.expert_offset} on are not among the layer's "
                f"{self.n_routed_experts}")
        if self.index_head_dim < self.qk_rope_head_dim \
                or self.qk_rope_head_dim % 2:
            raise ValueError("the indexer ropes its first qk_rope_head_dim "
                             "columns, in pairs")
        if self.index_topk < 1:
            raise ValueError("index_topk: at least the row itself")

    @classmethod
    def tiny(cls, **kw):  # test-sized: the selection binds past 16 rows
        for k, v in dict(
                vocab_size=256, max_position_embeddings=128,
                num_hidden_layers=3, hidden_size=64, num_attention_heads=4,
                q_lora_rank=32, kv_lora_rank=24, qk_nope_head_dim=12,
                qk_rope_head_dim=8, v_head_dim=16, index_n_heads=2,
                index_head_dim=16, index_topk=16, first_k_dense_replace=1,
                intermediate_size=96, n_routed_experts=16,
                num_experts_per_tok=4, moe_intermediate_size=32,
                experts_held=4, expert_offset=4, row_block=16).items():
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
    def num_experts(self) -> int:
        """The router's width: every expert of a layer, held or not."""
        return self.n_routed_experts

    def is_dense(self, i: int) -> bool:
        return i < self.first_k_dense_replace


def _norm(c: GlmDsaConfig, name: str) -> RMSNorm:
    return RMSNorm(c.rms_norm_eps, c.dtype, c.param_dtype, name=name)


def _dense(c: GlmDsaConfig, feats: int, name: str) -> nn.Dense:
    return nn.Dense(feats, use_bias=False, dtype=c.dtype,
                    param_dtype=c.param_dtype, kernel_init=_kernel_init,
                    name=name)


def rope_interleaved(x, positions, theta: float):
    """Rope over the whole of x's last dimension with channels (2i, 2i + 1)
    a pair, rotated by ``position * theta^(-2i / P)``: x [B, L, H, P],
    positions [B, L] absolute.  The angles and the rotation in float32."""
    p = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, p, 2, dtype=jnp.float32) / p))
    angles = positions.astype(jnp.float32)[..., None, None] * freqs
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    pairs = x.astype(jnp.float32).reshape(x.shape[:-1] + (p // 2, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape).astype(x.dtype)


def _rope_first(x, positions, theta: float, width: int):
    """``rope_interleaved`` on the first ``width`` columns of x [B, L, H,
    D], the rest as they are."""
    return jnp.concatenate([rope_interleaved(x[..., :width], positions,
                                             theta), x[..., width:]], -1)


class Indexer(nn.Module):
    """The indexer's projections of the normed input ``u`` and the query
    latent ``cq``: (q_idx [B, L, J, D], w [B, L, J] float32, k_idx [B, L,
    D])."""
    config: GlmDsaConfig

    @nn.compact
    def __call__(self, u, cq, positions):
        c = self.config
        bsz, length, _ = u.shape
        j, d = c.index_n_heads, c.index_head_dim
        q = _dense(c, j * d, "wq_b")(cq).reshape(bsz, length, j, d)
        q = _rope_first(q, positions, c.rope_theta, c.qk_rope_head_dim)
        k = nn.LayerNorm(epsilon=c.index_norm_eps, dtype=c.dtype,
                         param_dtype=c.param_dtype, name="k_norm")(
            _dense(c, d, "wk")(u))
        k = _rope_first(k[:, :, None], positions, c.rope_theta,
                        c.qk_rope_head_dim)[:, :, 0]
        # float32 out of the products' own sums: the weights multiply
        # scores whose order decides the selection
        kernel = self.param("weights_proj", _kernel_init,
                            (u.shape[-1], j), c.param_dtype)
        w = jnp.dot(u.astype(c.dtype), kernel.astype(c.dtype),
                    preferred_element_type=jnp.float32)
        return q, w * (j ** -0.5 * d ** -0.5), k


class SparseMLAttention(nn.Module):
    """The attention on the normed input ``u``.  ``kv`` None: a whole
    context, expanded, every row over its ``S_t``; ``rows`` True: the rows
    for a cache that is empty yet come back too (a prefill).  ``kv`` the
    engine's hook over its cache (``sparse_paged_attend``): absorbed, one
    new token a slot; ``active`` [B] marks the slots that count in
    ``kv_rows_read``.  ``lengths`` [B] (a prefill's bucket): the rows past
    the longest are padding, and their stretches of query rows are not
    attended.  A caller that asks for ``dsa`` gets the selection and the
    last rows' index scores of a context longer than ``index_topk``, and of
    a decode step the positions each slot selected (``selected``)."""
    config: GlmDsaConfig

    @nn.compact
    def __call__(self, u, positions, kv=None, rows=False, active=None,
                 lengths=None):
        c = self.config
        bsz, length, _ = u.shape
        h, nope, rope_d, rank = c.num_attention_heads, c.qk_nope_head_dim, \
            c.qk_rope_head_dim, c.kv_lora_rank
        cq = _norm(c, "q_a_norm")(_dense(c, c.q_lora_rank, "q_a_proj")(u))
        w_qb = self.param("q_b_proj", _kernel_init,
                          (c.q_lora_rank, h * c.qk_head_dim), c.param_dtype)
        w_o = self.param("o_proj", _kernel_init,
                         (h * c.v_head_dim, c.hidden_size), c.param_dtype)
        kva = _dense(c, rank + rope_d, "kv_a_proj")(u)
        latent = _norm(c, "kv_norm")(kva[..., :rank])
        k_rope = rope_interleaved(kva[..., None, rank:], positions,
                                  c.rope_theta)[:, :, 0]
        w_kvb = self.param("kv_b_proj", _kernel_init,
                           (rank, h * (nope + c.v_head_dim)),
                           c.param_dtype).reshape(rank, h, -1)
        q_idx, w_idx, k_idx = Indexer(c, name="indexer")(u, cq, positions)
        scale = c.qk_head_dim ** -0.5

        def queries(cq, positions):
            q_nope, q_rope = compressed_query(cq, w_qb, h, nope)
            return q_nope, rope_interleaved(q_rope, positions, c.rope_theta)

        def projected(out):
            return jnp.dot(out.reshape(out.shape[:2] + (h * c.v_head_dim,)),
                           w_o.astype(c.dtype))

        new_kv = None
        if kv is not None:
            counted = []
            keep = self.is_mutable_collection("dsa")

            def attend(q, k_row, v_row, sm_scale):
                out, n, *places = kv(
                    q, k_row, v_row, sm_scale=sm_scale,
                    index=(q_idx, w_idx, k_idx), topk=c.index_topk,
                    rank=rank, keep=keep)
                counted.append(n)
                if keep:
                    self.sow("dsa", "selected", places[0])
                return out

            with jax.named_scope("dsa.decode"):
                out, (k_row, _) = mla_absorbed(
                    attend, *queries(cq, positions), latent, k_rope, w_kvb,
                    nope, scale)
            new_kv = (k_row, index_rows(latent, k_idx))
            n = counted[0] if active is None else jnp.where(
                active, counted[0], 0)
            self.sow("moe", "kv_rows_read", jnp.sum(n))
            return projected(out), new_kv
        if rows:
            new_kv = (latent_rows(latent, k_rope)[0],
                      index_rows(latent, k_idx))
        if length <= c.index_topk:  # every row keeps every earlier row
            return projected(mla_expanded(
                *queries(cq, positions), latent, k_rope, w_kvb, nope,
                scale)), new_kv
        # A stretch of query rows at a time, from the query latent to the
        # output projection: a context's queries a head (16k rows x 64 x
        # 256) and what attention returns for them are as large again as
        # its expanded keys and values.
        keep = self.is_mutable_collection("dsa")
        kvx = jnp.einsum("blr,rhx->blhx", latent, w_kvb.astype(latent.dtype))
        n = min(c.row_block, length)
        stretches = -(-length // n)
        pad = lambda a: jnp.pad(a, (  # noqa: E731
            (0, 0), (0, stretches * n - length)) + ((0, 0),) * (a.ndim - 2))
        cq, positions, q_idx, w_idx = map(pad, (cq, positions, q_idx, w_idx))

        # rows from ``real`` on are a bucket's padding in every sequence:
        # their stretches and blocks of query rows are skipped
        real = None if lengths is None else jnp.max(lengths)

        def attended(i):
            take = lambda a: jax.lax.dynamic_slice_in_dim(  # noqa: E731
                a, i * n, n, axis=1)
            out = dsa_prefill_attention(
                *queries(take(cq), take(positions)), kvx, k_rope,
                take(q_idx), take(w_idx), k_idx, c.index_topk, scale,
                first=i * n, real=real, keep=keep)
            if keep:
                return projected(out[0]), out[1]
            return projected(out)

        def stretch(i):
            if real is None:
                return attended(i)
            nothing = jnp.zeros((bsz, n, c.hidden_size), c.dtype)
            return jax.lax.cond(
                i * n < real, lambda: attended(i),
                lambda: (nothing, jnp.zeros((bsz, n, length), bool))
                if keep else nothing)

        with jax.named_scope("dsa.prefill"):
            got = jax.lax.map(stretch, jnp.arange(stretches))
        join = lambda a: jnp.moveaxis(a, 0, 1).reshape(  # noqa: E731
            (bsz, stretches * n) + a.shape[3:])[:, :length]
        if keep:
            got, chosen = got
            self.sow("dsa", "selection", join(chosen))
            # the index scores of the context's last rows, by the function
            # and in the dtypes the blocks use
            t0 = max(length - dsa.BLOCK_Q, 0)
            last = lambda a: a[:, t0:length]  # noqa: E731
            self.sow("dsa", "last_scores", jnp.where(
                jnp.arange(length)[None] <= jnp.arange(t0, length)[:, None],
                index_scores(last(q_idx), last(w_idx), k_idx), -jnp.inf))
        return join(got), new_kv


class SwiGLU(nn.Module):
    config: GlmDsaConfig
    width: int

    @nn.compact
    def __call__(self, x):
        c = self.config
        gate = _dense(c, self.width, "gate_proj")(x)
        up = _dense(c, self.width, "up_proj")(x)
        return _dense(c, c.hidden_size, "down_proj")(nn.silu(gate) * up)


class SigmoidMoE(nn.Module):
    """The expert layer on the normed input ``u`` [B, L, d]: the held
    experts' part of ``sum_j w_j SwiGLU_{e_j}(u)`` plus the shared expert.
    ``live`` [B, L] bool (default: all) marks the rows that count.  Sown as
    ``models/ling_linear.py::GroupedMoE`` sows: ``expert_idx``,
    ``experts_streamed`` and ``local_choices`` into ``moe``, the two parts
    into ``branches``."""
    config: GlmDsaConfig

    @nn.compact
    def __call__(self, u, live=None):
        c = self.config
        d, f, k = c.hidden_size, c.moe_intermediate_size, \
            c.num_experts_per_tok
        router = self.param("router", _kernel_init, (d, c.n_routed_experts),
                            jnp.float32)
        bias = self.param("e_score_correction_bias", _score_bias_init,
                          (c.n_routed_experts,), jnp.float32)
        w_gate = self.param("w_gate", _stack_init, (c.experts_held, d, f),
                            c.param_dtype)
        w_up = self.param("w_up", _stack_init, (c.experts_held, d, f),
                          c.param_dtype)
        w_down = self.param("w_down", _stack_init, (c.experts_held, f, d),
                            c.param_dtype)
        bsz, length, _ = u.shape
        n = bsz * length
        rows = u.reshape(n, d).astype(c.dtype)
        with jax.named_scope("route"):
            weights, experts = route_sigmoid_topk(
                rows, router, bias, k, c.norm_topk_prob,
                c.routed_scaling_factor)
        self.sow("moe", "expert_idx", experts.reshape(bsz, length, k))
        active = jnp.ones((n,), bool) if live is None else live.reshape(-1)
        held = functools.partial(
            experts_held_swiglu, w_gate=w_gate, w_up=w_up, w_down=w_down,
            expert_offset=c.expert_offset)
        blk = c.row_block
        with jax.named_scope("experts"):
            if n > blk:
                # a block of rows at a time (the last one padded with rows
                # that are not live): the grouped form's scratch grows
                # with every choice of every row
                pad = lambda a: jnp.pad(a, (  # noqa: E731
                    (0, -n % blk),) + ((0, 0),) * (a.ndim - 1)).reshape(
                    (-1, blk) + a.shape[1:])
                zero = jnp.zeros((), jnp.int32)
                routed, streamed, landed = jax.lax.map(
                    lambda a: jax.lax.cond(  # a block of padding: nothing
                        jnp.any(a[3]),
                        lambda: held(a[0], a[1], a[2], active=a[3]),
                        lambda: (jnp.zeros_like(a[0]), zero, zero)),
                    (pad(rows), pad(weights), pad(experts), pad(active)))
                routed = routed.reshape(-1, d)[:n]
                streamed, landed = jnp.max(streamed), jnp.sum(landed)
            else:
                routed, streamed, landed = held(rows, weights, experts,
                                                active=active)
        self.sow("moe", "experts_streamed", streamed)
        self.sow("moe", "local_choices", landed)
        routed = routed.reshape(u.shape)
        with jax.named_scope("shared"):
            shared = SwiGLU(c, c.n_shared_experts * c.moe_intermediate_size,
                            name="shared")(u)
        self.sow("branches", "routed_out", routed)
        self.sow("branches", "shared_out", shared)
        return routed + shared


class GlmDsaBlock(nn.Module):
    """Layer ``index``.  What each part adds to the residual stream is sown
    into ``branches`` (``attn_out``; ``dense_out``, or the expert layer's
    two parts)."""
    config: GlmDsaConfig
    index: int

    @nn.compact
    def __call__(self, x, positions, kv=None, rows=False, lengths=None,
                 active=None):
        c = self.config
        mixed, new_kv = SparseMLAttention(c, name="attn")(
            _norm(c, "attn_norm")(x), positions, kv=kv, rows=rows,
            active=active, lengths=lengths)
        self.sow("branches", "attn_out", mixed)
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
            out = SigmoidMoE(c, name="moe")(u, live=live)
        return x + out, new_kv


class GlmDsa(nn.Module):
    config: GlmDsaConfig

    # The serve engine's cache hook for this family: attention that scores
    # the slot's cached index keys, takes the best and gathers those rows
    # alone (``ops/dsa.py``), in ``ops/paged_attention.py``'s place.
    sparse_paged_attend = staticmethod(sparse_paged_attention)

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
        also the new rows of every layer: a prefill where ``lengths`` [B]
        says how many rows are real (the attention runs expanded over the
        call's own rows, whatever the hooks are, and the padding chooses no
        expert), else a decode step, absorbed through the hooks, where
        ``active`` [B] marks the slots that count."""
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
            x, nkv = GlmDsaBlock(c, i, name=f"layer_{i}")(
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
