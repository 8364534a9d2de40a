"""Llama-family decoder in flax, TPU-first.

Second LM family beside GPT-2 (models/gpt2.py): the modern pre-norm
decoder recipe — RMSNorm, rotary position embeddings, grouped-query
attention, SwiGLU MLP, no biases, weights untied from the embedding.  The
reference framework ships no model implementations (its LM benchmarks
wrap HuggingFace torch through TorchTrainer, python/ray/train/
huggingface/); this is a ground-up jax design sharing the GPT-2 module's
conventions:

- bfloat16 activations / fp32 params via ``dtype``,
- attention through ray_tpu.ops (Pallas flash on TPU, XLA fallback) after
  GQA head expansion,
- the same parameter-name → logical-axis table as GPT-2, so
  ShardingRules runs it 1-chip, DP, FSDP or DP×TP unchanged.

Layer options carry other families through the same decoder, set from the
keys of their published ``config.json``: ``qk_norm`` (an RMSNorm over the
whole projected width of q and of k, before the split into heads and
before rope) and ``num_experts`` / ``num_experts_per_tok`` / ``expert_size``
/ ``norm_topk_prob`` (a dropless top-k SwiGLU expert FFN, ``ops/moe.py``,
in place of the dense MLP).  OLMoE-1B-7B is this decoder with both set.
``param_dtype`` is the dtype the leaves are made in.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from ray_tpu.ops.attention import mha_attention
from ray_tpu.ops.losses import next_token_cross_entropy
from ray_tpu.ops.moe import experts_dropless, route_topk


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    max_position_embeddings: int = 2048
    num_layers: int = 8
    num_heads: int = 8
    num_kv_heads: int = 4          # < num_heads → grouped-query attention
    hidden_size: int = 512
    intermediate_size: Optional[int] = None  # default ~8/3 * hidden
    rope_theta: float = 10_000.0
    rms_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    use_flash: Optional[bool] = None
    param_dtype: Any = jnp.float32   # the dtype init makes the leaves in
    qk_norm: bool = False            # whole-width RMSNorm on q and k
    num_experts: int = 0             # > 0 → expert FFN in every block
    num_experts_per_tok: int = 0
    expert_size: Optional[int] = None  # one expert's width (default mlp_dim)
    norm_topk_prob: bool = False     # rescale the chosen weights to sum 1

    @classmethod
    def tiny(cls, **kw):  # test-sized
        kw.setdefault("vocab_size", 256)
        kw.setdefault("max_position_embeddings", 64)
        kw.setdefault("num_layers", 2)
        kw.setdefault("num_heads", 4)
        kw.setdefault("num_kv_heads", 2)
        kw.setdefault("hidden_size", 64)
        return cls(**kw)

    @classmethod
    def llama_1b(cls, **kw):
        """~1.1B-param GQA config (TinyLlama-1.1B shape: 22 layers,
        2048 hidden, 32 q heads over 4 kv heads, 5632 SwiGLU) — the 3D
        pipeline x SPMD x ZeRO scale target (tests/test_mpmd_3d.py)."""
        kw.setdefault("vocab_size", 32000)
        kw.setdefault("max_position_embeddings", 2048)
        kw.setdefault("num_layers", 22)
        kw.setdefault("num_heads", 32)
        kw.setdefault("num_kv_heads", 4)
        kw.setdefault("hidden_size", 2048)
        kw.setdefault("intermediate_size", 5632)
        return cls(**kw)

    @classmethod
    def draft_of(cls, target: "LlamaConfig", num_layers: int = 1,
                 num_heads: Optional[int] = None,
                 num_kv_heads: Optional[int] = None,
                 hidden_size: Optional[int] = None, **kw):
        """A speculative-decoding draft config for ``target``: same
        vocab, context length and dtype (the serve engine's hard
        requirements), everything else shrunk — one layer at half width
        by default, GQA ratio preserved."""
        heads = num_heads or max(1, target.num_heads // 2)
        kvh = num_kv_heads or max(
            1, heads * target.num_kv_heads // target.num_heads)
        heads -= heads % kvh  # q heads must group evenly over kv heads
        hidden = hidden_size or max(heads * 8, target.hidden_size // 2)
        hidden -= hidden % heads
        return cls(vocab_size=target.vocab_size,
                   max_position_embeddings=target.max_position_embeddings,
                   num_layers=num_layers, num_heads=heads,
                   num_kv_heads=kvh, hidden_size=hidden,
                   rope_theta=target.rope_theta, dtype=target.dtype, **kw)

    @property
    def block_params(self) -> int:
        """Parameters per decoder block: q/o at h^2, GQA k/v at
        h^2 * kv/heads, three SwiGLU mats at h*mlp (+2 RMSNorm scales);
        with experts, E times the three mats at h*expert width plus the
        router; with qk_norm, its two scales."""
        h, m = self.hidden_size, self.mlp_dim
        kv = self.num_kv_heads / self.num_heads
        ffn = 3 * h * m
        if self.num_experts:
            ffn = self.num_experts * (3 * h * self.expert_dim + h)
        qk = int(h + h * kv) if self.qk_norm else 0
        return int(h * h * (2 + 2 * kv) + ffn + 2 * h + qk)

    @property
    def n_params(self) -> int:
        """Total parameter count (embed + blocks + final norm + head)."""
        h = self.hidden_size
        return int(2 * self.vocab_size * h + h
                   + self.num_layers * self.block_params)

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    @property
    def mlp_dim(self) -> int:
        if self.intermediate_size is not None:
            return self.intermediate_size
        # The 2/3·4h SwiGLU sizing, rounded to a multiple of 32 for MXU
        # tiling.
        raw = int(self.hidden_size * 8 / 3)
        return ((raw + 31) // 32) * 32

    @property
    def expert_dim(self) -> int:
        return self.expert_size or self.mlp_dim


class RMSNorm(nn.Module):
    eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        # Variance in fp32 regardless of activation dtype.
        var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1,
                       keepdims=True)
        norm = x * jax.lax.rsqrt(var + self.eps).astype(x.dtype)
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],),
                           self.param_dtype)
        return norm * scale.astype(x.dtype)


def _norm(c: "LlamaConfig", name: str) -> RMSNorm:
    return RMSNorm(c.rms_eps, c.dtype, c.param_dtype, name=name)


def _dense(c: "LlamaConfig", feats: int, name: str) -> nn.Dense:
    return nn.Dense(feats, use_bias=False, dtype=c.dtype,
                    param_dtype=c.param_dtype, name=name)


def rope_tables(length: int, head_dim: int, theta: float):
    """[L, D/2] cos/sin tables."""
    freqs = 1.0 / (theta ** (jnp.arange(0, head_dim, 2,
                                        dtype=jnp.float32) / head_dim))
    angles = jnp.arange(length, dtype=jnp.float32)[:, None] * freqs[None, :]
    return jnp.cos(angles), jnp.sin(angles)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """Rotate pairs of channels; x: [B, L, H, D].  cos/sin are either
    [L, D/2] (contiguous-from-zero, the training path) or [B, L, D/2]
    (per-token absolute positions, the decode path)."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    if cos.ndim == 2:
        cos, sin = cos[None], sin[None]
    cos = cos[:, :, None, :].astype(x.dtype)
    sin = sin[:, :, None, :].astype(x.dtype)
    return jnp.concatenate([x1 * cos - x2 * sin,
                            x2 * cos + x1 * sin], axis=-1)


class LlamaAttention(nn.Module):
    config: LlamaConfig

    @nn.compact
    def __call__(self, x, kv=None, positions=None):
        """kv = the caller's ``attend(q, k, v)`` for this layer →
        incremental decode: rope is applied at the tokens' absolute
        ``positions``, k and v stay at num_kv_heads (the GQA memory win
        carries into the KV pages; the caller's attention serves the
        grouped heads), and the layer also returns this step's post-rope
        (k, v) for the caller's cache."""
        c = self.config
        B, L, _ = x.shape
        hd = c.head_dim
        q = _dense(c, c.num_heads * hd, "q_proj")(x)
        k = _dense(c, c.num_kv_heads * hd, "k_proj")(x)
        if c.qk_norm:
            # Over all heads together: one variance a token, not one a head.
            q = _norm(c, "q_norm")(q)
            k = _norm(c, "k_norm")(k)
        q = q.reshape(B, L, c.num_heads, hd)
        k = k.reshape(B, L, c.num_kv_heads, hd)
        v = _dense(c, c.num_kv_heads * hd, "v_proj")(x).reshape(
            B, L, c.num_kv_heads, hd)
        if kv is not None:
            cos, sin = rope_tables(c.max_position_embeddings, hd,
                                   c.rope_theta)
            q = apply_rope(q, cos[positions], sin[positions])
            k = apply_rope(k, cos[positions], sin[positions])
            out = kv(q, k, v)
            out = out.reshape(B, L, c.num_heads * hd)
            return _dense(c, c.hidden_size, "o_proj")(out), (k, v)
        cos, sin = rope_tables(L, hd, c.rope_theta)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
        if c.num_kv_heads != c.num_heads:
            # GQA: expand kv heads to query heads (XLA turns the repeat
            # into a broadcast; memory win is in the kv cache/proj, which
            # stays at num_kv_heads).
            rep = c.num_heads // c.num_kv_heads
            k = jnp.repeat(k, rep, axis=2)
            v = jnp.repeat(v, rep, axis=2)
        out = mha_attention(q, k, v, causal=True, use_flash=c.use_flash)
        out = out.reshape(B, L, c.num_heads * hd)
        return _dense(c, c.hidden_size, "o_proj")(out)


class LlamaMLP(nn.Module):
    config: LlamaConfig

    @nn.compact
    def __call__(self, x):
        c = self.config
        gate = _dense(c, c.mlp_dim, "gate_proj")(x)
        up = _dense(c, c.mlp_dim, "up_proj")(x)
        return _dense(c, c.hidden_size, "down_proj")(nn.silu(gate) * up)


# One expert's matrices see fan-in d (or f), whatever the stack's size.
_expert_init = nn.initializers.variance_scaling(
    1.0, "fan_in", "truncated_normal", in_axis=-2, out_axis=-1,
    batch_axis=(0,))


class LlamaMoE(nn.Module):
    """The block's FFN as ``num_experts`` SwiGLU experts, each token
    through its ``num_experts_per_tok`` best, none dropped
    (``ops/moe.py``).  Three stacked leaves a layer and the router.  The
    chosen experts of every token (``expert_idx``, [B, L, k]) and the
    number of experts whose weights the layer streamed
    (``experts_streamed``) are sown into the ``moe`` collection for a
    caller that asks for it (``mutable=["moe"]``): the serve engine counts
    the experts a decode step touched and read from them; any other caller
    pays nothing.  ``active`` [B] bool (default: all) marks the sequences
    that are live: the layer adds zero for any other, and reads no expert
    on its behalf."""
    config: LlamaConfig

    @nn.compact
    def __call__(self, x, active=None):
        c = self.config
        d, e, f = c.hidden_size, c.num_experts, c.expert_dim
        router = self.param("router", nn.initializers.lecun_normal(),
                            (d, e), c.param_dtype)
        w_gate = self.param("w_gate", _expert_init, (e, d, f), c.param_dtype)
        w_up = self.param("w_up", _expert_init, (e, d, f), c.param_dtype)
        w_down = self.param("w_down", _expert_init, (e, f, d), c.param_dtype)
        B, L, _ = x.shape
        rows = x.reshape(B * L, d).astype(c.dtype)
        with jax.named_scope("route"):
            weights, experts = route_topk(
                rows, router, c.num_experts_per_tok, c.norm_topk_prob)
        self.sow("moe", "expert_idx",
                 experts.reshape(B, L, c.num_experts_per_tok))
        with jax.named_scope("experts"):
            out, streamed = experts_dropless(
                rows, weights, experts, w_gate, w_up, w_down,
                active=None if active is None else jnp.repeat(active, L))
        self.sow("moe", "experts_streamed", streamed)
        return out.reshape(B, L, d)


class LlamaBlock(nn.Module):
    config: LlamaConfig

    @nn.compact
    def __call__(self, x, kv=None, positions=None, active=None):
        c = self.config
        attn = LlamaAttention(c, name="attn")(
            _norm(c, "attn_norm")(x), kv=kv, positions=positions)
        new_kv = None
        if kv is not None:
            attn, new_kv = attn
        x = x + attn
        h = _norm(c, "mlp_norm")(x)
        if c.num_experts:
            x = x + LlamaMoE(c, name="moe")(h, active=active)
        else:
            x = x + LlamaMLP(c, name="mlp")(h)
        return x if kv is None else (x, new_kv)


class Llama(nn.Module):
    config: LlamaConfig

    @nn.compact
    def __call__(self, input_ids: jax.Array, positions: jax.Array = None,
                 kv_caches=None, active=None):
        """Full-context: input_ids [B, L] → logits [B, L, vocab].  With
        ``kv_caches`` (per-layer ``attend(q, k, v)`` callables over the
        caller's cache, k and v at num_kv_heads) and absolute
        ``positions``: incremental decode, returning (logits, new_kvs) —
        the same contract as GPT2.  ``active`` [B] bool: the live sequences
        of a decode step, for the expert layers (``LlamaMoE``)."""
        c = self.config
        emb = nn.Embed(c.vocab_size, c.hidden_size, dtype=c.dtype,
                       param_dtype=c.param_dtype, name="embed")
        x = emb(input_ids)
        decode = kv_caches is not None
        new_kvs = []
        for i in range(c.num_layers):
            if decode:
                x, nkv = LlamaBlock(c, name=f"layer_{i}")(
                    x, kv=kv_caches[i], positions=positions, active=active)
                new_kvs.append(nkv)
            else:
                x = LlamaBlock(c, name=f"layer_{i}")(x)
        x = _norm(c, "final_norm")(x)
        # Untied LM head (llama convention), fp32 logits for the softmax.
        logits = nn.Dense(c.vocab_size, use_bias=False, dtype=jnp.float32,
                          param_dtype=c.param_dtype,
                          name="lm_head")(x.astype(jnp.float32))
        if decode:
            return logits, new_kvs
        return logits


class LlamaStage(nn.Module):
    """One pipeline chunk of a split Llama (see :func:`split_stages`).

    Chunk 0 owns the token embedding and consumes ids; middle chunks
    consume/produce hidden states; the last chunk owns the final RMSNorm
    and the (already-untied, llama convention) LM head and produces the
    loss-side logits.  Rope is positional-from-zero inside each block,
    so splitting changes nothing about the attention math."""

    config: LlamaConfig
    first: bool
    last: bool
    blocks: tuple  # (start, stop) block index range owned by this chunk

    @nn.compact
    def __call__(self, x):
        c = self.config
        if self.first:
            x = nn.Embed(c.vocab_size, c.hidden_size, dtype=c.dtype,
                         param_dtype=c.param_dtype, name="embed")(x)
        else:
            x = x.astype(c.dtype)
        for i in range(*self.blocks):
            x = LlamaBlock(c, name=f"layer_{i}")(x)
        if self.last:
            x = _norm(c, "final_norm")(x)
            logits = nn.Dense(c.vocab_size, use_bias=False,
                              dtype=jnp.float32, param_dtype=c.param_dtype,
                              name="lm_head")(
                x.astype(jnp.float32))
            return logits
        return x


def _stage_ce_loss(logits: jax.Array, ids: jax.Array) -> jax.Array:
    """Next-token CE on a microbatch (same objective as llama_loss_fn)."""
    return next_token_cross_entropy(logits, ids)


def llama_head_cost(config: LlamaConfig) -> float:
    """LM-head cost in llama block-equivalents — the GQA/SwiGLU-aware
    analogue of gpt2's ``vocab/(12*hidden)``: a llama block costs
    ``h^2*(2 + 2*kv/heads) + 3*h*mlp`` param-FLOP units, the head
    ``vocab*h``."""
    return (config.vocab_size * config.hidden_size) / config.block_params


def split_stages(config: LlamaConfig, num_stages: int, *,
                 virtual_per_rank: int = 1,
                 boundary_dtype: Any = jnp.float32, seed: int = 0):
    """Split a Llama config into ``num_stages * virtual_per_rank``
    pipeline chunks for
    :class:`ray_tpu.parallel.mpmd_pipeline.MPMDPipeline` — same contract
    as ``models/gpt2.py::split_stages`` (GLOBAL chunk order, last chunk
    is the loss fn, init fns run on the stage actors), with the block
    cost model adjusted for GQA attention + SwiGLU MLP
    (:func:`llama_head_cost`).  Embedding pins to chunk 0 (stage 0),
    head to the last chunk (last stage), interleaved assignment
    ``chunk c -> stage c % num_stages``."""
    from ray_tpu.models.pipeline_split import balance_chunks, chunk_flags

    if num_stages < 1:
        raise ValueError(f"num_stages must be >= 1, got {num_stages}")
    C = num_stages * max(1, int(virtual_per_rank))
    bounds = balance_chunks(config.num_layers, C, embed_cost=0.3,
                            head_cost=llama_head_cost(config))

    stage_fns, init_fns = [], []
    for k, (first, last) in enumerate(chunk_flags(C)):
        module = LlamaStage(config, first=first, last=last,
                            blocks=bounds[k])

        if last:
            def fn(params, x, target, _m=module):
                logits = _m.apply({"params": params}, x)
                return _stage_ce_loss(logits, target)
        else:
            def fn(params, x, _m=module, _bd=boundary_dtype):
                return _m.apply({"params": params}, x).astype(_bd)

        def init_fn(_m=module, _first=first, _seed=seed + k, _c=config):
            dummy = jnp.zeros((1, 8), jnp.int32) if _first else \
                jnp.zeros((1, 8, _c.hidden_size), _c.dtype)
            return _m.init(jax.random.PRNGKey(_seed), dummy)["params"]

        stage_fns.append(fn)
        init_fns.append(init_fn)
    return stage_fns, init_fns


def llama_loss_fn(params, apply_fn, batch) -> jax.Array:
    """Next-token cross-entropy (same contract as gpt2_loss_fn)."""
    ids = batch["input_ids"]
    return next_token_cross_entropy(apply_fn({"params": params}, ids), ids)
