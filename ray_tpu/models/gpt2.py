"""GPT-2 in flax, TPU-first.

Flagship model for the Train/Data north-star config ("GPT-2 125M language
modeling with streaming Dataset shards", BASELINE.json).  The reference has
no GPT-2 implementation — its benchmark uses HuggingFace torch through
TorchTrainer (python/ray/train/huggingface/) — so this is a ground-up
design:

- bfloat16 activations, fp32 params/optimizer (mixed precision via `dtype`),
- attention through ray_tpu.ops (Pallas flash on TPU, XLA fallback, or ring
  attention over a `sequence` mesh axis for long context),
- logical sharding axes per parameter (embed/heads/mlp/vocab) so the same
  module runs 1-chip, DP, FSDP, or DP×TP via ShardingRules,
- static shapes + scan-free layer stack (12 layers unrolls fine; a
  lax.scan-over-layers variant kicks in above `scan_layers_threshold` to
  bound compile time for deep configs).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from ray_tpu.ops.attention import mha_attention_qkv
from ray_tpu.ops.layers import gelu
from ray_tpu.ops.losses import next_token_cross_entropy


@dataclasses.dataclass(frozen=True)
class GPT2Config:
    vocab_size: int = 50257
    max_position_embeddings: int = 1024
    num_layers: int = 12
    num_heads: int = 12
    hidden_size: int = 768
    mlp_ratio: int = 4
    dtype: Any = jnp.bfloat16
    use_flash: Optional[bool] = None  # None = auto by backend
    scan_layers_threshold: int = 24
    # Mixture-of-Experts: replace every block's dense MLP with a top-k
    # routed expert MLP (ray_tpu.ops.moe).  The dense-dispatch einsums
    # partition over the `expert` mesh axis under pjit via the logical
    # axes below (net-new TPU scope, SURVEY §2.4 EP).
    moe: Optional[Any] = None  # ops.moe.MoEConfig

    @classmethod
    def gpt2_small(cls, **kw):  # 125M
        return cls(**kw)

    @classmethod
    def moe_tiny(cls, num_experts: int = 8, top_k: int = 2, **kw):
        from ray_tpu.ops.moe import MoEConfig

        kw.setdefault("moe", MoEConfig(num_experts=num_experts, top_k=top_k))
        return cls.tiny(**kw)

    @classmethod
    def gpt2_medium(cls, **kw):  # 350M
        return cls(num_layers=24, num_heads=16, hidden_size=1024, **kw)

    @classmethod
    def gpt2_xl(cls, **kw):  # 1.5B — the MPMD pipeline scale target
        return cls(num_layers=48, num_heads=25, hidden_size=1600, **kw)

    @classmethod
    def tiny(cls, **kw):  # test-sized
        kw.setdefault("vocab_size", 512)
        kw.setdefault("max_position_embeddings", 128)
        kw.setdefault("num_layers", 2)
        kw.setdefault("num_heads", 2)
        kw.setdefault("hidden_size", 64)
        return cls(**kw)

    @classmethod
    def draft_of(cls, target: "GPT2Config", num_layers: int = 1,
                 num_heads: Optional[int] = None,
                 hidden_size: Optional[int] = None, **kw):
        """A speculative-decoding draft config for ``target``: shares
        the vocab, context length and dtype (the engine's hard
        requirements — serve/llm_engine.py), shrinks everything else.
        Defaults to one layer at half width, the \"tiny draft\" shape
        whose proposal cost is a small fraction of one target step."""
        heads = num_heads or max(1, target.num_heads // 2)
        hidden = hidden_size or max(heads * 8, target.hidden_size // 2)
        hidden -= hidden % heads  # head_dim must divide
        return cls(vocab_size=target.vocab_size,
                   max_position_embeddings=target.max_position_embeddings,
                   num_layers=num_layers, num_heads=heads,
                   hidden_size=hidden, dtype=target.dtype, **kw)

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads


class Block(nn.Module):
    config: GPT2Config
    attn_fn: Optional[Callable] = None

    @nn.compact
    def __call__(self, x, kv=None):
        """kv = the caller's ``attend(q, k, v)`` for this layer switches
        the block to the incremental-decode path: attention over the
        cached prefix plus the new tokens is the caller's, and the block
        ALSO returns this step's (k, v) projections so the caller
        (serve/llm_engine.py) can write them into its page pool — the
        cache layout is the engine's concern, not the model's."""
        c = self.config
        h = nn.LayerNorm(dtype=jnp.float32, name="ln_1")(x)
        qkv = nn.Dense(3 * c.hidden_size, dtype=c.dtype, name="attn_qkv")(h)
        if kv is None and self.attn_fn is None:
            # q, k and v stay where the projection wrote them: the flash
            # kernels read their blocks out of the one array.
            attn = mha_attention_qkv(qkv, c.num_heads, causal=True,
                                     use_flash=c.use_flash)
        else:
            b, l, _ = qkv.shape
            q, k, v = (t.reshape(b, l, c.num_heads, c.head_dim)
                       for t in jnp.split(qkv, 3, axis=-1))
            attn = (kv if kv is not None else self.attn_fn)(q, k, v)
            attn = attn.reshape(b, l, c.hidden_size)
        x = x + nn.Dense(c.hidden_size, dtype=c.dtype, name="attn_proj")(attn)
        h = nn.LayerNorm(dtype=jnp.float32, name="ln_2")(x)
        if c.moe is not None:
            from ray_tpu.ops.moe import moe_apply

            d, f, e = c.hidden_size, c.mlp_ratio * c.hidden_size, \
                c.moe.num_experts
            w_router = self.param("moe_router",
                                  nn.initializers.normal(0.02), (d, e),
                                  jnp.float32)
            w_in = self.param("moe_w_in", nn.initializers.normal(0.02),
                              (e, d, f), jnp.float32)
            w_out = self.param("moe_w_out", nn.initializers.normal(0.02),
                               (e, f, d), jnp.float32)
            bsz, l, _ = h.shape
            flat = h.reshape(bsz * l, d)
            out = moe_apply(flat, w_router, w_in, w_out, c.moe)
            x = x + out.reshape(bsz, l, d).astype(c.dtype)
        else:
            h = nn.Dense(c.mlp_ratio * c.hidden_size, dtype=c.dtype,
                         name="mlp_fc")(h)
            h = gelu(h)
            x = x + nn.Dense(c.hidden_size, dtype=c.dtype, name="mlp_proj")(h)
        if kv is not None:
            return x, (k, v)
        return x


class GPT2(nn.Module):
    config: GPT2Config
    attn_fn: Optional[Callable] = None

    @nn.compact
    def __call__(self, input_ids: jax.Array, positions: jax.Array = None,
                 kv_caches=None, return_hidden: bool = False):
        """Training/full-context: input_ids [B, L] int32 → logits
        [B, L, vocab] (unchanged contract).  ``return_hidden=True``
        (full-context only) additionally returns the post-ln_f hidden
        states [B, L, hidden] — the value head's input in the RLHF
        stack (:class:`GPT2WithValue`).

        Incremental decode (``kv_caches`` given): ``positions`` [B, L]
        are the absolute positions of the new tokens, ``kv_caches`` is a
        per-layer list of ``attend(q, k, v) -> [B, L, H, D]`` callables,
        each attending the new tokens to whatever its owner has cached
        for that layer plus themselves (``ops.attention.cached_attention``
        over a dense cache, ``ops.paged_attention`` over the engine's
        page pool); returns (logits, new_kvs) where new_kvs is the
        per-layer list of this call's (k, v) projections [B, L, H, D] for
        the caller to append to its cache."""
        c = self.config
        b, l = input_ids.shape
        decode = kv_caches is not None
        wte = self.param("wte", nn.initializers.normal(0.02),
                         (c.vocab_size, c.hidden_size), jnp.float32)
        wpe = self.param("wpe", nn.initializers.normal(0.01),
                         (c.max_position_embeddings, c.hidden_size), jnp.float32)
        pos = wpe[None, :l] if positions is None else wpe[positions]
        x = wte[input_ids].astype(c.dtype) + pos.astype(c.dtype)
        new_kvs = []
        if c.num_layers >= c.scan_layers_threshold:
            if decode:
                raise NotImplementedError(
                    "incremental decode is unrolled-layers only; lower "
                    "scan_layers_threshold applies to training compiles")
            block = nn.remat(Block)
            ScanBlocks = nn.scan(
                block, variable_axes={"params": 0}, split_rngs={"params": True},
                length=c.num_layers, metadata_params={"partition_name": "layers"})
            x, _ = ScanBlocks(c, self.attn_fn, name="h_scan")(x, None)
        else:
            for i in range(c.num_layers):
                if decode:
                    x, nkv = Block(c, self.attn_fn, name=f"h_{i}")(
                        x, kv=kv_caches[i])
                    new_kvs.append(nkv)
                else:
                    x = Block(c, self.attn_fn, name=f"h_{i}")(x)
        x = nn.LayerNorm(dtype=jnp.float32, name="ln_f")(x)
        # Tied LM head: the matmul runs at the compute dtype (bf16 doubles
        # MXU rate on the single biggest matmul in the model); the logits
        # are promoted to fp32 so the downstream log-softmax keeps full
        # precision where it matters.
        logits = jnp.einsum("bld,vd->blv", x.astype(c.dtype),
                            wte.astype(c.dtype))
        logits = logits.astype(jnp.float32)
        if decode:
            if return_hidden:
                raise NotImplementedError(
                    "return_hidden is a full-context (training) path")
            return logits, new_kvs
        if return_hidden:
            return logits, x
        return logits


class GPT2WithValue(nn.Module):
    """GPT-2 plus a scalar value head — the RLHF actor-critic.

    The policy half is a plain :class:`GPT2` submodule named ``lm``, so
    ``params["lm"]`` is EXACTLY the param tree a serving
    ``LLMEngine``/``NaiveLM`` built on the same config accepts: the
    RLHF learner trains this module and hot-swaps ``params["lm"]`` into
    the generation engine with no renaming or surgery.  The value head
    is one fp32 linear over the post-ln_f hidden states (the standard
    PPO-for-LLMs shape), initialized near zero so early value estimates
    don't swamp the policy gradient.

    ``__call__(input_ids) -> (logits [B, L, V] f32, values [B, L] f32)``
    where ``values[:, t]`` estimates the return from the state AFTER
    consuming token t — the baseline for the token sampled at t+1.
    """

    config: GPT2Config

    @nn.compact
    def __call__(self, input_ids: jax.Array):
        logits, hidden = GPT2(self.config, name="lm")(
            input_ids, return_hidden=True)
        v = nn.Dense(1, dtype=jnp.float32, name="value_head",
                     kernel_init=nn.initializers.normal(0.01))(
            hidden.astype(jnp.float32))
        return logits, v[..., 0]

    def init_from_lm(self, rng, lm_params, example_len: int = 8):
        """Params with the ``lm`` subtree REPLACED by ``lm_params`` —
        the RLHF entry point: start the actor-critic from the exact
        weights the serving engine already holds (the value head alone
        is freshly initialized)."""
        ids = jnp.zeros((1, example_len), jnp.int32)
        params = self.init(rng, ids)["params"]
        params = dict(params)
        params["lm"] = lm_params
        return params


def gpt2_loss_fn(params, apply_fn, batch) -> jax.Array:
    """Next-token cross-entropy. batch: {"input_ids": [B, L]} (labels are the
    shifted inputs, standard LM objective)."""
    ids = batch["input_ids"]
    return next_token_cross_entropy(apply_fn({"params": params}, ids), ids)


class GPT2Stage(nn.Module):
    """One pipeline stage of a split GPT-2 (see :func:`split_stages`).

    Stage 0 owns the embeddings (wte/wpe) and consumes token ids; middle
    stages consume/produce hidden states; the last stage owns ln_f and
    the LM head and produces logits.  The head is UNTIED from wte —
    pipeline splitting puts them on different processes, and the
    tied-embedding gradient exchange (Megatron's first↔last allreduce)
    costs more than the head's extra parameters buy (documented in
    docs/PERFORMANCE.md)."""

    config: GPT2Config
    first: bool
    last: bool
    blocks: tuple  # (start, stop) block index range owned by this stage

    @nn.compact
    def __call__(self, x):
        c = self.config
        if self.first:
            ids = x
            _, l = ids.shape
            wte = self.param("wte", nn.initializers.normal(0.02),
                             (c.vocab_size, c.hidden_size), jnp.float32)
            wpe = self.param("wpe", nn.initializers.normal(0.01),
                             (c.max_position_embeddings, c.hidden_size),
                             jnp.float32)
            x = wte[ids].astype(c.dtype) + wpe[None, :l].astype(c.dtype)
        else:
            x = x.astype(c.dtype)
        for i in range(*self.blocks):
            x = Block(c, name=f"h_{i}")(x)
        if self.last:
            x = nn.LayerNorm(dtype=jnp.float32, name="ln_f")(x)
            head = self.param("lm_head", nn.initializers.normal(0.02),
                              (c.vocab_size, c.hidden_size), jnp.float32)
            logits = jnp.einsum("bld,vd->blv", x.astype(c.dtype),
                                head.astype(c.dtype))
            return logits.astype(jnp.float32)
        return x


def _stage_ce_loss(logits: jax.Array, ids: jax.Array) -> jax.Array:
    """Next-token CE on a microbatch (same objective as gpt2_loss_fn)."""
    return next_token_cross_entropy(logits, ids)


def gpt2_head_cost(config: GPT2Config) -> float:
    """LM-head cost in block-equivalents: a GPT-2 block is ~12*h^2
    params/FLOP-units, the head matmul vocab*h."""
    return config.vocab_size / (12.0 * config.hidden_size)


def split_stages(config: GPT2Config, num_stages: int, *,
                 virtual_per_rank: int = 1,
                 boundary_dtype: Any = jnp.float32, seed: int = 0):
    """Split a GPT-2 config into ``num_stages * virtual_per_rank``
    pipeline chunks for
    :class:`ray_tpu.parallel.mpmd_pipeline.MPMDPipeline`.

    Blocks are partitioned by COST, not count
    (``models/pipeline_split.py``): the embedding lookup is nearly free
    but the LM-head matmul costs ~``vocab/(12*hidden)`` block-equivalents
    (5+ blocks for GPT-2 vocab at small/XL widths), so the head-owning
    chunk gets proportionally fewer blocks.  With ``virtual_per_rank=v``
    the chunks interleave over the stages (chunk c on stage ``c % S``):
    the embedding stays pinned to stage 0 and the head to the last
    stage.  Returns ``(stage_fns, init_fns)`` in GLOBAL chunk order:
    ``stage_fns[c](params, x[, target])`` with the last returning the
    scalar loss, and ``init_fns[c]()`` building that chunk's params on
    the caller (run them ON the stage actors so XL-scale params never
    visit the driver).  Activations cross chunk boundaries as
    ``boundary_dtype`` (fp32 by default: bf16 objects are shippable but
    fp32 keeps the cotangent math bit-stable on CPU)."""
    from ray_tpu.models.pipeline_split import balance_chunks, chunk_flags

    if num_stages < 1:
        raise ValueError(f"num_stages must be >= 1, got {num_stages}")
    C = num_stages * max(1, int(virtual_per_rank))
    bounds = balance_chunks(config.num_layers, C, embed_cost=0.3,
                            head_cost=gpt2_head_cost(config))

    stage_fns, init_fns = [], []
    for k, (first, last) in enumerate(chunk_flags(C)):
        module = GPT2Stage(config, first=first, last=last, blocks=bounds[k])

        if last:
            def fn(params, x, target, _m=module):
                logits = _m.apply({"params": params}, x)
                return _stage_ce_loss(logits, target)
        else:
            def fn(params, x, _m=module, _bd=boundary_dtype):
                return _m.apply({"params": params}, x).astype(_bd)

        def init_fn(_m=module, _first=first, _seed=seed + k,
                    _c=config):
            dummy = jnp.zeros((1, 8), jnp.int32) if _first else \
                jnp.zeros((1, 8, _c.hidden_size), _c.dtype)
            return _m.init(jax.random.PRNGKey(_seed), dummy)["params"]

        stage_fns.append(fn)
        init_fns.append(init_fn)
    return stage_fns, init_fns


# Logical sharding axes per parameter name suffix (DP/FSDP/TP ready).
_AXIS_BY_NAME: Dict[str, tuple] = {
    "wte": ("vocab", "embed"),
    "wpe": (None, "embed"),
    "attn_qkv/kernel": ("embed", "heads"),   # fused qkv: shard output dim
    "attn_qkv/bias": ("heads",),
    "attn_proj/kernel": ("heads", "embed_fsdp"),
    "attn_proj/bias": (None,),
    "mlp_fc/kernel": ("embed", "mlp"),
    "mlp_fc/bias": ("mlp",),
    "mlp_proj/kernel": ("mlp", "embed_fsdp"),
    "mlp_proj/bias": (None,),
    "moe_router": ("embed", None),
    "moe_w_in": ("expert", "embed", "mlp"),
    "moe_w_out": ("expert", "mlp", "embed_fsdp"),
    # Llama family (models/llama.py) — same logical axes, llama names.
    "embed/embedding": ("vocab", "embed"),
    "q_proj/kernel": ("embed", "heads"),
    "k_proj/kernel": ("embed", "heads"),
    "v_proj/kernel": ("embed", "heads"),
    "o_proj/kernel": ("heads", "embed_fsdp"),
    "gate_proj/kernel": ("embed", "mlp"),
    "up_proj/kernel": ("embed", "mlp"),
    "down_proj/kernel": ("mlp", "embed_fsdp"),
    "lm_head/kernel": ("embed", "vocab"),
}


def param_logical_axes(params) -> Any:
    """Pytree of logical-axis tuples matching `params` (None = replicate)."""
    flat = jax.tree_util.tree_flatten_with_path(params)[0]

    def axes_for(path) -> Optional[tuple]:
        name = "/".join(getattr(k, "key", str(k)) for k in path)
        for suffix, axes in _AXIS_BY_NAME.items():
            if name.endswith(suffix):
                return axes
        return None

    leaves = [axes_for(path) for path, _ in flat]
    treedef = jax.tree_util.tree_structure(
        params, is_leaf=lambda x: hasattr(x, "shape"))
    return jax.tree_util.tree_unflatten(treedef, leaves)
