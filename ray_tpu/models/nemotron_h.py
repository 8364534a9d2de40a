"""Nemotron-H-family decoder in flax: every layer is ONE thing, a Mamba-2
mixer, an attention or a latent expert layer, chosen by a pattern.

Fourth LM family beside GPT-2, the Llama decoder and Falcon-H1.  The
published ``hybrid_override_pattern`` has one letter a layer (``M`` mixer,
``*`` attention, ``E`` experts; ``-``, a dense MLP, is not built) and every
layer is

    x = x + f_i(RMSNorm_i(x))

with ``f_i`` by the letter:

- ``M``: ``models/falcon_h1.py``'s ``Mamba2Mixer`` (``ssd_scan`` for a
  context, ``ops/ssm.py::ssm_step`` for a token) at this family's numbers
  and with no muP multiplier.
- ``*``: grouped-query attention, q, k, v, o without bias, scale
  ``1/sqrt(head_dim)``, causal, and NO position embedding: the family's
  published forward applies none (the mixers carry the order), and
  ``rope_theta`` stands in its config unused.
- ``E``: LatentMoE (``ops/moe.py``).  A float32 sigmoid router over all
  ``n_routed_experts`` on the full hidden width, chosen by score plus
  ``e_score_correction_bias`` and weighed by the score alone; the routed
  experts work in a latent width (``moe_latent_size``): ``W_down`` in,
  experts of two matrices and ``relu^2`` (no gate), ``W_up`` out; one
  shared expert at the full width beside them.

**A chip's share of the experts.**  ``experts_held`` / ``expert_offset``
say which of a layer's experts this program holds (expert parallelism's
share, ROADMAP R14): the router keeps its published width and top-k, the
layer computes the part of the result its own experts give, and what the
absent experts would add is left out.  ``W_up`` is linear and has no bias,
so the shares of all chips add after it; the shared expert is what every
chip computes alike and counts once.  Nothing stands in for the exchange.

What a layer hands the serve engine depends on its letter: ``*`` layers
the new K/V rows, ``M`` layers the recurrent state.  The model says how
many of each it has (``kv_layers``, ``state_layers``, ``expert_layers``);
``kv_caches[i]`` and ``state[i]`` are indexed by a layer's ordinal among
its kind.  The multi-token-prediction head the family publishes
(``num_nextn_predict_layers``) is a head beside this forward and is not
built.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from ray_tpu.models.falcon_h1 import FalconH1Config, Mamba2Mixer
from ray_tpu.models.llama import _norm
from ray_tpu.ops.attention import mha_attention
from ray_tpu.ops.moe import experts_held_relu2, route_sigmoid_topk
from ray_tpu.ops.ssm import live_slots


@dataclasses.dataclass(frozen=True)
class NemotronHConfig:
    """Fields under the names of the published ``config.json``, plus the
    share (``experts_held``, ``expert_offset``) and the two dtypes."""
    vocab_size: int = 131072
    max_position_embeddings: int = 262144  # no table is built to it
    hybrid_override_pattern: str = "MEMEMEM*EME"
    hidden_size: int = 4096
    layer_norm_epsilon: float = 1e-5
    # '*' layers
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    # 'M' layers
    mamba_num_heads: int = 128
    mamba_head_dim: int = 64
    n_groups: int = 8
    ssm_state_size: int = 128
    conv_kernel: int = 4
    chunk_size: int = 128
    # 'E' layers
    n_routed_experts: int = 512
    num_experts_per_tok: int = 22
    moe_intermediate_size: int = 2688
    moe_latent_size: int = 1024
    moe_shared_expert_intermediate_size: int = 5376
    routed_scaling_factor: float = 5.0
    norm_topk_prob: bool = True
    # this program's share of every layer's experts (0: all of them)
    experts_held: int = 0
    expert_offset: int = 0
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    def __post_init__(self):
        if not self.experts_held:
            object.__setattr__(self, "experts_held", self.n_routed_experts)
        stray = set(self.hybrid_override_pattern) - set("ME*")
        if stray or not self.hybrid_override_pattern:
            raise ValueError(
                "hybrid_override_pattern takes M (mixer), E (experts) and "
                f"* (attention), one letter a layer; got {sorted(stray)}")
        if not 0 <= self.expert_offset <= \
                self.n_routed_experts - self.experts_held:
            raise ValueError(
                f"experts_held {self.experts_held} from expert_offset "
                f"{self.expert_offset} on are not among the layer's "
                f"{self.n_routed_experts}")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("num_attention_heads must divide into "
                             "num_key_value_heads")

    @classmethod
    def tiny(cls, **kw):  # test-sized: every letter, a share of 4 of 16
        for k, v in dict(
                vocab_size=256, max_position_embeddings=64,
                hybrid_override_pattern="ME*E", hidden_size=48,
                num_attention_heads=4, num_key_value_heads=2, head_dim=8,
                mamba_num_heads=4, mamba_head_dim=16, n_groups=2,
                ssm_state_size=8, chunk_size=8, n_routed_experts=16,
                num_experts_per_tok=6, moe_intermediate_size=40,
                moe_latent_size=24, moe_shared_expert_intermediate_size=64,
                experts_held=4, expert_offset=4).items():
            kw.setdefault(k, v)
        return cls(**kw)

    # What the serve engine and the shared modules read off any LM config.
    @property
    def num_layers(self) -> int:
        return len(self.hybrid_override_pattern)

    @property
    def num_heads(self) -> int:
        return self.num_attention_heads

    @property
    def num_kv_heads(self) -> int:
        return self.num_key_value_heads

    @property
    def num_experts(self) -> int:
        """The router's width: every expert of a layer, held or not."""
        return self.n_routed_experts

    @property
    def rms_eps(self) -> float:
        return self.layer_norm_epsilon

    @property
    def mixer(self) -> FalconH1Config:
        """``Mamba2Mixer``'s config at this family's numbers, every muP
        multiplier left at 1."""
        return FalconH1Config(
            hidden_size=self.hidden_size, rms_eps=self.layer_norm_epsilon,
            mamba_d_ssm=self.mamba_num_heads * self.mamba_head_dim,
            mamba_n_heads=self.mamba_num_heads,
            mamba_d_head=self.mamba_head_dim, mamba_n_groups=self.n_groups,
            mamba_d_state=self.ssm_state_size, mamba_d_conv=self.conv_kernel,
            mamba_chunk_size=self.chunk_size, dtype=self.dtype,
            param_dtype=self.param_dtype)


def _drawn_in_float32(init):
    """``init`` drawn in float32 and cast to the leaf's dtype.  A leaf drawn
    IN bfloat16 comes out with a mean of -1.6% of its deviation
    (``jax.random`` in bfloat16: 84 deviations of the mean of a 5376 x 4096
    leaf).  Behind zero-mean inputs that is nothing; behind ``relu^2``,
    whose outputs are all positive, it is a shift of the same sign in every
    output column and every token, half the shared expert's output at the
    published widths, which the next expert layer's ``relu^2`` doubles: by
    the third expert layer every token chose the same experts (PERF.md,
    PR 43).  So this family's leaves are drawn in float32, one by one."""
    def drawn(key, shape, dtype=jnp.float32):
        return init(key, shape, jnp.float32).astype(dtype)

    return drawn


_kernel_init = _drawn_in_float32(nn.initializers.lecun_normal())


def _stack_init(key, shape, dtype=jnp.float32):
    """[E, in, out]: every expert's matrix drawn in float32 for its own
    fan-in (``models/llama.py::_expert_init``'s scaling), one expert at a
    time, so that no float32 copy of the stack exists (128 experts of
    1024 x 2688 are 1.4 GB in float32, beside 9 GB of leaves made
    before)."""
    return jax.lax.map(lambda k: _kernel_init(k, shape[1:], dtype),
                       jax.random.split(key, shape[0]))


def _dense(c: "NemotronHConfig", feats: int, name: str) -> nn.Dense:
    """``models/llama.py::_dense`` with the kernel drawn in float32."""
    return nn.Dense(feats, use_bias=False, dtype=c.dtype,
                    param_dtype=c.param_dtype, kernel_init=_kernel_init,
                    name=name)


class NemotronHAttention(nn.Module):
    """Grouped-query attention with no position embedding.  ``kv`` is the
    caller's ``attend(q, k, v)`` for this layer, as in ``models/llama.py``:
    k and v stay at ``num_key_value_heads`` and come back for the caller's
    cache."""
    config: NemotronHConfig

    @nn.compact
    def __call__(self, x, kv=None):
        c = self.config
        bsz, length, _ = x.shape
        hd, h, hkv = c.head_dim, c.num_attention_heads, c.num_key_value_heads
        q = _dense(c, h * hd, "q_proj")(x).reshape(bsz, length, h, hd)
        k = _dense(c, hkv * hd, "k_proj")(x).reshape(bsz, length, hkv, hd)
        v = _dense(c, hkv * hd, "v_proj")(x).reshape(bsz, length, hkv, hd)
        if kv is not None:
            out = kv(q, k, v)
        else:
            out = mha_attention(q, jnp.repeat(k, h // hkv, axis=2),
                                jnp.repeat(v, h // hkv, axis=2), causal=True)
        out = _dense(c, c.hidden_size, "o_proj")(
            out.reshape(bsz, length, h * hd))
        return out, (k, v) if kv is not None else None


def _score_bias_init(key, shape, dtype=jnp.float32):
    """``e_score_correction_bias``: uniform in [-0.02, 0.02], not zero, so
    that choosing by score + bias and weighing by score differ.  A published
    checkpoint carries the values its balancing run ended on, which even
    the experts' load out; no config key fixes them.  At the 22nd of 512
    scores (0.85, where a score moves 0.13 a unit of the router's logit)
    0.02 moves an expert's share of the tokens by a third; 0.1 would
    quadruple it."""
    return jax.random.uniform(key, shape, dtype, -0.02, 0.02)


class LatentMoE(nn.Module):
    """The ``E`` layer on the normed input ``u`` [B, L, d]:

        s, idx = route_sigmoid_topk(u)              # float32, all experts
        routed = W_up( sum_j w_j relu(l U_e)^2 D_e ),  l = W_down u
        out    = routed + D_s relu(U_s u)^2         # the shared expert

    with only the held experts' terms in the sum.  ``live`` [B, L] bool
    (default: all) marks the rows that count: any other row chooses
    nothing and reads no expert.  Sown into ``moe`` for a caller that asks
    (``mutable=["moe"]``), as ``LlamaMoE`` does: ``expert_idx`` [B, L, k]
    (ids over all experts), ``experts_streamed`` and, beside them,
    ``local_choices``: the live rows' choices that landed on a held
    expert.  The two parts are sown into ``branches``."""
    config: NemotronHConfig

    @nn.compact
    def __call__(self, u, live=None):
        c = self.config
        d, lat, f = c.hidden_size, c.moe_latent_size, c.moe_intermediate_size
        router = self.param("router", _kernel_init,
                            (d, c.n_routed_experts), jnp.float32)
        bias = self.param("e_score_correction_bias", _score_bias_init,
                          (c.n_routed_experts,), jnp.float32)
        w_up = self.param("w_up", _stack_init, (c.experts_held, lat, f),
                          c.param_dtype)
        w_down = self.param("w_down", _stack_init, (c.experts_held, f, lat),
                            c.param_dtype)
        bsz, length, _ = u.shape
        rows = u.reshape(bsz * length, d).astype(c.dtype)
        with jax.named_scope("route"):
            weights, experts = route_sigmoid_topk(
                rows, router, bias, c.num_experts_per_tok, c.norm_topk_prob,
                c.routed_scaling_factor)
        self.sow("moe", "expert_idx",
                 experts.reshape(bsz, length, c.num_experts_per_tok))
        with jax.named_scope("latent.down"):
            latent = _dense(c, lat, "latent_down")(rows)
        with jax.named_scope("experts"):
            mixed, streamed, landed = experts_held_relu2(
                latent, weights, experts, w_up, w_down, c.expert_offset,
                active=None if live is None else live.reshape(-1))
        self.sow("moe", "experts_streamed", streamed)
        self.sow("moe", "local_choices", landed)
        with jax.named_scope("latent.up"):
            routed = _dense(c, d, "latent_up")(mixed).reshape(u.shape)
        with jax.named_scope("shared"):
            hidden = nn.relu(_dense(
                c, c.moe_shared_expert_intermediate_size, "shared_up")(u))
            shared = _dense(c, d, "shared_down")(hidden * hidden)
        self.sow("branches", "routed_out", routed)
        self.sow("branches", "shared_out", shared)
        return routed + shared


class NemotronHBlock(nn.Module):
    """One layer of kind ``kind``.  What an ``M`` or ``*`` layer adds to
    the residual stream is sown into ``branches`` (``mixer_out``,
    ``attn_out``; an ``E`` layer sows its two parts itself)."""
    config: NemotronHConfig
    kind: str

    @nn.compact
    def __call__(self, x, kv=None, state=None, lengths=None, active=None,
                 live=None):
        c = self.config
        u = _norm(c, "norm")(x)
        new_kv = new_state = None
        if self.kind == "M":
            out, new_state = Mamba2Mixer(c.mixer, name="mixer")(
                u, state=state, lengths=lengths, active=active, live=live)
            self.sow("branches", "mixer_out", out)
        elif self.kind == "*":
            out, new_kv = NemotronHAttention(c, name="attn")(u, kv=kv)
            self.sow("branches", "attn_out", out)
        else:
            live = None  # a free lane, and a bucket's padding, choose nothing
            if active is not None:
                live = jnp.broadcast_to(active[:, None], u.shape[:2])
            if lengths is not None:
                real = jnp.arange(u.shape[1])[None] < lengths[:, None]
                live = real if live is None else live & real
            out = LatentMoE(c, name="moe")(u, live=live)
        return x + out, new_kv, new_state


class NemotronH(nn.Module):
    config: NemotronHConfig

    @property
    def kv_layers(self) -> int:
        """Layers that write K/V rows: the serve engine's pool has as many."""
        return self.config.hybrid_override_pattern.count("*")

    @property
    def state_layers(self) -> int:
        """Layers that carry ``slot_state``."""
        return self.config.hybrid_override_pattern.count("M")

    @property
    def expert_layers(self) -> int:
        return self.config.hybrid_override_pattern.count("E")

    @property
    def slot_state(self) -> dict:
        """What one sequence carries from token to token in one ``M`` layer:
        name -> (shape, dtype) (``FalconH1.slot_state``)."""
        m = self.config.mixer
        return {"ssm": ((m.mamba_n_heads, m.mamba_d_head, m.mamba_d_state),
                        jnp.float32),
                "conv": ((m.mamba_d_conv - 1, m.conv_dim), m.dtype)}

    @nn.compact
    def __call__(self, input_ids: jax.Array, positions: jax.Array = None,
                 kv_caches=None, state=None, lengths=None, active=None,
                 logits_at=None):
        """``FalconH1.__call__``'s contract, with ``kv_caches`` one
        ``attend(q, k, v)`` a ``*`` layer and ``state`` one set an ``M``
        layer, each in the layers' order; ``new_kvs`` and ``new_state``
        come back likewise.  ``positions`` is taken and not used: no layer
        embeds a position."""
        c = self.config
        emb = nn.Embed(c.vocab_size, c.hidden_size, dtype=c.dtype,
                       param_dtype=c.param_dtype, name="embed",
                       embedding_init=_drawn_in_float32(
                           nn.initializers.variance_scaling(
                               1.0, "fan_in", "normal", out_axis=0)))
        x = emb(input_ids)
        cached = kv_caches is not None
        # the list of live rows, once for every ``M`` layer's state pass
        live = live_slots(active) if state is not None else None
        new_kvs, new_state = [], []
        for i, kind in enumerate(c.hybrid_override_pattern):
            kw = {}
            if kind == "*" and cached:
                kw["kv"] = kv_caches[len(new_kvs)]
            elif kind == "M" and state is not None:
                kw.update(state=state[len(new_state)], live=live)
            x, nkv, nst = NemotronHBlock(c, kind, name=f"layer_{i}")(
                x, lengths=lengths, active=active, **kw)
            if kind == "*":
                new_kvs.append(nkv)
            elif kind == "M":
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
