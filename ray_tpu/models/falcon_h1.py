"""Falcon-H1-family decoder in flax: a Mamba-2 mixer beside grouped-query
attention in every block.

Third LM family beside GPT-2 and the Llama decoder.  Every layer runs two
sequence mixers IN PARALLEL on one normed input and sums them into the
residual stream, then a SwiGLU feed-forward:

    u = RMSNorm_in(x)
    x = x + ssm_out_multiplier * Mixer(u)
          + attention_out_multiplier * Attn(u * attention_in_multiplier)
    x = x + FFN(RMSNorm_ff(x))

with the family's published scalar multipliers (muP) on the embedding, the
head, the keys, both mixers' inputs and outputs, the feed-forward's gate and
down projection and the five sections of the mixer's input projection.
``head_dim`` is a field of its own: the family publishes it, and it is not
``hidden_size / num_heads``.  RMSNorm, the projections' and norms' makers,
the rope tables and the per-layer ``attend(q, k, v)`` cache hook are
``models/llama.py``'s.

The mixer (``Mamba2Mixer``) exists in two forms that give the same numbers:
``ssd_scan``, the chunked scan (matrix products inside a chunk, the state
carried from chunk to chunk; float32 state and decay) for a whole context,
and ``ssd_step``, the one-token recurrence: its plain definition, which a
decode step runs as ``ops/ssm.py::ssm_step`` (a kernel over the live slots
of the caller's pool, in place).  What a sequence
carries from token to token is of fixed size, whatever its length: the
``[heads, head_dim, d_state]`` float32 state and the last ``d_conv - 1``
rows of the causal convolution's input.  ``FalconH1.slot_state`` says those
shapes to whoever holds them (the serve engine, one set a slot a layer,
beside the K/V pages).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ray_tpu.models.llama import _dense, _norm, apply_rope, rope_tables
from ray_tpu.ops.attention import mha_attention
from ray_tpu.ops.ssm import live_slots, ssm_step


@dataclasses.dataclass(frozen=True)
class FalconH1Config:
    vocab_size: int = 32000
    max_position_embeddings: int = 2048   # rows of the rope tables
    num_layers: int = 4
    num_heads: int = 8
    num_kv_heads: int = 2
    head_dim: int = 64                    # published; not hidden / heads
    hidden_size: int = 512
    intermediate_size: int = 2048
    rope_theta: float = 1e11
    rms_eps: float = 1e-5
    # Mamba-2 mixer
    mamba_d_ssm: int = 512                # = mamba_n_heads * mamba_d_head
    mamba_n_heads: int = 8
    mamba_d_head: int = 64
    mamba_n_groups: int = 1
    mamba_d_state: int = 128
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 128           # of the scan, not of the result
    # muP multipliers
    embedding_multiplier: float = 1.0
    lm_head_multiplier: float = 1.0
    key_multiplier: float = 1.0
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 1.0
    ssm_in_multiplier: float = 1.0
    ssm_out_multiplier: float = 1.0
    ssm_multipliers: Tuple[float, ...] = (1.0,) * 5   # z, x, B, C, dt
    mlp_multipliers: Tuple[float, float] = (1.0, 1.0)  # gate, down
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    def __post_init__(self):
        # A published 100000000000 is an int no int32 holds.
        object.__setattr__(self, "rope_theta", float(self.rope_theta))
        # JSON gives lists; a frozen, hashable config wants tuples.
        for name in ("ssm_multipliers", "mlp_multipliers"):
            object.__setattr__(self, name, tuple(
                float(v) for v in getattr(self, name)))
        if self.mamba_d_ssm != self.mamba_n_heads * self.mamba_d_head:
            raise ValueError("mamba_d_ssm must be mamba_n_heads * "
                             "mamba_d_head")
        if self.mamba_n_heads % self.mamba_n_groups:
            raise ValueError("mamba_n_heads must divide into "
                             "mamba_n_groups")

    @classmethod
    def tiny(cls, **kw):  # test-sized: head_dim != hidden / heads, 2 groups
        for k, v in dict(
                vocab_size=256, max_position_embeddings=64, num_layers=2,
                num_heads=4, num_kv_heads=2, head_dim=8, hidden_size=48,
                intermediate_size=96, mamba_d_ssm=64, mamba_n_heads=4,
                mamba_d_head=16, mamba_n_groups=2, mamba_d_state=8,
                mamba_chunk_size=8).items():
            kw.setdefault(k, v)
        return cls(**kw)

    @property
    def conv_dim(self) -> int:
        """Channels of the causal convolution: [x | B | C]."""
        return self.mamba_d_ssm + 2 * self.mamba_n_groups * self.mamba_d_state

    @property
    def in_proj_dim(self) -> int:
        """[z | x | B | C | dt]."""
        return self.mamba_d_ssm + self.conv_dim + self.mamba_n_heads


# ---------------------------------------------------------------------------
# The state-space recurrence, in its two forms.  Per head h of group g:
#   S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t^g ,  y_t = S_t C_t^g
# ---------------------------------------------------------------------------
def _heads_of(groups: jax.Array, n_heads: int) -> jax.Array:
    """[..., G, N] -> [..., H, N]: head h reads group h // (H / G)."""
    return jnp.repeat(groups, n_heads // groups.shape[-2], axis=-2)


def ssd_step(state, x, dt, a, b, c):
    """One token.  state [B, H, P, N] float32; x [B, H, P]; dt [B, H]
    float32 (after softplus); a [H] float32 (negative); b, c [B, G, N].
    Returns (y [B, H, P] float32, new state).  Elementwise in float32: the
    state is read once and written once."""
    h = x.shape[1]
    f32 = jnp.float32
    b, c = _heads_of(b.astype(f32), h), _heads_of(c.astype(f32), h)
    decay = jnp.exp(dt * a)[:, :, None, None]
    inject = (dt[:, :, None] * x.astype(f32))[..., None] * b[:, :, None, :]
    state = decay * state + inject
    return jnp.sum(state * c[:, :, None, :], axis=-1), state


def ssd_scan(x, dt, a, b, c, chunk: int):
    """A whole context, chunk by chunk.  x [B, L, H, P]; dt [B, L, H]
    float32 (0 on a row that must advance nothing: padding); a [H]; b, c
    [B, L, G, N]; from an empty state.  Returns (y [B, L, H, P] float32,
    the state [B, H, P, N] float32 after row L - 1).

    Inside a chunk of Q rows everything is matrix products: with
    ``cum_t`` the running sum of ``dt A`` in the chunk,
    ``y_t = sum_{s<=t} exp(cum_t - cum_s) dt_s (C_t . B_s) x_s`` and the
    chunk adds ``sum_s exp(cum_Q - cum_s) dt_s x_s (x) B_s`` to a state
    that decays by ``exp(cum_Q)``; the states at the chunks' starts are a
    short sequential scan, and ``exp(cum_t) C_t . S_start`` is their part
    of y.  Products take the operands' dtype with float32 sums; decays,
    cumulative sums and the state are float32.  Any chunk length gives the
    same numbers up to rounding."""
    f32 = jnp.float32
    bsz, length, h, p = x.shape
    n = b.shape[-1]
    q = min(chunk, length)
    pad = -length % q
    if pad:  # rows that advance nothing
        x, dt, b, c = (
            jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
            for v in (x, dt, b, c))
    nc = (length + pad) // q
    x = x.reshape(bsz, nc, q, h, p)
    dt = dt.reshape(bsz, nc, q, h)
    b = _heads_of(b, h).reshape(bsz, nc, q, h, n)
    c = _heads_of(c, h).reshape(bsz, nc, q, h, n)
    cum = jnp.cumsum(dt * a, axis=2)                      # [B, nc, Q, H]
    total = cum[:, :, -1]                                 # [B, nc, H]

    # within a chunk
    scores = jnp.einsum("bcthn,bcshn->bchts", c, b,
                        preferred_element_type=f32)
    lower = jnp.tril(jnp.ones((q, q), bool))
    gap = cum.transpose(0, 1, 3, 2)                       # [B, nc, H, Q]
    decay = jnp.where(lower, jnp.exp(jnp.where(
        lower, gap[..., :, None] - gap[..., None, :], 0.0)), 0.0)
    weights = scores * decay * dt.transpose(0, 1, 3, 2)[..., None, :]
    y = jnp.einsum("bchts,bcshp->bcthp", weights.astype(x.dtype), x,
                   preferred_element_type=f32)

    # what each chunk adds to the state, and the states at the chunks' starts
    to_end = jnp.exp(total[:, :, None] - cum) * dt        # [B, nc, Q, H]
    added = jnp.einsum("bcsh,bcshp,bcshn->bchpn", to_end, x.astype(f32),
                       b.astype(f32),
                       precision=jax.lax.Precision.HIGHEST)
    def carry(s, chunk_in):
        tot, add = chunk_in
        return jnp.exp(tot)[:, :, None, None] * s + add, s

    state, starts = jax.lax.scan(
        carry, jnp.zeros((bsz, h, p, n), f32), (total.transpose(1, 0, 2),
                       added.transpose(1, 0, 2, 3, 4)))
    starts = starts.transpose(1, 0, 2, 3, 4)              # [B, nc, H, P, N]
    y = y + jnp.exp(cum)[..., None] * jnp.einsum(
        "bcthn,bchpn->bcthp", c.astype(f32), starts,
        precision=jax.lax.Precision.HIGHEST)
    return y.reshape(bsz, nc * q, h, p)[:, :length], state


class Mamba2Mixer(nn.Module):
    """``p = (W_in (u * ssm_in_multiplier)) * m`` with ``W_in``: hidden ->
    [z | x | B | C | dt] and ``m`` the five ``ssm_multipliers`` section by
    section; a causal depthwise convolution of width ``d_conv`` and a silu
    over [x | B | C]; ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``;
    the recurrence above plus ``D x_t``; ``y * silu(z)`` through an RMSNorm
    whose variance is taken over each group's channels (gate, then norm);
    ``W_out``.

    ``state`` None: a whole context from an empty state.  With ``lengths``
    ([B]: real rows; the rest is padding, which advances nothing) it also
    returns what the sequence carries on: ``{"ssm": [B, H, P, N] float32,
    "conv": [B, d_conv - 1, conv_dim]}`` as they stand after row
    ``lengths - 1``.  ``state`` given (one new token a sequence; the
    caller's pool, a row a slot): the one-token recurrence over the rows
    that ``active`` [B] marks, which ``live`` lists (``ops/ssm.py::
    live_slots(active)``, made once for all layers).  ``ops/ssm.py::
    ssm_step`` advances those rows' ``ssm`` in place and neither reads nor
    writes another's; their ``conv`` rows stay as they were."""
    config: FalconH1Config

    @nn.compact
    def __call__(self, u, state=None, lengths=None, active=None, live=None):
        c = self.config
        f32 = jnp.float32
        bsz, length, _ = u.shape
        h, p, g, n = (c.mamba_n_heads, c.mamba_d_head, c.mamba_n_groups,
                      c.mamba_d_state)
        k = c.mamba_d_conv
        w_conv = self.param("conv_kernel", nn.initializers.lecun_normal(),
                            (k, c.conv_dim), c.param_dtype)
        b_conv = self.param("conv_bias", nn.initializers.zeros,
                            (c.conv_dim,), c.param_dtype)
        dt_bias = self.param("dt_bias", _dt_bias_init, (h,), f32)
        a_log = self.param("A_log", _a_log_init, (h,), f32)
        d_skip = self.param("D", nn.initializers.ones, (h,), f32)
        norm_scale = self.param("norm_scale", nn.initializers.ones,
                                (c.mamba_d_ssm,), c.param_dtype)

        # A family without muP multipliers (``models/nemotron_h.py``) leaves
        # them at 1 and multiplies by nothing.
        mup = None
        if any(m != 1.0 for m in c.ssm_multipliers):
            sections = (c.mamba_d_ssm, c.mamba_d_ssm, g * n, g * n, h)
            mup = jnp.concatenate([jnp.full((w,), m, c.dtype) for w, m in
                                   zip(sections, c.ssm_multipliers)])
        if c.ssm_in_multiplier != 1.0:
            u = u * c.ssm_in_multiplier
        proj = _dense(c, c.in_proj_dim, "in_proj")(u)
        if mup is not None:
            proj = proj * mup
        z, xbc, dt = jnp.split(
            proj, [c.mamba_d_ssm, c.mamba_d_ssm + c.conv_dim], axis=-1)
        dt = jax.nn.softplus(dt.astype(f32) + dt_bias)
        a = -jnp.exp(a_log)

        # causal depthwise convolution: out_t = sum_j w[j] in_{t-(k-1)+j}
        before = (jnp.zeros((bsz, k - 1, c.conv_dim), xbc.dtype)
                  if state is None else state["conv"].astype(xbc.dtype))
        window = jnp.concatenate([before, xbc], axis=1)
        conv = sum(window[:, j:j + length] * w_conv[j].astype(xbc.dtype)
                   for j in range(k)) + b_conv.astype(xbc.dtype)
        x, b, cc = jnp.split(nn.silu(conv),
                             [c.mamba_d_ssm, c.mamba_d_ssm + g * n], axis=-1)
        x = x.reshape(bsz, length, h, p)
        b = b.reshape(bsz, length, g, n)
        cc = cc.reshape(bsz, length, g, n)

        new_state = None
        if state is not None:
            with jax.named_scope("mixer.step"):
                ssm, y = ssm_step(state["ssm"], *live, x[:, 0], dt[:, 0], a,
                                  b[:, 0], cc[:, 0])
                y = y[:, None]
            new_state = {"ssm": ssm, "conv": jnp.where(
                active[:, None, None],
                window[:, 1:].astype(state["conv"].dtype), state["conv"])}
        else:
            with jax.named_scope("mixer.scan"):
                if lengths is not None:  # padding advances nothing
                    real = jnp.arange(length)[None] < lengths[:, None]
                    dt = jnp.where(real[..., None], dt, 0.0)
                y, ssm = ssd_scan(x, dt, a, b, cc, c.mamba_chunk_size)
            if lengths is not None:
                # rows lengths-(k-1) .. lengths-1 of the convolution's input
                at = lengths[:, None] + jnp.arange(k - 1)[None]
                new_state = {"ssm": ssm, "conv": jnp.take_along_axis(
                    window, at[..., None], axis=1)}
        y = y + d_skip[:, None] * x.astype(f32)
        y = y.reshape(bsz, length, c.mamba_d_ssm) * nn.silu(z.astype(f32))
        # gate, then an RMSNorm with one variance a group
        grouped = y.reshape(bsz, length, g, c.mamba_d_ssm // g)
        var = jnp.mean(jnp.square(grouped), axis=-1, keepdims=True)
        y = (grouped * jax.lax.rsqrt(var + c.rms_eps)).reshape(y.shape)
        y = y.astype(c.dtype) * norm_scale.astype(c.dtype)
        return _dense(c, c.hidden_size, "out_proj")(y), new_state


def _a_log_init(key, shape, dtype=jnp.float32):
    """A = -exp(A_log) with A drawn uniformly from [1, 16] (Mamba-2's own
    initialiser; no published key fixes it)."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def _dt_bias_init(key, shape, dtype=jnp.float32):
    """softplus(dt_bias) log-uniform in [1e-3, 1e-1] (Mamba-2's own)."""
    dt = jnp.exp(jax.random.uniform(key, shape, dtype, jnp.log(1e-3),
                                    jnp.log(1e-1)))
    return dt + jnp.log(-jnp.expm1(-dt))  # softplus's inverse


class FalconH1Attention(nn.Module):
    """Grouped-query attention with heads of ``config.head_dim``, rope over
    the whole head (rotate-half) and the keys scaled by ``key_multiplier``.
    ``kv`` is the caller's ``attend(q, k, v)`` for this layer, as in
    ``models/llama.py``: k and v stay at ``num_kv_heads`` and come back
    (post-rope) for the caller's cache."""
    config: FalconH1Config

    @nn.compact
    def __call__(self, x, kv=None, positions=None):
        c = self.config
        bsz, length, _ = x.shape
        hd = c.head_dim
        q = _dense(c, c.num_heads * hd, "q_proj")(x).reshape(
            bsz, length, c.num_heads, hd)
        k = (_dense(c, c.num_kv_heads * hd, "k_proj")(x)
             * c.key_multiplier).reshape(bsz, length, c.num_kv_heads, hd)
        v = _dense(c, c.num_kv_heads * hd, "v_proj")(x).reshape(
            bsz, length, c.num_kv_heads, hd)
        if kv is not None:
            cos, sin = rope_tables(c.max_position_embeddings, hd,
                                   c.rope_theta)
            q = apply_rope(q, cos[positions], sin[positions])
            k = apply_rope(k, cos[positions], sin[positions])
            out = kv(q, k, v).reshape(bsz, length, c.num_heads * hd)
            return _dense(c, c.hidden_size, "o_proj")(out), (k, v)
        cos, sin = rope_tables(length, hd, c.rope_theta)
        q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
        rep = c.num_heads // c.num_kv_heads
        out = mha_attention(q, jnp.repeat(k, rep, axis=2),
                            jnp.repeat(v, rep, axis=2), causal=True)
        out = out.reshape(bsz, length, c.num_heads * hd)
        return _dense(c, c.hidden_size, "o_proj")(out), None


class FalconH1MLP(nn.Module):
    config: FalconH1Config

    @nn.compact
    def __call__(self, x):
        c = self.config
        gate = _dense(c, c.intermediate_size, "gate_proj")(x) \
            * c.mlp_multipliers[0]
        up = _dense(c, c.intermediate_size, "up_proj")(x)
        return _dense(c, c.hidden_size, "down_proj")(up * nn.silu(gate)) \
            * c.mlp_multipliers[1]


class FalconH1Block(nn.Module):
    """The three branches as they are added to the residual stream are
    sown into the ``branches`` collection (``mixer_out``, ``attn_out``,
    ``ffn_out``: [B, L, hidden]) for a caller that asks for it
    (``mutable=["branches"]``), so that a comparison with a reference can
    be made branch by branch; any other caller pays nothing."""
    config: FalconH1Config

    @nn.compact
    def __call__(self, x, kv=None, positions=None, state=None, lengths=None,
                 active=None, live=None):
        c = self.config
        u = _norm(c, "in_norm")(x)
        mixed, new_state = Mamba2Mixer(c, name="mixer")(
            u, state=state, lengths=lengths, active=active, live=live)
        mixed = mixed * c.ssm_out_multiplier
        attn, new_kv = FalconH1Attention(c, name="attn")(
            u * c.attention_in_multiplier, kv=kv, positions=positions)
        attn = attn * c.attention_out_multiplier
        x = x + mixed + attn
        ffn = FalconH1MLP(c, name="mlp")(_norm(c, "ff_norm")(x))
        for name, branch in (("mixer", mixed), ("attn", attn), ("ffn", ffn)):
            self.sow("branches", name + "_out", branch)
        return x + ffn, new_kv, new_state


class FalconH1(nn.Module):
    config: FalconH1Config

    @property
    def slot_state(self) -> dict:
        """What one sequence carries from token to token in one layer
        besides its K/V rows: name -> (shape, dtype).  The serve engine
        keeps one such set a slot a layer."""
        c = self.config
        return {"ssm": ((c.mamba_n_heads, c.mamba_d_head, c.mamba_d_state),
                        jnp.float32),
                "conv": ((c.mamba_d_conv - 1, c.conv_dim), c.dtype)}

    @nn.compact
    def __call__(self, input_ids: jax.Array, positions: jax.Array = None,
                 kv_caches=None, state=None, lengths=None, active=None,
                 logits_at=None):
        """Full-context: ``input_ids`` [B, L] -> logits [B, L, vocab].

        With ``kv_caches`` (per-layer ``attend(q, k, v)``) and absolute
        ``positions``, the cached forms, which return ``(logits, new_kvs,
        new_state)``: a prefill (``state`` None, ``lengths`` [B] real rows
        of the padded context) whose ``new_state`` is, per layer, what
        ``slot_state`` describes after row ``lengths - 1``; or a decode
        step (``state``: that list, one new token a sequence; ``active``
        [B]: rows that advance; the state of any other row is not touched).

        ``logits_at`` ([B] row indices): the head on those rows only,
        logits [B, 1, vocab]; no [L, vocab] array is built.  The head
        multiplies in the activations' dtype and sums in float32."""
        c = self.config
        emb = nn.Embed(c.vocab_size, c.hidden_size, dtype=c.dtype,
                       param_dtype=c.param_dtype, name="embed")
        x = emb(input_ids) * c.embedding_multiplier
        cached = kv_caches is not None
        # the list of live rows, once for every layer's state pass
        live = live_slots(active) if state is not None else None
        new_kvs, new_state = [], []
        for i in range(c.num_layers):
            x, nkv, nst = FalconH1Block(c, name=f"layer_{i}")(
                x, kv=kv_caches[i] if cached else None, positions=positions,
                state=state[i] if state is not None else None,
                lengths=lengths, active=active, live=live)
            new_kvs.append(nkv)
            new_state.append(nst)
        if logits_at is not None:
            x = jnp.take_along_axis(x, logits_at[:, None, None], axis=1)
        x = _norm(c, "final_norm")(x)
        head = self.param("lm_head", nn.initializers.lecun_normal(),
                          (c.hidden_size, c.vocab_size), c.param_dtype)
        logits = jnp.dot(x, head.astype(c.dtype),
                         preferred_element_type=jnp.float32) \
            * c.lm_head_multiplier
        if cached:
            return logits, new_kvs, new_state
        return logits
