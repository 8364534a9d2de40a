"""Plain reference for ``falcon_h1_34b``: Falcon-H1's forward pass in float32
``jax.numpy`` at the highest matmul precision, with no cache, no chunked
scan, no kernel and no batching trick.  Independent of ``ray_tpu``: it reads
the weights out of the program's parameter tree and nothing else.

Follows ``tiiuae/Falcon-H1-34B-Instruct``'s published ``config.json``
(``model_type`` falcon_h1) and the layer it describes.  ``x`` is the
residual stream, every projection is without bias, ``rms`` is an RMSNorm
with a learned scale and epsilon ``rms_norm_eps``:

    x = E[ids] * embedding_multiplier
    per layer:
      u = rms_in(x)
      x = x + ssm_out_multiplier * Mixer(u)
            + attention_out_multiplier * Attn(u * attention_in_multiplier)
      x = x + FFN(rms_ff(x))
    logits = (W_head rms_final(x)) * lm_head_multiplier         (untied)

    Attn:  q = W_q u (num_attention_heads heads of head_dim),
           k = (W_k u) * key_multiplier, v = W_v u (num_key_value_heads);
           rope over the whole head (rotate-half, rope_theta) on q and k;
           causal softmax at 1/sqrt(head_dim); W_o.
    Mixer (Mamba-2; d_ssm = mamba_n_heads * mamba_d_head, n_groups, d_state,
           d_conv):
           p = (W_in (u * ssm_in_multiplier)) * m,  W_in: hidden ->
           [z d_ssm | x d_ssm | B groups*d_state | C groups*d_state |
            dt heads], m = 1 scaled section by section by ssm_multipliers
           in that order (z, x, B, C, dt);
           [x|B|C] = silu(causal depthwise conv1d_{d_conv}([x|B|C]) + b_conv);
           dt = softplus(dt + dt_bias);  A = -exp(A_log), one a head;
           per head h of group g = h // (heads / groups), token by token:
             S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t^g   (S: head x state)
             y_t = S_t C_t^g + D x_t
           gate THEN norm (mamba_norm_before_gate false, mamba_rms_norm
           true): y = rms_grouped(y * silu(z)), the variance over each
           group's d_ssm / groups channels, one learned scale of d_ssm;
           W_out: d_ssm -> hidden.
    FFN:   W_down(W_up v * silu((W_gate v) * mlp_multipliers[0]))
           * mlp_multipliers[1]

``mamba_chunk_size`` is the chunk of a scan, not of the result: the
recurrence here has no chunks.

Departures of the program from this: none in the mathematics.  The program
computes in bfloat16 with float32 sums, keeps the recurrent state and its
decay in float32, runs the recurrence as a chunked scan over a whole context
and token by token only in decode, and builds its rope tables to the
serving context, not to ``max_position_embeddings``.  Layout conventions that
no published key fixes are the program's: the convolution's weight is
``conv_kernel[j, channel]`` with ``out_t = sum_j conv_kernel[j] in_{t-(d_conv
-1)+j}``, and projections are ``[in, out]``.

So that it fits beside a serving engine on one chip (10.5 GB of bfloat16
weights), it is jitted layer by layer, the feed-forward by blocks of its
width and the head by blocks of the vocabulary, each block upcast alone; the
head can be asked for the last rows only (``first_row``).  ``forward_with_
branches`` also returns what each of a layer's three branches adds to the
residual stream (after its multiplier), for a comparison branch by branch.
"""
import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
FFN_BLOCKS = 8
VOCAB_BLOCK = 16384


def _rms(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * scale.astype(F32)


def _rope(x, theta):
    """x [B, S, H, D] at positions 0..S-1, rotate-half form."""
    s, d = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=F32) / d)
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[None, :, None]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[None, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _attn(a, u, *, heads, kv_heads, hd, key_mult, theta):
    b, s, _ = u.shape
    w = lambda name: a[name]["kernel"].astype(F32)  # noqa: E731
    q = _rope((u @ w("q_proj")).reshape(b, s, heads, hd), theta)
    k = _rope(((u @ w("k_proj")) * key_mult).reshape(b, s, kv_heads, hd),
              theta)
    v = (u @ w("v_proj")).reshape(b, s, kv_heads, hd)
    k = jnp.repeat(k, heads // kv_heads, axis=2)
    v = jnp.repeat(v, heads // kv_heads, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(F32(hd))
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    att = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)
    return att.reshape(b, s, heads * hd) @ w("o_proj")


def _mixer(m, u, *, n_heads, d_head, groups, d_state, d_conv, in_mult,
           section_mults, eps):
    b, s, _ = u.shape
    d_ssm, gn = n_heads * d_head, groups * d_state
    widths = (d_ssm, d_ssm, gn, gn, n_heads)
    mup = jnp.concatenate([jnp.full((w,), mult, F32)
                           for w, mult in zip(widths, section_mults)])
    p = ((u * in_mult) @ m["in_proj"]["kernel"].astype(F32)) * mup
    z, xbc, dt = jnp.split(p, [d_ssm, 2 * d_ssm + 2 * gn], axis=-1)
    # causal depthwise convolution, then silu
    w_conv = m["conv_kernel"].astype(F32)
    padded = jnp.pad(xbc, ((0, 0), (d_conv - 1, 0), (0, 0)))
    conv = sum(padded[:, j:j + s] * w_conv[j] for j in range(d_conv))
    xbc = jax.nn.silu(conv + m["conv_bias"].astype(F32))
    x, bb, cc = jnp.split(xbc, [d_ssm, d_ssm + gn], axis=-1)
    x = x.reshape(b, s, n_heads, d_head)
    per = n_heads // groups
    bb = jnp.repeat(bb.reshape(b, s, groups, d_state), per, axis=2)
    cc = jnp.repeat(cc.reshape(b, s, groups, d_state), per, axis=2)
    dt = jax.nn.softplus(dt + m["dt_bias"].astype(F32))     # [B, S, H]
    a = -jnp.exp(m["A_log"].astype(F32))                    # [H]

    def token(state, t):  # the recurrence, one token at a time
        x_t, b_t, c_t, dt_t = t
        state = (jnp.exp(dt_t * a)[..., None, None] * state
                 + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :])
        return state, jnp.sum(state * c_t[:, :, None, :], axis=-1)

    first = jnp.zeros((b, n_heads, d_head, d_state), F32)
    _, y = jax.lax.scan(token, first, tuple(
        jnp.moveaxis(v, 1, 0) for v in (x, bb, cc, dt)))
    y = jnp.moveaxis(y, 0, 1) + m["D"].astype(F32)[:, None] * x
    y = y.reshape(b, s, d_ssm) * jax.nn.silu(z)             # gate, then norm
    grouped = y.reshape(b, s, groups, d_ssm // groups)
    var = jnp.mean(jnp.square(grouped), axis=-1, keepdims=True)
    y = (grouped / jnp.sqrt(var + eps)).reshape(b, s, d_ssm)
    y = y * m["norm_scale"].astype(F32)
    return y @ m["out_proj"]["kernel"].astype(F32)


_STATIC = ("heads", "kv_heads", "hd", "theta", "eps", "n_heads", "d_head",
           "groups", "d_state", "d_conv", "mults")


@functools.partial(jax.jit, static_argnames=_STATIC)
def _mixers(p, x, *, heads, kv_heads, hd, theta, eps, n_heads, d_head,
            groups, d_state, d_conv, mults):
    """The two parallel branches of one layer on x [B, S, d] float32:
    (mixer, attention), each as it is added to the residual stream."""
    key_mult, attn_in, attn_out, ssm_in, ssm_out, sections = mults
    with jax.default_matmul_precision("highest"):
        u = _rms(x, p["in_norm"]["scale"], eps)
        mixed = ssm_out * _mixer(
            p["mixer"], u, n_heads=n_heads, d_head=d_head, groups=groups,
            d_state=d_state, d_conv=d_conv, in_mult=ssm_in,
            section_mults=sections, eps=eps)
        attn = attn_out * _attn(
            p["attn"], u * attn_in, heads=heads, kv_heads=kv_heads, hd=hd,
            key_mult=key_mult, theta=theta)
        return mixed, attn


@functools.partial(jax.jit, static_argnames=("gate_mult",))
def _ffn_block(gate, up, down, v, *, gate_mult):
    """One block of the feed-forward's width: its part of W_down(...)."""
    with jax.default_matmul_precision("highest"):
        hidden = (v @ up.astype(F32)) * jax.nn.silu(
            (v @ gate.astype(F32)) * gate_mult)
        return hidden @ down.astype(F32)


@functools.partial(jax.jit, static_argnames=("eps",))
def _normed(x, scale, *, eps):
    return _rms(x, scale, eps)


@jax.jit
def _head_block(x, w):
    with jax.default_matmul_precision("highest"):
        return x @ w.astype(F32)


def _ffn(m, v, mults):
    width = m["up_proj"]["kernel"].shape[1]
    step = -(-width // FFN_BLOCKS)
    out = 0.0
    for lo in range(0, width, step):
        cut = slice(lo, min(lo + step, width))
        out = out + _ffn_block(
            m["gate_proj"]["kernel"][:, cut], m["up_proj"]["kernel"][:, cut],
            m["down_proj"]["kernel"][cut], v, gate_mult=float(mults[0]))
    return out * float(mults[1])


def forward_with_branches(params, ids, cfg, first_row: int = 0):
    """ids [B, S] int32 -> (logits [B, S - first_row, V] float32 for the
    rows from ``first_row`` on, {"mixer" | "attn" | "ffn": [layers, B, S, d]}:
    what each branch adds to the residual stream)."""
    eps = float(cfg["rms_norm_eps"])
    x = params["embed"]["embedding"][ids].astype(F32) \
        * float(cfg["embedding_multiplier"])
    branches = {"mixer": [], "attn": [], "ffn": []}
    mults = (float(cfg["key_multiplier"]),
             float(cfg["attention_in_multiplier"]),
             float(cfg["attention_out_multiplier"]),
             float(cfg["ssm_in_multiplier"]),
             float(cfg["ssm_out_multiplier"]),
             tuple(float(v) for v in cfg["ssm_multipliers"]))
    for i in range(cfg["num_hidden_layers"]):
        p = params[f"layer_{i}"]
        mixed, attn = _mixers(
            p, x, heads=cfg["num_attention_heads"],
            kv_heads=cfg["num_key_value_heads"], hd=cfg["head_dim"],
            theta=float(cfg["rope_theta"]), eps=eps,
            n_heads=cfg["mamba_n_heads"], d_head=cfg["mamba_d_head"],
            groups=cfg["mamba_n_groups"], d_state=cfg["mamba_d_state"],
            d_conv=cfg["mamba_d_conv"], mults=mults)
        x = x + mixed + attn
        ffn = _ffn(p["mlp"], _normed(x, p["ff_norm"]["scale"], eps=eps),
                   cfg["mlp_multipliers"])
        x = x + ffn
        for name, branch in (("mixer", mixed), ("attn", attn), ("ffn", ffn)):
            branches[name].append(branch)
    x = _normed(x[:, first_row:], params["final_norm"]["scale"], eps=eps)
    head = params["lm_head"]
    logits = jnp.concatenate([
        _head_block(x, head[:, lo:lo + VOCAB_BLOCK])
        for lo in range(0, head.shape[1], VOCAB_BLOCK)], axis=-1)
    return (logits * float(cfg["lm_head_multiplier"]),
            {k: jnp.stack(v) for k, v in branches.items()})


def forward(params, ids, cfg, first_row: int = 0):
    """ids [B, S] int32 -> logits [B, S - first_row, V] float32."""
    return forward_with_branches(params, ids, cfg, first_row)[0]
