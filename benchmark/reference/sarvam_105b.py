"""Plain reference for ``sarvam_105b``: Sarvam-105B's language model
(``model_type`` ``sarvam_mla``) in float32 ``jax.numpy`` at the highest
matmul precision, with no cache, no kernel, no absorbed form, no sorting of
rows by expert and no batching trick.  Independent of ``ray_tpu``: it reads
the weights out of the program's parameter tree and nothing else.

Follows ``sarvamai/sarvam-105b``'s published ``config.json`` and the layers
its keys name (the configuration file's ``assumed`` gives each reading that
is not a key).  ``x`` is the residual stream, no projection has a bias,
``rms`` is an RMSNorm with a learned scale and epsilon ``rms_norm_eps``:

    x = E[ids]
    per layer i:  h = x + Attn(rms(x));  x = h + FFN_i(rms(h))
    logits = W_head rms_final(x)                                  (untied)

    Attn (u the normed input, position t; MLA without query compression,
          DeepSeek-V2 arXiv:2405.04434 section 2.1):
         q_h = rms_q(W_q,h u) = [q_nope,h | q_rope,h]   (one scale of
           qk_nope + qk_rope_head_dim over each head's whole query:
           use_qk_norm)
         [c | k_r] = W_kva u;  c <- rms(c)                   (kv_lora_rank)
         [k_nope,h | v_h] = W_kvb,h c;  k_h = [k_nope,h | rope(k_r)]
         o_h[t] = softmax over s <= t of (q_h[t] . k_h[s]) * scale applied
           to v_h;  out = W_o [o_h]_h
         rope: rotate-half (channel i pairs with i + P/2), pair i rotated
           by position * inv_freq_i, YaRN as DeepSeek publishes it:
             f_i = base^(-2i/P);  corr(n) = P ln(L0 / (2 pi n)) / (2 ln base)
             low = max(floor(corr(beta_fast)), 0)
             high = min(ceil(corr(beta_slow)), P - 1)
             ramp_i = clip((i - low) / (high - low), 0, 1)
             inv_freq_i = f_i (1 - ramp_i) + (f_i / factor) ramp_i
           m(s, k) = 0.1 k ln s + 1; cos and sin times m(factor, mscale) /
           m(factor, mscale_all_dim); scale = (qk_nope + qk_rope)^-1/2 *
           m(factor, mscale_all_dim)^2.
    FFN_i = SwiGLU of intermediate_size for i < first_k_dense_replace, else
         s = sigmoid(W_r u) over ALL experts (the router's second
           dimension; no groups);
         chosen: the num_experts_per_tok largest s + b (the expert bias);
         w = s[chosen] / (sum + 1e-20) * routed_scaling_factor;
         routed = sum_{j: e_j held} w_j D_e (silu(G_e u) * (U_e u)), every
           held expert for every token, masked by the router's choice;
         out = routed + D_s (silu(G_s u) * (U_s u))     (the shared expert).

**The share.**  The experts held are ``expert_offset`` .. ``expert_offset +
H`` of each layer's (H: the first dimension of the stacked expert weights).
What the other experts would add is left out, here as in the program, and
that partial result goes on to the next layer.

**Given choices.**  ``given`` puts the program's own choices of experts in
the place of this reference's, which are computed beside them all the same
and compared (``forward_with_parts`` says what comes back), for
``reference/nemotron3_super_120b.py``'s reason: a near-tie that bfloat16
activations decide the other way is no fault, and each flips a whole
expert's weight.

Departures of the program from this: none in the mathematics.  The program
computes in bfloat16 with float32 sums, routes in float32 on bfloat16
activations, attends against its cache in the absorbed form (one cached row
``[c | rope(k_r)]`` a token, the values its first columns) and computes
only the held experts some row chose.  Layout conventions that no published
key fixes are the program's: projections ``[in, out]``, ``W_q``'s columns
head-major ``[nope | rope]``, ``W_kvb``'s a head's ``[k_nope | v]``.

So that 12,000 rows fit beside a serving engine that holds 13.8 of the
chip's 16.9 GB it is jitted layer by layer, the attention in blocks of query
rows (each block through ``W_o`` at once) and a head at a time with that
head's weights upcast and its queries, keys and values made inside (no
[S, S], no [S, H, 256] and no [S, H * 128] array), a long context's
feed-forward in blocks of rows and, where it is wider than 2,048, of hidden
columns (the dense layer's matrices are never whole in float32), the experts
upcast one at a time inside a scan, the head by blocks of the vocabulary; a
caller that wants no layer's parts gets none made (``each=NOTHING``: a layer
then hands back the residual stream alone); the head can be asked for the
last rows only (``first_row``).
"""
import functools
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
VOCAB_BLOCK = 16384
ROW_BLOCK = 2048  # rows of a feed-forward at a time, past twice as many
QUERY_BLOCK = 1024  # query rows at a time: [block, S] arrays, not [S, S]
PARTS = ("attn", "dense", "routed", "shared")


def _rms(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * scale.astype(F32)


def _w(p, name):
    return p[name]["kernel"].astype(F32)


def _by_rows(one, u):
    """``one`` over u [B, S, d]; a long context in blocks of rows (its
    float32 hidden rows, [S, 16384] three times over, are gigabytes)."""
    b, s, d = u.shape
    if s <= 2 * ROW_BLOCK:
        return one(u)
    pad = -s % ROW_BLOCK
    blocks = jnp.pad(u, ((0, 0), (0, pad), (0, 0))).reshape(
        b, -1, ROW_BLOCK, d)
    out = jax.lax.map(one, jnp.moveaxis(blocks, 1, 0))
    return jnp.moveaxis(out, 0, 1).reshape(b, s + pad, d)[:, :s]


HIDDEN_BLOCK = 2048  # columns of a feed-forward's hidden width at a time


def _swiglu(gate, up, down, u):
    """``(silu(u gate) * (u up)) down`` with the weights as stored (they
    are upcast a block of ``HIDDEN_BLOCK`` hidden columns at a time: the
    dense layer's three matrices in float32 are 0.8 GB): the sum over the
    blocks is the product."""
    width = gate.shape[1]
    if width <= HIDDEN_BLOCK:
        gate, up, down = (w.astype(F32) for w in (gate, up, down))
        return _by_rows(
            lambda x: (jax.nn.silu(x @ gate) * (x @ up)) @ down, u)
    blocks = width // HIDDEN_BLOCK
    d = gate.shape[0]

    def rows(x):
        def block(acc, w):
            g, p, dn = (a.astype(F32) for a in w)
            return acc + (jax.nn.silu(x @ g) * (x @ p)) @ dn, None

        return jax.lax.scan(block, jnp.zeros_like(x), (
            jnp.moveaxis(gate.reshape(d, blocks, -1), 1, 0),
            jnp.moveaxis(up.reshape(d, blocks, -1), 1, 0),
            down.reshape(blocks, -1, d)))[0]

    return _by_rows(rows, u)


def yarn_range(dim, base, original, beta_fast, beta_slow):
    """(low, high) of the module's docstring."""
    corr = lambda n: dim * math.log(original / (n * 2 * math.pi)) / (  # noqa
        2 * math.log(base))
    return (max(math.floor(corr(beta_fast)), 0),
            min(math.ceil(corr(beta_slow)), dim - 1))


def yarn_m(factor, k):
    return 1.0 if factor <= 1 else 0.1 * k * math.log(factor) + 1.0


def yarn_inv_freq(dim, base, scaling):
    """float32 [dim / 2]; ``scaling`` the published ``rope_scaling`` block,
    or None for a plain rope."""
    f = base ** (-jnp.arange(0, dim, 2, dtype=F32) / dim)
    if not scaling:
        return f
    low, high = yarn_range(dim, base,
                           scaling["original_max_position_embeddings"],
                           scaling["beta_fast"], scaling["beta_slow"])
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=F32) - low)
                    / max(high - low, 0.001), 0.0, 1.0)
    return f * (1.0 - ramp) + f / scaling["factor"] * ramp


def _rope(x, freqs, positions, mscale=1.0):
    """x [B, T, ..., P] at ``positions`` [T]: rotate-half, channel i with
    i + P/2."""
    angles = (positions[:, None] * freqs).reshape(
        (1, x.shape[1]) + (1,) * (x.ndim - 3) + (freqs.shape[0],))
    cos, sin = jnp.cos(angles) * mscale, jnp.sin(angles) * mscale
    a, b = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _attention(m, u, *, heads, nope, rope, vd, rank, theta, eps, yarn):
    """What the attention adds [B, S, d].  ``yarn``: the ``rope_scaling``
    block as a tuple of (key, value) pairs (hashable), or None."""
    b, s, _ = u.shape
    scaling = dict(yarn) if yarn else None
    freqs = yarn_inv_freq(rope, theta, scaling)
    m_cos = m_all = 1.0
    if scaling:
        m_all = yarn_m(scaling["factor"], scaling.get("mscale_all_dim", 0))
        m_cos = yarn_m(scaling["factor"], scaling.get("mscale", 1)) / m_all
        if not scaling.get("mscale_all_dim", 0):
            m_all = 1.0
    scale = (nope + rope) ** -0.5 * m_all * m_all
    kva = u @ _w(m, "kv_a_proj")
    c = _rms(kva[..., :rank], m["kv_norm"]["scale"], eps)
    block = min(QUERY_BLOCK, s)
    n_blocks = -(-s // block)
    positions = jnp.arange(n_blocks * block, dtype=F32)
    k_r = _rope(kva[..., rank:], freqs, positions[:s], m_cos)    # [B, S, P]
    # as stored: a head's slices are upcast inside its turn of the loop
    w_q = m["q_proj"]["kernel"].reshape(-1, heads, nope + rope)
    w_kvb = m["kv_b_proj"].reshape(rank, heads, nope + vd)
    w_o = _w(m, "o_proj")
    q_scale = m["q_norm"]["scale"]
    u_p = jnp.pad(u, ((0, 0), (0, n_blocks * block - s), (0, 0)))

    def rows_block(i):  # [block, S] scores fit beside an engine
        first = i * block
        take = lambda a: jax.lax.dynamic_slice_in_dim(  # noqa: E731
            a, first, block, axis=1)
        at = jax.lax.dynamic_slice_in_dim(positions, first, block)
        mask = (jnp.arange(s)[None] <= (first + jnp.arange(block))[:, None])

        def head(one):  # a head at a time, its queries, keys and values
            wq, wkv = (w.astype(F32) for w in one)
            q = _rms(take(u_p) @ wq, q_scale, eps)
            q = jnp.concatenate([q[..., :nope], _rope(
                q[..., nope:], freqs, at, m_cos)], -1)
            kv = c @ wkv
            k = jnp.concatenate([kv[..., :nope], k_r], axis=-1)
            att = jnp.einsum("bqd,bkd->bqk", q, k) * scale
            att = jax.nn.softmax(jnp.where(mask, att, -jnp.inf), -1)
            return jnp.einsum("bqk,bkd->bqd", att, kv[..., nope:])

        out = jnp.moveaxis(jax.lax.map(head, (
            jnp.moveaxis(w_q, 1, 0), jnp.moveaxis(w_kvb, 1, 0))), 0, 2)
        return out.reshape(b, block, heads * vd) @ w_o

    out = jax.lax.map(rows_block, jnp.arange(n_blocks))
    return jnp.moveaxis(out, 0, 1).reshape(b, n_blocks * block, -1)[:, :s]


def route(m, u, *, top_k, norm_topk, scaling, given=None):
    """(weights [B, S, E] float32, zero but at the experts used; this
    router's own choice [B, S, top_k]; the slack of the given choices) over
    ALL experts of the layer.  ``given`` [B, S, top_k]: choices made
    elsewhere (the program's), used in place of this router's own and
    weighed by its scores; their slack is how far the lowest of them lies,
    in score + bias, below the last place of this router's own ``top_k``
    (0 where they are the same set)."""
    scores = jax.nn.sigmoid(u @ m["router"].astype(F32))
    biased = scores + m["e_score_correction_bias"].astype(F32)
    top_v, top_i = jax.lax.top_k(biased, top_k)
    slack, used = jnp.zeros((), F32), top_i
    if given is not None:
        slack = jnp.maximum(jnp.max(top_v[..., -1:] - jnp.take_along_axis(
            biased, given, axis=-1)), 0.0)
        used = given
    top_s = jnp.take_along_axis(scores, used, axis=-1)
    if norm_topk:
        top_s = top_s / (jnp.sum(top_s, -1, keepdims=True) + 1e-20)
    weight = jnp.sum(jax.nn.one_hot(used, scores.shape[-1], dtype=F32)
                     * (top_s * scaling)[..., None], axis=-2)
    return weight, top_i, slack


def _moe(m, u, *, offset, given=None, **routing):
    """(routed part, shared part, the router's own choice, the slack of the
    ``given`` ones)."""
    weight, top_i, slack = route(m, u, given=given, **routing)
    held = m["w_gate"].shape[0]
    mine = weight[..., offset:offset + held]     # the absent weigh nothing

    def expert(acc, e):  # every held expert, for every token
        gate, up, down, w_e = e
        hidden = jax.nn.silu(u @ gate.astype(F32)) * (u @ up.astype(F32))
        return acc + w_e[..., None] * (hidden @ down.astype(F32)), None

    routed, _ = jax.lax.scan(expert, jnp.zeros_like(u), (
        m["w_gate"], m["w_up"], m["w_down"], jnp.moveaxis(mine, -1, 0)))
    shared = m["shared"]
    return routed, _swiglu(*(shared[n]["kernel"] for n in (
        "gate_proj", "up_proj", "down_proj")), u), top_i, slack


_STATIC = ("dense", "eps", "heads", "nope", "rope", "vd", "rank", "theta",
           "yarn", "top_k", "norm_topk", "scaling", "offset", "parts")


@functools.partial(jax.jit, static_argnames=_STATIC)
def _layer(p, x, given=None, *, dense, eps, heads, nope, rope, vd, rank,
           theta, yarn, top_k, norm_topk, scaling, offset, parts=True):
    """One layer on x [B, S, d] float32: what it adds to the residual
    stream, by part and in the order added (``parts`` False: the stream
    with them added, ``_kept``), and (an expert layer) the experts its
    router chose with the slack of those it was ``given``."""
    with jax.default_matmul_precision("highest"):
        u = _rms(x, p["attn_norm"]["scale"], eps)
        att = _attention(p["attn"], u, heads=heads, nope=nope, rope=rope,
                         vd=vd, rank=rank, theta=theta, eps=eps, yarn=yarn)
        added = {"attn": att}
        u = _rms(x + att, p["ffn_norm"]["scale"], eps)
        if dense:
            added["dense"] = _swiglu(*(p["mlp"][n] for n in (
                "gate_proj", "up_proj", "down_proj")), u)
            return _kept(x, added, parts), None
        routed, shared, top_i, slack = _moe(
            p["moe"], u, offset=offset, given=given, top_k=top_k,
            norm_topk=norm_topk, scaling=scaling)
        added["routed"], added["shared"] = routed, shared
        return _kept(x, added, parts), (top_i, slack)


def _kept(x, added, parts: bool):
    """What a layer hands back: its parts, or (``parts`` False: a context
    of 12,000 rows beside a serving engine) the residual stream with them
    added in their order, one array of the stream's size and not three."""
    if parts:
        return added
    for value in added.values():
        x = x + value
    return x


@functools.partial(jax.jit, static_argnames=("eps",))
def _normed(x, scale, *, eps):
    return _rms(x, scale, eps)


@jax.jit
def _head_block(x, w):
    with jax.default_matmul_precision("highest"):
        return x @ w.astype(F32)


def NOTHING(i, added):
    """The ``each`` of a caller that wants no layer's parts: the layers then
    make none (``_kept``)."""


def forward_with_parts(params, ids, cfg, first_row: int = 0, given=None,
                       each=None):
    """ids [B, S] int32 -> (logits [B, S - first_row, V] float32 for the
    rows from ``first_row`` on; {"attn" | "dense" | "routed" | "shared":
    [layers that have that part, B, S, d]}: what each part adds to the
    residual stream; the routers' own choices [expert layers, B, S,
    num_experts_per_tok]; the largest slack of the ``given`` choices, 0.0
    with none).

    ``given`` [expert layers, B, S, num_experts_per_tok]: the experts to
    use in place of the routers' own choices, weighed by the routers' own
    scores.  ``each(i, {part: [B, S, d]})``: called as layer ``i`` is done
    with what it added, which is then let go and not among what comes
    back; ``each=NOTHING``: no layer's parts are wanted or made."""
    eps = float(cfg["rms_norm_eps"])
    yarn = cfg.get("rope_scaling")
    if yarn:
        yarn = tuple(sorted((k, v) for k, v in yarn.items()
                            if not isinstance(v, str)))
    x = params["embed"]["embedding"][ids].astype(F32)
    parts = {name: [] for name in PARTS}
    chosen, slack = [], 0.0
    wanted = each is None or each is not NOTHING
    for i in range(int(cfg["num_hidden_layers"])):
        dense = i < int(cfg["first_k_dense_replace"])
        use = None
        if not dense and given is not None:
            use = jnp.asarray(given[len(chosen)], jnp.int32)
        added, routed = _layer(
            params[f"layer_{i}"], x, use, dense=dense, eps=eps,
            heads=cfg["num_attention_heads"], nope=cfg["qk_nope_head_dim"],
            rope=cfg["qk_rope_head_dim"], vd=cfg["v_head_dim"],
            rank=cfg["kv_lora_rank"], theta=float(cfg["rope_theta"]),
            yarn=yarn or None, top_k=cfg["num_experts_per_tok"],
            norm_topk=bool(cfg.get("norm_topk_prob", True)),
            scaling=float(cfg["routed_scaling_factor"]),
            offset=int(cfg.get("expert_offset", 0)), parts=wanted)
        if not wanted:
            x = added
        else:
            for name, value in added.items():
                x = x + value
                if each is None:
                    parts[name].append(value)
            if each is not None:
                each(i, added)
        if routed is not None:
            chosen.append(routed[0])
            slack = max(slack, float(routed[1]))
    x = _normed(x[:, first_row:], params["final_norm"]["scale"], eps=eps)
    head = params["lm_head"]
    logits = jnp.concatenate([
        _head_block(x, head[:, lo:lo + VOCAB_BLOCK])
        for lo in range(0, head.shape[1], VOCAB_BLOCK)], axis=-1)
    return (logits, {k: jnp.stack(v) for k, v in parts.items() if v},
            jnp.stack(chosen) if chosen else None, slack)


def forward(params, ids, cfg, first_row: int = 0):
    """ids [B, S] int32 -> logits [B, S - first_row, V] float32 (no
    layer's parts are kept)."""
    return forward_with_parts(params, ids, cfg, first_row, each=NOTHING)[0]


def choice_overlap(chosen_a, chosen_b) -> float:
    """Mean share of a token's chosen experts that the other side chose
    too, over (layer, token) pairs: both [layers, B, S, k]."""
    same = chosen_a[..., :, None] == chosen_b[..., None, :]
    return float(jnp.mean(jnp.any(same, axis=-1)))
