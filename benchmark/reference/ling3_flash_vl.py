"""Plain reference for ``ling3_flash_vl``: the language model of
Ling-3.0-flash-VL in float32 ``jax.numpy`` at the highest matmul precision,
with no cache, no chunking, no kernel, no sorting and no batching trick.
Independent of ``ray_tpu``: it reads the weights out of the program's
parameter tree and nothing else.

Follows ``inclusionAI/Ling-3.0-flash-VL``'s published ``config.json`` (the
language model's keys) and the three published layer kinds its flags pick.
``x`` is the residual stream, no projection has a bias, ``rms`` is an
RMSNorm with a learned scale and epsilon ``rms_norm_eps``:

    x = E[ids]
    per layer i:  h = x + Mix_i(rms(x));  x = h + FFN_i(rms(h))
    logits = W_head rms_final(x)                                  (untied)

    Mix_i = MLA where (i + 1) % layer_group_size == 0, else KDA.

    KDA (Kimi Delta Attention, arXiv:2510.26692 section 3; H =
         num_attention_heads heads, K = V = head_dim):
         [q~ | k~ | v~] = silu(conv(W_qkv u)): causal depthwise, width
           short_conv_kernel_size, no bias (linear_silu);
         q = q~ / sqrt(|q~|^2 + 1e-6) / sqrt(K),  k = k~ / sqrt(|k~|^2 + 1e-6)
           a head;
         g = kda_lower_bound * sigmoid(exp(A_log_h) * (W_f u + dt_bias)),
           one a head AND channel (kda_safe_gate; W_f full: no_kda_lora);
         beta = sigmoid(W_beta u), one a head;
         a head's state S [K, V], token by token:
           S <- Diag(exp g_t) S;  S <- S + beta_t k_t (v_t - S^T k_t)^T;
           o_t = S^T q_t;
         out = W_o [ rms_V(o_h) * sigmoid(w_g,h . u) ]_h  (group_norm_size 1:
           the norm a head, one learned scale of V; head_wise: one gate a
           head).
    MLA (DeepSeek-V2, arXiv:2405.04434 section 2.1; q_lora_rank null):
         q = rms_q(W_q u) a head of qk_nope_head_dim + qk_rope_head_dim (one
           learned scale over a head's whole query: use_qk_norm), rope on
           its last qk_rope_head_dim;
         [c | k_r] = W_kva u;  c <- rms(c) (use_qk_norm on the key side:
           the latent's norm);  rope on k_r, which all heads share;
         [k_nope,h | v_h] = W_kvb,h c;  k_h = [k_nope,h | k_r];
         causal softmax of q_h . k_h / sqrt(qk_nope + qk_rope);  W_o.
         rope: rotate-half over the qk_rope_head_dim channels, angle
           position * rope_theta^(-2j / qk_rope_head_dim).
    FFN_i = SwiGLU of intermediate_size for i < first_k_dense_replace, else
         s = sigmoid(W_r u) over ALL experts (the router's second
           dimension);
         chosen by s + b (expert_bias): n_group groups of neighbours, a
           group's score the sum of its 2 largest s + b, the topk_group
           best groups kept, the num_experts_per_tok largest s + b inside
           them;
         w = s[chosen] / (sum + 1e-20) * routed_scaling_factor;
         routed = sum_{j: e_j held} w_j D_e (silu(G_e u) * (U_e u)), every
           held expert for every token, masked by the router's choice;
         out = routed + D_s (silu(G_s u) * (U_s u))     (the shared expert).

**The share.**  The experts held are ``expert_offset`` .. ``expert_offset +
H`` of each layer's (H: the first dimension of the stacked expert weights).
What the other experts would add is left out, here as in the program, and
that partial result goes on to the next layer.

Departures from the published model, here and in the program alike (the
configuration file's ``assumed`` gives each reason): which layer of a group
is the full one; how ``use_qk_norm`` is read; no vision tower, no
multi-token-prediction head, no clamped SwiGLU (the layers that have it are
not among those kept).  Departures of the program from this: none in the
mathematics.  The program computes in bfloat16 with float32 sums, routes in
float32 on bfloat16 activations, keeps the state, the gates and the decays
in float32, runs the recurrence in chunks over a context and token by token
only in decode, attends against its cache in the absorbed form, and
computes only the held experts some row chose.  Layout conventions that no
published key fixes are the program's: ``conv_kernel[j, channel]`` with
``out_t = sum_j conv_kernel[j] in_{t-(k-1)+j}``, projections ``[in, out]``,
``W_qkv``'s columns [q | k | v] head-major, ``W_kvb``'s a head's
``[k_nope | v]``.

So that it fits beside a serving engine on one chip it is jitted layer by
layer, the experts upcast one at a time inside a scan, the head by blocks
of the vocabulary; the head can be asked for the last rows only
(``first_row``).
"""
import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
VOCAB_BLOCK = 16384
PARTS = ("kda", "mla", "dense", "routed", "shared")


def _rms(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * scale.astype(F32)


def _w(p, name):
    return p[name]["kernel"].astype(F32)


def _swiglu(p, u):
    return (jax.nn.silu(u @ _w(p, "gate_proj")) * (u @ _w(p, "up_proj"))) \
        @ _w(p, "down_proj")


def _rope(x, theta):
    """x [B, S, H, P] at positions 0 .. S-1."""
    p = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, p, 2, dtype=F32) / p)
    angles = jnp.arange(x.shape[1], dtype=F32)[:, None] * freqs
    cos, sin = jnp.cos(angles)[None, :, None], jnp.sin(angles)[None, :, None]
    x1, x2 = x[..., :p // 2], x[..., p // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _kda(m, u, *, heads, hd, lower, eps):
    b, s, _ = u.shape
    w_conv = m["conv_kernel"].astype(F32)
    kw = w_conv.shape[0]
    padded = jnp.pad(u @ _w(m, "qkv_proj"), ((0, 0), (kw - 1, 0), (0, 0)))
    conv = jax.nn.silu(sum(padded[:, j:j + s] * w_conv[j]
                           for j in range(kw)))
    q, k, v = (a.reshape(b, s, heads, hd) for a in jnp.split(conv, 3, -1))
    q = q / jnp.sqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6) / jnp.sqrt(
        F32(hd))
    k = k / jnp.sqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
    f = (u @ _w(m, "f_proj")).reshape(b, s, heads, hd)
    g = lower * jax.nn.sigmoid(jnp.exp(m["A_log"].astype(F32))[:, None]
                               * (f + m["dt_bias"].astype(F32)))
    beta = jax.nn.sigmoid(u @ _w(m, "b_proj"))              # [B, S, H]

    def token(state, t):  # the recurrence, one token at a time
        q_t, k_t, v_t, g_t, beta_t = t
        state = jnp.exp(g_t)[..., None] * state
        held = jnp.sum(state * k_t[..., None], axis=-2)     # S^T k
        state = state + (beta_t[..., None] * k_t)[..., None] \
            * (v_t - held)[..., None, :]
        return state, jnp.sum(state * q_t[..., None], axis=-2)

    _, o = jax.lax.scan(token, jnp.zeros((b, heads, hd, hd), F32), tuple(
        jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta)))
    o = _rms(jnp.moveaxis(o, 0, 1), m["o_norm_scale"], eps)
    o = o * jax.nn.sigmoid(u @ _w(m, "g_proj"))[..., None]
    return o.reshape(b, s, heads * hd) @ _w(m, "o_proj")


def _mla(m, u, *, heads, nope, rope, vd, rank, theta, eps):
    b, s, _ = u.shape
    q = _rms((u @ _w(m, "q_proj")).reshape(b, s, heads, nope + rope),
             m["q_norm"]["scale"], eps)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], theta)], -1)
    kva = u @ _w(m, "kv_a_proj")
    c = _rms(kva[..., :rank], m["kv_norm"]["scale"], eps)
    k_r = _rope(kva[..., None, rank:], theta)               # [B, S, 1, rope]
    kv = (c @ m["kv_b_proj"].astype(F32)).reshape(b, s, heads, nope + vd)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_r, (b, s, heads, rope))], -1)
    causal = jnp.tril(jnp.ones((s, s), bool))

    def head(one):  # a head at a time: [S, S] scores fit beside an engine
        q_h, k_h, v_h = one
        scores = jnp.einsum("bqd,bkd->bqk", q_h, k_h) / jnp.sqrt(
            F32(nope + rope))
        scores = jnp.where(causal[None], scores, -jnp.inf)
        return jnp.einsum("bqk,bkd->bqd", jax.nn.softmax(scores, -1), v_h)

    by_head = lambda a: jnp.moveaxis(a, 2, 0)  # noqa: E731
    att = jnp.moveaxis(jax.lax.map(head, (
        by_head(q), by_head(k), by_head(kv[..., nope:]))), 0, 2)
    return att.reshape(b, s, heads * vd) @ _w(m, "o_proj")


def route(m, u, *, top_k, n_group, topk_group, norm_topk, scaling,
          given=None):
    """(weights [B, S, E] float32, zero but at the experts used; this
    router's own choice [B, S, top_k]; the slack of the given choices) over
    ALL experts of the layer.  ``given`` [B, S, top_k]: choices made
    elsewhere (the program's), used in place of this router's own and
    weighed by its scores.  Their slack is the larger of two distances,
    each 0 where the given choices are this router's own and small where
    only near-ties were decided the other way: how far the worst group
    that holds a given choice lies, in the group's score, below the last
    group this router kept; and how far the lowest given choice lies, in
    score + bias, below the last place of the ``top_k`` best inside the
    groups the given choices lie in."""
    scores = jax.nn.sigmoid(u @ m["router"].astype(F32))
    biased = scores + m["expert_bias"].astype(F32)
    e = scores.shape[-1]
    per = e // n_group
    grouped = biased.reshape(biased.shape[:-1] + (n_group, per))
    group_score = jnp.sum(jnp.sort(grouped, axis=-1)[..., -2:], axis=-1)
    best_v, best = jax.lax.top_k(group_score, topk_group)
    groups = jnp.arange(n_group)
    kept = jnp.any(best[..., None] == groups, axis=-2)

    def inside(which):  # score + bias inside ``which`` groups, -inf outside
        return jnp.where(which[..., None], grouped, -jnp.inf).reshape(
            biased.shape)

    top_v, top_i = jax.lax.top_k(inside(kept), top_k)
    slack = jnp.zeros((), F32)
    used = top_i
    if given is not None:
        theirs = jnp.any((given // per)[..., None] == groups, axis=-2)
        of_groups = jnp.max(best_v[..., -1:] - jnp.where(
            theirs, group_score, jnp.inf))
        last = jax.lax.top_k(inside(theirs), top_k)[0][..., -1:]
        of_experts = jnp.max(
            last - jnp.take_along_axis(biased, given, axis=-1))
        slack = jnp.maximum(jnp.maximum(of_groups, of_experts), 0.0)
        used = given
    top_s = jnp.take_along_axis(scores, used, axis=-1)
    if norm_topk:
        top_s = top_s / (jnp.sum(top_s, -1, keepdims=True) + 1e-20)
    weight = jnp.sum(jax.nn.one_hot(used, e, dtype=F32)
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
    return routed, _swiglu(m["shared"], u), top_i, slack


_STATIC = ("latent", "dense", "eps", "heads", "hd", "lower", "nope", "rope",
           "vd", "rank", "theta", "top_k", "n_group", "topk_group",
           "norm_topk", "scaling", "offset")


@functools.partial(jax.jit, static_argnames=_STATIC)
def _layer(p, x, given=None, *, latent, dense, eps, heads, hd, lower, nope,
           rope, vd, rank, theta, top_k, n_group, topk_group, norm_topk,
           scaling, offset):
    """One layer on x [B, S, d] float32: what it adds to the residual
    stream, by part and in the order added, and (an expert layer) the
    experts its router chose with the slack of those it was ``given``."""
    with jax.default_matmul_precision("highest"):
        u = _rms(x, p["mix_norm"]["scale"], eps)
        if latent:
            added = {"mla": _mla(p["mla"], u, heads=heads, nope=nope,
                                 rope=rope, vd=vd, rank=rank, theta=theta,
                                 eps=eps)}
        else:
            added = {"kda": _kda(p["kda"], u, heads=heads, hd=hd,
                                 lower=lower, eps=eps)}
        u = _rms(x + next(iter(added.values())), p["ffn_norm"]["scale"], eps)
        if dense:
            added["dense"] = _swiglu(p["mlp"], u)
            return added, None
        routed, shared, top_i, slack = _moe(
            p["moe"], u, offset=offset, given=given, top_k=top_k,
            n_group=n_group, topk_group=topk_group, norm_topk=norm_topk,
            scaling=scaling)
        added["routed"], added["shared"] = routed, shared
        return added, (top_i, slack)


@functools.partial(jax.jit, static_argnames=("eps",))
def _normed(x, scale, *, eps):
    return _rms(x, scale, eps)


@jax.jit
def _head_block(x, w):
    with jax.default_matmul_precision("highest"):
        return x @ w.astype(F32)


def forward_with_parts(params, ids, cfg, first_row: int = 0, given=None,
                       each=None):
    """ids [B, S] int32 -> (logits [B, S - first_row, V] float32 for the
    rows from ``first_row`` on; {"kda" | "mla" | "dense" | "routed" |
    "shared": [layers that have that part, B, S, d]}: what each part adds
    to the residual stream; the routers' own choices [expert layers, B, S,
    num_experts_per_tok]; the largest slack of the ``given`` choices, 0.0
    with none).

    ``given`` [expert layers, B, S, num_experts_per_tok]: the experts to
    use in place of the routers' own choices, weighed by the routers' own
    scores (``reference/nemotron3_super_120b.py`` says why: near-ties that
    bfloat16 activations decide the other way, each at a choice's whole
    weight).  ``each(i, {part: [B, S, d]})``: called as layer ``i`` is done
    with what it added, which is then let go and not among what comes back
    (a long context's parts, all layers', are more than fits beside a
    serving engine)."""
    eps = float(cfg["rms_norm_eps"])
    x = params["embed"]["embedding"][ids].astype(F32)
    parts = {name: [] for name in PARTS}
    chosen, slack = [], 0.0
    for i in range(int(cfg["num_hidden_layers"])):
        dense = i < int(cfg["first_k_dense_replace"])
        use = None
        if not dense and given is not None:
            use = jnp.asarray(given[len(chosen)], jnp.int32)
        added, routed = _layer(
            params[f"layer_{i}"], x, use,
            latent=(i + 1) % int(cfg["layer_group_size"]) == 0, dense=dense,
            eps=eps, heads=cfg["num_attention_heads"], hd=cfg["head_dim"],
            lower=float(cfg["kda_lower_bound"]),
            nope=cfg["qk_nope_head_dim"], rope=cfg["qk_rope_head_dim"],
            vd=cfg["v_head_dim"], rank=cfg["kv_lora_rank"],
            theta=float(cfg["rope_theta"]),
            top_k=cfg["num_experts_per_tok"], n_group=cfg["n_group"],
            topk_group=cfg["topk_group"],
            norm_topk=bool(cfg["norm_topk_prob"]),
            scaling=float(cfg["routed_scaling_factor"]),
            offset=int(cfg.get("expert_offset", 0)))
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
    """ids [B, S] int32 -> logits [B, S - first_row, V] float32."""
    return forward_with_parts(params, ids, cfg, first_row)[0]


def choice_overlap(chosen_a, chosen_b) -> float:
    """Mean share of a token's chosen experts that the other side chose
    too, over (layer, token) pairs: both [layers, B, S, k]."""
    same = chosen_a[..., :, None] == chosen_b[..., None, :]
    return float(jnp.mean(jnp.any(same, axis=-1)))
