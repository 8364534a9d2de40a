"""Plain reference for ``nemotron3_super_120b``: the Nemotron-H forward pass
in float32 ``jax.numpy`` at the highest matmul precision, with no cache, no
chunked scan, no kernel, no sorting and no batching trick.  Independent of
``ray_tpu``: it reads the weights out of the program's parameter tree and
nothing else.

Follows ``nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16``'s published
``config.json`` (``model_type`` nemotron_h) and the layers it describes.
``x`` is the residual stream, every projection is without bias, ``rms`` is
an RMSNorm with a learned scale and epsilon ``layer_norm_epsilon``:

    x = E[ids]
    per layer i, by letter i of hybrid_override_pattern:
      x = x + f_i(rms_i(x)),  f_i = Mixer (M) | Attn (*) | LatentMoE (E)
    logits = W_head rms_final(x)                                  (untied)

    Attn:  q = W_q u (num_attention_heads heads of head_dim), k = W_k u,
           v = W_v u (num_key_value_heads); NO position embedding; causal
           softmax at 1/sqrt(head_dim); W_o.
    Mixer (Mamba-2; d_ssm = mamba_num_heads * mamba_head_dim, n_groups,
           ssm_state_size, conv_kernel):
           [z d_ssm | x d_ssm | B groups*state | C groups*state | dt heads]
             = W_in u;
           [x|B|C] = silu(causal depthwise conv1d([x|B|C]) + b_conv);
           dt = softplus(dt + dt_bias);  A = -exp(A_log), one a head;
           per head h of group g = h // (heads / groups), token by token:
             S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t^g
             y_t = S_t C_t^g + D x_t
           y = rms_grouped(y * silu(z)) (gate, then norm; the variance over
           each group's d_ssm / groups channels, one learned scale);
           W_out: d_ssm -> hidden.
    LatentMoE (all of it float32 here):
           s = sigmoid(W_r u) over ALL n_routed_experts (the router's width
             is the router's second dimension: 512, though 128 are held);
           idx = the num_experts_per_tok largest of s + b
             (e_score_correction_bias; n_group 1, topk_group 1: no limit);
           w = s[idx] / (sum s[idx] + 1e-20)  (norm_topk_prob),
             times routed_scaling_factor;
           l = W_down u                          (hidden -> moe_latent_size)
           r = sum_{j: e_j held} w_j relu(l U_{e_j})^2 D_{e_j}
             (U latent x moe_intermediate_size, D back; NO gate matrix:
              mlp_hidden_act relu2), every held expert for every token,
             masked by the router's choice;
           out = W_up r  +  D_s relu(U_s u)^2    (the shared expert, at
             moe_shared_expert_intermediate_size on the full width).

**The share.**  The experts held are ``expert_offset`` ..
``expert_offset + H`` of each layer's (H: the first dimension of the
stacked expert weights; ``cfg["expert_offset"]``, 0 where absent).  What
the other experts would add is left out, here as in the program: the sum
over j runs over the choices that land on a held expert, and that partial
result goes on to the next layer.  With every expert held it is the
published layer.

Departures from the published forward, here and in the program alike: no
position embedding in ``*`` layers is the published forward's own choice
(``rope_theta`` stands in the config unused); the multi-token-prediction
head (``num_nextn_predict_layers`` 1) is a head beside this forward and is
not computed.  Departures of the program from this: none in the
mathematics.  The program computes in bfloat16 with float32 sums, routes in
float32 on bfloat16 activations, keeps the recurrent state and its decay
in float32, runs the recurrence as a chunked scan over a whole context and
token by token only in decode, and computes only the held experts some row
chose.  Layout conventions that no published key fixes are the program's:
the convolution's weight is ``conv_kernel[j, channel]`` with ``out_t =
sum_j conv_kernel[j] in_{t-(k-1)+j}``, projections are ``[in, out]``.

So that it fits beside a serving engine on one chip (9.3 GB of bfloat16
weights), it is jitted layer by layer, the experts upcast one at a time
inside a scan, the head by blocks of the vocabulary; the head can be asked
for the last rows only (``first_row``).
"""
import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
VOCAB_BLOCK = 16384
PARTS = ("mixer", "attn", "routed", "shared")


def _rms(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * scale.astype(F32)


def _attn(a, u, *, heads, kv_heads, hd):
    b, s, _ = u.shape
    w = lambda name: a[name]["kernel"].astype(F32)  # noqa: E731
    q = (u @ w("q_proj")).reshape(b, s, heads, hd)
    k = (u @ w("k_proj")).reshape(b, s, kv_heads, hd)
    v = (u @ w("v_proj")).reshape(b, s, kv_heads, hd)
    k = jnp.repeat(k, heads // kv_heads, axis=2)
    v = jnp.repeat(v, heads // kv_heads, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(F32(hd))
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    att = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)
    return att.reshape(b, s, heads * hd) @ w("o_proj")


def _mixer(m, u, *, n_heads, d_head, groups, d_state, d_conv, eps):
    b, s, _ = u.shape
    d_ssm, gn = n_heads * d_head, groups * d_state
    p = u @ m["in_proj"]["kernel"].astype(F32)
    z, xbc, dt = jnp.split(p, [d_ssm, 2 * d_ssm + 2 * gn], axis=-1)
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


def route(m, u, *, top_k, norm_topk, scaling, given=None):
    """(weights [B, S, E] float32, zero but at the experts used; this
    router's own choice [B, S, top_k]; the slack of the given choices) over
    ALL experts of the layer.  ``given`` [B, S, top_k]: choices made
    elsewhere (the program's, see ``forward_with_parts``), used in place
    of this router's own and weighed by its scores.  Their slack is how
    far the lowest of them lies, in score + bias, below the last place of
    the own choice: 0 where they are the own ones, and small where only
    near-ties were decided the other way."""
    scores = jax.nn.sigmoid(u @ m["router"].astype(F32))
    biased = scores + m["e_score_correction_bias"].astype(F32)
    top_v, top_i = jax.lax.top_k(biased, top_k)
    slack = jnp.zeros((), F32)
    used = top_i
    if given is not None:
        slack = jnp.max(top_v[..., -1:]
                        - jnp.take_along_axis(biased, given, axis=-1))
        used = given
    top_s = jnp.take_along_axis(scores, used, axis=-1)
    if norm_topk:
        top_s = top_s / (jnp.sum(top_s, -1, keepdims=True) + 1e-20)
    weight = jnp.sum(jax.nn.one_hot(used, scores.shape[-1], dtype=F32)
                     * (top_s * scaling)[..., None], axis=-2)
    return weight, top_i, slack


def _moe(m, u, *, top_k, norm_topk, scaling, offset, given=None):
    """(routed part after W_up, shared part, the router's own choice, the
    slack of the ``given`` ones)."""
    weight, top_i, slack = route(m, u, top_k=top_k, norm_topk=norm_topk,
                                 scaling=scaling, given=given)
    held = m["w_up"].shape[0]
    mine = weight[..., offset:offset + held]     # the absent weigh nothing
    latent = u @ m["latent_down"]["kernel"].astype(F32)

    def expert(acc, e):  # every held expert, for every token
        up, down, w_e = e
        hidden = jax.nn.relu(latent @ up.astype(F32))
        return acc + w_e[..., None] * ((hidden * hidden)
                                       @ down.astype(F32)), None

    mixed, _ = jax.lax.scan(expert, jnp.zeros_like(latent), (
        m["w_up"], m["w_down"], jnp.moveaxis(mine, -1, 0)))
    routed = mixed @ m["latent_up"]["kernel"].astype(F32)
    hidden = jax.nn.relu(u @ m["shared_up"]["kernel"].astype(F32))
    shared = (hidden * hidden) @ m["shared_down"]["kernel"].astype(F32)
    return routed, shared, top_i, slack


_STATIC = ("kind", "eps", "heads", "kv_heads", "hd", "n_heads", "d_head",
           "groups", "d_state", "d_conv", "top_k", "norm_topk", "scaling",
           "offset")


@functools.partial(jax.jit, static_argnames=_STATIC)
def _layer(p, x, given=None, *, kind, eps, heads, kv_heads, hd, n_heads,
           d_head, groups, d_state, d_conv, top_k, norm_topk, scaling, offset):
    """One layer on x [B, S, d] float32: what it adds to the residual
    stream, by part, and (an E layer) the experts its router chose with
    the slack of those it was ``given`` to use instead."""
    with jax.default_matmul_precision("highest"):
        u = _rms(x, p["norm"]["scale"], eps)
        if kind == "M":
            return {"mixer": _mixer(
                p["mixer"], u, n_heads=n_heads, d_head=d_head,
                groups=groups, d_state=d_state, d_conv=d_conv, eps=eps)}, None
        if kind == "*":
            return {"attn": _attn(p["attn"], u, heads=heads,
                                  kv_heads=kv_heads, hd=hd)}, None
        routed, shared, top_i, slack = _moe(
            p["moe"], u, top_k=top_k, norm_topk=norm_topk, scaling=scaling,
            offset=offset, given=given)
        return {"routed": routed, "shared": shared}, (top_i, slack)


@functools.partial(jax.jit, static_argnames=("eps",))
def _normed(x, scale, *, eps):
    return _rms(x, scale, eps)


@jax.jit
def _head_block(x, w):
    with jax.default_matmul_precision("highest"):
        return x @ w.astype(F32)


def forward_with_parts(params, ids, cfg, first_row: int = 0, given=None):
    """ids [B, S] int32 -> (logits [B, S - first_row, V] float32 for the
    rows from ``first_row`` on; {"mixer" | "attn" | "routed" | "shared":
    [layers of that kind, B, S, d]}: what each part adds to the residual
    stream; the routers' own choices [E layers, B, S,
    num_experts_per_tok]; the largest slack of the ``given`` choices, 0.0
    with none).

    ``given`` [E layers, B, S, num_experts_per_tok]: the experts to use in
    place of the routers' own choices, weighed by the routers' own scores.
    With seeded weights the 22nd and 23rd of a token's 512 scores lie
    closer than bfloat16 activations move them, so a program in bfloat16
    decides some near-ties the other way, each at a choice's whole weight,
    and every later layer then routes on another residual.  Given the
    program's choices, the reference computes what the program should have
    computed *having chosen so*, and the slack says that each of its
    choices was one this router could have made (``route``)."""
    eps = float(cfg["layer_norm_epsilon"])
    x = params["embed"]["embedding"][ids].astype(F32)
    parts = {name: [] for name in PARTS}
    chosen, slack = [], 0.0
    for i, kind in enumerate(cfg["hybrid_override_pattern"]):
        use = None
        if kind == "E" and given is not None:
            use = jnp.asarray(given[len(chosen)], jnp.int32)
        added, routed = _layer(
            params[f"layer_{i}"], x, use, kind=kind, eps=eps,
            heads=cfg["num_attention_heads"],
            kv_heads=cfg["num_key_value_heads"], hd=cfg["head_dim"],
            n_heads=cfg["mamba_num_heads"], d_head=cfg["mamba_head_dim"],
            groups=cfg["n_groups"], d_state=cfg["ssm_state_size"],
            d_conv=cfg["conv_kernel"], top_k=cfg["num_experts_per_tok"],
            norm_topk=bool(cfg["norm_topk_prob"]),
            scaling=float(cfg["routed_scaling_factor"]),
            offset=int(cfg.get("expert_offset", 0)))
        for name, value in added.items():
            parts[name].append(value)
            x = x + value
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
    too, over (layer, token) pairs: both [layers, B, S, k].  With 22 of
    512 and scores that rounding moves, the last places of a choice swap
    often and the sets are seldom the same: the share is what holds."""
    same = chosen_a[..., :, None] == chosen_b[..., None, :]
    return float(jnp.mean(jnp.any(same, axis=-1)))
