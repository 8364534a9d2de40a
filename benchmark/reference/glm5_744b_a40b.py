"""Plain reference for ``glm5_744b_a40b``: GLM-5's language model
(``model_type`` ``glm_moe_dsa``) in float32 ``jax.numpy`` at the highest
matmul precision, with no cache, no kernel, no blocks of query rows, no
sorting of rows by expert and no batching trick.  Independent of
``ray_tpu``: it reads the weights out of the program's parameter tree and
nothing else.

Follows ``zai-org/GLM-5``'s published ``config.json`` and the layers its
``model_type`` names (the configuration file's ``assumed`` gives each
source).  ``x`` is the residual stream, no projection has a bias, ``rms`` is
an RMSNorm with a learned scale and epsilon ``rms_norm_eps``:

    x = E[ids]
    per layer i:  h = x + Attn(rms(x));  x = h + FFN_i(rms(h))
    logits = W_head rms_final(x)                                  (untied)

    Attn (u the normed input, position t; MLA with query compression,
          DeepSeek-V2 arXiv:2405.04434 section 2.1, under a DeepSeek sparse
          attention indexer, DeepSeek-V3.2-Exp ``inference/model.py``):
         cq = rms(W_qa u)                                    (q_lora_rank)
         q_h = W_qb,h cq = [q_nope,h | q_rope,h]  (qk_nope | qk_rope_head_dim)
         [c | k_r] = W_kva u;  c <- rms(c)                   (kv_lora_rank)
         [k_nope,h | v_h] = W_kvb,h c;  k_h = [k_nope,h | rope(k_r)]
         indexer: qI_j = W_qbI,j cq  (index_n_heads x index_head_dim),
           kI = LayerNorm(W_kI u) (ONE key a row; scale and bias, epsilon
           1e-6), rope on the first qk_rope_head_dim columns of both,
           w = W_wI u * index_n_heads^-1/2 * index_head_dim^-1/2
         I[t, s] = sum_j w[t, j] relu(qI_j[t] . kI[s])          for s <= t
         S_t = the min(index_topk, t + 1) rows s of largest I[t, s]
           (ties: the lower s), one set for all heads
         o_h[t] = softmax over S_t of (q_h[t] . k_h[s]) / sqrt(qk_nope +
           qk_rope) applied to v_h;  out = W_o [o_h]_h
         rope: interleaved, channels (2i, 2i + 1) a pair rotated by
           position * rope_theta^(-2i / qk_rope_head_dim).
    FFN_i = SwiGLU of intermediate_size for i < first_k_dense_replace, else
         s = sigmoid(W_r u) over ALL experts (the router's second
           dimension; n_group 1: no limit on groups);
         chosen: the num_experts_per_tok largest s + b
           (e_score_correction_bias);
         w = s[chosen] / (sum + 1e-20) * routed_scaling_factor;
         routed = sum_{j: e_j held} w_j D_e (silu(G_e u) * (U_e u)), every
           held expert for every token, masked by the router's choice;
         out = routed + D_s (silu(G_s u) * (U_s u))     (the shared expert).

**The share.**  The experts held are ``expert_offset`` .. ``expert_offset +
H`` of each layer's (H: the first dimension of the stacked expert weights).
What the other experts would add is left out, here as in the program, and
that partial result goes on to the next layer.

**Given choices.**  ``given`` (the experts) and ``selected`` (the rows
attended to) put the program's own choices in the place of this
reference's, which are computed beside them all the same and compared
(``forward_with_parts`` says what comes back).  The reason is
``reference/nemotron3_super_120b.py``'s: a near-tie that bfloat16
activations decide the other way is no fault, and each flips a whole
expert's weight or a whole row of the softmax.

Departures from the published model, here and in the program alike: no
multi-token-prediction module; the indexer's FP8 keys and the Hadamard
rotation before them are left out (the rotation is orthogonal and leaves
every q . k as it is).  Departures of the program from this: none in the
mathematics.  The program computes in bfloat16 with float32 sums, scores and
routes in float32 on bfloat16 activations, runs the selection as a mask in
blocks of query rows over a context and as a gather of rows in decode,
attends against its cache in the absorbed form, and computes only the held
experts some row chose.  Layout conventions that no published key fixes are
the program's: projections ``[in, out]``, ``W_qb``'s columns head-major
``[nope | rope]``, ``W_kvb``'s a head's ``[k_nope | v]``.

So that it fits beside a serving engine on one chip it is jitted layer by
layer, the attention in blocks of query rows and a head at a time with that
head's queries, keys and values made inside (no [S, S] and no [S, H, 448]
array), the indexer's heads one at a time, a long context's feed-forward
in blocks of rows, the experts upcast one at a time inside a scan, the
head by blocks of the vocabulary; the head can be asked for the last rows
only (``first_row``).
"""
import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
VOCAB_BLOCK = 16384
PARTS = ("attn", "dense", "routed", "shared")


def _rms(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * scale.astype(F32)


def _w(p, name):
    return p[name]["kernel"].astype(F32)


ROW_BLOCK = 2048  # rows of a feed-forward at a time, past twice as many


def _swiglu(p, u):
    """u [B, S, d]; a long context in blocks of rows (its float32 hidden
    rows, [S, 12288] three times over, are gigabytes beside an engine)."""
    gate, up, down = (_w(p, n) for n in ("gate_proj", "up_proj",
                                         "down_proj"))
    one = lambda x: (jax.nn.silu(x @ gate) * (x @ up)) @ down  # noqa: E731
    b, s, d = u.shape
    if s <= 2 * ROW_BLOCK:
        return one(u)
    pad = -s % ROW_BLOCK
    blocks = jnp.pad(u, ((0, 0), (0, pad), (0, 0))).reshape(
        b, -1, ROW_BLOCK, d)
    out = jax.lax.map(one, jnp.moveaxis(blocks, 1, 0))
    return jnp.moveaxis(out, 0, 1).reshape(b, s + pad, d)[:, :s]


def _rope(x, theta, positions=None):
    """x [B, T, ..., P] at ``positions`` [T] (default 0 .. T-1): pairs
    (2i, 2i + 1)."""
    p = x.shape[-1]
    if positions is None:
        positions = jnp.arange(x.shape[1], dtype=F32)
    freqs = theta ** (-jnp.arange(0, p, 2, dtype=F32) / p)
    angles = (positions[:, None] * freqs).reshape(
        (1, x.shape[1]) + (1,) * (x.ndim - 3) + (p // 2,))
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * jnp.cos(angles) - b * jnp.sin(angles),
                      b * jnp.cos(angles) + a * jnp.sin(angles)],
                     axis=-1).reshape(x.shape)


def _rope_first(x, theta, width):
    return jnp.concatenate([_rope(x[..., :width], theta), x[..., width:]],
                           axis=-1)


QUERY_BLOCK = 1024  # query rows at a time: [block, S] arrays, not [S, S]


def _index_parts(m, u, cq, *, heads, dim, rope, theta, norm_eps):
    """The indexer's projections: (q [B, S, J, D], w [B, S, J], k [B, S,
    D])."""
    b, s, _ = u.shape
    q = _rope_first((cq @ _w(m, "wq_b")).reshape(b, s, heads, dim), theta,
                    rope)
    k = u @ _w(m, "wk")
    mean = jnp.mean(k, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(k - mean), axis=-1, keepdims=True)
    k = (k - mean) / jnp.sqrt(var + norm_eps) \
        * m["k_norm"]["scale"].astype(F32) + m["k_norm"]["bias"].astype(F32)
    k = _rope_first(k, theta, rope)
    w = (u @ m["weights_proj"].astype(F32)) * heads ** -0.5 * dim ** -0.5
    return q, w, k


def index_scores(q, w, k, first):
    """I [B, T, S] float32 of the query rows ``first .. first + T``,
    ``-inf`` above the diagonal: the indexer's heads one at a time."""
    b, t, _, _ = q.shape
    s = k.shape[1]

    def head(acc, one):
        q_j, w_j = one                               # [B, T, D], [B, T]
        return acc + w_j[..., None] * jax.nn.relu(
            jnp.einsum("btd,bsd->bts", q_j, k)), None

    scores, _ = jax.lax.scan(head, jnp.zeros((b, t, s), F32), (
        jnp.moveaxis(q, 2, 0), jnp.moveaxis(w, 2, 0)))
    causal = jnp.arange(s)[None] <= (first + jnp.arange(t))[:, None]
    return jnp.where(causal[None], scores, -jnp.inf)


def select(scores, topk):
    """scores [B, T, S] (``-inf``: no candidate) → bool [B, T, S]: each
    row's ``topk`` best candidates, ``lax.top_k`` a row."""
    b, t, s = scores.shape
    vals, idx = jax.lax.top_k(scores, min(topk, s))
    return jnp.zeros((b, t, s), bool).at[
        jnp.arange(b)[:, None, None], jnp.arange(t)[None, :, None],
        idx].set(vals > -jnp.inf)


def _attention(m, u, selected=None, *, heads, nope, rope, vd, rank, theta,
               eps, index, topk, rows_kept):
    """(what the attention adds [B, S, d]; over the rows from ``topk`` on,
    how many rows this reference's own selection holds and how many of
    them ``selected`` holds too; its index scores of the last ``rows_kept``
    rows [B, rows_kept, S])."""
    b, s, _ = u.shape
    cq = _rms(u @ _w(m, "q_a_proj"), m["q_a_norm"]["scale"], eps)
    kva = u @ _w(m, "kv_a_proj")
    c = _rms(kva[..., :rank], m["kv_norm"]["scale"], eps)
    k_r = _rope(kva[..., rank:], theta)                          # [B, S, P]
    q_i, w_i, k_i = _index_parts(m["indexer"], u, cq, theta=theta, rope=rope,
                                 **index)
    w_qb = m["q_b_proj"].astype(F32).reshape(-1, heads, nope + rope)
    w_kvb = m["kv_b_proj"].astype(F32).reshape(rank, heads, nope + vd)
    block = min(QUERY_BLOCK, s)
    n_blocks = -(-s // block)
    pad = lambda a: jnp.pad(a, (  # noqa: E731
        (0, 0), (0, n_blocks * block - s)) + ((0, 0),) * (a.ndim - 2))
    cq_p, q_ip, w_ip = pad(cq), pad(q_i), pad(w_i)
    given = None if selected is None else pad(selected)
    positions = jnp.arange(n_blocks * block, dtype=F32)

    def rows_block(i):  # [block, S] scores fit beside an engine
        first = i * block
        take = lambda a: jax.lax.dynamic_slice_in_dim(  # noqa: E731
            a, first, block, axis=1)
        own = select(index_scores(take(q_ip), take(w_ip), k_i, first), topk)
        mask = own if given is None else take(given)
        binds = (first + jnp.arange(block) >= topk)[None, :, None] \
            & (first + jnp.arange(block) < s)[None, :, None]
        counts = jnp.stack([jnp.sum(own & binds),
                            jnp.sum(own & mask & binds)])
        at = jax.lax.dynamic_slice_in_dim(positions, first, block)

        def head(one):  # a head at a time, its queries, keys and values
            wq, wkv = one
            q = take(cq_p) @ wq
            q = jnp.concatenate([q[..., :nope], _rope(
                q[..., nope:], theta, at)], -1)
            kv = c @ wkv
            k = jnp.concatenate([kv[..., :nope], k_r], axis=-1)
            att = jnp.einsum("bqd,bkd->bqk", q, k) / jnp.sqrt(
                F32(nope + rope))
            att = jnp.where(mask, att, -jnp.inf)
            # a row of padding attends to nothing: zeros, not NaN
            att = jnp.where(jnp.any(mask, -1, keepdims=True),
                            jax.nn.softmax(att, -1), 0.0)
            return jnp.einsum("bqk,bkd->bqd", att, kv[..., nope:])

        out = jnp.moveaxis(jax.lax.map(head, (
            jnp.moveaxis(w_qb, 1, 0), jnp.moveaxis(w_kvb, 1, 0))), 0, 2)
        return out.reshape(b, block, heads * vd), counts

    out, counts = jax.lax.map(rows_block, jnp.arange(n_blocks))
    out = jnp.moveaxis(out, 0, 1).reshape(b, n_blocks * block, -1)[:, :s]
    kept = index_scores(q_i[:, s - rows_kept:], w_i[:, s - rows_kept:], k_i,
                        s - rows_kept)
    return out @ m["o_proj"].astype(F32), jnp.sum(counts, axis=0), kept


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
    return routed, _swiglu(m["shared"], u), top_i, slack


_STATIC = ("dense", "eps", "heads", "nope", "rope", "vd", "rank", "theta",
           "index_heads", "index_dim", "index_eps", "topk", "top_k",
           "norm_topk", "scaling", "offset", "rows_kept")


@functools.partial(jax.jit, static_argnames=_STATIC)
def _layer(p, x, given=None, selected=None, *, dense, eps, heads, nope, rope,
           vd, rank, theta, index_heads, index_dim, index_eps, topk, top_k,
           norm_topk, scaling, offset, rows_kept):
    """One layer on x [B, S, d] float32: what it adds to the residual
    stream, by part and in the order added; what its attention selected
    (the share of this reference's own ``S_t`` that ``selected`` holds too,
    over the rows past ``topk``, NaN with none; the index scores of the last
    ``rows_kept`` rows); and (an expert layer) the experts its router chose
    with the slack of those it was ``given``."""
    with jax.default_matmul_precision("highest"):
        u = _rms(x, p["attn_norm"]["scale"], eps)
        att, counts, scores = _attention(
            p["attn"], u, selected, heads=heads, nope=nope, rope=rope, vd=vd,
            rank=rank, theta=theta, eps=eps, topk=topk, rows_kept=rows_kept,
            index=dict(heads=index_heads, dim=index_dim,
                       norm_eps=index_eps))
        chose = {"agreement": counts[1] / counts[0], "scores": scores}
        added = {"attn": att}
        u = _rms(x + att, p["ffn_norm"]["scale"], eps)
        if dense:
            added["dense"] = _swiglu(p["mlp"], u)
            return added, chose, None
        routed, shared, top_i, slack = _moe(
            p["moe"], u, offset=offset, given=given, top_k=top_k,
            norm_topk=norm_topk, scaling=scaling)
        added["routed"], added["shared"] = routed, shared
        return added, chose, (top_i, slack)


@functools.partial(jax.jit, static_argnames=("eps",))
def _normed(x, scale, *, eps):
    return _rms(x, scale, eps)


@jax.jit
def _head_block(x, w):
    with jax.default_matmul_precision("highest"):
        return x @ w.astype(F32)


def forward_with_parts(params, ids, cfg, first_row: int = 0, given=None,
                       selected=None, each=None, rows_kept: int = 1):
    """ids [B, S] int32 -> (logits [B, S - first_row, V] float32 for the
    rows from ``first_row`` on; {"attn" | "dense" | "routed" | "shared":
    [layers that have that part, B, S, d]}: what each part adds to the
    residual stream; the routers' own choices [expert layers, B, S,
    num_experts_per_tok]; the largest slack of the ``given`` choices, 0.0
    with none; {"agreement": [layers] the share of this reference's own
    selection that ``selected`` holds too, over the rows from
    ``index_topk`` on (1.0 with no ``selected``, NaN where no row
    selects), "scores": [layers, B, rows_kept, S] the index scores of the
    last ``rows_kept`` rows}).

    ``given`` [expert layers, B, S, num_experts_per_tok]: the experts to
    use in place of the routers' own choices, weighed by the routers' own
    scores.  ``selected``: a list, one [B, S, S] bool (or None) a layer:
    the rows each row attends to in place of this reference's own
    ``S_t``.  ``each(i, {part: [B, S, d]})``: called as layer ``i`` is
    done with what it added, which is then let go and not among what comes
    back."""
    eps = float(cfg["rms_norm_eps"])
    x = params["embed"]["embedding"][ids].astype(F32)
    parts = {name: [] for name in PARTS}
    chosen, slack, agreement, scores = [], 0.0, [], []
    for i in range(int(cfg["num_hidden_layers"])):
        dense = i < int(cfg["first_k_dense_replace"])
        use = None
        if not dense and given is not None:
            use = jnp.asarray(given[len(chosen)], jnp.int32)
        sel = None if selected is None or selected[i] is None \
            else jnp.asarray(selected[i], bool)
        added, chose, routed = _layer(
            params[f"layer_{i}"], x, use, sel, dense=dense, eps=eps,
            heads=cfg["num_attention_heads"], nope=cfg["qk_nope_head_dim"],
            rope=cfg["qk_rope_head_dim"], vd=cfg["v_head_dim"],
            rank=cfg["kv_lora_rank"],
            theta=float(cfg["rope_parameters"]["rope_theta"]),
            index_heads=cfg["index_n_heads"], index_dim=cfg["index_head_dim"],
            index_eps=float(cfg.get("index_norm_eps", 1e-6)),
            topk=int(cfg["index_topk"]), top_k=cfg["num_experts_per_tok"],
            norm_topk=bool(cfg["norm_topk_prob"]),
            scaling=float(cfg["routed_scaling_factor"]),
            offset=int(cfg.get("expert_offset", 0)),
            rows_kept=min(rows_kept, ids.shape[1]))
        for name, value in added.items():
            x = x + value
            if each is None:
                parts[name].append(value)
        if each is not None:
            each(i, added)
        agreement.append(float(chose["agreement"]))
        scores.append(chose["scores"])
        if routed is not None:
            chosen.append(routed[0])
            slack = max(slack, float(routed[1]))
    x = _normed(x[:, first_row:], params["final_norm"]["scale"], eps=eps)
    head = params["lm_head"]
    logits = jnp.concatenate([
        _head_block(x, head[:, lo:lo + VOCAB_BLOCK])
        for lo in range(0, head.shape[1], VOCAB_BLOCK)], axis=-1)
    return (logits, {k: jnp.stack(v) for k, v in parts.items() if v},
            jnp.stack(chosen) if chosen else None, slack,
            {"agreement": agreement, "scores": jnp.stack(scores)})


def forward(params, ids, cfg, first_row: int = 0):
    """ids [B, S] int32 -> logits [B, S - first_row, V] float32 (no
    layer's parts are kept)."""
    return forward_with_parts(params, ids, cfg, first_row,
                              each=lambda i, added: None)[0]


def choice_overlap(chosen_a, chosen_b) -> float:
    """Mean share of a token's chosen experts that the other side chose
    too, over (layer, token) pairs: both [layers, B, S, k]."""
    same = chosen_a[..., :, None] == chosen_b[..., None, :]
    return float(jnp.mean(jnp.any(same, axis=-1)))
