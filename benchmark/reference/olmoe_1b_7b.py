"""Plain reference for ``olmoe_1b_7b``: OLMoE's forward pass in float32
``jax.numpy`` at the highest matmul precision, with no cache, no kernel, no
sorting and no batching trick.  Independent of ``ray_tpu``: it reads the
weights out of the program's parameter tree and nothing else.

Follows ``allenai/OLMoE-1B-7B-0125-Instruct``'s published ``config.json``
and the layer it describes (``model_type`` olmoe).  With ``rms`` an RMSNorm
with a learned weight and epsilon ``rms_norm_eps``, per layer:

    n1 = rms(x)
    q, k = q_norm(q_proj(n1)), k_norm(k_proj(n1))    # RMSNorms over the WHOLE
                                                     # projected width (all
                                                     # heads together), before
                                                     # the split into heads
    h = x + o_proj(attn(rope(q), rope(k), v_proj(n1)))   # causal, 1/sqrt(head)
    n2 = rms(h)
    p = softmax_float32(router(n2))                  # over all 64 experts
    y = h + sum_{e in top8(p)} p_e * down_e(silu(gate_e(n2)) * up_e(n2))

and then the final RMSNorm and an untied ``lm_head``.  The eight weights are
NOT renormalised (``norm_topk_prob`` false: they sum to well under 1); there
is no shared expert, no capacity, and no token is dropped.  No bias anywhere
(``attention_bias`` false), no clipping (``clip_qkv`` null).  Rotary
embedding in the rotate-half form over each head, base ``rope_theta``.  Every
expert is computed for every token, and the chosen eight are summed with
their softmax weights.

Departures of the program from this: none in the mathematics.  The program
computes in bfloat16 with float32 sums, picks the experts from a float32
softmax of bfloat16 activations, and computes only the chosen experts (or
all of them, masked, for few rows).  One width is read from a key that does
not name it: ``intermediate_size`` is taken as one expert's width (the
catalog's note).

Parameters are upcast one layer at a time, one expert at a time inside it,
so that the reference fits beside a serving engine on one chip: the model's
bfloat16 weights are 7.1 GB, one layer's experts in float32 would be 1.6 GB.
"""
import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32


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


@functools.partial(jax.jit, static_argnames=(
    "heads", "kv_heads", "top_k", "norm_topk", "eps", "theta"))
def _layer(p, x, *, heads, kv_heads, top_k, norm_topk, eps, theta):
    """One decoder layer: x [B, S, d] float32 -> (y, chosen experts
    [B, S, top_k])."""
    with jax.default_matmul_precision("highest"):
        b, s, d = x.shape
        a = p["attn"]
        w = lambda name: a[name]["kernel"].astype(F32)  # noqa: E731
        n1 = _rms(x, p["attn_norm"]["scale"], eps)
        q = _rms(n1 @ w("q_proj"), a["q_norm"]["scale"], eps)
        k = _rms(n1 @ w("k_proj"), a["k_norm"]["scale"], eps)
        v = n1 @ w("v_proj")
        hd = q.shape[-1] // heads
        q = _rope(q.reshape(b, s, heads, hd), theta)
        k = _rope(k.reshape(b, s, kv_heads, hd), theta)
        v = v.reshape(b, s, kv_heads, hd)
        if kv_heads != heads:
            k = jnp.repeat(k, heads // kv_heads, axis=2)
            v = jnp.repeat(v, heads // kv_heads, axis=2)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(F32(hd))
        causal = jnp.tril(jnp.ones((s, s), bool))
        scores = jnp.where(causal[None, None], scores, -jnp.inf)
        att = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)
        h = x + att.reshape(b, s, heads * hd) @ w("o_proj")

        m = p["moe"]
        n2 = _rms(h, p["mlp_norm"]["scale"], eps)
        probs = jax.nn.softmax(n2 @ m["router"].astype(F32), axis=-1)
        top_p, top_i = jax.lax.top_k(probs, top_k)
        if norm_topk:
            top_p = top_p / jnp.sum(top_p, -1, keepdims=True)
        # [B, S, E]: the chosen experts' weights, zero for the others.
        weight = jnp.sum(jax.nn.one_hot(top_i, probs.shape[-1], dtype=F32)
                         * top_p[..., None], axis=-2)

        def expert(acc, e):  # every expert, for every token
            gate, up, down, w_e = e
            out = (jax.nn.silu(n2 @ gate.astype(F32))
                   * (n2 @ up.astype(F32))) @ down.astype(F32)
            return acc + w_e[..., None] * out, None

        ffn, _ = jax.lax.scan(expert, jnp.zeros_like(h), (
            m["w_gate"], m["w_up"], m["w_down"],
            jnp.moveaxis(weight, -1, 0)))
        return h + ffn, top_i


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(params, x, *, eps):
    with jax.default_matmul_precision("highest"):
        x = _rms(x, params["final_norm"]["scale"], eps)
        return x @ params["lm_head"]["kernel"].astype(F32)


def forward_with_experts(params, ids, cfg):
    """ids [B, S] int32 -> (logits [B, S, V] float32, chosen experts
    [layers, B, S, num_experts_per_tok])."""
    x = params["embed"]["embedding"][ids].astype(F32)
    chosen = []
    for i in range(cfg["num_hidden_layers"]):
        x, top_i = _layer(
            params[f"layer_{i}"], x, heads=cfg["num_attention_heads"],
            kv_heads=cfg["num_key_value_heads"],
            top_k=cfg["num_experts_per_tok"],
            norm_topk=bool(cfg["norm_topk_prob"]),
            eps=float(cfg["rms_norm_eps"]), theta=float(cfg["rope_theta"]))
        chosen.append(top_i)
    head = {k: params[k] for k in ("final_norm", "lm_head")}
    return _head(head, x, eps=float(cfg["rms_norm_eps"])), jnp.stack(chosen)


def forward(params, ids, cfg):
    """ids [B, S] int32 -> logits [B, S, V] float32."""
    return forward_with_experts(params, ids, cfg)[0]


def router_agreement(chosen_a, chosen_b) -> float:
    """Share of (layer, token) pairs whose chosen expert SETS are the same,
    in whatever order: both [layers, B, S, k]."""
    same = jnp.all(jnp.sort(chosen_a, -1) == jnp.sort(chosen_b, -1), -1)
    return float(jnp.mean(same))
