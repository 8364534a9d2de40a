"""Plain reference for ``gpt2_medium``: GPT-2's forward pass and next-token
loss in float32 ``jax.numpy`` at the highest matmul precision, with no
kernels, no cache and no batching tricks.  Independent of ``ray_tpu``: it
reads the weights out of the program's parameter tree and nothing else.

Follows "Language Models are Unsupervised Multitask Learners" and the
published ``config.json``: pre-norm blocks, learned positions, ``gelu_new``
(tanh), a head tied to the token embedding.  One departure, taken from the
program so that the comparison is of arithmetic and not of a constant: the
layer norms use epsilon 1e-6 (``flax.linen.LayerNorm``'s default) where the
published config says 1e-5.  ``PERF.md`` lists it for the program to repair.
"""
import jax
import jax.numpy as jnp

PROGRAM_LN_EPS = 1e-6


def _ln(x, p, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def _dense(x, p):
    return x @ p["kernel"].astype(jnp.float32) + p["bias"]


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        jnp.sqrt(2.0 / jnp.pi) * (x + 0.044715 * x ** 3)))


def forward(params, ids, cfg, eps=PROGRAM_LN_EPS):
    """ids [B, S] int32 -> logits [B, S, V] float32."""
    with jax.default_matmul_precision("highest"):
        b, s = ids.shape
        heads, d = cfg["n_head"], cfg["n_embd"]
        wte = params["wte"].astype(jnp.float32)
        x = wte[ids] + params["wpe"].astype(jnp.float32)[None, :s]
        causal = jnp.tril(jnp.ones((s, s), bool))
        for i in range(cfg["n_layer"]):
            p = params[f"h_{i}"]
            q, k, v = jnp.split(_dense(_ln(x, p["ln_1"], eps),
                                       p["attn_qkv"]), 3, axis=-1)
            q, k, v = (t.reshape(b, s, heads, d // heads) for t in (q, k, v))
            scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(
                jnp.float32(d // heads))
            scores = jnp.where(causal[None, None], scores, -jnp.inf)
            a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)
            x = x + _dense(a.reshape(b, s, d), p["attn_proj"])
            h = _gelu_new(_dense(_ln(x, p["ln_2"], eps), p["mlp_fc"]))
            x = x + _dense(h, p["mlp_proj"])
        return _ln(x, params["ln_f"], eps) @ wte.T


def token_nll(params, ids, cfg, eps=PROGRAM_LN_EPS):
    """Next-token cross-entropy of every position of ids [B, S]: [B, S-1]."""
    logp = jax.nn.log_softmax(forward(params, ids, cfg, eps)[:, :-1], -1)
    return -jnp.take_along_axis(logp, ids[:, 1:, None], -1)[..., 0]


def loss(params, ids, cfg, eps=PROGRAM_LN_EPS):
    """Mean next-token cross-entropy over ids [B, S]."""
    return jnp.mean(token_nll(params, ids, cfg, eps))
