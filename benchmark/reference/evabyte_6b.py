"""Plain reference for ``evabyte_6b``: EvaByte's byte-level language model
(``model_type`` ``evabyte``) in float32 ``jax.numpy`` at the highest matmul
precision, with no cache, no kernel, no ring, no merging of partial
softmaxes and no batching trick.  Independent of ``ray_tpu``: it reads the
weights out of the program's parameter tree and nothing else.

Follows ``EvaByte/EvaByte``'s published ``config.json`` and the layers its
keys name (the configuration file's ``assumed`` gives each reading that is
not a key).  ``x`` is the residual stream (float32: ``fp32_skip_add``), no
projection has a bias, ``N(x) = x / sqrt(mean(x^2) + rms_norm_eps) * (1 +
g)`` (``norm_add_unit_offset``):

    x = E[ids]
    per layer:  h = x + Attn(N(x));  x = h + W_down(silu(W_gate N(h)) *
                                              W_up N(h))
    logits[i, m] = W_head,m N_final(x_i)     (num_pred_heads heads of
                   vocab_size columns of ONE untied matrix, head m's columns
                   m * vocab_size ..; head m at position i predicts byte
                   i + 1 + m)

    Attn (EVA, arXiv:2302.04542 section 4 as the release runs it; u the
          normed input; H heads of D = hidden / H; s = D^-1/2):
         q, k, v = W_q u, W_k u, W_v u; rope on q and k at the absolute
           position over all D columns, rotate-half (channel i pairs with
           i + D/2), pair i turned by position * rope_theta^(-2i/D).
         window of a position w(i) = i // window_size; chunk c(j) = j //
           chunk_size, P_c its chunk_size positions.  For every COMPLETE
           chunk c and head h, from the roped keys, with two learned
           vectors phi_h and mu_h of D:
             a_j = softmax over j in P_c of (s k_j . phi_h);
               v~_c = sum_j a_j v_j
             b_j = softmax over j in P_c of (s k_j . mu_h);
               k~_c = sum_j b_j k_j
         query i attends to L_i = {j: w(j) = w(i), j <= i} exactly and to
           R_i = {c: c < (window_size / chunk_size) * w(i)} (every chunk of
           every window before its own; never a chunk of its own window)
           through the summaries, under ONE softmax over [L_i ; R_i]:
             o_i = softmax([s q_i . k_j for j in L_i ; s q_i . k~_c for c in
                   R_i]) applied to [v_j ; v~_c];   out = W_o [o_h]_h

Departures of the program from this: none in the mathematics.  The program
computes in bfloat16 with float32 sums and a float32 residual stream, runs a
context's windows as a batch of causal attentions and merges the summaries'
part by log-sum-exp, and a decode step reads a ring of the open window's
rows and the stored (bfloat16) summaries through the paged kernel.  Layout
conventions that no published key fixes are the program's: projections
``[in, out]``, ``W_q``'s columns head-major.

So that ~6,200 rows fit beside a serving engine that holds 12 of the chip's
16.9 GB it is jitted layer by layer, and the attention runs in blocks of
``QUERY_BLOCK`` query rows: a block's scores are [H, block, window_size +
S / chunk_size], never [S, S].
"""
import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
QUERY_BLOCK = 256  # query rows at a time; divides every window_size used
PARTS = ("attn", "mlp")


def _norm(x, g, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * (1.0 + g.astype(F32))


def _w(p, name):
    return p[name]["kernel"].astype(F32)


def _rope(x, theta):
    """x [B, S, H, D] at positions 0..S-1: rotate-half."""
    d = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    angles = jnp.arange(x.shape[1], dtype=F32)[:, None] * freqs
    cos, sin = (f(angles)[None, :, None] for f in (jnp.cos, jnp.sin))
    a, b = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def summaries(k, v, phi, mu, chunk):
    """(k~, v~) [B, S // chunk, H, D] of the complete chunks of k, v
    [B, S, H, D] (roped keys)."""
    b, s, h, d = k.shape
    n = s // chunk
    kc, vc = (a[:, :n * chunk].reshape(b, n, chunk, h, d) for a in (k, v))
    scale = d ** -0.5

    def weights(vec):
        return jax.nn.softmax(scale * jnp.einsum(
            "bnchd,hd->bnch", kc, vec.astype(F32)), axis=2)[..., None]

    return jnp.sum(weights(mu) * kc, axis=2), jnp.sum(weights(phi) * vc,
                                                      axis=2)


def _attention(m, u, *, heads, theta, window, chunk):
    """What the attention adds [B, S, d]."""
    b, s, hidden = u.shape
    d = hidden // heads
    scale = d ** -0.5
    split = lambda name: (u @ _w(m, name)).reshape(b, s, heads, d)  # noqa
    q, k, v = _rope(split("q_proj"), theta), _rope(split("k_proj"), theta), \
        split("v_proj")
    k_sum, v_sum = summaries(k, v, m["phi"], m["mu"], chunk)
    n_sum = k_sum.shape[1]
    block = min(QUERY_BLOCK, window)
    assert window % block == 0
    n_blocks = -(-s // block)
    rows = -(-s // window) * window  # keys and values in whole windows
    fill = lambda a, n: jnp.pad(  # noqa: E731
        a, ((0, 0), (0, n - a.shape[1]), (0, 0), (0, 0)))
    q, k, v = fill(q, n_blocks * block), fill(k, rows), fill(v, rows)

    def rows_block(i):
        first = i * block
        at = first + jnp.arange(block)                     # positions i
        start = first // window * window                   # w(i)'s first row
        take = lambda a: jax.lax.dynamic_slice_in_dim(  # noqa: E731
            a, start, window, axis=1)
        q_blk = jax.lax.dynamic_slice_in_dim(q, first, block, axis=1)
        # L_i: the rows of the window up to i; R_i: chunks before the window
        in_l = (start + jnp.arange(window))[None] <= at[:, None]
        in_r = jnp.broadcast_to(jnp.arange(n_sum)[None] < start // chunk,
                                (block, n_sum))
        keys = jnp.concatenate([take(k), k_sum], axis=1)
        values = jnp.concatenate([take(v), v_sum], axis=1)
        att = jnp.einsum("bqhd,bkhd->bhqk", q_blk, keys) * scale
        seen = jnp.concatenate([in_l, in_r], axis=1)
        att = jax.nn.softmax(jnp.where(seen, att, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", att, values)

    out = jax.lax.map(rows_block, jnp.arange(n_blocks))
    out = jnp.moveaxis(out, 0, 1).reshape(b, n_blocks * block, hidden)
    return out[:, :s] @ _w(m, "o_proj")


@functools.partial(jax.jit, static_argnames=(
    "eps", "heads", "theta", "window", "chunk"))
def _layer(p, x, *, eps, heads, theta, window, chunk):
    """One layer on x [B, S, d] float32: what each part adds to the
    residual stream, in the order added."""
    with jax.default_matmul_precision("highest"):
        att = _attention(p["attn"], _norm(x, p["attn_norm"]["scale"], eps),
                         heads=heads, theta=theta, window=window,
                         chunk=chunk)
        u = _norm(x + att, p["mlp_norm"]["scale"], eps)
        gate, up, down = (p["mlp"][n].astype(F32) for n in (
            "gate_proj", "up_proj", "down_proj"))
        return {"attn": att, "mlp": (jax.nn.silu(u @ gate) * (u @ up)) @ down}


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, g, w, *, eps):
    with jax.default_matmul_precision("highest"):
        return _norm(x, g, eps) @ w.astype(F32)


def forward_with_parts(params, ids, cfg, first_row: int = 0, each=None):
    """ids [B, S] int32 -> (logits [B, S - first_row, num_pred_heads,
    vocab_size] float32 for the rows from ``first_row`` on; {"attn" |
    "mlp": [layers, B, S, d]}: what each part adds to the residual
    stream).  ``each(i, {part: [B, S, d]})``: called as layer ``i`` is
    done with what it added, which is then let go and not among what comes
    back."""
    x = params["embed"]["embedding"][ids].astype(F32)
    parts = {name: [] for name in PARTS}
    for i in range(int(cfg["num_hidden_layers"])):
        added = _layer(params[f"layer_{i}"], x,
                       eps=float(cfg["rms_norm_eps"]),
                       heads=int(cfg["num_attention_heads"]),
                       theta=float(cfg["rope_theta"]),
                       window=int(cfg["window_size"]),
                       chunk=int(cfg["chunk_size"]))
        for name, value in added.items():
            x = x + value
            if each is None:
                parts[name].append(value)
        if each is not None:
            each(i, added)
    logits = _head(x[:, first_row:], params["final_norm"]["scale"],
                   params["lm_head"], eps=float(cfg["rms_norm_eps"]))
    return (logits.reshape(logits.shape[:2] + (int(cfg["num_pred_heads"]),
                                               int(cfg["vocab_size"]))),
            {k: jnp.stack(v) for k, v in parts.items() if v})


def forward(params, ids, cfg, first_row: int = 0):
    """ids [B, S] int32 -> logits [B, S - first_row, num_pred_heads,
    vocab_size] float32 (no layer's parts are kept)."""
    return forward_with_parts(params, ids, cfg, first_row,
                              each=lambda i, added: None)[0]
