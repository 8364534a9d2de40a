"""Losses with a backward pass of their own.

Pure jnp, like ``layers.py``: XLA fuses these passes itself.  What it cannot
do is see through autodiff's ``log_softmax``: that writes the float32
log-probabilities of every class so that one of them can be picked, and the
backward pass sums the incoming cotangent over the classes, a sum a
cross-entropy knows beforehand.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


@jax.custom_vjp
def _nll_sum(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """``-sum(log_softmax(logits)[..., labels])`` over the rows whose label is
    not negative, in float32 whatever the logits' dtype.  Between the passes
    it keeps the logits as they came and one log-sum-exp a row; the backward
    pass is ``softmax - onehot`` in the logits' dtype, with no reduction over
    the classes."""
    return _forward(logits, labels)[0]


def _is_label(logits, labels):
    classes = jax.lax.broadcasted_iota(labels.dtype, logits.shape,
                                       logits.ndim - 1)
    return classes == labels[..., None]


def _forward(logits, labels):
    with jax.named_scope("loss_head"):
        x = logits.astype(jnp.float32)
        top = jnp.max(x, axis=-1)
        lse = top + jnp.log(jnp.sum(jnp.exp(x - top[..., None]), axis=-1))
        # The label's logit as a masked sum in the pass that sums the
        # exponentials: a gather would have the float32 logits written.
        picked = jnp.sum(jnp.where(_is_label(logits, labels), x, 0.0), axis=-1)
        loss = jnp.sum(jnp.where(labels >= 0, lse - picked, 0.0))
        return loss, (logits, lse, labels)


def _backward(saved, g):
    logits, lse, labels = saved
    with jax.named_scope("loss_head"):
        softmax = jnp.exp(logits.astype(jnp.float32) - lse[..., None])
        d = jnp.where(_is_label(logits, labels), softmax - 1.0, softmax)
        d = d * jnp.where(labels >= 0, g, 0.0)[..., None]
        return d.astype(logits.dtype), None


_nll_sum.defvjp(_forward, _backward)


def softmax_cross_entropy(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """``-mean(log_softmax(logits)[..., labels])`` over the rows whose label
    is not negative: ``logits [..., V]`` in any float dtype, integer
    ``labels [...]``; a float32 scalar."""
    rows = jnp.maximum(jnp.sum(labels >= 0), 1)
    return _nll_sum(logits, labels) / rows.astype(jnp.float32)


def next_token_cross_entropy(logits: jax.Array, ids: jax.Array) -> jax.Array:
    """The language-model objective: ``logits[:, t]`` against ``ids[:, t + 1]``,
    the mean over ``B * (L - 1)`` tokens.  The last position gets a negative
    label and not a slice: the ``[B, L, V]`` logits stay whole, so the
    gradient fuses into the head's two backward matmuls as their operand."""
    labels = ids.astype(jnp.int32)  # unsigned ids have no negative label
    labels = jnp.concatenate(
        [labels[:, 1:], jnp.full_like(labels[:, :1], -1)], axis=1)
    return _nll_sum(logits, labels) / max(ids.shape[0] * (ids.shape[1] - 1), 1)
