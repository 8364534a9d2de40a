"""Seeded sampling for the LLM decode engine.

Real serving is not greedy-only: temperature and nucleus (top-p)
sampling are table stakes.  The constraint that makes them compatible
with this engine's correctness machinery — recompute preemption,
speculative-decode verification, and token-identity test gates — is
**determinism**: the token sampled at absolute position ``t`` of a
request must depend only on ``(request seed, t, logits)``, never on how
the engine happened to batch or schedule the step that produced it.

The rule: ``key(t) = fold_in(PRNGKey(seed), t)`` where ``t`` is the
absolute position of the token being *generated*.  A preempted request
re-prefilled from ``prompt + generated-so-far`` resumes at the same
absolute positions, so it re-draws the exact tokens it would have
produced; a speculative verify step samples positions ``len+1..len+k``
with the same keys the plain decode loop would have used, which is what
lets the accept-longest-prefix rule emit *bitwise* the non-speculative
stream.

``temperature == 0`` selects argmax (greedy) — the engine default, and
the contract every pre-existing token-identity gate asserts.

Everything here is jit-inlinable jnp code over fixed ``[N]``/``[N, V]``
shapes, so adding sampling to the engine's compiled steps does not add
recompiles.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    """Per-request sampling knobs.

    temperature: 0.0 = greedy argmax; > 0 softmax-temperature sampling.
    top_p: nucleus truncation — sample only from the smallest set of
        tokens whose cumulative probability reaches ``top_p`` (1.0 = no
        truncation).  Applied after temperature scaling.
    seed: the per-request PRNG seed; the token at absolute position t is
        drawn with ``fold_in(PRNGKey(seed), t)``, making decode
        deterministic across runs, schedules, and preemption-resume.
    """

    temperature: float = 0.0
    top_p: float = 1.0
    seed: int = 0

    def validate(self) -> "SamplingParams":
        if self.temperature < 0.0:
            raise ValueError(f"temperature must be >= 0, got "
                             f"{self.temperature}")
        if not (0.0 < self.top_p <= 1.0):
            raise ValueError(f"top_p must be in (0, 1], got {self.top_p}")
        return self


GREEDY = SamplingParams()


def top_p_mask(logits, top_p):
    """Boolean [.., V] nucleus mask: True for tokens in the smallest set
    whose cumulative probability (descending order) reaches ``top_p``.

    The highest-probability token is always kept (its cumulative mass
    *before* itself is 0 < top_p), so the mask can never be empty.
    Ties are broken by sort order, which is stable — the numpy reference
    in tests mirrors it exactly.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    axis = probs.ndim - 1
    iota = lax.broadcasted_iota(jnp.int32, probs.shape, axis)
    # One sort gives the descending probabilities and their order; a
    # second, keyed on that order (a permutation), carries the decision
    # back to vocabulary order.  No gather over [.., V].
    neg_sorted, order = lax.sort((-probs, iota), dimension=axis,
                                 is_stable=True, num_keys=1)
    sorted_probs = -neg_sorted
    csum = jnp.cumsum(sorted_probs, axis=-1)
    # Keep a token while the mass accumulated BEFORE it is < top_p.
    keep_sorted = (csum - sorted_probs) < top_p[..., None]
    _, keep = lax.sort((order, keep_sorted.astype(jnp.int32)),
                       dimension=axis, is_stable=False, num_keys=1)
    return keep.astype(bool)


def _draw(row_logits, positions, seeds):
    """One seeded categorical draw a row: ``fold_in(PRNGKey(seed), pos)``.
    Every sampling branch draws through this, on the same ``logits /
    temperature`` expression, so which branch a step took never shows in
    a row's token."""
    import jax
    import jax.numpy as jnp

    def draw(logits, pos, seed):
        key = jax.random.fold_in(jax.random.PRNGKey(seed), pos)
        return jax.random.categorical(key, logits).astype(jnp.int32)

    return jax.vmap(draw)(row_logits, positions, seeds)


def sample_tokens_with_logprobs(logits, positions, temperature, top_p,
                                seeds):
    """Draw one token per row and capture its behavior logprob.  All
    jnp, fixed shapes, jit-inlinable.

    logits: [N, V] fp32; positions: [N] absolute position of the token
    being generated; temperature/top_p: [N] f32; seeds: [N] int32.
    Rows with ``temperature <= 0`` take the argmax instead (greedy and
    sampled requests share one compiled step).

    The work follows what the step's rows ask for, decided on the device
    from ``temperature`` and ``top_p`` (one ``lax.switch``; no host read,
    no second program): no row samples — the argmax and nothing else;
    some row samples and none of those truncates — the seeded draw, no
    nucleus pass; otherwise the nucleus pass too.  A row's token is the
    same in every branch: greedy rows take one argmax computed outside
    the switch, a ``top_p >= 1`` row is never masked, and a truncating
    row only ever runs the third branch.

    Returns ``(tokens [N] int32, logps [N] f32)``.  The logprob is the
    RAW log-softmax of the model's logits at the chosen token —
    ``log pi(token | context)`` at temperature 1 with no nucleus
    truncation — which is exactly what a full-context forward pass
    recomputes and what the PPO ratio's behavior term needs.  Sampling
    transforms (temperature, top-p) change *which* token is drawn, not
    the definition of the captured logprob, so greedy and sampled
    requests stamp comparable values.
    """
    import jax.numpy as jnp
    from jax import lax

    logits = logits.astype(jnp.float32)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    samples = temperature > 0.0

    def scaled():
        return logits / jnp.maximum(temperature, 1e-6)[..., None]

    def argmax_only():
        return greedy

    def plain():
        return jnp.where(samples, _draw(scaled(), positions, seeds), greedy)

    def nucleus():
        s = scaled()
        # (csum - p) < 1.0 can cut a tail token once the float32 running
        # sum passes 1.0; a row that asked for no truncation gets none,
        # so it draws what ``plain`` draws.
        keep = top_p_mask(s, top_p) | (top_p >= 1.0)[..., None]
        masked = jnp.where(keep, s, -jnp.inf)
        return jnp.where(samples, _draw(masked, positions, seeds), greedy)

    branch = (jnp.any(samples).astype(jnp.int32)
              + jnp.any(samples & (top_p < 1.0)).astype(jnp.int32))
    tokens = lax.switch(branch, (argmax_only, plain, nucleus))
    # log_softmax at one token a row, in jax.nn.log_softmax's own order
    # of operations (same value), without the [N, V] table and its gather.
    m = jnp.max(logits, axis=-1, keepdims=True)
    lse = jnp.log(jnp.sum(jnp.exp(logits - m), axis=-1))
    at_token = jnp.take_along_axis(logits, tokens[..., None], axis=-1)
    logps = (at_token - m)[..., 0] - lse
    return tokens, logps


def sample_tokens(logits, positions, temperature, top_p, seeds):
    """Token-only form of :func:`sample_tokens_with_logprobs` (the
    logprob computation is dead code XLA eliminates when unused)."""
    return sample_tokens_with_logprobs(logits, positions, temperature,
                                       top_p, seeds)[0]
