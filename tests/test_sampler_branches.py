"""The sampler does only what a step's rows ask for (ISSUE 31).

``sample_tokens_with_logprobs`` picks, on the device, one of three
branches from the step's ``temperature`` / ``top_p`` rows: the argmax
alone, the seeded draw, or the draw behind the nucleus pass.  Which one a
step took must never show in a row's token or log-probability: the
module's contract is that a token depends on ``(seed, position, logits)``
only.  The reference below is the function as it stood at the parent
commit (every pass for every row, the result chosen by a ``where``).
"""
import numpy as np
import pytest

from ray_tpu import observability as obs
from ray_tpu.serve import sampling
from ray_tpu.serve.sampling import SamplingParams

pytestmark = pytest.mark.timeout(240)


def _parent_top_p_mask(logits, top_p):
    import jax
    import jax.numpy as jnp

    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    order = jnp.argsort(-probs, axis=-1)
    sorted_probs = jnp.take_along_axis(probs, order, axis=-1)
    csum = jnp.cumsum(sorted_probs, axis=-1)
    keep_sorted = (csum - sorted_probs) < top_p[..., None]
    inv = jnp.argsort(order, axis=-1)
    return jnp.take_along_axis(keep_sorted, inv, axis=-1)


def _parent_sampler(logits, positions, temperature, top_p, seeds):
    import jax
    import jax.numpy as jnp

    logits = logits.astype(jnp.float32)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    temp = jnp.maximum(temperature, 1e-6)[..., None]
    scaled = logits / temp
    masked = jnp.where(_parent_top_p_mask(scaled, top_p), scaled, -jnp.inf)

    def draw(row_logits, pos, seed):
        key = jax.random.fold_in(jax.random.PRNGKey(seed), pos)
        return jax.random.categorical(key, row_logits).astype(jnp.int32)

    sampled = jax.vmap(draw)(masked, positions, seeds)
    tokens = jnp.where(temperature <= 0.0, greedy, sampled)
    logp_all = jax.nn.log_softmax(logits, axis=-1)
    logps = jnp.take_along_axis(logp_all, tokens[..., None],
                                axis=-1)[..., 0]
    return tokens, logps


def _batch(n, vocab, seed=0, scale=3.0):
    rng = np.random.default_rng(seed)
    return (rng.normal(scale=scale, size=(n, vocab)).astype(np.float32),
            rng.integers(1, 500, size=(n,)).astype(np.int32),
            rng.integers(0, 2**31 - 1, size=(n,)).astype(np.int32))


def _bits(x):
    return np.asarray(x, np.float32).view(np.uint32)


@pytest.fixture(scope="module")
def jitted():
    import jax

    return {"new": jax.jit(sampling.sample_tokens_with_logprobs),
            "parent": jax.jit(_parent_sampler)}


@pytest.mark.parametrize("n,vocab", [(16, 50257), (1, 1000), (6, 257)])
def test_greedy_batch_is_argmax_with_the_parents_logprob(jitted, n, vocab):
    """No row samples: the tokens are the argmax and the log-probability
    is bitwise the parent's ``log_softmax`` gathered at the token."""
    logits, pos, seeds = _batch(n, vocab)
    temps, top_ps = np.zeros(n, np.float32), np.ones(n, np.float32)
    # A top_p < 1 on a greedy row asks for nothing either.
    top_ps[::2] = 0.5
    toks, lps = jitted["new"](logits, pos, temps, top_ps, seeds)
    want_toks, want_lps = jitted["parent"](logits, pos, temps, top_ps, seeds)
    assert (np.asarray(toks) == logits.argmax(-1)).all()
    assert (np.asarray(toks) == np.asarray(want_toks)).all()
    assert (_bits(lps) == _bits(want_lps)).all()


# (temperature, top_p) a row, cycled over the batch.  The branch a batch
# takes is what the rows together ask for; the rows' own draws must not
# depend on it.
MIXES = {
    "plain_only": [(0.8, 1.0), (1.0, 1.0), (1.3, 1.0)],
    "greedy_and_plain": [(0.0, 1.0), (0.8, 1.0), (0.0, 0.5), (1.0, 1.0)],
    "all_three": [(0.0, 1.0), (0.8, 1.0), (0.8, 0.9), (1.0, 0.5)],
    "nucleus_only": [(0.7, 0.9), (1.0, 0.3)],
}


@pytest.mark.parametrize("mix", sorted(MIXES))
def test_a_row_draws_what_it_draws_alone_and_what_the_parent_drew(
        jitted, mix):
    """Every row of a mixed batch gets bitwise the token and
    log-probability (a) the parent's function gives the same batch and
    (b) the new function gives the row alone, where a sampled row with
    ``top_p`` 1 takes the branch without the nucleus pass and a greedy
    row the argmax branch."""
    n, vocab = 12, 2003
    logits, pos, seeds = _batch(n, vocab, seed=3)
    rows = [MIXES[mix][i % len(MIXES[mix])] for i in range(n)]
    temps = np.asarray([r[0] for r in rows], np.float32)
    top_ps = np.asarray([r[1] for r in rows], np.float32)
    toks, lps = (np.asarray(a) for a in
                 jitted["new"](logits, pos, temps, top_ps, seeds))
    want_toks, want_lps = (np.asarray(a) for a in
                           jitted["parent"](logits, pos, temps, top_ps,
                                            seeds))
    assert (toks == want_toks).all(), (toks, want_toks)
    assert (_bits(lps) == _bits(want_lps)).all()
    for i in range(n):
        one = slice(i, i + 1)
        t1, l1 = jitted["new"](logits[one], pos[one], temps[one],
                               top_ps[one], seeds[one])
        assert int(t1[0]) == toks[i], f"row {i} {rows[i]} differs alone"
        assert _bits(l1)[0] == _bits(lps)[i]
    sampled = temps > 0
    if sampled.any():  # the draws are draws: not all of them the argmax
        assert (toks[sampled] != logits.argmax(-1)[sampled]).any()


def test_top_p_one_is_never_truncated_beside_a_truncating_row(monkeypatch):
    """``(csum - p) < 1.0`` can cut tail tokens once the float32 running
    sum passes 1.0.  A row that asked for no truncation must draw what it
    draws with no nucleus pass at all, whatever the pass says: here the
    pass (replaced) keeps only each row's first token, so a ``top_p`` 1
    row that were masked by it could only ever draw token 0."""
    import jax.numpy as jnp

    n, vocab = 8, 501
    logits, pos, seeds = _batch(n, vocab, seed=5, scale=1.0)
    temps = np.ones(n, np.float32)
    top_ps = np.ones(n, np.float32)
    plain, _ = sampling.sample_tokens_with_logprobs(
        logits, pos, temps, top_ps, seeds)
    monkeypatch.setattr(
        sampling, "top_p_mask",
        lambda lg, tp: jnp.zeros(lg.shape, bool).at[..., 0].set(True))
    top_ps[-1] = 0.5  # one truncating row: the whole step runs the pass
    toks, _ = sampling.sample_tokens_with_logprobs(
        logits, pos, temps, top_ps, seeds)
    toks, plain = np.asarray(toks), np.asarray(plain)
    assert (toks[:-1] == plain[:-1]).all()
    assert (plain[:-1] != 0).any() and toks[-1] == 0


def test_the_parents_mask_did_cut_a_top_p_one_row():
    """Why the nucleus branch forces the mask: at the vocabulary's real
    size the parent's mask drops hundreds of tail tokens at ``top_p`` 1.0
    (the float32 running sum reaches 1.0 before the end), and the new
    mask function, unchanged in meaning, says the same; the sampler is
    what overrides it."""
    import jax.numpy as jnp

    logits = jnp.asarray(_batch(4, 50257, seed=1)[0])
    one = jnp.ones((4,), jnp.float32)
    parent = np.asarray(_parent_top_p_mask(logits, one))
    new = np.asarray(sampling.top_p_mask(logits, one))
    assert (parent == new).all()
    assert not parent.all()


def test_stats_count_greedy_and_sampled_steps(monkeypatch):
    """``greedy_steps`` rises and ``sampled_steps`` stands over a greedy
    run, the reverse over a sampled one, and a retired sampled request
    does not hold the sampled branch open for the greedy ones after it.
    The ``engine.decode.dispatch`` span says the same a step
    (``sampling_rows``)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import GPT2, GPT2Config
    from ray_tpu.serve.llm_engine import LLMEngine

    cfg = GPT2Config.tiny(dtype=jnp.float32)
    model = GPT2(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    eng = LLMEngine(model, params, max_slots=4, page_size=8, max_ctx=64)
    rng = np.random.default_rng(2)
    prompts = [list(map(int, rng.integers(0, cfg.vocab_size, size=n)))
               for n in (5, 9, 13)]
    sp = SamplingParams(temperature=0.8, top_p=0.9, seed=3)

    def run(sampling_params):
        before = eng.stats()
        for r in [eng.submit(p, 6, sampling=sampling_params)
                  for p in prompts]:
            eng.result(r, timeout=200)
        after = eng.stats()
        return {k: after[k] - before[k]
                for k in ("steps", "greedy_steps", "sampled_steps")}

    try:
        first = run(None)
        assert first["greedy_steps"] == first["steps"] > 0
        assert first["sampled_steps"] == 0
        second = run(sp)
        assert second["sampled_steps"] == second["steps"] > 0
        assert second["greedy_steps"] == 0
        monkeypatch.setattr(obs, "enabled", lambda: True)
        obs.drain_spans()
        third = run(None)
        spans = [s for s in obs.drain_spans()
                 if s["name"] == "engine.decode.dispatch"]
        assert third["greedy_steps"] == third["steps"] > 0
        assert third["sampled_steps"] == 0
        assert len(spans) == third["steps"]
        assert all(s["args"]["sampling_rows"] == 0 for s in spans)
        assert eng.stats()["decode_cache_size"] == 1
    finally:
        eng.close()
