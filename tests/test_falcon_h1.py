"""Falcon-H1's decoder (``models/falcon_h1.py``) and the serve engine's
per-slot recurrent state (ISSUE 38), on the CPU at tiny widths: ``head_dim``
is not ``hidden / heads``, the mixer has two groups and every multiplier
differs from 1.

The yardstick is the benchmark's plain reference
(``benchmark/reference/falcon_h1_34b.py``: float32, the recurrence token by
token, no cache): the program's forward against it branch by branch, the
chunked scan against the recurrence, and prefill then cached decode through
``LLMEngine`` against its full forward.  What has to fail does: a multiplier
left out, padding that advances the state, a slot that keeps its last
request's state.  The engine's loop is stepped by hand (``start=False``)
where the order of steps and admissions matters; the token-identity cases
are ``tests/test_decode_lookahead.py``'s, run on this kind.
"""
import dataclasses
import math

import numpy as np
import pytest

from test_decode_lookahead import (Synchronous, _drive, _engine,
                                   _mid_stream_eos, _prompt)

from ray_tpu.serve.sampling import GREEDY, SamplingParams

MULTS = dict(
    embedding_multiplier=5.6, lm_head_multiplier=0.3, key_multiplier=0.4,
    attention_in_multiplier=0.9, attention_out_multiplier=0.5,
    ssm_in_multiplier=0.7, ssm_out_multiplier=0.6,
    ssm_multipliers=(0.35, 0.25, 0.18, 0.5, 0.36),
    mlp_multipliers=(0.6, 0.2))
SCALARS = [(k, None) for k, v in MULTS.items() if not isinstance(v, tuple)] \
    + [(k, i) for k, v in MULTS.items() if isinstance(v, tuple)
       for i in range(len(v))]


def published(c) -> dict:
    """The reference's configuration (published key names) of a program
    config."""
    return dict(
        num_hidden_layers=c.num_layers, hidden_size=c.hidden_size,
        num_attention_heads=c.num_heads, num_key_value_heads=c.num_kv_heads,
        head_dim=c.head_dim, intermediate_size=c.intermediate_size,
        rope_theta=c.rope_theta, rms_norm_eps=c.rms_eps,
        mamba_n_heads=c.mamba_n_heads, mamba_d_head=c.mamba_d_head,
        mamba_n_groups=c.mamba_n_groups, mamba_d_state=c.mamba_d_state,
        mamba_d_conv=c.mamba_d_conv, **{k: getattr(c, k) for k in MULTS})


@pytest.fixture(scope="module")
def ref():
    from benchmark.reference import falcon_h1_34b

    return falcon_h1_34b


@pytest.fixture(scope="module")
def lm():
    """The tiny decoder, its one-dimensional leaves (norm scales, biases,
    D) moved off their trivial initial values."""
    import jax

    from ray_tpu.serve.llm_engine import build_model

    model, params = build_model("falcon_h1", {"dtype": "float32", **MULTS})
    c = model.config
    assert c.head_dim != c.hidden_size // c.num_heads
    assert c.mamba_n_groups == 2
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(5), len(leaves))
    leaves = [x + 0.1 * jax.random.normal(k, x.shape) if x.ndim == 1 else x
              for x, k in zip(leaves, keys)]
    return model, jax.tree_util.tree_unflatten(tree, leaves)


def _ids(vocab, shape, seed):
    import jax

    return jax.random.randint(jax.random.PRNGKey(seed), shape, 0, vocab)


def _rel(a, b) -> float:
    import jax.numpy as jnp

    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


# the program's forward against the reference -----------------------------
def test_forward_matches_the_reference_branch_by_branch(lm, ref):
    import jax

    model, params = lm
    c = model.config
    ids = _ids(c.vocab_size, (2, 21), 1)  # 21: no multiple of the chunk, 8
    logits, sown = model.apply({"params": params}, ids,
                               mutable=["branches"])
    want, branches = ref.forward_with_branches(params, ids, published(c))
    assert _rel(logits, want) < 1e-5
    for i in range(c.num_layers):
        for name in ("mixer", "attn", "ffn"):
            got = sown["branches"][f"layer_{i}"][name + "_out"][0]
            assert _rel(got, branches[name][i]) < 1e-5, (i, name)
    # the benchmark's count of what a step streams is the program's tree
    from benchmark import costs_hybrid

    counts = costs_hybrid.param_counts({
        **published(c), "vocab_size": c.vocab_size,
        "mamba_d_ssm": c.mamba_d_ssm})
    leaves = jax.tree_util.tree_leaves(params)
    assert sum(x.size for x in leaves) == sum(counts.values())


@pytest.mark.parametrize("name,index", SCALARS,
                         ids=[f"{k}{'' if i is None else i}"
                              for k, i in SCALARS])
def test_every_multiplier_is_seen(lm, ref, name, index):
    """The program with one of the fourteen multipliers left out (set to
    1) no longer agrees with the reference that keeps it."""
    from ray_tpu.models import FalconH1

    model, params = lm
    c = model.config
    value = 1.0 if index is None else tuple(
        1.0 if j == index else v for j, v in enumerate(getattr(c, name)))
    without = FalconH1(dataclasses.replace(c, **{name: value}))
    ids = _ids(c.vocab_size, (1, 13), 2)
    want = ref.forward(params, ids, published(c))
    assert _rel(model.apply({"params": params}, ids), want) < 1e-5
    assert _rel(without.apply({"params": params}, ids), want) > 2e-4


# the mixer's two forms ---------------------------------------------------
def _scan_inputs(length, seed=0, batch=2, h=4, p=16, g=2, n=8):
    import jax
    import jax.numpy as jnp

    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(k[0], (batch, length, h, p))
    dt = jax.nn.softplus(jax.random.normal(k[1], (batch, length, h)) - 2.0)
    a = -jnp.exp(jax.random.normal(k[2], (h,)))
    b = jax.random.normal(k[3], (batch, length, g, n))
    c = jax.random.normal(k[4], (batch, length, g, n))
    return x, dt, a, b, c


def _recurrence(x, dt, a, b, c):
    """``ssd_step`` token by token from an empty state."""
    import jax.numpy as jnp

    from ray_tpu.models.falcon_h1 import ssd_step

    state = jnp.zeros(x.shape[:1] + x.shape[2:] + b.shape[-1:])
    ys = []
    for t in range(x.shape[1]):
        y, state = ssd_step(state, x[:, t], dt[:, t], a, b[:, t], c[:, t])
        ys.append(y)
    return jnp.stack(ys, 1), state


@pytest.mark.parametrize("chunk", [4, 8])
@pytest.mark.parametrize("length", [1, 7, 13, 24])
def test_chunked_scan_equals_the_recurrence(length, chunk):
    from ray_tpu.models.falcon_h1 import ssd_scan

    inputs = _scan_inputs(length)
    want_y, want_state = _recurrence(*inputs)
    y, state = ssd_scan(*inputs, chunk)
    np.testing.assert_allclose(y, want_y, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(state, want_state, atol=2e-5, rtol=2e-5)


def test_a_row_with_dt_zero_advances_nothing():
    """Padding is ``dt`` = 0: the state after 11 real rows and 5 such rows
    is the state after 11 rows; with ``dt`` left as it is, it is not."""
    import jax.numpy as jnp

    from ray_tpu.models.falcon_h1 import ssd_scan

    x, dt, a, b, c = _scan_inputs(16, seed=3)
    _, want = ssd_scan(x[:, :11], dt[:, :11], a, b[:, :11], c[:, :11], 8)
    masked = jnp.where(jnp.arange(16)[None, :, None] < 11, dt, 0.0)
    _, state = ssd_scan(x, masked, a, b, c, 8)
    np.testing.assert_allclose(state, want, atol=2e-5, rtol=2e-5)
    _, advanced = ssd_scan(x, dt, a, b, c, 8)
    assert _rel(advanced, want) > 1e-2


# prefill, then decode through the cache ----------------------------------
def _against_reference(ref, model, params, prompt, got):
    """Largest log-probability error of the chosen tokens, and whether each
    is the reference's argmax, over the reference's one full forward."""
    import jax
    import jax.numpy as jnp

    ids = jnp.asarray([list(prompt) + got["tokens"]], jnp.int32)
    logits = ref.forward(params, ids, published(model.config))[
        0, len(prompt) - 1:-1]
    logp = jax.nn.log_softmax(logits, -1)
    chosen = jnp.asarray(got["tokens"])
    err = jnp.abs(jnp.take_along_axis(logp, chosen[:, None], -1)[:, 0]
                  - jnp.asarray(got["logprobs"]))
    return float(jnp.max(err)), bool(jnp.all(jnp.argmax(logits, -1)
                                             == chosen))


@pytest.mark.parametrize("prompt_tokens", [3, 5, 11, 19, 33])
def test_prefill_then_cached_decode_equals_the_full_forward(lm, ref,
                                                            prompt_tokens):
    """Prompts that are no multiple of the chunk (8) nor of a bucket
    (8, 16, 32, 64): the bucket's padding advances neither the state nor
    the convolution's rows, or the first decoded token already differs."""
    model, params = lm
    eng = _engine(model, params, chunk_tokens=1)
    try:
        prompt = _prompt(model.config.vocab_size, prompt_tokens, 40)
        rid = eng.submit(prompt, 9)
        _drive(eng, [rid])
        got = eng.rollout(rid, timeout=5)
        st = eng.stats()
    finally:
        eng.close()
    err, same = _against_reference(ref, model, params, prompt, got)
    assert same and err < 1e-5
    c = model.config
    per_slot_layer = 4 * c.mamba_n_heads * c.mamba_d_head * c.mamba_d_state \
        + 4 * (c.mamba_d_conv - 1) * c.conv_dim
    assert st["state_pool_bytes"] == 4 * c.num_layers * per_slot_layer
    assert st.get("decode_cache_size", 1) == 1


def test_the_prefill_program_masks_its_padding(lm):
    """The model's own prefill form on a padded context against the same
    on the bare prompt: state and convolution rows as after the last real
    row.  With the mask taken out (``lengths`` = the bucket) they are
    not."""
    import jax.numpy as jnp

    from ray_tpu.serve.llm_engine import _attend_uncached

    model, params = lm
    c = model.config
    prompt = jnp.asarray([_prompt(c.vocab_size, 11, 41)], jnp.int32)
    padded = jnp.pad(prompt, ((0, 0), (0, 5)))

    def left(ids, n):
        _, _, state = model.apply(
            {"params": params}, ids, jnp.arange(ids.shape[1])[None],
            [_attend_uncached] * c.num_layers,
            lengths=jnp.asarray([n]), logits_at=jnp.asarray([n - 1]))
        return state

    want, got, unmasked = left(prompt, 11), left(padded, 11), left(padded, 16)
    for layer in range(c.num_layers):
        for key in ("ssm", "conv"):
            np.testing.assert_allclose(got[layer][key], want[layer][key],
                                       atol=1e-5, rtol=1e-5)
        assert _rel(unmasked[layer]["ssm"], want[layer]["ssm"]) > 1e-3
        assert _rel(unmasked[layer]["conv"], want[layer]["conv"]) > 1e-3


# slots: reuse, admission under a running step, preemption -----------------
def _alone(model, params, want):
    """Each request's answer from an engine that has seen no other."""
    out = []
    for w in want:
        eng = _engine(model, params)
        try:
            rid = eng.submit(**w)
            _drive(eng, [rid])
            out.append(eng.rollout(rid, timeout=5))
        finally:
            eng.close()
    return out


def test_admission_resets_the_slot(lm):
    """One slot: a short request admitted into the slot a longer one just
    left answers as in a fresh engine.  (Its prefill writes the slot's
    state; nothing else resets it.)"""
    model, params = lm
    vocab = model.config.vocab_size
    want = [dict(prompt=_prompt(vocab, 27, 50), max_new_tokens=12),
            dict(prompt=_prompt(vocab, 6, 51), max_new_tokens=10)]
    alone = _alone(model, params, want)
    eng = _engine(model, params, max_slots=1)
    try:
        rids = [eng.submit(**w) for w in want]
        _drive(eng, rids)
        got = [eng.rollout(r, timeout=5) for r in rids]
        assert eng.stats()["admitted"] == 2
    finally:
        eng.close()
    for g, a in zip(got, alone):
        assert g["tokens"] == a["tokens"]
        np.testing.assert_allclose(g["logprobs"], a["logprobs"], atol=1e-5)


def test_a_slot_that_kept_its_state_would_differ(lm):
    """The test above has teeth: the second request's decode steps from
    the state the first left behind do not give its log-probabilities."""
    import jax

    model, params = lm
    vocab = model.config.vocab_size
    eng = _engine(model, params, max_slots=1)
    try:
        first = eng.submit(_prompt(vocab, 27, 50), 12)
        _drive(eng, [first])
        stale = jax.tree_util.tree_map(lambda x: x.copy(), eng._state)
        second = eng.submit(_prompt(vocab, 6, 51), 10)
        eng._iteration(None)  # admission: the prefill writes the slot
        fresh = eng._state
        assert any(_rel(a["ssm"], b["ssm"]) > 1e-2
                   for a, b in zip(stale, fresh))
        eng._state = stale  # as if admission had reset nothing
        _drive(eng, [second])
        kept = eng.rollout(second, timeout=5)
    finally:
        eng.close()
    alone = _alone(model, params, [dict(prompt=_prompt(vocab, 6, 51),
                                        max_new_tokens=10)])[0]
    assert not np.allclose(kept["logprobs"], alone["logprobs"], atol=1e-4)


def test_admitted_while_a_step_is_in_flight(lm):
    """A request admitted under a running decode step: the step in flight
    leaves the new slot's state alone (its lane was not active), and both
    streams are what a fresh engine gives."""
    model, params = lm
    vocab = model.config.vocab_size
    want = [dict(prompt=_prompt(vocab, 9, 60), max_new_tokens=14),
            dict(prompt=_prompt(vocab, 21, 61), max_new_tokens=8)]
    alone = _alone(model, params, want)
    eng = _engine(model, params)
    try:
        rids = [eng.submit(**want[0])]
        for _ in range(4):
            eng._iteration(None)
        assert eng._inflight is not None
        held = eng._state[0]["ssm"]
        rids.append(eng.submit(**want[1]))
        _drive(eng, rids)
        assert held.is_deleted()  # donated: updated in place, never copied
        got = [eng.rollout(r, timeout=5) for r in rids]
        st = eng.stats()
    finally:
        eng.close()
    assert st["admitted_mid_batch"] == 1
    for g, a in zip(got, alone):
        assert g["tokens"] == a["tokens"]
        np.testing.assert_allclose(g["logprobs"], a["logprobs"], atol=1e-5)


def test_preemption_and_re_prefill_continue_identically(lm):
    """Three requests over a pool that cannot hold them: a preempted
    request's state is rebuilt by the prefill of prompt + answer so far."""
    model, params = lm
    vocab = model.config.vocab_size
    want = [dict(prompt=_prompt(vocab, 8, 21), max_new_tokens=16),
            dict(prompt=_prompt(vocab, 8, 22), max_new_tokens=16),
            dict(prompt=_prompt(vocab, 6, 23), max_new_tokens=14)]
    alone = _alone(model, params, want)
    eng = _engine(model, params, max_slots=3, page_size=4, max_ctx=32,
                  num_pages=12)
    try:
        rids = [eng.submit(**w) for w in want]
        _drive(eng, rids)
        got = [eng.rollout(r, timeout=5) for r in rids]
        st = eng.stats()
    finally:
        eng.close()
    assert st["preemptions"] >= 1
    for g, a in zip(got, alone):
        assert g["tokens"] == a["tokens"]
        np.testing.assert_allclose(g["logprobs"], a["logprobs"], atol=1e-4)


# what the engine refuses --------------------------------------------------
@pytest.mark.parametrize("option", ["prefix_cache", "draft_model", "prefill",
                                    "tail_prefill"])
def test_options_that_hand_over_pages_alone_are_refused(lm, option):
    from ray_tpu.serve.llm_engine import LLMEngine

    model, params = lm
    kw = {"prefix_cache": dict(prefix_cache=True),
          "draft_model": dict(draft_model=model, draft_params=params),
          "prefill": dict(prefill=object())}.get(option, {})
    with pytest.raises(ValueError, match="recurrent state"):
        eng = LLMEngine(model, params, start=False, max_slots=2,
                        page_size=8, max_ctx=64, **kw)
        try:
            eng._tail_prefill_fn(8)
        finally:
            eng.close()


# the spans -----------------------------------------------------------------
def test_spans_say_the_state_slots_and_the_scanned_rows(lm):
    from ray_tpu import observability as obs
    from ray_tpu.util import tracing

    model, params = lm
    eng = _engine(model, params)
    obs.drain_spans()
    tracing.enable_tracing()
    try:
        rid = eng.submit(_prompt(model.config.vocab_size, 11, 70), 4)
        _drive(eng, [rid])
    finally:
        tracing.disable_tracing()
        eng.close()
    spans = obs.drain_spans()
    prefill = [s["args"] for s in spans if s["name"] == "engine.prefill"]
    assert len(prefill) == 1 and prefill[0]["scanned_rows"] == 11
    assert prefill[0]["padded_rows"] == 5 and prefill[0]["bucket"] == 16
    steps = [s["args"] for s in spans
             if s["name"] == "engine.decode.dispatch"]
    assert steps and all(s["state_slots"] == 1 for s in steps)


# tests/test_decode_lookahead.py's token identity, on this kind ------------
class SynchronousWithState(Synchronous):
    """``Synchronous`` for an engine that holds recurrent state: the
    prefill and the step take it and hand it back."""

    def run(self, prompt, max_new_tokens, eos_id=None, sampling=GREEDY):
        eng, s, p = self.eng, sampling, len(prompt)
        n = eng.max_slots
        table = np.zeros((n, eng.pages_per_slot), np.int32)
        pages = math.ceil((p + max_new_tokens) / eng.page_size)
        table[0, :pages] = 1 + np.arange(pages)
        bucket = eng._bucket_for(p)
        ids = np.zeros((bucket,), np.int32)
        ids[:p] = prompt
        k, v, tok, lp, state = eng._prefill_fn(bucket)(
            self.params, eng._k_pages, eng._v_pages, table[0], ids,
            np.int32(p), np.float32(s.temperature), np.float32(s.top_p),
            np.int32(s.seed), np.int32(0), eng._state)
        toks, lps = [int(tok)], [float(lp)]
        active = np.arange(n) == 0
        fill = lambda x, dt: np.full((n,), x, dt)  # noqa: E731
        while len(toks) < max_new_tokens and toks[-1] != eos_id:
            k, v, nxt, nlp, _, state = self.step(
                self.params, k, v, table,
                fill(p + len(toks) - 1, np.int32), fill(toks[-1], np.int32),
                active, fill(s.temperature, np.float32),
                fill(s.top_p, np.float32), fill(s.seed, np.int32),
                state=state)
            toks.append(int(np.asarray(nxt)[0]))
            lps.append(float(np.asarray(nlp)[0]))
        eng._k_pages, eng._v_pages, eng._state = k, v, state
        return {"tokens": toks, "logprobs": lps}


def test_streams_equal_the_synchronous_loops(lm):
    """``test_decode_lookahead.py``'s case (a): greedy, temperature and
    top-p requests arriving over several steps, one ended by its
    ``eos_id`` in mid-stream, one admitted into a running batch; tokens
    and log-probabilities bit for bit those of a loop that keeps nothing
    in flight."""
    model, params = lm
    vocab = model.config.vocab_size
    sync = SynchronousWithState(model, params)
    want = [
        dict(prompt=_prompt(vocab, 5, 1), max_new_tokens=12),
        dict(prompt=_prompt(vocab, 11, 2), max_new_tokens=9,
             sampling=SamplingParams(temperature=0.8, seed=3)),
        dict(prompt=_prompt(vocab, 19, 3), max_new_tokens=7,
             sampling=SamplingParams(temperature=1.0, top_p=0.9, seed=5)),
        dict(prompt=_prompt(vocab, 7, 4), max_new_tokens=10,
             sampling=SamplingParams(temperature=1.5, seed=7)),
        dict(prompt=_prompt(vocab, 9, 5), max_new_tokens=1),
    ]
    free = sync.run(**want[3])["tokens"]
    cut = _mid_stream_eos(free)
    want[3]["eos_id"] = free[cut]
    refs = [sync.run(**w) for w in want]
    eng = _engine(model, params)
    try:
        rids = [eng.submit(**w) for w in want[:2]]
        for _ in range(3):
            eng._iteration(None)
        rids.append(eng.submit(**want[2]))  # into a running batch
        for _ in range(2):
            eng._iteration(None)
        rids += [eng.submit(**w) for w in want[3:]]
        _drive(eng, rids)
        got = [eng.rollout(r, timeout=5) for r in rids]
        st = eng.stats()
    finally:
        eng.close()
    for g, ref_, w in zip(got, refs, want):
        assert g["tokens"] == ref_["tokens"]
        assert g["logprobs"] == ref_["logprobs"]  # bit for bit
    assert st["late_eos_rows"] == 1 and st["pages_in_use"] == 0
    assert st["lookahead_steps"] > st["drained_steps"] >= 1
    assert st.get("decode_cache_size", 1) == 1
