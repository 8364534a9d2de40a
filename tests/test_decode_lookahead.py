"""The plain decode loop runs one step ahead of the host (ISSUE 34).

``LLMEngine._decode_once`` dispatches step n+1 from step n's tokens on the
device and reads step n under it.  What may not change: every request's
tokens, log-probabilities and version stamps, which are held here against
a synchronous loop written in this file (one request alone, every input
from the host, a blocking read a step); a request gets no token past
``max_new_tokens``; the row computed for a request whose ``eos_id`` came a
step late is dropped and counted; a dry pool and a weight swap find the
loop in step with the device.  The loop thread's work is done by hand
(``start=False``, ``_iteration``), so which step meets which admission is
the same in every run.
"""
import math

import numpy as np
import pytest

from ray_tpu.serve.sampling import GREEDY, SamplingParams

ENGINE = dict(max_slots=4, page_size=8, max_ctx=64)


def _gpt2_tiny():
    import jax.numpy as jnp

    from ray_tpu.serve.llm_engine import build_model

    return build_model("gpt2", {"dtype": jnp.float32})


def _olmoe_tiny():
    """The decoder of ``tests/test_olmoe.py``: 2 layers, 8 experts top-2,
    QK-norm, rope."""
    from ray_tpu.serve.llm_engine import build_model

    return build_model("llama", {
        "tiny": False, "vocab_size": 256, "max_position_embeddings": 64,
        "num_layers": 2, "num_heads": 4, "num_kv_heads": 4,
        "hidden_size": 64, "qk_norm": True, "num_experts": 8,
        "num_experts_per_tok": 2, "expert_size": 32,
        "norm_topk_prob": False, "dtype": "float32",
        "param_dtype": "float32"})


@pytest.fixture(scope="module", params=["gpt2", "olmoe"])
def lm(request):
    model, params = {"gpt2": _gpt2_tiny, "olmoe": _olmoe_tiny}[
        request.param]()
    return model, params


@pytest.fixture(scope="module")
def gpt2():
    return _gpt2_tiny()


def _engine(model, params, **kw):
    from ray_tpu.serve.llm_engine import LLMEngine

    return LLMEngine(model, params, start=False, **{**ENGINE, **kw})


def _prompt(vocab, n, seed):
    return list(map(int, np.random.default_rng(seed).integers(0, vocab, n)))


def _drive(eng, rids, turns=400):
    """The loop thread's work, by hand, until ``rids`` are done."""
    for _ in range(turns):
        if all(eng._requests[r].done.is_set() for r in rids):
            return
        eng._iteration(None)
    raise AssertionError("requests did not finish")


class Synchronous:
    """The loop as it was: one request alone in slot 0, its prefill, then
    the decode step function called directly, every input a host array and
    every output read before the next call."""

    def __init__(self, model, params):
        import jax

        self.params = params
        self.eng = _engine(model, params)
        self.step = jax.jit(self.eng._make_decode_step(model))

    def run(self, prompt, max_new_tokens, eos_id=None,
            sampling=GREEDY):
        eng, s, p = self.eng, sampling, len(prompt)
        n = eng.max_slots
        table = np.zeros((n, eng.pages_per_slot), np.int32)
        pages = math.ceil((p + max_new_tokens) / eng.page_size)
        table[0, :pages] = 1 + np.arange(pages)
        bucket = eng._bucket_for(p)
        ids = np.zeros((bucket,), np.int32)
        ids[:p] = prompt
        k, v, tok, lp = eng._prefill_fn(bucket)(
            self.params, eng._k_pages, eng._v_pages, table[0], ids,
            np.int32(p), np.float32(s.temperature), np.float32(s.top_p),
            np.int32(s.seed))
        toks, lps = [int(tok)], [float(lp)]
        active = np.arange(n) == 0
        fill = lambda x, dt: np.full((n,), x, dt)  # noqa: E731
        while len(toks) < max_new_tokens and toks[-1] != eos_id:
            k, v, nxt, nlp, *_ = self.step(
                self.params, k, v, table,
                fill(p + len(toks) - 1, np.int32), fill(toks[-1], np.int32),
                active, fill(s.temperature, np.float32),
                fill(s.top_p, np.float32), fill(s.seed, np.int32))
            toks.append(int(np.asarray(nxt)[0]))
            lps.append(float(np.asarray(nlp)[0]))
        eng._k_pages, eng._v_pages = k, v  # the prefill's were donated
        return {"tokens": toks, "logprobs": lps}


def _mid_stream_eos(tokens):
    """An index past the second token whose token has not come before it:
    as ``eos_id`` it ends the stream there and nowhere sooner."""
    return next(i for i in range(2, len(tokens) - 2)
                if tokens[i] not in tokens[:i])


# (a) ----------------------------------------------------------------------
def test_streams_equal_the_synchronous_loops(lm):
    """Greedy, temperature and top-p requests, arriving over several
    steps, of different lengths, one ended by its ``eos_id`` in mid-stream
    and one admitted into a running batch."""
    model, params = lm
    vocab = model.config.vocab_size
    sync = Synchronous(model, params)
    want = [
        dict(prompt=_prompt(vocab, 5, 1), max_new_tokens=12),
        dict(prompt=_prompt(vocab, 11, 2), max_new_tokens=9,
             sampling=SamplingParams(temperature=0.8, seed=3)),
        dict(prompt=_prompt(vocab, 19, 3), max_new_tokens=7,
             sampling=SamplingParams(temperature=1.0, top_p=0.9, seed=5)),
        dict(prompt=_prompt(vocab, 7, 4), max_new_tokens=10,  # gets eos_id
             sampling=SamplingParams(temperature=1.5, seed=7)),
        dict(prompt=_prompt(vocab, 9, 5), max_new_tokens=1),
        dict(prompt=_prompt(vocab, 3, 6), max_new_tokens=2,
             sampling=SamplingParams(temperature=0.5, seed=9)),
    ]
    free = sync.run(**want[3])["tokens"]
    cut = _mid_stream_eos(free)
    want[3]["eos_id"] = free[cut]
    refs = [sync.run(**w) for w in want]
    assert len(refs[3]["tokens"]) == cut + 1 < want[3]["max_new_tokens"]

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
    for g, ref, w in zip(got, refs, want):
        assert g["tokens"] == ref["tokens"]
        assert g["logprobs"] == ref["logprobs"]  # bit for bit
        assert g["versions"] == [0] * len(ref["tokens"])
        assert len(g["tokens"]) <= w["max_new_tokens"]
    assert st["admitted_mid_batch"] >= 2 and st["completed"] == len(want)
    assert st["late_eos_rows"] == 1
    assert st["lookahead_steps"] > st["drained_steps"] >= 1
    assert st["lookahead_steps"] + st["drained_steps"] == st["steps"]
    assert st["pages_in_use"] == 0
    assert st.get("decode_cache_size", 1) == 1


# (b) ----------------------------------------------------------------------
@pytest.mark.parametrize("max_new_tokens", [1, 2, 3, 8])
def test_no_token_past_max_new_tokens(gpt2, max_new_tokens):
    """``max_new_tokens`` is known before the dispatch: the slot is out of
    the first step it does not need, so no row is computed in vain."""
    model, params = gpt2
    eng = _engine(model, params, chunk_tokens=1)
    try:
        rids = [eng.submit(_prompt(model.config.vocab_size, 6, s),
                           max_new_tokens) for s in (1, 2)]
        _drive(eng, rids)
        for r in rids:
            assert len(eng.result(r, timeout=5)) == max_new_tokens
            assert sum(len(c) for c in eng.stream(r, timeout=5)) \
                == max_new_tokens
        st = eng.stats()
    finally:
        eng.close()
    assert st["late_eos_rows"] == 0
    assert st["steps"] == max_new_tokens - 1
    assert st["tokens_generated"] == 2 * (max_new_tokens - 1)


def test_a_late_eos_row_is_dropped_and_counted(gpt2):
    """An ``eos_id`` is read one step late: the step in flight has a row
    for the request, which is not emitted; the request beside it goes on
    untouched."""
    model, params = gpt2
    vocab = model.config.vocab_size
    sync = Synchronous(model, params)
    a, b = _prompt(vocab, 6, 11), _prompt(vocab, 10, 12)
    hot = SamplingParams(temperature=1.5, seed=13)  # tokens that differ
    free = sync.run(a, 12, sampling=hot)["tokens"]
    cut = _mid_stream_eos(free)
    ref_b = sync.run(b, 12)
    eng = _engine(model, params, chunk_tokens=1)
    try:
        ra = eng.submit(a, 12, eos_id=free[cut], sampling=hot)
        rb = eng.submit(b, 12)
        _drive(eng, [ra, rb])
        got_a, got_b = eng.rollout(ra, timeout=5), eng.rollout(rb, timeout=5)
        streamed = sum(len(c) for c in eng.stream(ra, timeout=5))
        st = eng.stats()
    finally:
        eng.close()
    assert got_a["tokens"] == free[:cut + 1] and streamed == cut + 1
    assert got_b["tokens"] == ref_b["tokens"]
    assert got_b["logprobs"] == ref_b["logprobs"]
    assert st["late_eos_rows"] == 1
    # a's cut tokens after its prefill's, b's eleven; the dropped row is
    # no token
    assert st["tokens_generated"] == cut + 11
    assert st["pages_in_use"] == 0


# (c) ----------------------------------------------------------------------
def test_a_dry_pool_drains_before_it_preempts(gpt2):
    """Three requests over a pool that cannot hold them: the loop reads
    the step in flight before it puts a request back, so no token is lost
    or emitted twice, and every stream is the synchronous loop's."""
    model, params = gpt2
    vocab = model.config.vocab_size
    sync = Synchronous(model, params)
    want = [
        dict(prompt=_prompt(vocab, 8, 21), max_new_tokens=16),
        dict(prompt=_prompt(vocab, 8, 22), max_new_tokens=16,
             sampling=SamplingParams(temperature=0.7, seed=2)),
        dict(prompt=_prompt(vocab, 6, 23), max_new_tokens=14,
             sampling=SamplingParams(temperature=1.0, top_p=0.8, seed=4)),
    ]
    refs = [sync.run(**w) for w in want]
    # 11 usable pages of 4 tokens; the three grow to 6 + 6 + 5.
    eng = _engine(model, params, max_slots=3, page_size=4, max_ctx=32,
                  num_pages=12)
    try:
        rids = [eng.submit(**w) for w in want]
        _drive(eng, rids)
        got = [eng.rollout(r, timeout=5) for r in rids]
        st = eng.stats()
    finally:
        eng.close()
    assert st["preemptions"] >= 1 and st["drained_steps"] >= 2
    for g, ref in zip(got, refs):
        assert g["tokens"] == ref["tokens"]
        # a resumed request's cache was rebuilt by a prefill
        np.testing.assert_allclose(g["logprobs"], ref["logprobs"],
                                   atol=1e-4)
    assert st["pages_in_use"] == 0 and st["late_eos_rows"] == 0
    assert st.get("decode_cache_size", 1) == 1


# (d) ----------------------------------------------------------------------
def test_a_token_keeps_the_version_that_computed_it(gpt2):
    """``swap_weights`` in mid-stream, a step in flight: each token's
    stamp names the parameters under which a full forward over its context
    gives that token that log-probability, and the other set does not."""
    import jax
    import jax.numpy as jnp

    model, p0 = gpt2
    p1 = jax.tree_util.tree_map(lambda x: x * 1.5, p0)
    vocab = model.config.vocab_size
    prompts = [_prompt(vocab, 6, 31), _prompt(vocab, 9, 32)]
    eng = _engine(model, p0)
    try:
        rids = [eng.submit(p, 12) for p in prompts]
        for _ in range(5):
            eng._iteration(None)
        assert eng._inflight is not None
        eng.swap_weights(p1, 1, timeout=None)
        _drive(eng, rids)
        got = [eng.rollout(r, timeout=5) for r in rids]
        st = eng.stats()
    finally:
        eng.close()
    assert st["swaps"] == 1 and st["drained_steps"] >= 2
    told_apart = 0
    for prompt, g in zip(prompts, got):
        vs = g["versions"]
        assert len(vs) == 12 and set(vs) == {0, 1} and vs == sorted(vs)
        ids = jnp.asarray([prompt + g["tokens"]], jnp.int32)
        under = [np.asarray(jax.nn.log_softmax(
            model.apply({"params": p}, ids)[0, len(prompt) - 1:-1], -1))
            for p in (p0, p1)]
        for i, (tok, lp, ver) in enumerate(zip(g["tokens"], g["logprobs"],
                                               vs)):
            assert int(np.argmax(under[ver][i])) == tok
            assert under[ver][i, tok] == pytest.approx(lp, abs=1e-4)
            told_apart += abs(under[1 - ver][i, tok] - lp) > 1e-2
    assert told_apart >= 12  # the two sets do not pass for each other
