"""The EVA decoder (``models/eva_decoder.py``: an exact window of rows beside
one pooled row for every chunk before it, in every layer), ``ops/eva.py`` and
the serve engine behind ``model_kind="eva_decoder"`` (ISSUE 61), on the CPU
at tiny widths: hidden 64, 4 heads of 16, a window of 64 rows in chunks of 8
(pages of 8), 2 layers, 3 prediction heads of 96: three windows are 192
rows.

The yardstick is the benchmark's plain reference
(``benchmark/reference/evabyte_6b.py``: float32, for every query the literal
sets of its window's rows and of the earlier windows' summaries under one
softmax).
"""
import hashlib
import math

import numpy as np
import pytest

from test_decode_lookahead import _drive, _prompt

ENGINE = dict(max_slots=2, page_size=8, max_ctx=512, chunk_tokens=1)


def published(c) -> dict:
    """The reference's configuration (the file's key names) of a program
    config."""
    return {k: getattr(c, k) for k in (
        "num_hidden_layers", "rms_norm_eps", "num_attention_heads",
        "rope_theta", "window_size", "chunk_size", "num_pred_heads",
        "vocab_size")}


@pytest.fixture(scope="module")
def ref():
    from benchmark.reference import evabyte_6b

    return evabyte_6b


@pytest.fixture(scope="module")
def lm():
    """The tiny decoder, its norms' g moved off zero."""
    import jax

    from ray_tpu.serve.llm_engine import build_model

    model, params = build_model("eva_decoder", {"dtype": "float32"})
    c = model.config
    assert (c.window_size, c.chunk_size, c.num_pred_heads) == (64, 8, 3)
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(5), len(leaves))
    leaves = [x + 0.1 * jax.random.normal(k, x.shape) if x.ndim == 1 else x
              for x, k in zip(leaves, keys)]
    return model, jax.tree_util.tree_unflatten(tree, leaves)


def _engine(model, params, **kw):
    from ray_tpu.serve.llm_engine import LLMEngine

    return LLMEngine(model, params, start=False, **{**ENGINE, **kw})


def _against_reference(ref, model, params, prompt, got):
    """(largest error of a chosen token's log-probability against the
    reference's next-byte head, whether every token is its argmax)."""
    import jax
    import jax.numpy as jnp

    ids = jnp.asarray([prompt + got["tokens"][:-1]], jnp.int32)
    logits = ref.forward(params, ids, published(model.config),
                         first_row=len(prompt) - 1)[0, :, 0]
    logp = jax.nn.log_softmax(logits, -1)
    chosen = jnp.asarray(got["tokens"])
    err = jnp.abs(jnp.take_along_axis(logp, chosen[:, None], -1)[:, 0]
                  - jnp.asarray(got["logprobs"]))
    return float(jnp.max(err)), bool(jnp.all(jnp.argmax(logits, -1)
                                             == chosen))


# ------------------------------------------- (a) the engine, on its logits
@pytest.mark.parametrize("prompt_tokens,new_tokens", [
    (20, 9),     # inside one window: plain causal attention, no summary read
    (150, 6),    # more than two windows: the prefill's summaries are read
    (185, 16),   # closes chunks at 191 (and window 2 with it) and at 199
    (64, 5),     # a whole window exactly: the ring gets no row of it
    (128, 70),   # two whole windows, then a whole window decoded and closed
])
def test_prefill_then_cached_decode_equals_the_full_forward(
        lm, ref, prompt_tokens, new_tokens):
    """Logits, not tokens: the engine's log-probability of each token it
    chose against the reference's full forward over prompt + answer.
    Tolerance 1e-4 on a log-probability: both sides are float32 here, and
    what is left is the order of sums (the flash recurrence over pages and
    the merge by log-sum-exp against one softmax) and the stored summaries
    (float32 here too)."""
    model, params = lm
    eng = _engine(model, params)
    try:
        prompt = _prompt(model.config.vocab_size, prompt_tokens, 61)
        rid = eng.submit(prompt, new_tokens)
        _drive(eng, [rid], turns=1000)
        got = eng.rollout(rid, timeout=5)
        st = eng.stats()
    finally:
        eng.close()
    err, same = _against_reference(ref, model, params, prompt, got)
    assert same and err < 1e-4
    assert st.get("decode_cache_size", 1) == 1


def test_requests_side_by_side_and_one_after_the_other(lm, ref):
    """More requests than slots through the same two slots: each answer is
    the reference's for its own prompt alone (a reused slot's ring and
    summary pages hold another sequence's rows until they are overwritten; a
    free lane's garbage reaches no softmax and no summary)."""
    model, params = lm
    eng = _engine(model, params)
    try:
        prompts = [_prompt(model.config.vocab_size, n, 50 + n)
                   for n in (130, 5, 70, 190, 22)]
        rids = [eng.submit(p, 12) for p in prompts]
        _drive(eng, rids, turns=2000)
        got = [eng.rollout(r, timeout=5) for r in rids]
    finally:
        eng.close()
    for prompt, answer in zip(prompts, got):
        err, same = _against_reference(ref, model, params, prompt, answer)
        assert same and err < 1e-4


# --------------------------------------- (b) the plain forward, all heads
@pytest.mark.parametrize("length", [40, 64, 150, 192, 200])
def test_forward_matches_the_reference_on_every_head(lm, ref, length):
    import jax.numpy as jnp

    model, params = lm
    c = model.config
    ids = jnp.asarray(np.random.default_rng(length).integers(
        0, c.vocab_size, (2, length)), jnp.int32)
    got = model.apply({"params": params}, ids)
    want, parts = ref.forward_with_parts(params, ids, published(c))
    assert got.shape == (2, length, c.num_pred_heads, c.vocab_size)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-4
    assert parts["attn"].shape == (c.num_hidden_layers, 2, length,
                                   c.hidden_size)


def test_the_serve_programs_head_is_the_first_of_the_plain_forward_s(lm):
    import jax.numpy as jnp

    model, params = lm
    ids = jnp.asarray([_prompt(model.config.vocab_size, 70, 3)], jnp.int32)
    plain = model.apply({"params": params}, ids)
    served, kept = model.apply(
        {"params": params}, ids, None, [None] * 2,
        lengths=jnp.asarray([70]), logits_at=jnp.asarray([69]))
    assert float(jnp.max(jnp.abs(served[0, 0] - plain[0, 69, 0]))) < 1e-5
    ring_k, ring_v, k_sum, v_sum = kept[0]
    assert ring_k.shape == ring_v.shape == (1, 64, 4, 16)  # one window
    assert k_sum.shape == v_sum.shape == (1, 8, 4, 16)     # 70 // 8 chunks


# ------------------------------------------------ (c) the prefill attention
def _literal(q, k, v, k_sum, v_sum, window, chunk):
    """For every query the softmax over [its window's rows up to itself ;
    the summaries of the chunks before its window], written out."""
    n, scale = q.shape[1], q.shape[-1] ** -0.5
    i, j = np.arange(n)[:, None], np.arange(n)[None]
    rows = (j // window == i // window) & (j <= i)
    sums = np.arange(k_sum.shape[1])[None] < (i // window) * (window // chunk)
    s = np.concatenate([
        np.einsum("bqhd,bkhd->bhqk", q, k),
        np.einsum("bqhd,bchd->bhqc", q, k_sum)], -1) * scale
    s = np.where(np.concatenate([rows, sums], -1), s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("bhqk,bkhd->bqhd", p,
                     np.concatenate([v, v_sum], axis=1))


@pytest.mark.parametrize("n", [48, 64, 128, 200, 256])
def test_prefill_attention_is_the_literal_masked_softmax(n):
    """With padding (200 rows are not whole windows: the call fills them up
    with rows that no real row sees), and for ``n <= window`` plain causal
    attention with the same operands."""
    import jax.numpy as jnp

    from ray_tpu.ops import eva
    from ray_tpu.ops.attention import mha_attention

    rng = np.random.default_rng(n)
    q, k, v = (rng.standard_normal((2, n, 4, 16)).astype(np.float32)
               for _ in range(3))
    phi, mu = (rng.standard_normal((4, 16)).astype(np.float32)
               for _ in range(2))
    window, chunk = 64, 8
    whole = lambda a: a[:, :n // chunk * chunk].reshape(  # noqa: E731
        2, n // chunk, chunk, 4, 16)
    k_sum, v_sum = eva.eva_pool_chunks(whole(k), whole(v), phi, mu)
    got = eva.eva_prefill_attention(*map(jnp.asarray, (q, k, v)), k_sum,
                                    v_sum, window=window, chunk=chunk)
    want = _literal(q, k, v, np.asarray(k_sum), np.asarray(v_sum), window,
                    chunk)
    assert np.max(np.abs(np.asarray(got) - want)) < 1e-5
    if n <= window:
        plain = mha_attention(*map(jnp.asarray, (q, k, v)), causal=True)
        assert np.array_equal(np.asarray(got), np.asarray(plain))


def test_pooling_is_the_softmax_of_the_keys_against_the_learned_vectors():
    from ray_tpu.ops import eva

    rng = np.random.default_rng(0)
    k, v = (rng.standard_normal((3, 8, 2, 16)).astype(np.float32)
            for _ in range(2))
    phi, mu = (rng.standard_normal((2, 16)).astype(np.float32)
               for _ in range(2))
    k_sum, v_sum = eva.eva_pool_chunks(k, v, phi, mu)

    def pooled(x, vec):
        s = np.einsum("nchd,hd->nch", k, vec) / 4.0
        w = np.exp(s - s.max(1, keepdims=True))
        return np.einsum("nch,nchd->nhd", w / w.sum(1, keepdims=True), x)

    assert np.max(np.abs(np.asarray(v_sum) - pooled(v, phi))) < 1e-5
    assert np.max(np.abs(np.asarray(k_sum) - pooled(k, mu))) < 1e-5
    # zero vectors: the chunk's mean, which the learned pooling is not
    flat = eva.eva_pool_chunks(k, v, 0 * phi, 0 * mu)
    assert np.max(np.abs(np.asarray(flat[0]) - k.mean(1))) < 1e-6
    assert np.max(np.abs(np.asarray(flat[1]) - np.asarray(v_sum))) > 0.1


def test_attention_with_its_log_sum_exp_is_attention():
    import jax.numpy as jnp

    from ray_tpu.ops.attention import mha_attention, mha_attention_lse

    rng = np.random.default_rng(1)
    q, k, v = (jnp.asarray(rng.standard_normal((2, 24, 3, 8)), jnp.float32)
               for _ in range(3))
    out, lse = mha_attention_lse(q, k, v, causal=True)
    assert float(jnp.max(jnp.abs(out - mha_attention(q, k, v)))) < 1e-6
    s = np.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(8)
    s = np.where(np.tril(np.ones((24, 24), bool)), s, -np.inf)
    want = np.log(np.exp(s).sum(-1))
    assert lse.shape == (2, 3, 24)
    assert np.max(np.abs(np.asarray(lse) - want)) < 1e-5


# ------------------------------------------------------- (d) the cache map
def test_the_cache_map_for_every_position_up_to_three_windows():
    """A slot's cache played by hand from the map alone, position by
    position: every row written where the map says, every chunk's summary
    where it says, and what a step reads (the composed row, as the device
    composes it) is exactly the window's rows so far and the summaries of
    the chunks before the window."""
    from ray_tpu.ops.eva import EvaCacheMap

    m = EvaCacheMap(window=64, chunk=8, page_size=8, max_ctx=512)
    assert (m.summary_pages, m.ring_pages, m.pages_per_slot) == (8, 8, 16)
    assert m.pages_per_slot == 2 * m.window // m.page_size
    assert m.rows_per_slot == 128
    table = np.arange(1, 17)[None]            # the slot owns pages 1..16
    pool = {}                                 # (page, offset) -> what is there
    for n in range(3 * 64 + 5):
        import jax.numpy as jnp

        composed, live = m.read_table(jnp.asarray(table), jnp.asarray([n]))
        composed, live = np.asarray(composed)[0], int(live[0])
        assert live == m.rows_read(n) == 8 * (n // 64) + n % 64
        read = [pool[(int(composed[r // 8]), r % 8)] for r in range(live)]
        first = n // 64 * 64
        assert read == ([("summary", c) for c in range(first // 8)]
                        + [("row", j) for j in range(first, n)])
        # the step's writes
        col = int(m.ring_column(n))
        assert 8 <= col < 16 and col == 8 + (n % 64) // 8
        pool[(int(table[0, col]), int(m.offset(n)))] = ("row", n)
        assert bool(m.closes_chunk(n)) == (n % 8 == 7)
        assert bool(m.closes_window(n)) == (n % 64 == 63)
        if m.closes_chunk(n):
            page = int(table[0, int(m.summary_column(n))])
            assert int(m.summary_column(n)) == n // 64
            pool[(page, int(m.summary_offset(n)))] = ("summary", n // 8)
        summaries, ring = m.owned(n)
        assert summaries == n // 64 + 1 and ring == min(n, 63) // 8 + 1
        assert summaries + ring <= m.pages_per_slot


def test_the_map_at_the_published_sizes():
    from ray_tpu.ops.eva import EvaCacheMap

    m = EvaCacheMap(window=2048, chunk=16, page_size=16, max_ctx=32768)
    assert (m.summary_pages, m.ring_pages, m.pages_per_slot) == (128, 128, 256)
    assert m.rows_per_slot == 4096
    assert m.owned(32767) == (128, 128)
    assert max(int(m.rows_read(n)) for n in range(0, 32768, 7)) <= 3967
    assert int(m.rows_read(32767)) == 3967 == 128 * 15 + 2047
    with pytest.raises(ValueError, match="a page is a chunk"):
        EvaCacheMap(window=2048, chunk=16, page_size=8, max_ctx=32768)


# ---------------------------------------------------------- (e) the ring
def test_a_ring_page_written_in_one_window_is_overwritten_in_the_next(
        lm, ref):
    """A request decoded through two window boundaries: its ring's pages
    are the first window's (no page is added after it but a summary page a
    window), the first ring page's first row holds another position's key
    after the boundary, and the stream is the reference's."""
    model, params = lm
    eng = _engine(model, params)
    try:
        prompt = _prompt(model.config.vocab_size, 50, 9)
        rid = eng.submit(prompt, 90)          # positions 50 .. 139
        seen = {}
        for _ in range(2000):
            if eng._requests[rid].done.is_set():
                break
            eng._iteration(None)
            n = int(eng._lengths[0])
            if eng._active[0] and n in (60, 70, 130):
                eng._drain()
                ring0 = int(eng._table[0, eng._cmap.summary_pages])
                seen[n] = (ring0, np.asarray(eng._k_pages[0, ring0, 0]),
                           sorted(eng._slot_pages[0]))
        got = eng.rollout(rid, timeout=5)
        st = eng.stats()
    finally:
        eng.close()
    assert set(seen) == {60, 70, 130}
    assert seen[60][0] == seen[70][0] == seen[130][0]       # the same page
    assert not np.array_equal(seen[60][1], seen[70][1])     # row 0 -> row 64
    assert not np.array_equal(seen[70][1], seen[130][1])    # row 64 -> 128
    # window 0: 8 ring pages + 1 summary page; later one summary page more
    # a window and no ring page
    assert [len(seen[n][2]) for n in (60, 70, 130)] == [9, 10, 11]
    assert st["page_pool"]["peak_in_use"] == 11
    err, same = _against_reference(ref, model, params, prompt, got)
    assert same and err < 1e-4


# ------------------------------------------------ (f) preempt and resume
def test_preempted_in_the_third_window_and_resumed_is_the_same_stream(
        lm, ref):
    model, params = lm
    prompt = _prompt(model.config.vocab_size, 120, 4)

    def play(preempt_at):
        eng = _engine(model, params)
        try:
            rid = eng.submit(prompt, 40)      # positions 120 .. 159
            for _ in range(2000):
                if eng._requests[rid].done.is_set():
                    break
                eng._iteration(None)
                if preempt_at and eng._active[0] \
                        and int(eng._lengths[0]) >= preempt_at:
                    eng._drain()
                    if eng._active[0]:
                        eng._preempt(0)
                        preempt_at = None
            return eng.rollout(rid, timeout=5), eng.stats()
        finally:
            eng.close()

    straight, _ = play(None)
    resumed, st = play(140)                   # 140 // 64 = 2: third window
    assert st["preemptions"] == 1
    assert resumed["tokens"] == straight["tokens"]
    np.testing.assert_allclose(resumed["logprobs"], straight["logprobs"],
                               atol=1e-5)
    err, same = _against_reference(ref, model, params, prompt, resumed)
    assert same and err < 1e-4


def test_a_dry_pool_preempts_and_every_answer_is_still_the_reference_s(
        lm, ref):
    """Two requests in a pool that holds one of them at its full length:
    ``_grow`` finds no page, the younger goes back to the queue and resumes
    from its context."""
    model, params = lm
    eng = _engine(model, params, num_pages=15)
    try:
        prompts = [_prompt(model.config.vocab_size, n, n) for n in (40, 30)]
        rids = [eng.submit(p, 60) for p in prompts]
        _drive(eng, rids, turns=4000)
        got = [eng.rollout(r, timeout=5) for r in rids]
        st = eng.stats()
    finally:
        eng.close()
    assert st["preemptions"] >= 1
    for prompt, answer in zip(prompts, got):
        err, same = _against_reference(ref, model, params, prompt, answer)
        assert same and err < 1e-4


# ------------------------------------------------------- (g) what is refused
@pytest.mark.parametrize("option", ["prefix_cache", "draft_model", "prefill",
                                    "prefix_directory"])
def test_options_that_hand_over_pages_of_k_and_v_are_refused(lm, option):
    from ray_tpu.serve.llm_engine import LLMEngine

    model, params = lm
    kw = {"prefix_cache": dict(prefix_cache=True),
          "draft_model": dict(draft_model=model, draft_params=params),
          "prefill": dict(prefill=object()),
          "prefix_directory": dict(prefix_directory=object())}[option]
    with pytest.raises(ValueError, match="rows are not its tokens"):
        LLMEngine(model, params, start=False, **{**ENGINE, **kw})


def test_a_tail_prefill_and_a_page_that_is_no_chunk_are_refused(lm):
    from ray_tpu.serve.llm_engine import LLMEngine

    model, params = lm
    eng = _engine(model, params)
    try:
        with pytest.raises(ValueError, match="tail prefill"):
            eng._tail_prefill_fn(16)
    finally:
        eng.close()
    with pytest.raises(ValueError, match="a page is a chunk"):
        LLMEngine(model, params, start=False, **{**ENGINE, "page_size": 16})


def test_the_config_refuses_what_it_cannot_build():
    from ray_tpu.models.eva_decoder import EvaDecoderConfig

    with pytest.raises(ValueError, match="num_key_value_heads"):
        EvaDecoderConfig.tiny(num_key_value_heads=2)
    with pytest.raises(ValueError, match="whole chunks"):
        EvaDecoderConfig.tiny(window_size=60)
    assert hash(EvaDecoderConfig.tiny()) is not None


# ------------------------------------------------- stats, spans, the count
def test_stats_and_spans_say_rows_that_are_not_tokens(lm):
    """``engine.decode.dispatch`` says the positions the live slots hold
    (``ctx_tokens``) beside the rows they read (``kv_tokens`` =
    ``summary_rows`` + ``window_rows``) and what the step closes;
    ``engine.prefill`` the bucket, the real rows, the windows and what is
    handed over; ``stats()`` the sums and what a slot owns."""
    from ray_tpu import observability as obs
    from ray_tpu.util import tracing

    model, params = lm
    c = model.config
    eng = _engine(model, params)
    obs.drain_spans()
    tracing.enable_tracing()
    try:
        rids = [eng.submit(_prompt(c.vocab_size, n, 70 + n), 12)
                for n in (150, 60)]
        _drive(eng, rids, turns=1000)
        st = eng.stats()
    finally:
        tracing.disable_tracing()
        eng.close()
    spans = obs.drain_spans()
    sent = [s["args"] for s in spans if s["name"] == "engine.decode.dispatch"]
    fills = {s["args"]["prompt_tokens"]: s["args"] for s in spans
             if s["name"] == "engine.prefill"}
    assert (fills[150]["bucket"], fills[150]["windows"],
            fills[150]["window_rows"], fills[150]["summary_rows"]) \
        == (256, 3, 22, 18)
    assert (fills[60]["bucket"], fills[60]["windows"],
            fills[60]["window_rows"], fills[60]["summary_rows"]) \
        == (64, 1, 60, 7)
    assert len(sent) == 11
    for i, args in enumerate(sent):
        a, b = 150 + i, 60 + i                # the two slots' positions
        assert args["ctx_tokens"] == a + b
        assert args["summary_rows"] == 8 * (a // 64) + 8 * (b // 64)
        assert args["window_rows"] == a % 64 + b % 64
        assert args["kv_tokens"] == args["summary_rows"] + args["window_rows"]
        assert args["chunks_closed"] == (a % 8 == 7) + (b % 8 == 7)
        assert args["windows_closed"] == (a % 64 == 63) + (b % 64 == 63)
    assert st["cache_ctx_tokens"] == sum(a["ctx_tokens"] for a in sent)
    assert st["cache_rows_read"] == sum(a["kv_tokens"] for a in sent)
    assert st["cache_chunks_closed"] == sum(a["chunks_closed"] for a in sent)
    assert st["cache_windows_closed"] == 1    # the second slot's, at 63
    assert 0.0 < st["cache_rows_share"] < 1.0
    assert (st["kv_pages_per_slot"], st["kv_rows_per_slot"],
            st["kv_positions_per_slot"]) == (16, 128, 512)


def test_at_the_published_widths_a_slot_owns_256_pages_and_holds_4096_rows():
    """The engine's own arithmetic at the cell's sizes, with a pool of two
    pages (no weight is made, ``params`` is None): 256 pages a slot, 4,096
    rows beside 32,768 positions, 16,384 bytes a token as stored at
    ``max_ctx`` (a row of 8 layers, K and V, is 131,072)."""
    import jax.numpy as jnp

    from ray_tpu.models.eva_decoder import EvaDecoder, EvaDecoderConfig
    from ray_tpu.serve.llm_engine import LLMEngine

    model = EvaDecoder(EvaDecoderConfig(
        num_hidden_layers=8, dtype=jnp.bfloat16, param_dtype=jnp.bfloat16))
    eng = LLMEngine(model, None, start=False, max_slots=16, page_size=16,
                    max_ctx=32768, num_pages=2)
    try:
        st = eng.stats()
        assert eng._k_pages.shape == eng._v_pages.shape == (8, 2, 16, 4096)
        assert eng._table.shape == (16, 256)
        assert (st["kv_pages_per_slot"], st["kv_rows_per_slot"],
                st["kv_positions_per_slot"]) == (256, 4096, 32768)
        assert st["kv_bytes_per_token"] == 16384
        assert eng._admission_columns(8192) == list(range(32)) + list(
            range(128, 256))
        assert eng._admission_columns(100) == [0] + list(range(128, 135))
    finally:
        eng.close()


def test_param_count_at_the_published_widths():
    """The issue's arithmetic, from shapes alone (``jax.eval_shape``: no
    weight is made): 202,391,552 a layer, 1,630,932,992 in the cut,
    6,488,330,240 whole."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.eva_decoder import EvaDecoder, EvaDecoderConfig

    count = lambda t: sum(  # noqa: E731
        math.prod(x.shape) for x in jax.tree_util.tree_leaves(t))
    for layers, total in ((8, 1_630_932_992), (32, 6_488_330_240)):
        c = EvaDecoderConfig(num_hidden_layers=layers, dtype=jnp.bfloat16,
                             param_dtype=jnp.bfloat16)
        shapes = jax.eval_shape(
            lambda: EvaDecoder(c).init(jax.random.PRNGKey(0),
                                       jnp.zeros((1, 8), jnp.int32)))["params"]
        assert count(shapes["layer_0"]) == 202_391_552
        assert count(shapes["layer_0"]["attn"]) == 67_108_864 + 8_192
        assert shapes["lm_head"].shape == (4096, 2560)
        assert count(shapes) == total


def test_no_other_kind_imports_the_new_model():
    """The file is imported where its kind is built and nowhere else."""
    import subprocess
    import sys

    code = ("import sys; from ray_tpu.serve.llm_engine import build_model; "
            "import ray_tpu.models; build_model('gpt2', None); "
            "assert 'ray_tpu.models.eva_decoder' not in sys.modules; "
            "assert 'ray_tpu.ops.eva' not in sys.modules")
    subprocess.run([sys.executable, "-c", code], check=True, timeout=300)


# ------------------------- (h) what the other kinds run is what they ran
def program_hashes(kind: str):
    """sha256 of the StableHLO text of ``kind``'s decode program and of its
    16-row prefill program at its tiny preset, as the engine lowers them."""
    from ray_tpu.serve.llm_engine import LLMEngine, build_model

    model, params = build_model(kind, None)
    eng = LLMEngine(model, params, start=False, max_slots=2, page_size=8,
                    max_ctx=64)
    try:
        state = () if eng._state is None else (eng._state,)
        decode = eng._decode.lower(
            eng._params, eng._k_pages, eng._v_pages, eng._table,
            eng._lengths, eng._last_tok, eng._active, eng._temps,
            eng._top_ps, eng._seeds, eng._prev_tok, eng._fresh, *state)
        slot = (np.int32(0),) if state else ()
        prefill = eng._prefill_fn(16).lower(
            eng._params, eng._k_pages, eng._v_pages, eng._table[0],
            np.zeros((16,), np.int32), np.int32(5), np.float32(0),
            np.float32(1), np.int32(0), *slot, *state)
        return tuple(hashlib.sha256(low.as_text().encode()).hexdigest()[:16]
                     for low in (decode, prefill))
    finally:
        eng.close()


# (decode, prefill) of the parent commit, 24b89ca (PR 60), taken with this
# function on a checkout of it: CHANGES.md, PR 61
PARENT_PROGRAMS = {
    "gpt2": ("a846261b64916add", "0f60cd6ee9882e28"),
    "llama": ("0bf740fe2ebeb481", "a40e9860f70942db"),
    "falcon_h1": ("95d720619a40cf09", "dfdb7a3ec4624e36"),
    "nemotron_h": ("393ea83f2ba6a5b6", "5011660ca91290fc"),
    "ling_linear": ("beaeb800d11b2dbf", "d084fdf562733ed4"),
    "glm_dsa": ("06fd1976fa76b0ed", "bc5fb057926d6bb6"),
    "latent_moe": ("d7426a32e8aa5158", "69983a5f06356b90"),
}


@pytest.mark.parametrize("kind", ["gpt2", "llama", "falcon_h1", "nemotron_h",
                                  "ling_linear", "glm_dsa", "latent_moe"])
def test_the_other_kinds_programs_are_byte_for_byte_the_parent_s(kind):
    """Each kind the engine served before this one, at its tiny preset: the
    StableHLO text of its decode program and of a prefill program hashes to
    what the parent commit's did (the map's branches are Python's, and a
    model without ``cache_map`` takes none of them)."""
    from ray_tpu.serve import llm_engine
    from ray_tpu.serve.llm_engine import build_model

    assert llm_engine._cache_map(build_model(kind, None)[0], 8, 64) is None
    assert program_hashes(kind) == PARENT_PROGRAMS[kind]
