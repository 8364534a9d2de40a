"""The GLM-DSA decoder (``models/glm_dsa.py``: latent attention with a
compressed query under a learned selection of the rows it attends to, a
leading dense layer, a sigmoid router's experts with a share held) and the
serve engine behind ``model_kind="glm_dsa"`` (ISSUE 53), on the CPU at tiny
widths: hidden 64, 4 heads of 12 + 8 / 16, a query latent of 32, a latent
row of 24 + 8, an indexer of 2 heads of 16 that keeps 16 rows, 16 experts
top-4 of which 4 are held from the fifth on, 3 layers (one dense).  Contexts
of up to 64 rows: the selection binds from the 17th row on.

The yardstick is the benchmark's plain reference
(``benchmark/reference/glm5_744b_a40b.py``: float32, every index score in
full and ``lax.top_k`` a row, attention expanded a head at a time, every
held expert for every token masked by the router's choice, given the same
share).
"""
import dataclasses

import numpy as np
import pytest

from test_decode_lookahead import _drive, _engine, _prompt


def published(c) -> dict:
    """The reference's configuration (the file's key names) of a program
    config."""
    keys = ("rms_norm_eps", "num_hidden_layers", "first_k_dense_replace",
            "num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "kv_lora_rank", "index_n_heads", "index_head_dim",
            "index_topk", "num_experts_per_tok", "norm_topk_prob",
            "routed_scaling_factor", "expert_offset")
    return {**{k: getattr(c, k) for k in keys},
            "rope_parameters": {"rope_theta": c.rope_theta}}


@pytest.fixture(scope="module")
def ref():
    from benchmark.reference import glm5_744b_a40b

    return glm5_744b_a40b


@pytest.fixture(scope="module")
def lm():
    """The tiny decoder, its one-dimensional leaves (norm scales, the
    LayerNorm's bias, the routers' bias) moved off their trivial initial
    values."""
    import jax

    from ray_tpu.serve.llm_engine import build_model

    model, params = build_model("glm_dsa", {"dtype": "float32"})
    c = model.config
    assert (c.experts_held, c.expert_offset, c.num_experts) == (4, 4, 16)
    assert c.index_topk == 16
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(5), len(leaves))
    leaves = [x + 0.1 * jax.random.normal(k, x.shape) if x.ndim == 1 else x
              for x, k in zip(leaves, keys)]
    return model, jax.tree_util.tree_unflatten(tree, leaves)


def _ids(vocab, shape, seed):
    import jax

    return jax.random.randint(jax.random.PRNGKey(seed), shape, 0, vocab)


# the definitions ----------------------------------------------------------
def test_interleaved_rope_rotates_neighbouring_channels():
    """Channels (2i, 2i + 1) are a pair, rotated by position * theta^(-2i /
    P), a pair at a time; a dot product of two roped rows depends on the
    positions' difference alone."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.glm_dsa import rope_interleaved

    x = jax.random.normal(jax.random.PRNGKey(0), (2, 5, 3, 8))
    positions = jnp.asarray([[0, 1, 2, 7, 40], [3, 3, 9, 0, 1]])
    got = np.asarray(rope_interleaved(x, positions, 1e4))
    for b in range(2):
        for t in range(5):
            for i in range(4):
                angle = float(positions[b, t]) * 1e4 ** (-2 * i / 8)
                a, c = np.asarray(x[b, t, :, 2 * i]), \
                    np.asarray(x[b, t, :, 2 * i + 1])
                np.testing.assert_allclose(
                    got[b, t, :, 2 * i], a * np.cos(angle) - c * np.sin(angle),
                    atol=1e-5)
                np.testing.assert_allclose(
                    got[b, t, :, 2 * i + 1],
                    c * np.cos(angle) + a * np.sin(angle), atol=1e-5)
    q, k = x[0, 0, 0], x[0, 1, 0]
    at = lambda v, p: rope_interleaved(  # noqa: E731
        v[None, None, None], jnp.asarray([[p]]), 1e4)[0, 0, 0]
    np.testing.assert_allclose(at(q, 11) @ at(k, 4), at(q, 27) @ at(k, 20),
                               atol=1e-5)


def test_query_compression_is_two_projections_around_a_norm(lm):
    """``compressed_query`` of the RMSNorm'd query latent is ``W_qb
    rms(W_qa u)``, split [nope | rope] a head."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.mla import compressed_query

    model, params = lm
    c = model.config
    p = params["layer_1"]["attn"]
    u = jax.random.normal(jax.random.PRNGKey(1), (2, 5, c.hidden_size))
    latent = u @ p["q_a_proj"]["kernel"]
    cq = latent / jnp.sqrt(jnp.mean(latent ** 2, -1, keepdims=True)
                           + c.rms_norm_eps) * p["q_a_norm"]["scale"]
    q_nope, q_rope = compressed_query(cq, p["q_b_proj"],
                                      c.num_attention_heads,
                                      c.qk_nope_head_dim)
    want = (cq @ p["q_b_proj"]).reshape(2, 5, c.num_attention_heads, -1)
    assert q_nope.shape[-1] == 12 and q_rope.shape[-1] == 8
    np.testing.assert_allclose(jnp.concatenate([q_nope, q_rope], -1), want,
                               atol=1e-6)


def test_the_v_row_carries_the_index_key():
    import jax.numpy as jnp

    from ray_tpu.ops.mla import index_rows, latent_rows
    from ray_tpu.ops.paged_attention import pool_width

    c, k_rope, k_idx = jnp.ones((1, 3, 512)), jnp.ones((1, 3, 64)), \
        2 * jnp.ones((1, 3, 128))
    k_row, _ = latent_rows(c, k_rope)
    v_row = index_rows(c, k_idx)
    assert k_row.shape == (1, 3, 1, 576)
    assert v_row.shape == (1, 3, 1, 640) and pool_width(1, 576) == 640
    assert bool(jnp.all(v_row[..., :512] == 1) & jnp.all(v_row[..., 512:]
                                                         == 2))


# the model against the reference ------------------------------------------
@pytest.mark.parametrize("length", [9, 16, 17, 45, 64])
def test_forward_matches_the_reference_part_by_part(lm, ref, length):
    """Logits and every part's addition to the residual stream, contexts on
    both sides of ``index_topk`` (16) and across the stretches of query
    rows (16) and their blocks (8 / 16); past 16 rows the program's
    selection is the reference's own, row for row."""
    import jax

    from benchmark.drivers import serve_sparse_moe

    model, params = lm
    c = model.config
    ids = _ids(c.vocab_size, (1, length), length)
    logits, sown = model.apply({"params": params}, ids,
                               mutable=["dsa", "moe", "branches"])
    selected, scores, chosen, parts = serve_sparse_moe.program_choices(
        model, jax.device_get(sown))
    want, want_parts, own, _, chose = ref.forward_with_parts(
        params, ids, published(c), rows_kept=8)
    np.testing.assert_allclose(logits, want, atol=2e-4)
    for name, value in want_parts.items():
        np.testing.assert_allclose(np.stack(parts[name]), value, atol=2e-4,
                                   err_msg=name)
    np.testing.assert_array_equal(np.sort(chosen, -1), np.sort(own, -1))
    binds = length > c.index_topk
    assert all((s is not None) == binds for s in selected)
    if binds:
        _, _, _, _, given = ref.forward_with_parts(
            params, ids, published(c), selected=selected, rows_kept=length)
        assert given["agreement"] == [1.0] * c.num_hidden_layers
        for mine, theirs in zip(scores, given["scores"]):
            # the last block of query rows: the whole of so short a context
            assert mine.shape == (1, length, length)
            np.testing.assert_allclose(mine[0], theirs[0], atol=1e-5)
            assert np.isneginf(np.asarray(mine[0, 0, 1:])).all()


def test_a_given_selection_is_used_and_its_agreement_is_told(lm, ref):
    """The reference attends to the rows it is given; a selection that
    keeps the newest 16 rows (a sliding window) agrees with its own in
    part, and the logits move."""
    import jax.numpy as jnp

    model, params = lm
    c = model.config
    ids = _ids(c.vocab_size, (1, 40), 3)
    rows = jnp.arange(40)
    window = ((rows[None] <= rows[:, None])
              & (rows[None] > rows[:, None] - 16))[None]
    own, _, _, _, chose = ref.forward_with_parts(params, ids, published(c))
    slid, _, _, _, given = ref.forward_with_parts(
        params, ids, published(c), selected=[window] * 3)
    assert chose["agreement"] == [1.0] * 3
    assert all(0.2 < a < 0.95 for a in given["agreement"])
    assert float(jnp.max(jnp.abs(own - slid))) > 1e-3
    assert np.isnan(ref.forward_with_parts(
        params, ids[:, :9], published(c))[4]["agreement"]).all()


def test_the_reference_in_blocks_is_the_reference(lm, ref, monkeypatch):
    """What makes it fit beside an engine changes no number: query rows 16
    at a time and feed-forward rows 8 at a time give what one block
    gives."""
    import jax

    model, params = lm
    ids = _ids(model.config.vocab_size, (1, 45), 7)
    whole = ref.forward(params, ids, published(model.config))
    monkeypatch.setattr(ref, "QUERY_BLOCK", 16)
    monkeypatch.setattr(ref, "ROW_BLOCK", 8)
    jax.clear_caches()
    try:
        blocked, _, _, _, chose = ref.forward_with_parts(
            params, ids, published(model.config), rows_kept=4)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    np.testing.assert_allclose(blocked, whole, atol=1e-5)
    assert chose["agreement"] == [1.0] * 3


def test_the_four_shares_and_the_shared_expert_once_are_the_uncut_layer(ref):
    """16 experts in 4 shares of 4 (tiny's stand-in for 256 in 16 shares of
    16): the routed parts of the four shares, plus the shared expert
    counted once, equal the layer that holds all 16; and that is the
    reference's uncut layer.  In blocks of rows (18 rows, blocks of 6) as
    whole."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.glm_dsa import GlmDsaConfig, SigmoidMoE

    whole_cfg = GlmDsaConfig.tiny(experts_held=16, expert_offset=0,
                                  dtype=jnp.float32, row_block=6)
    u = jax.random.normal(jax.random.PRNGKey(0), (2, 9, 64))
    p = SigmoidMoE(whole_cfg).init(jax.random.PRNGKey(1), u)["params"]
    p["e_score_correction_bias"] = jax.random.uniform(
        jax.random.PRNGKey(2), (16,), minval=-0.1, maxval=0.1)

    def parts(cfg, params):
        out, sown = SigmoidMoE(cfg).apply({"params": params}, u,
                                          mutable=["branches", "moe"])
        b = sown["branches"]
        return (out, b["routed_out"][0], b["shared_out"][0],
                int(sown["moe"]["local_choices"][0]))

    uncut, routed, shared, landed = parts(whole_cfg, p)
    assert landed == 2 * 9 * whole_cfg.num_experts_per_tok
    np.testing.assert_allclose(uncut, routed + shared, atol=1e-5)
    unblocked = parts(dataclasses.replace(whole_cfg, row_block=64), p)
    np.testing.assert_allclose(unblocked[0], uncut, atol=1e-5)
    summed, landed_sum = 0.0, 0
    for share in range(4):
        cfg = dataclasses.replace(whole_cfg, experts_held=4,
                                  expert_offset=4 * share)
        cut = slice(4 * share, 4 * share + 4)
        mine = dict(p, w_gate=p["w_gate"][cut], w_up=p["w_up"][cut],
                    w_down=p["w_down"][cut])
        _, part, again, n = parts(cfg, mine)
        np.testing.assert_allclose(again, shared, atol=1e-6)
        summed, landed_sum = summed + part, landed_sum + n
    assert landed_sum == landed  # every choice lands on exactly one share
    np.testing.assert_allclose(summed + shared, uncut, atol=2e-5)
    want_routed, want_shared, _, _ = ref._moe(
        p, u, offset=0, top_k=whole_cfg.num_experts_per_tok, norm_topk=True,
        scaling=whole_cfg.routed_scaling_factor)
    np.testing.assert_allclose(summed + shared, want_routed + want_shared,
                               atol=5e-5)


def test_the_config_refuses_what_it_cannot_build():
    from ray_tpu.models.glm_dsa import GlmDsaConfig

    with pytest.raises(ValueError, match="experts_held"):
        GlmDsaConfig.tiny(experts_held=8, expert_offset=12)
    with pytest.raises(ValueError, match="index_topk"):
        GlmDsaConfig.tiny(index_topk=0)
    with pytest.raises(ValueError, match="ropes"):
        GlmDsaConfig.tiny(index_head_dim=4)


def test_param_count_at_the_published_widths():
    """``jax.eval_shape`` of the program's init at the benchmark's cut: the
    issue's arithmetic, 3,909,632,768."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.glm_dsa import GlmDsa, GlmDsaConfig

    model = GlmDsa(GlmDsaConfig(
        num_hidden_layers=5, first_k_dense_replace=1, vocab_size=19360,
        experts_held=16, param_dtype=jnp.bfloat16))
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    assert sum(x.size for x in jax.tree_util.tree_leaves(shapes)) \
        == 3_909_632_768
    attn = shapes["layer_1"]["attn"]
    assert sum(x.size for x in jax.tree_util.tree_leaves(attn)) \
        == 165_022_208 + 9_371_904


# through the engine -------------------------------------------------------
def _against_reference(ref, model, params, prompt, got):
    import jax
    import jax.numpy as jnp

    ids = jnp.asarray([prompt + got["tokens"][:-1]], jnp.int32)
    logits = ref.forward(params, ids, published(model.config),
                         first_row=len(prompt) - 1)[0]
    logp = jax.nn.log_softmax(logits, -1)
    chosen = jnp.asarray(got["tokens"])
    err = jnp.abs(jnp.take_along_axis(logp, chosen[:, None], -1)[:, 0]
                  - jnp.asarray(got["logprobs"]))
    return float(jnp.max(err)), bool(jnp.all(jnp.argmax(logits, -1)
                                             == chosen))


@pytest.mark.parametrize("prompt_tokens", [3, 13, 19, 37, 50])
def test_prefill_then_cached_decode_equals_the_full_forward(lm, ref,
                                                            prompt_tokens):
    """Logits, not tokens: the engine's log-probability of each token it
    chose against the reference's full forward over prompt + answer, left to
    its own selection.  From 19 tokens on the selection binds in the
    prefill, from 13 on within the nine decode steps (the index kernel,
    ``lax.top_k``, the gather by row): both forms select what the
    reference selects."""
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
    assert same and err < 1e-4
    assert st.get("decode_cache_size", 1) == 1


def test_requests_side_by_side_and_one_after_the_other(lm, ref):
    """More requests than slots through the same two slots, of lengths on
    both sides of ``index_topk``: each answer is the reference's for its own
    prompt alone (a reused slot's pages hold another sequence's rows and
    index keys until they are overwritten; a free lane's garbage reaches
    no softmax)."""
    model, params = lm
    eng = _engine(model, params, chunk_tokens=1, max_slots=2)
    try:
        prompts = [_prompt(model.config.vocab_size, n, 50 + n)
                   for n in (30, 5, 9, 41, 22)]
        rids = [eng.submit(p, 6) for p in prompts]
        _drive(eng, rids, turns=800)
        got = [eng.rollout(r, timeout=5) for r in rids]
    finally:
        eng.close()
    for prompt, answer in zip(prompts, got):
        err, same = _against_reference(ref, model, params, prompt, answer)
        assert same and err < 1e-4


def test_the_stores_hold_latent_rows_and_index_keys(lm):
    """The pool has a layer a model layer of ONE KV head as wide as a
    latent row; no recurrent state; a cached token costs its K row and its V
    row (which carries the index key: no third pool), as stored."""
    from ray_tpu.ops.paged_attention import pool_width

    model, params = lm
    c = model.config
    eng = _engine(model, params)
    try:
        assert eng._k_pages.shape[0] == eng._v_pages.shape[0] == 3
        assert eng._k_pages.shape[-1] == pool_width(1, 32) == 128
        assert (eng.kv_heads, eng.head_dim) == (1, 32)
        assert eng._state is None and eng._sparse
        assert c.kv_lora_rank + c.index_head_dim <= 128
        assert eng._moe_experts == 2 * c.experts_held
        st = eng.stats()
        assert st["kv_bytes_per_token"] == 3 * 2 * 128 * 4
        assert st["dsa_rows_read"] == st["dsa_rows_scored"] == 0
    finally:
        eng.close()


@pytest.mark.parametrize("option", ["prefix_cache", "draft_model", "prefill",
                                    "prefix_directory", "tail_prefill"])
def test_options_that_know_nothing_of_the_selection_are_refused(lm, option):
    from ray_tpu.serve.llm_engine import LLMEngine

    model, params = lm
    kw = {"prefix_cache": dict(prefix_cache=True),
          "draft_model": dict(draft_model=model, draft_params=params),
          "prefill": dict(prefill=object()),
          "prefix_directory": dict(prefix_directory=object())}.get(option, {})
    with pytest.raises(ValueError, match="learned sparse attention"):
        eng = LLMEngine(model, params, start=False, max_slots=2,
                        page_size=8, max_ctx=64, **kw)
        try:
            eng._tail_prefill_fn(8)
        finally:
            eng.close()


def test_spans_and_stats_count_the_rows_scored_and_read(lm):
    """Two requests decoding side by side, one past ``index_topk`` and one
    short of it: ``engine.decode.dispatch`` says the cached rows the
    indexers score, ``engine.decode.fetch`` the rows the attention read, by
    the program's own count: min(cached + 1, index_topk) a live slot a
    layer, never the context; ``engine.prefill`` the rows that select;
    ``stats()`` holds the sums."""
    from ray_tpu import observability as obs
    from ray_tpu.util import tracing

    model, params = lm
    c = model.config
    eng = _engine(model, params, chunk_tokens=1)
    obs.drain_spans()
    tracing.enable_tracing()
    try:
        rids = [eng.submit(_prompt(c.vocab_size, n, 70 + n), 6)
                for n in (29, 6)]
        _drive(eng, rids)
        st = eng.stats()
    finally:
        tracing.disable_tracing()
        eng.close()
    spans = obs.drain_spans()
    steps = [s["args"] for s in spans if s["name"] == "engine.decode.fetch"]
    sent = [s["args"] for s in spans if s["name"] == "engine.decode.dispatch"]
    fills = [s["args"] for s in spans if s["name"] == "engine.prefill"]
    assert sorted((a["prompt_tokens"], a["selecting_rows"]) for a in fills) \
        == [(6, 0), (29, 13)]
    assert steps and len(steps) == len(sent)
    layers = c.num_hidden_layers
    # both slots live in every step: lengths 29 + i and 6 + i before step i
    for i, (args, out) in enumerate(zip(sent, steps)):
        assert args["kv_tokens"] == 29 + 6 + 2 * i
        assert args["index_rows"] == layers * args["kv_tokens"]
        assert out["kv_rows_read"] == layers * (
            min(29 + i + 1, c.index_topk) + min(6 + i + 1, c.index_topk))
        assert out["kv_rows_read"] <= 2 * layers * c.index_topk
        assert out["experts_hit"] == out["experts_streamed"]
    assert st["dsa_rows_scored"] == sum(a["index_rows"] for a in sent)
    assert st["dsa_rows_read"] == sum(a["kv_rows_read"] for a in steps)
    assert st["dsa_selected_share"] == pytest.approx(
        st["dsa_rows_read"] / st["dsa_rows_scored"])
    assert st["dsa_selected_share"] < 1.0


def test_a_rollout_carries_the_experts_its_rows_chose(lm, ref):
    """``record_experts`` through this family's programs: [rows fed, expert
    layers, k], and given them the reference's log-probabilities are the
    engine's."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.serve.llm_engine import LLMEngine

    model, params = lm
    c = model.config
    eng = LLMEngine(model, params, start=False, max_slots=4, page_size=8,
                    max_ctx=64, chunk_tokens=1, record_experts=True)
    try:
        prompt = _prompt(c.vocab_size, 21, 9)
        rid = eng.submit(prompt, 5, record_experts=True)
        _drive(eng, [rid])
        got = eng.rollout(rid, timeout=5)
    finally:
        eng.close()
    fed = jnp.asarray([prompt + got["tokens"][:-1]], jnp.int32)
    assert got["experts"].shape == (fed.shape[1], model.expert_layers,
                                    c.num_experts_per_tok)
    given = jnp.moveaxis(jnp.asarray(got["experts"]), 0, 1)[:, None]
    logits, _, _, slack, _ = ref.forward_with_parts(
        params, fed, published(c), first_row=len(prompt) - 1, given=given)
    assert slack == 0.0
    logp = jnp.take_along_axis(jax.nn.log_softmax(logits[0], -1),
                               jnp.asarray(got["tokens"])[:, None], -1)
    np.testing.assert_allclose(logp[:, 0], got["logprobs"], atol=1e-4)
    # ... and the positions its decode steps selected: one [layers,
    # index_topk] array a row a step fed (rows 21 .. 24, past index_topk:
    # the selection binds), each the reference's own S_t of that row
    taken = got["selected"]
    assert taken.shape == (4, c.num_hidden_layers, c.index_topk)
    assert (taken >= 0).all()
    scores = ref.forward_with_parts(
        params, fed, published(c), given=given, rows_kept=4)[4]["scores"]
    for layer in range(c.num_hidden_layers):
        for step in range(4):
            want = np.argsort(-np.asarray(scores[layer, 0, step]),
                              kind="stable")[:c.index_topk]
            np.testing.assert_array_equal(np.sort(taken[step, layer]),
                                          np.sort(want))


def _compared(model, params, ref):
    """``serve_sparse_moe.compare`` over 40 + 6 tokens through an engine of
    ``model``'s, and the limits a sound float32 program is held to."""
    from benchmark.drivers import serve_sparse_moe
    from ray_tpu.serve.llm_engine import LLMEngine

    c = model.config
    eng = LLMEngine(model, params, start=False, max_slots=2, page_size=8,
                    max_ctx=64, chunk_tokens=1, record_experts=True)
    try:
        prompt = _prompt(c.vocab_size, 40, 3)
        rid = eng.submit(prompt, 6, record_experts=True)
        _drive(eng, [rid])
        got = eng.rollout(rid, timeout=5)
    finally:
        eng.close()
    config = {**published(c), "serve": {"page_size": 8}}
    limits = {"new_tokens": 6, "logprob_tolerance": 1e-3,
              "selection_agreement_min": 0.99, "index_score_err_max": 1e-3,
              "choice_slack_max": 1e-3, "choice_overlap_min": 0.99,
              "branch_rel_err_max": dict.fromkeys(
                  serve_sparse_moe.PARTS, 1e-3)}
    return serve_sparse_moe.compare(ref, config, model, params, prompt, got,
                                    parts=True), limits


def test_the_benchmark_s_comparison_holds_at_tiny_widths(lm, ref):
    """``serve_sparse_moe.compare`` on a context past ``index_topk``: every
    number it limits reads as a sound program's does in float32."""
    from benchmark.drivers import serve_sparse_moe

    check, limits = _compared(*lm, ref)
    assert check["tokens"] == 6 and check["selecting_layers"] == 3
    assert check["decode_steps"] == 5
    assert check["decode_selection_agreement"] == 1.0
    assert "own_choice_logprob_err" not in check  # the probe's, not a run's
    assert check["logprob_max_err"] < 1e-4
    assert check["argmax_margin_max"] == 0.0
    assert check["selection_agreement"] == 1.0
    assert check["index_score_err"] < 1e-4
    assert check["choice_slack"] == 0.0 and check["choice_overlap"] == 1.0
    assert max(check["branch_rel_err"].values()) < 1e-4
    assert 0.0 < check["spread"]["selected_among_newest_share"] <= 1.0
    assert serve_sparse_moe.within(check, limits)
    assert not serve_sparse_moe.within(
        {**check, "selection_agreement": 0.9}, limits)


def test_a_decode_step_that_selects_the_wrong_rows_is_not_correct(lm, ref):
    """The precision probe's planted fault (the decode form's index queries
    zeroed: the oldest ``index_topk`` rows, whatever they score), true
    weights, the prefill as it is: the comparison is given the steps' own
    selection, so the log-probabilities still agree, and it is
    ``decode_selection_agreement`` that says the rows are not the
    reference's."""
    from benchmark.drivers import serve_sparse_moe
    from benchmark.rehearsal.precision_probe_sparse_moe import planted

    model, params = lm
    check, limits = _compared(planted(model), params, ref)
    assert check["selection_agreement"] == 1.0      # the prefill form's
    assert check["logprob_max_err"] < 1e-4          # given what it selected
    assert check["decode_selection_agreement"] < 0.8
    assert not serve_sparse_moe.within(check, limits)


# what the other kinds run is what they ran --------------------------------
@pytest.mark.parametrize("kind", ["gpt2", "llama", "falcon_h1", "nemotron_h",
                                  "ling_linear"])
def test_the_other_kinds_engines_know_nothing_of_the_selection(kind):
    """Each kind the engine served before this one, at its tiny preset: its
    model has no reader of the pool of its own, so its decode step goes
    through ``paged_attention`` and returns what it returned (no count of
    rows read, no selection), and its ``stats()`` and spans say nothing of
    an indexer.  (That their lowered programs are the parent's text for text
    was checked against the parent commit when this family came: CHANGES.md,
    PR 53.)"""
    from ray_tpu.serve import llm_engine
    from ray_tpu.serve.llm_engine import LLMEngine, build_model

    model, params = build_model(kind, None)
    assert llm_engine._sparse_attend(model) is None
    routed = bool(getattr(model.config, "num_experts", 0))
    eng = LLMEngine(model, params, start=False, max_slots=2, page_size=8,
                    max_ctx=64, record_experts=routed)
    try:
        assert not eng._sparse
        rid = eng.submit([1, 2, 3, 4, 5], 3, record_experts=routed)
        _drive(eng, [rid])
        got = eng.rollout(rid, timeout=5)
        stats = eng.stats()
    finally:
        eng.close()
    assert len(got["tokens"]) == 3 and "selected" not in got
    assert not [k for k in stats if k.startswith("dsa_")]


def test_no_other_kind_imports_the_new_model():
    """The file is imported where its kind is built and nowhere else."""
    import subprocess
    import sys

    code = ("import sys; from ray_tpu.serve.llm_engine import build_model; "
            "import ray_tpu.models; build_model('gpt2', None); "
            "assert 'ray_tpu.models.glm_dsa' not in sys.modules; "
            "assert 'ray_tpu.ops.dsa' not in sys.modules")
    subprocess.run([sys.executable, "-c", code], check=True, timeout=300)
