"""OLMoE through the Llama decoder (ISSUE 27): the dropless expert FFN of
``ops/moe.py``, the whole-width QK-norm and the expert options of
``models/llama.py``, the serve engine's cache and its ``experts_hit``
count, all against ``benchmark/reference/olmoe_1b_7b.py`` at toy widths
(2 layers, hidden 64, 4 heads, 8 experts top-2) on seeded weights.
"""
import numpy as np
import pytest

CFG = {  # the published keys the reference reads, at toy sizes
    "num_hidden_layers": 2, "hidden_size": 64, "num_attention_heads": 4,
    "num_key_value_heads": 4, "num_experts": 8, "num_experts_per_tok": 2,
    "intermediate_size": 32, "norm_topk_prob": False, "rms_norm_eps": 1e-5,
    "rope_theta": 10000.0, "vocab_size": 256, "max_position_embeddings": 64,
}


def _config_kw(dtype, **over):
    cfg = {**CFG, **over}
    return {"tiny": False, "vocab_size": cfg["vocab_size"],
            "max_position_embeddings": cfg["max_position_embeddings"],
            "num_layers": cfg["num_hidden_layers"],
            "num_heads": cfg["num_attention_heads"],
            "num_kv_heads": cfg["num_key_value_heads"],
            "hidden_size": cfg["hidden_size"],
            "rope_theta": cfg["rope_theta"], "rms_eps": cfg["rms_norm_eps"],
            "qk_norm": True, "num_experts": cfg["num_experts"],
            "num_experts_per_tok": cfg["num_experts_per_tok"],
            "expert_size": cfg["intermediate_size"],
            "norm_topk_prob": cfg["norm_topk_prob"],
            "dtype": dtype, "param_dtype": dtype}


def _build(dtype="float32", seed=0, **over):
    from ray_tpu.serve.llm_engine import build_model

    model, params = build_model("llama", _config_kw(dtype, **over), seed)
    return model, params, {**CFG, **over}


def _reference():
    from benchmark.reference import olmoe_1b_7b

    return olmoe_1b_7b


def _moe_weights(rng, n, d=16, e=8, f=24, dtype=np.float32):
    import jax.numpy as jnp

    mk = lambda *s: jnp.asarray(  # noqa: E731
        rng.standard_normal(s) / np.sqrt(s[-2]), dtype)
    return (jnp.asarray(rng.standard_normal((n, d)), dtype), mk(d, e),
            mk(e, d, f), mk(e, d, f), mk(e, f, d))


def _loop(x, w_router, w_gate, w_up, w_down, top_k, norm):
    """Token by token, in float64 numpy: the definition."""
    x, w_router, w_gate, w_up, w_down = (
        np.asarray(a, np.float64) for a in (x, w_router, w_gate, w_up,
                                            w_down))
    out = np.zeros_like(x)
    rows = np.zeros(w_router.shape[1], np.int64)
    for n, row in enumerate(x):
        logits = row @ w_router
        p = np.exp(logits - logits.max())
        p /= p.sum()
        chosen = np.argsort(-p, kind="stable")[:top_k]
        w = p[chosen] / (p[chosen].sum() if norm else 1.0)
        for e, w_e in zip(chosen, w):
            g = row @ w_gate[e]
            out[n] += w_e * ((g / (1 + np.exp(-g)) * (row @ w_up[e]))
                             @ w_down[e])
            rows[e] += 1
    return out, rows


# ---- ops/moe.py -----------------------------------------------------------
@pytest.mark.parametrize("n", [5, 512, 513, 700])
def test_moe_dropless_matches_token_loop(n):
    """Few rows (the kernel over the experts hit), many rows (sorted,
    grouped), and both sides of the row count where the form changes."""
    from ray_tpu.ops import moe

    assert moe.DENSE_MAX_ROWS == 512
    args = _moe_weights(np.random.default_rng(n), n)
    out, rows = moe.moe_dropless(*args, top_k=2)
    want, want_rows = _loop(*args, 2, False)
    # float32 against float64: sums of 16 and 24 products
    np.testing.assert_allclose(out, want, atol=2e-5, rtol=1e-5)
    assert rows.tolist() == want_rows.tolist() and int(rows.sum()) == 2 * n


def test_both_forms_agree_in_bfloat16(monkeypatch):
    """The same rows through the kernel that follows the list of hit
    experts and through the grouped form, bfloat16 products and float32
    sums in both: they differ by rounding only."""
    import jax.numpy as jnp

    from ray_tpu.ops import moe

    args = _moe_weights(np.random.default_rng(1), 40, dtype=jnp.bfloat16)
    listed, rows_l = moe.moe_dropless(*args, top_k=2)
    monkeypatch.setattr(moe, "DENSE_MAX_ROWS", 0)
    grouped, rows_g = moe.moe_dropless(*args, top_k=2)
    assert listed.dtype == grouped.dtype == jnp.bfloat16
    # one bfloat16 rounding of h (2**-8 relative) and of the output
    np.testing.assert_allclose(np.asarray(listed, np.float32),
                               np.asarray(grouped, np.float32),
                               atol=0.02, rtol=0.02)
    assert rows_l.tolist() == rows_g.tolist()


@pytest.mark.parametrize("n", [7, 600])
def test_every_token_to_one_expert_loses_none(n):
    """A router that sends every token to expert 3 (and then 5): a
    capacity factor would drop most of them; here all N reach both."""
    import jax.numpy as jnp

    from ray_tpu.ops import moe

    x, _, w_gate, w_up, w_down = _moe_weights(np.random.default_rng(2), n)
    x = jnp.abs(x) + 0.1  # positive rows, so the router's sign decides
    w_router = jnp.zeros((16, 8)).at[:, 3].set(2.0).at[:, 5].set(1.0)
    out, rows = moe.moe_dropless(x, w_router, w_gate, w_up, w_down, top_k=2)
    assert rows.tolist() == [0, 0, 0, n, 0, n, 0, 0]
    want, _ = _loop(x, w_router, w_gate, w_up, w_down, 2, False)
    np.testing.assert_allclose(out, want, atol=1e-4, rtol=1e-4)
    assert float(jnp.min(jnp.max(jnp.abs(out), axis=-1))) > 0  # no zero row


@pytest.mark.parametrize("norm", [False, True])
def test_topk_weights_renormalised_only_on_request(norm):
    from ray_tpu.ops import moe

    args = _moe_weights(np.random.default_rng(3), 9)
    weights, experts = moe.route_topk(args[0], args[1], 2, norm)
    sums = np.asarray(weights.sum(-1))
    if norm:
        np.testing.assert_allclose(sums, 1.0, atol=1e-6)
    else:  # two of eight softmax values: well under 1
        assert sums.max() < 0.95
    out, _ = moe.moe_dropless(*args, top_k=2, norm_topk_prob=norm)
    np.testing.assert_allclose(out, _loop(*args, 2, norm)[0], atol=2e-5,
                               rtol=1e-5)
    assert experts.shape == (9, 2) and experts.dtype == np.int32


# ---- models/llama.py ------------------------------------------------------
def test_qk_norm_acts_on_the_whole_width_before_the_heads():
    """q_norm's variance is over all 64 projected columns of a token, not
    over one head's 16; at position 0 rope is the identity, so the q the
    attention hook sees is the normed projection itself."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.llama import LlamaAttention, LlamaConfig

    kw = _config_kw("float32")
    kw.pop("tiny")
    cfg = LlamaConfig(**kw)
    x = jnp.asarray(np.random.default_rng(4).standard_normal((1, 1, 64)),
                    jnp.float32)
    seen = {}

    def hook(q, k, v):
        seen.update(q=q, k=k)
        return q

    attn = LlamaAttention(cfg)
    pos = jnp.zeros((1, 1), jnp.int32)
    params = attn.init(jax.random.PRNGKey(0), x, kv=hook,
                       positions=pos)["params"]
    scale = jnp.linspace(0.5, 1.5, 64)
    params = {**params, "q_norm": {"scale": scale}}
    attn.apply({"params": params}, x, kv=hook, positions=pos)
    proj = x[0, 0] @ params["q_proj"]["kernel"]
    whole = proj / jnp.sqrt(jnp.mean(proj ** 2) + 1e-5) * scale
    np.testing.assert_allclose(seen["q"].reshape(-1), whole, atol=1e-5)
    heads = proj.reshape(4, 16)
    per_head = (heads / jnp.sqrt(jnp.mean(heads ** 2, -1, keepdims=True)
                                 + 1e-5)).reshape(-1) * scale
    assert float(jnp.max(jnp.abs(per_head - whole))) > 0.05
    assert seen["k"].shape == (1, 1, 4, 16)


@pytest.mark.parametrize("dtype,atol", [("float32", 2e-4), ("bfloat16", 0.1)])
def test_llama_with_the_options_matches_the_reference(dtype, atol):
    """Logits of the program's full-context forward against the plain
    reference on the same seeded tree, and the experts each chose.
    float32: the same arithmetic in another order, logits of size ~1, so
    2e-4, and every token's expert sets agree.  bfloat16 (weights and
    products; 8 bits of mantissa, two layers): errors up to 0.06 are
    rounding, so 0.1 on the tokens whose experts agree in both layers; a
    token whose router flipped (1 in 96 here: two near-equal softmax
    values of bfloat16 activations) swaps one of its TWO experts at these
    toy sizes and moves a logit by 0.5, so it is counted and not compared.
    A wrong formula (renormalised weights) moves float32 logits 10x atol."""
    import jax.numpy as jnp

    model, params, cfg = _build(dtype)
    ref = _reference()
    ids = jnp.asarray(np.random.default_rng(5).integers(0, 256, (2, 24)),
                      jnp.int32)
    got, sown = model.apply({"params": params}, ids, mutable=["moe"])
    want, chosen = ref.forward_with_experts(params, ids, cfg)
    mine = jnp.stack([sown["moe"][f"layer_{i}"]["moe"]["expert_idx"][0]
                      for i in range(2)])
    assert got.dtype == jnp.float32 and chosen.shape == mine.shape == (
        2, 2, 24, 2)
    same = jnp.all(jnp.sort(mine, -1) == jnp.sort(chosen, -1), -1)
    assert ref.router_agreement(mine, chosen) >= (
        1.0 if dtype == "float32" else 0.95)
    err = jnp.max(jnp.abs(got - want), -1) * jnp.all(same, 0)
    assert float(jnp.max(err)) <= atol
    if dtype == "float32":
        wrong = ref.forward(params, ids, {**cfg, "norm_topk_prob": True})
        assert float(jnp.max(jnp.abs(wrong - want))) > 10 * atol


def test_norm_topk_prob_reaches_the_model():
    import jax.numpy as jnp

    model, params, cfg = _build(norm_topk_prob=True)
    ids = jnp.asarray(np.random.default_rng(6).integers(0, 256, (1, 16)),
                      jnp.int32)
    np.testing.assert_allclose(model.apply({"params": params}, ids),
                               _reference().forward(params, ids, cfg),
                               atol=2e-4)


def test_a_sequence_that_is_not_live_gets_zero_and_reads_no_expert():
    """``active`` through ``Llama`` to every expert layer: the layer's
    output for a sequence that is not live is zero, a live one's is what
    it was, and each layer says it streamed the experts the live chose."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.llama import LlamaConfig, LlamaMoE

    kw = _config_kw("float32")
    kw.pop("tiny")
    layer = LlamaMoE(LlamaConfig(**kw))
    x = jnp.asarray(np.random.default_rng(8).standard_normal((4, 1, 64)),
                    jnp.float32)
    params = layer.init(jax.random.PRNGKey(0), x)["params"]
    whole, sown = layer.apply({"params": params}, x, mutable=["moe"])
    active = jnp.asarray([True, False, False, True])
    part, sown_part = layer.apply({"params": params}, x, active=active,
                                  mutable=["moe"])
    part, whole = np.asarray(part), np.asarray(whole)
    np.testing.assert_array_equal(part[[0, 3]], whole[[0, 3]])
    assert not part[[1, 2]].any()
    chosen = np.asarray(sown["moe"]["expert_idx"][0])[:, 0]  # [4, k]
    assert int(sown["moe"]["experts_streamed"][0]) == len(set(chosen.ravel()))
    assert int(sown_part["moe"]["experts_streamed"][0]) == len(
        set(chosen[[0, 3]].ravel()))


# ---- serve/llm_engine.py --------------------------------------------------
def _drive(eng, rids):
    """The loop thread's work, by hand: deterministic steps."""
    for _ in range(200):
        if all(eng._requests[r].done.is_set() for r in rids):
            return
        eng._iteration(None)
    raise AssertionError("requests did not finish")


def test_prefill_then_cached_decode_matches_the_references_full_forward(
        monkeypatch):
    """Two requests admitted together, then decoded in lockstep through the
    paged cache: each chosen token's log-probability against the
    reference's one full forward over prompt + answer (log-probabilities,
    not tokens: a rounding flip of an argmax is not an error), the
    ``experts_hit`` of every decode step against the union of the
    reference's top-k sets of the two tokens of that step, its
    ``experts_streamed`` against that (two of the four slots stay free),
    and the shares ``stats()`` reports."""
    import jax
    import jax.numpy as jnp

    from ray_tpu import observability as obs
    from ray_tpu.serve.llm_engine import LLMEngine

    model, params, cfg = _build()
    ref = _reference()
    monkeypatch.setattr(obs, "enabled", lambda: True)
    obs.drain_spans()
    eng = LLMEngine(model, params, max_slots=4, page_size=8, max_ctx=64,
                    start=False)
    try:
        rng = np.random.default_rng(7)
        prompts = [list(map(int, rng.integers(0, 256, 12))) for _ in "ab"]
        new = 6
        rids = [eng.submit(p, new) for p in prompts]
        _drive(eng, rids)
        outs = [eng.rollout(r, timeout=5) for r in rids]
        stats = eng.stats()
    finally:
        eng.close()
    spans = obs.drain_spans()
    chosen = []
    for prompt, got in zip(prompts, outs):
        assert len(got["tokens"]) == new
        ids = jnp.asarray([prompt + got["tokens"]], jnp.int32)
        logits, top = ref.forward_with_experts(params, ids, cfg)
        logp = jax.nn.log_softmax(logits[0, len(prompt) - 1:-1], -1)
        want = jnp.take_along_axis(
            logp, jnp.asarray(got["tokens"])[:, None], -1)[:, 0]
        # float32 on both sides, cache against no cache: 1e-4
        np.testing.assert_allclose(got["logprobs"], want, atol=1e-4)
        chosen.append(np.asarray(top[:, 0]))  # [layers, positions, k]
    # Decode step t feeds each slot its token at position 12 + t.
    want_hit = [sum(len(set(chosen[0][layer, 12 + t])
                        | set(chosen[1][layer, 12 + t]))
                    for layer in range(2)) for t in range(new - 1)]
    fetches = [s["args"] for s in spans if s["name"] == "engine.decode.fetch"]
    hits = [a["experts_hit"] for a in fetches]
    assert hits == want_hit and stats["steps"] == new - 1
    # Two of four slots are free: the expert layers read the experts the two
    # live rows chose and no other, on every step.
    assert [a["experts_streamed"] for a in fetches] == hits
    assert max(hits) <= 2 * 2 * 2 < 2 * 8
    assert stats["moe_experts_hit_share"] == pytest.approx(
        sum(want_hit) / ((new - 1) * 2 * 8))
    assert stats["moe_experts_streamed_share"] == \
        stats["moe_experts_hit_share"]
    # Two slots, two choices each: the busiest expert has 1 or 2 of 4.
    assert 0.25 <= stats["moe_max_expert_share"] <= 0.5
    assert stats.get("decode_cache_size", 1) == 1


def test_free_lanes_touch_no_expert():
    """One active slot of four: its top-2 in each of 2 layers and nothing
    else, whatever the free lanes' garbage rows route to."""
    from ray_tpu.serve.llm_engine import LLMEngine

    model, params, _ = _build()
    eng = LLMEngine(model, params, max_slots=4, page_size=8, max_ctx=64,
                    start=False)
    try:
        rid = eng.submit([5, 6, 7, 8, 9], 4)
        _drive(eng, [rid])
        stats = eng.stats()
    finally:
        eng.close()
    assert stats["moe_experts_hit_share"] == pytest.approx(4 / 16)
    assert stats["moe_experts_streamed_share"] == pytest.approx(4 / 16)
    assert stats["moe_max_expert_share"] == pytest.approx(0.5)


def test_build_model_makes_leaves_in_the_dtype_asked_for():
    """bfloat16 leaves when the configuration asks, every one of them;
    GPT-2's and dense Llama's trees as they were: float32, the same names,
    no ``moe_*`` in a dense engine's stats."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.serve.llm_engine import LLMEngine, build_model

    _, params, _ = _build("bfloat16")
    leaves = jax.tree_util.tree_leaves(params)
    assert {x.dtype for x in leaves} == {jnp.dtype(jnp.bfloat16)}
    layer = params["layer_0"]
    assert sorted(layer["moe"]) == ["router", "w_down", "w_gate", "w_up"]
    assert layer["moe"]["w_gate"].shape == (8, 64, 32)
    assert layer["attn"]["q_norm"]["scale"].shape == (64,) and \
        "mlp" not in layer
    model, dense = build_model("llama", {"dtype": "float32"}, 0)
    assert {x.dtype for x in jax.tree_util.tree_leaves(dense)} == {
        jnp.dtype(jnp.float32)}
    assert sorted(dense["layer_0"]) == ["attn", "attn_norm", "mlp",
                                        "mlp_norm"]
    assert sorted(dense["layer_0"]["attn"]) == ["k_proj", "o_proj", "q_proj",
                                                "v_proj"]
    _, gpt2 = build_model("gpt2", None, 0)
    assert {x.dtype for x in jax.tree_util.tree_leaves(gpt2)} == {
        jnp.dtype(jnp.float32)}
    eng = LLMEngine(model, dense, max_slots=2, page_size=8, max_ctx=64,
                    start=False)
    try:
        assert not [k for k in eng.stats() if k.startswith("moe_")]
    finally:
        eng.close()
