"""Nemotron-H's decoder (``models/nemotron_h.py``), the share-aware expert
op (``ops/moe.py``) and the serve engine's stores sized by the model's own
counts (ISSUE 43), on the CPU at tiny widths: the pattern ``ME*E``, 16
experts of which 4 are held from the fifth on, top-6, two mixer groups,
``head_dim`` not ``hidden / heads``.

The yardstick is the benchmark's plain reference
(``benchmark/reference/nemotron3_super_120b.py``: float32, the recurrence
token by token, every held expert for every token masked by the router's
choice, given the same share): the program's forward against it part by
part, prefill then cached decode through ``LLMEngine`` against its full
forward, and the four shares of a layer against the uncut layer.
"""
import dataclasses

import numpy as np
import pytest

from test_decode_lookahead import _drive, _engine, _prompt


def published(c) -> dict:
    """The reference's configuration (the file's key names) of a program
    config."""
    keys = ("hybrid_override_pattern", "layer_norm_epsilon",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "mamba_num_heads", "mamba_head_dim", "n_groups",
            "ssm_state_size", "conv_kernel", "num_experts_per_tok",
            "norm_topk_prob", "routed_scaling_factor", "expert_offset")
    return {k: getattr(c, k) for k in keys}


@pytest.fixture(scope="module")
def ref():
    from benchmark.reference import nemotron3_super_120b

    return nemotron3_super_120b


@pytest.fixture(scope="module")
def lm():
    """The tiny decoder, its one-dimensional leaves (norm scales, biases,
    D) moved off their trivial initial values."""
    import jax

    from ray_tpu.serve.llm_engine import build_model

    model, params = build_model("nemotron_h", {"dtype": "float32"})
    c = model.config
    assert c.head_dim != c.hidden_size // c.num_attention_heads
    assert (c.experts_held, c.expert_offset, c.n_routed_experts) == (4, 4, 16)
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(5), len(leaves))
    leaves = [x + 0.1 * jax.random.normal(k, x.shape) if x.ndim == 1 else x
              for x, k in zip(leaves, keys)]
    return model, jax.tree_util.tree_unflatten(tree, leaves)


def _ids(vocab, shape, seed):
    import jax

    return jax.random.randint(jax.random.PRNGKey(seed), shape, 0, vocab)


# the model against the reference -------------------------------------------
def test_forward_matches_the_reference_part_by_part(lm, ref):
    import jax.numpy as jnp

    from benchmark.drivers import serve_hybrid_moe

    model, params = lm
    ids = _ids(model.config.vocab_size, (2, 21), 1)
    logits = model.apply({"params": params}, ids)
    want, parts, chosen, _ = ref.forward_with_parts(
        params, ids, published(model.config))
    np.testing.assert_allclose(logits, want, atol=2e-5)
    have, have_chosen = serve_hybrid_moe.program_parts(model, params, ids)
    assert {k: v.shape[0] for k, v in have.items()} == {
        "mixer": 1, "attn": 1, "routed": 2, "shared": 2}
    for name in serve_hybrid_moe.PARTS:
        np.testing.assert_allclose(have[name], parts[name], atol=2e-5)
    assert bool(jnp.all(jnp.sort(have_chosen, -1) == jnp.sort(chosen, -1)))
    assert ref.choice_overlap(have_chosen, chosen) == 1.0
    # the head on the sampled rows only is the same head
    last = jnp.asarray([20, 7])
    at = model.apply({"params": params}, ids, logits_at=last)
    np.testing.assert_allclose(at[:, 0], logits[jnp.arange(2), last],
                               atol=1e-6)


def test_an_attention_layer_embeds_no_position(lm):
    """Without a mixer before it, attention over a permuted prompt gives
    the last row what it gave before: nothing in a ``*`` layer says where a
    token stands."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.nemotron_h import NemotronHAttention

    c = lm[0].config
    attn = NemotronHAttention(c)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 9, c.hidden_size))
    p = attn.init(jax.random.PRNGKey(1), x)
    out, _ = attn.apply(p, x)
    shuffled = jnp.concatenate([x[:, 7::-1], x[:, 8:]], axis=1)
    again, _ = attn.apply(p, shuffled)
    np.testing.assert_allclose(out[:, -1], again[:, -1], atol=1e-5)


def test_the_config_refuses_what_it_cannot_build():
    from ray_tpu.models import NemotronHConfig

    with pytest.raises(ValueError, match="hybrid_override_pattern"):
        NemotronHConfig.tiny(hybrid_override_pattern="ME-*")
    with pytest.raises(ValueError, match="experts_held"):
        NemotronHConfig.tiny(experts_held=8, expert_offset=12)
    whole = NemotronHConfig.tiny(experts_held=0, expert_offset=0)
    assert whole.experts_held == whole.n_routed_experts == 16


# the router -----------------------------------------------------------------
def test_the_router_chooses_by_score_plus_bias_and_weighs_by_score():
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import moe

    n, d, e, k = 7, 16, 12, 3
    x = jax.random.normal(jax.random.PRNGKey(0), (n, d))
    w = jax.random.normal(jax.random.PRNGKey(1), (d, e)) / 4
    bias = jnp.zeros((e,)).at[5].set(10.0).at[2].set(-10.0)
    scores = np.asarray(jax.nn.sigmoid(x @ w))
    weights, experts = moe.route_sigmoid_topk(x, w, bias, k, True, 5.0)
    plain, unbiased = moe.route_sigmoid_topk(x, w, jnp.zeros((e,)), k, True,
                                             5.0)
    for row in range(n):
        got = set(np.asarray(experts[row]).tolist())
        # the favoured expert is always chosen, the disfavoured never ...
        assert 5 in got and 2 not in got
        rest = [i for i in np.argsort(-scores[row]) if i not in (2, 5)]
        assert got == {5, *rest[:k - 1]}
        # ... but it weighs by its score, not by score + bias
        s = scores[row, np.asarray(experts[row])]
        np.testing.assert_allclose(weights[row], 5.0 * s / s.sum(),
                                   rtol=1e-5)
    assert any(set(np.asarray(a).tolist()) != set(np.asarray(b).tolist())
               for a, b in zip(experts, unbiased))
    np.testing.assert_allclose(jnp.sum(plain, -1), 5.0, rtol=1e-5)
    raw, _ = moe.route_sigmoid_topk(x, w, bias, k, False, 1.0)
    np.testing.assert_allclose(
        raw, np.take_along_axis(scores, np.asarray(experts), -1), rtol=1e-5)


# the share-aware expert op --------------------------------------------------
def _held_inputs(seed, n, d, e, held, f, k, dtype=None):
    import jax.numpy as jnp

    from ray_tpu.ops import moe

    dtype = dtype or jnp.float32
    rng = np.random.default_rng(seed)
    mk = lambda *shape: jnp.asarray(  # noqa: E731
        rng.standard_normal(shape) / np.sqrt(shape[-2]), dtype)
    x = jnp.asarray(rng.standard_normal((n, d)), dtype)
    weights, experts = moe.route_sigmoid_topk(
        x, mk(d, e), jnp.asarray(rng.uniform(-0.1, 0.1, e), jnp.float32), k,
        True, 1.0)
    return x, weights, experts, mk(held, d, f), mk(held, f, d)


def _masked(x, weights, experts, w_up, w_down, offset, active):
    """The reference: every held expert on every row, masked by the
    router's choice.  Products in x's dtype, sums in float32."""
    import jax
    import jax.numpy as jnp

    n, held, f32 = x.shape[0], w_up.shape[0], jnp.float32
    weights = jnp.where(active[:, None], weights, 0.0)
    e = int(jnp.max(experts)) + held + offset + 1
    combine = jnp.zeros((n, e), f32).at[
        jnp.arange(n)[:, None], experts].add(weights)[:, offset:offset + held]
    u = jax.nn.relu(jnp.einsum("nd,edf->enf", x, w_up,
                               preferred_element_type=f32))
    h = (u * u * combine.T[:, :, None]).astype(x.dtype)
    return jnp.einsum("enf,efd->nd", h, w_down,
                      preferred_element_type=f32).astype(x.dtype)


def _form(monkeypatch, form, tile=128, itemsize=4, d=32):
    from ray_tpu.ops import moe

    monkeypatch.setattr(moe, "DENSE_MAX_ROWS",
                        {"hit": 1 << 20, "grouped": 0}[form])
    monkeypatch.setattr(moe, "HIT_TILE_BYTES", tile * d * itemsize)
    return moe.experts_held_relu2


LIVE = {"none": lambda n: np.zeros(n, bool),
        "one": lambda n: np.arange(n) == n // 2,
        "half": lambda n: np.arange(n) % 2 == 0,
        "all": lambda n: np.ones(n, bool)}


@pytest.mark.parametrize("live", list(LIVE))
@pytest.mark.parametrize("e,held,offset,f", [
    (16, 4, 4, 256),    # a middle share; two tiles of the width
    (16, 4, 12, 192),   # the last share; 128 does not divide 192
    (8, 8, 0, 24),      # every expert held: the whole layer
])
def test_hit_list_and_grouped_forms_agree_with_the_masked_form(
        monkeypatch, live, e, held, offset, f):
    """The same rows, choices and weights through the hit-list kernel, the
    grouped form and the masked reference, with the same rows live: each
    sums the held experts a live row chose and no other; a row that is not
    live, and a choice that landed on an absent expert, add nothing."""
    import jax.numpy as jnp

    n, d, k = 12, 32, 5
    x, weights, experts, w_up, w_down = _held_inputs(e + f, n, d, e, held,
                                                     f, k)
    active = jnp.asarray(LIVE[live](n))
    args = (x, weights, experts, w_up, w_down, offset)
    got, streamed, landed = _form(monkeypatch, "hit")(*args, active=active)
    local = np.asarray(experts)[np.asarray(active)].ravel() - offset
    local = local[(local >= 0) & (local < held)]
    assert int(landed) == local.size
    assert int(streamed) == len(set(local.tolist()))
    grouped, read, landed_g = _form(monkeypatch, "grouped")(
        *args, active=active)
    assert int(read) == held and int(landed_g) == int(landed)
    want = _masked(x, weights, experts, w_up, w_down, offset, active)
    for other in (want, grouped):
        np.testing.assert_allclose(got, other, atol=5e-5, rtol=1e-5)
    assert not np.asarray(got)[~np.asarray(active)].any()
    assert not np.asarray(grouped)[~np.asarray(active)].any()
    if live == "all":  # no mask is all rows live
        whole, _, _ = _form(monkeypatch, "hit")(*args)
        np.testing.assert_array_equal(whole, got)


@pytest.mark.parametrize("form", ["hit", "grouped"])
def test_a_held_expert_no_live_row_chose_adds_nothing(monkeypatch, form):
    """Every held expert that no live row chose, NaN throughout.  The
    hit-list kernel never reads it: its answer is bit for bit its answer on
    clean weights.  The masked reference, which multiplies every expert,
    answers NaN."""
    import jax.numpy as jnp

    n, d, e, held, offset, f, k = 16, 32, 32, 16, 8, 256, 4
    x, weights, experts, *clean = _held_inputs(3, n, d, e, held, f, k)
    active = jnp.arange(n) % 4 == 0
    unhit = np.ones(held, bool)
    local = np.asarray(experts)[np.asarray(active)].ravel() - offset
    unhit[local[(local >= 0) & (local < held)]] = False
    assert 0 < unhit.sum() < held
    poisoned = [jnp.where(unhit[:, None, None], jnp.nan, w) for w in clean]
    run = _form(monkeypatch, form)
    on_clean, _, _ = run(x, weights, experts, *clean, offset, active=active)
    if form == "hit":
        got, streamed, _ = run(x, weights, experts, *poisoned, offset,
                               active=active)
        np.testing.assert_array_equal(got, on_clean)
        assert int(streamed) == held - unhit.sum()
    np.testing.assert_allclose(
        on_clean, _masked(x, weights, experts, *clean, offset, active),
        atol=5e-5, rtol=1e-5)
    bad = _masked(x, weights, experts, *poisoned, offset, active)
    assert np.isnan(np.asarray(bad)).any()


def test_olmoe_s_kernel_is_the_one_it_was():
    """``moe_hit`` goes through the body it shares with ``moe_hit_relu2``
    under its own name and with three matrices."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import moe

    n, d, e, f = 16, 32, 4, 128
    w = jnp.ones((e, d, f), jnp.float32)
    text = str(jax.make_jaxpr(moe.moe_hit)(
        jnp.ones((n, d)), jnp.ones((n, e)), jnp.arange(e, dtype=jnp.int32),
        jnp.asarray([e], jnp.int32), w, w, jnp.ones((e, f, d))))
    assert "moe_hit" in text and "moe_hit_relu2" not in text


# the shares add up ----------------------------------------------------------
def test_the_four_shares_and_the_shared_expert_once_are_the_uncut_layer(ref):
    """16 experts in 4 shares of 4: the routed parts of the four shares
    (each after its own ``W_up``, which every share holds alike), plus the
    shared expert counted once, equal the layer that holds all 16; and that
    is the reference's uncut layer."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import NemotronHConfig
    from ray_tpu.models.nemotron_h import LatentMoE

    whole_cfg = NemotronHConfig.tiny(experts_held=16, expert_offset=0,
                                     dtype=jnp.float32)
    u = jax.random.normal(jax.random.PRNGKey(0), (2, 9, 48))
    whole = LatentMoE(whole_cfg)
    p = whole.init(jax.random.PRNGKey(1), u)["params"]
    p["e_score_correction_bias"] = jax.random.uniform(
        jax.random.PRNGKey(2), (16,), minval=-0.1, maxval=0.1)

    def parts(cfg, params):
        out, sown = LatentMoE(cfg).apply({"params": params}, u,
                                         mutable=["branches", "moe"])
        b = sown["branches"]
        return (out, b["routed_out"][0], b["shared_out"][0],
                int(sown["moe"]["local_choices"][0]))

    uncut, routed, shared, landed = parts(whole_cfg, p)
    assert landed == 2 * 9 * whole_cfg.num_experts_per_tok
    np.testing.assert_allclose(uncut, routed + shared, atol=1e-5)
    summed, landed_sum = 0.0, 0
    for share in range(4):
        cfg = dataclasses.replace(whole_cfg, experts_held=4,
                                  expert_offset=4 * share)
        mine = dict(p, w_up=p["w_up"][4 * share:4 * share + 4],
                    w_down=p["w_down"][4 * share:4 * share + 4])
        _, part, again, n = parts(cfg, mine)
        np.testing.assert_allclose(again, shared, atol=1e-6)
        summed, landed_sum = summed + part, landed_sum + n
    assert landed_sum == landed  # every choice lands on exactly one share
    np.testing.assert_allclose(summed + shared, uncut, atol=2e-5)
    want_routed, want_shared, _, _ = ref._moe(
        p, u, top_k=whole_cfg.num_experts_per_tok, norm_topk=True,
        scaling=whole_cfg.routed_scaling_factor, offset=0)
    np.testing.assert_allclose(summed + shared, want_routed + want_shared,
                               atol=5e-5)


# through the serve engine ---------------------------------------------------
def _against_reference(ref, model, params, prompt, got):
    import jax
    import jax.numpy as jnp

    ids = jnp.asarray([prompt + got["tokens"]], jnp.int32)
    logits = ref.forward(params, ids, published(model.config))
    logits = logits[0, len(prompt) - 1:-1]
    logp = jax.nn.log_softmax(logits, -1)
    chosen = jnp.asarray(got["tokens"])
    err = jnp.abs(jnp.take_along_axis(logp, chosen[:, None], -1)[:, 0]
                  - jnp.asarray(got["logprobs"]))
    return float(jnp.max(err)), bool(jnp.all(jnp.argmax(logits, -1)
                                             == chosen))


@pytest.mark.parametrize("prompt_tokens", [3, 11, 19, 33])
def test_prefill_then_cached_decode_equals_the_full_forward(lm, ref,
                                                            prompt_tokens):
    """Logits, not tokens: the engine's log-probability of each token it
    chose against the reference's full forward over prompt + answer.
    Prompts that are no multiple of the chunk (8) nor of a bucket: the
    padding advances no state and chooses no expert."""
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
    assert same and err < 2e-5
    assert st.get("decode_cache_size", 1) == 1


def test_a_rollout_carries_the_experts_its_rows_chose(lm, ref):
    """``record_experts``: two requests side by side, one of them asking;
    it gets, for every row the programs were fed (the prompt's, from the
    prefill's bucket without its padding, and every answered token's but
    the last, from the decode steps' slots), what the routers chose: the
    model's own choices over the same rows, and, given to the reference,
    the log-probabilities the engine answered with."""
    import jax
    import jax.numpy as jnp

    from benchmark.drivers import serve_hybrid_moe

    model, params = lm
    c = model.config
    eng = _engine(model, params, chunk_tokens=1, record_experts=True)
    try:
        prompt = _prompt(c.vocab_size, 13, 41)
        rids = [eng.submit(_prompt(c.vocab_size, 5, 42), 9),
                eng.submit(prompt, 7, record_experts=True)]
        _drive(eng, rids)
        other, got = (eng.rollout(r, timeout=5) for r in rids)
    finally:
        eng.close()
    assert "experts" not in other
    fed = jnp.asarray([prompt + got["tokens"][:-1]], jnp.int32)
    assert got["experts"].shape == (fed.shape[1], model.expert_layers,
                                    c.num_experts_per_tok)
    _, own = serve_hybrid_moe.program_parts(model, params, fed)
    given = jnp.moveaxis(jnp.asarray(got["experts"]), 0, 1)[:, None]
    assert bool(jnp.all(jnp.sort(given, -1) == jnp.sort(own, -1)))
    logits, _, _, slack = ref.forward_with_parts(
        params, fed, published(c), first_row=len(prompt) - 1, given=given)
    assert slack == 0.0
    logp = jnp.take_along_axis(jax.nn.log_softmax(logits[0], -1),
                               jnp.asarray(got["tokens"])[:, None], -1)
    np.testing.assert_allclose(logp[:, 0], got["logprobs"], atol=2e-5)


def test_given_choices_are_used_and_their_slack_is_told(lm, ref):
    """The reference given choices that are not its own computes the
    layer with them (so its routed part moves, and the layers after it),
    and says how far below its own last place the lowest of them scored;
    given its own it is itself and the slack is 0."""
    import jax.numpy as jnp

    model, params = lm
    cfg = published(model.config)
    ids = _ids(model.config.vocab_size, (1, 9), 3)
    logits, parts, own, none = ref.forward_with_parts(params, ids, cfg)
    same = ref.forward_with_parts(params, ids, cfg, given=own)
    assert none == 0.0 and same[3] == 0.0
    np.testing.assert_array_equal(same[0], logits)
    # the lowest of a token's choices swapped for an expert it did not take
    k = own.shape[-1]
    absent = jnp.argmax(jnp.all(
        own[..., None] != jnp.arange(16), axis=-2), axis=-1)
    swapped = own.at[..., k - 1].set(absent)
    moved = ref.forward_with_parts(params, ids, cfg, given=swapped)
    assert moved[3] > 0.0
    assert bool(jnp.all(moved[2][0] == own[0]))  # its own choice, as told
    assert float(jnp.max(jnp.abs(moved[0] - logits))) > 1e-4
    np.testing.assert_array_equal(moved[1]["mixer"], parts["mixer"])


def test_record_experts_is_refused_where_nothing_can_give_them(lm):
    from ray_tpu.serve.llm_engine import LLMEngine, build_model

    model, params = lm
    eng = _engine(model, params)
    try:
        with pytest.raises(ValueError, match="record_experts"):
            eng.submit([1, 2, 3], 2, record_experts=True)
    finally:
        eng.close()
    dense, dense_params = build_model("gpt2", None)
    with pytest.raises(ValueError, match="routed expert layers"):
        LLMEngine(dense, dense_params, start=False, record_experts=True)


def test_the_stores_are_sized_by_the_model_s_own_counts(lm):
    """The pool has as many layers as the pattern has ``*``, the state list
    as many sets as it has ``M``; the expert counts are of the ``E`` layers
    and the held experts."""
    model, params = lm
    c = model.config
    assert (model.kv_layers, model.state_layers, model.expert_layers) \
        == (1, 1, 2) and c.num_layers == 4
    eng = _engine(model, params)
    try:
        assert eng._k_pages.shape[0] == eng._v_pages.shape[0] == 1
        assert len(eng._state) == 1
        assert eng._state[0]["ssm"].shape == (
            eng.max_slots, c.mamba_num_heads, c.mamba_head_dim,
            c.ssm_state_size)
        assert eng._moe_experts == 2 * c.experts_held
        assert eng._moe_choices == 2 * c.num_experts_per_tok
        per_slot = 4 * c.mamba_num_heads * c.mamba_head_dim \
            * c.ssm_state_size + 4 * (c.conv_kernel - 1) * c.mixer.conv_dim
        assert eng.stats()["state_pool_bytes"] == eng.max_slots * per_slot
    finally:
        eng.close()


def test_a_model_that_says_nothing_keeps_a_layer_a_layer():
    from ray_tpu.serve.llm_engine import build_model

    model, params = build_model("falcon_h1", {"dtype": "float32"})
    eng = _engine(model, params)
    try:
        n = model.config.num_layers
        assert eng._k_pages.shape[0] == len(eng._state) == n
    finally:
        eng.close()


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


def test_spans_and_stats_count_the_held_experts(lm):
    """Two requests decoding side by side: every ``engine.decode.fetch``
    span says the experts held, hit and streamed and the choices that
    landed here of those the live rows made; a free lane counts for
    nothing; ``stats()`` holds the sums."""
    from ray_tpu import observability as obs
    from ray_tpu.util import tracing

    model, params = lm
    c = model.config
    eng = _engine(model, params)
    obs.drain_spans()
    tracing.enable_tracing()
    try:
        rids = [eng.submit(_prompt(c.vocab_size, n, 70 + n), 5)
                for n in (11, 6)]
        _drive(eng, rids)
        st = eng.stats()
    finally:
        tracing.disable_tracing()
        eng.close()
    spans = obs.drain_spans()
    steps = [s["args"] for s in spans if s["name"] == "engine.decode.fetch"]
    live = [s["args"]["state_slots"] for s in spans
            if s["name"] == "engine.decode.dispatch"]
    assert steps and len(steps) == len(live) and max(live) == 2
    for args, rows in zip(steps, live):
        assert args["experts_held"] == 2 * c.experts_held
        assert args["choices"] == rows * 2 * c.num_experts_per_tok
        assert 0 <= args["local_choices"] <= args["choices"]
        assert args["experts_hit"] == args["experts_streamed"] \
            <= min(args["experts_held"], args["local_choices"])
        assert (args["experts_hit"] == 0) == (args["local_choices"] == 0)
    assert st["moe_experts_held"] == 2 * c.experts_held
    for key in ("experts_hit", "experts_streamed", "local_choices",
                "choices"):
        assert st["moe_" + key] == sum(a[key] for a in steps)
    assert st["moe_local_choice_share"] == pytest.approx(
        st["moe_local_choices"] / st["moe_choices"])
    assert st["moe_experts_streamed_share"] == st["moe_experts_hit_share"]
