"""The Ling-linear decoder (``models/ling_linear.py``), the delta rule with a
decay per channel (``ops/kda.py``), latent attention in its two forms
(``ops/mla.py``), the grouped router and the share-aware SwiGLU expert op
(``ops/moe.py``) and the serve engine behind ``model_kind="ling_linear"``
(ISSUE 47), on the CPU at tiny widths: hidden 64, 4 heads of 16, 16 experts
in 4 groups of which 2 are kept, top-4, 4 held from the fifth on, 7 layers
by the published rule (a dense KDA layer, then K K K K M K).

The yardstick is the benchmark's plain reference
(``benchmark/reference/ling3_flash_vl.py``: float32, the recurrence token by
token, attention expanded, every held expert for every token masked by the
router's choice, given the same share).
"""
import dataclasses

import numpy as np
import pytest

from test_decode_lookahead import _drive, _engine, _prompt


def published(c) -> dict:
    """The reference's configuration (the file's key names) of a program
    config."""
    keys = ("rms_norm_eps", "num_hidden_layers", "first_k_dense_replace",
            "layer_group_size", "num_attention_heads", "kda_lower_bound",
            "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
            "kv_lora_rank", "rope_theta", "num_experts_per_tok", "n_group",
            "topk_group", "norm_topk_prob", "routed_scaling_factor",
            "expert_offset")
    return {**{k: getattr(c, k) for k in keys}, "head_dim": c.kda_head_dim}


@pytest.fixture(scope="module")
def ref():
    from benchmark.reference import ling3_flash_vl

    return ling3_flash_vl


@pytest.fixture(scope="module")
def lm():
    """The tiny decoder, its one-dimensional leaves (norm scales, biases,
    A_log) moved off their trivial initial values."""
    import jax

    from ray_tpu.serve.llm_engine import build_model

    model, params = build_model("ling_linear", {"dtype": "float32"})
    c = model.config
    assert (c.experts_held, c.expert_offset, c.num_experts) == (4, 4, 16)
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(5), len(leaves))
    leaves = [x + 0.1 * jax.random.normal(k, x.shape) if x.ndim == 1 else x
              for x, k in zip(leaves, keys)]
    return model, jax.tree_util.tree_unflatten(tree, leaves)


def _ids(vocab, shape, seed):
    import jax

    return jax.random.randint(jax.random.PRNGKey(seed), shape, 0, vocab)


# the delta rule ---------------------------------------------------------------
def _kda_inputs(length, lower=False, seed=0, b=2, h=3, k=16, v=16,
                alike=0.0):
    """``alike``: how much of a part common to every token the keys and
    queries carry before they are normalised (the residual stream of a
    deep layer gives keys that are nearly one direction)."""
    import jax
    import jax.numpy as jnp

    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)  # noqa
    common = alike * jax.random.normal(ks[5], (1, 1, h, k))
    q = unit(common + jax.random.normal(ks[0], (b, length, h, k))) * k ** -0.5
    kk = unit(common + jax.random.normal(ks[1], (b, length, h, k)))
    vv = jax.random.normal(ks[2], (b, length, h, v))
    g = -5.0 * jax.nn.sigmoid(2 * jax.random.normal(ks[3], (b, length, h, k)))
    if lower:  # every gate at its lower bound, every token
        g = jnp.full_like(g, -5.0)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, length, h)))
    return q, kk, vv, g, beta


def _token_by_token(q, k, v, g, beta, upto=None):
    import jax.numpy as jnp

    from ray_tpu.ops.kda import kda_step

    state = jnp.zeros(q.shape[:1] + q.shape[2:] + v.shape[-1:])
    outs = []
    for t in range(upto or q.shape[1]):
        state, o = kda_step(state, q[:, t], k[:, t], v[:, t], g[:, t],
                            beta[:, t])
        outs.append(o)
    return jnp.stack(outs, 1), state


@pytest.mark.parametrize("length,chunk,sub,lower", [
    (200, 64, 16, False),  # four chunks, the last one padded
    (200, 64, 16, True),   # e^-5 a token: 64 tokens are e^-320
    (64, 64, 16, True),    # a whole chunk at the lower bound, no padding
    (7, 64, 16, False),    # shorter than a sub-block
    (130, 32, 8, False),   # another chunk gives the same numbers
    (48, 16, 16, False),   # one sub-block a chunk
])
def test_the_chunked_form_equals_the_recurrence(length, chunk, sub, lower):
    import jax

    from ray_tpu.ops.kda import kda_chunked

    with jax.default_matmul_precision("highest"):
        q, k, v, g, beta = _kda_inputs(length, lower)
        want, state = _token_by_token(q, k, v, g, beta)
        got, left = kda_chunked(q, k, v, g, beta, chunk=chunk, sub=sub)
    assert bool(np.all(np.isfinite(got)))
    np.testing.assert_allclose(got, want, atol=2e-6)
    np.testing.assert_allclose(left, state, atol=5e-6)


@pytest.mark.parametrize("alike", [3.0, 10.0])
def test_keys_that_are_nearly_one_direction_lose_no_digits(alike):
    """Keys with a large common part make the chunk's triangular system
    ill-conditioned: its inverse by forward substitution keeps float32's
    digits (the Neumann product lost three at ``alike`` 10 here, and two
    to three behind a deep layer's residual stream on the chip: PR 47)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.kda import kda_chunked

    with jax.default_matmul_precision("highest"):
        q, k, v, g, beta = _kda_inputs(256, seed=8, alike=alike)
        g = -5.0 * jax.nn.sigmoid(4 * g / -5.0 - 6)  # most decays near 1
        want, state = _token_by_token(q, k, v, g, beta)
        got, left = kda_chunked(q, k, v, g, beta)
    rel = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
    assert rel < 5e-6, rel
    np.testing.assert_allclose(left, state, atol=2e-5)


def test_a_padded_bucket_advances_nothing():
    """Rows past the real ones with g = 0 and beta = 0, as the mixer masks
    its bucket's padding: the state is what the real rows left."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.kda import kda_chunked

    with jax.default_matmul_precision("highest"):
        q, k, v, g, beta = _kda_inputs(128, seed=3)
        real = (jnp.arange(128) < 77)[None, :, None]
        g = jnp.where(real[..., None], g, 0.0)
        beta = jnp.where(real, beta, 0.0)
        want, state = _token_by_token(q, k, v, g, beta, upto=77)
        got, left = kda_chunked(q, k, v, g, beta)
    np.testing.assert_allclose(got[:, :77], want, atol=2e-6)
    np.testing.assert_allclose(left, state, atol=5e-6)


def test_steps_go_on_from_the_state_a_context_leaves():
    import jax

    from ray_tpu.ops.kda import kda_chunked, kda_step

    with jax.default_matmul_precision("highest"):
        q, k, v, g, beta = _kda_inputs(96, seed=4)
        whole, state = kda_chunked(q, k, v, g, beta)
        first, s = kda_chunked(*(a[:, :40] for a in (q, k, v, g, beta)))
        rest = []
        for t in range(40, 96):
            s, o = kda_step(s, q[:, t], k[:, t], v[:, t], g[:, t],
                            beta[:, t])
            rest.append(o)
    np.testing.assert_allclose(
        np.concatenate([first, np.stack(rest, 1)], 1), whole, atol=2e-6)
    np.testing.assert_allclose(s, state, atol=2e-5)


def test_a_step_leaves_a_row_that_is_not_active_alone():
    import jax.numpy as jnp

    from ray_tpu.ops.kda import kda_step

    q, k, v, g, beta = _kda_inputs(1, seed=6)
    state = jnp.ones((2, 3, 16, 16))
    new, _ = kda_step(state, q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0],
                      active=jnp.asarray([True, False]))
    assert not np.allclose(new[0], state[0])
    np.testing.assert_array_equal(new[1], state[1])


def test_the_gate_keeps_to_its_lower_bound():
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.kda import kda_gate

    f = 50.0 * jax.random.normal(jax.random.PRNGKey(0), (4, 9, 3, 16))
    g = kda_gate(f, jnp.log(jnp.asarray([1.0, 4.0, 16.0])),
                 jnp.zeros((3, 16)), -5.0)
    assert float(g.min()) >= -5.0 and float(g.max()) <= 0.0
    assert float(g.min()) < -4.99 and float(g.max()) > -0.01


# latent attention ---------------------------------------------------------------
def test_absorbed_decode_through_the_paged_kernel_equals_the_expanded_form():
    """One MLA layer: a context expanded, against the same context's first
    rows written into a page pool as latent rows and the last row attending
    to them absorbed, through ``ops/paged_attention.py`` with ONE KV head
    (a row of 40 columns in a pool padded to 128)."""
    import functools

    import jax
    import jax.numpy as jnp

    from ray_tpu.models.ling_linear import LingLinearConfig, MLAttention
    from ray_tpu.ops.paged_attention import paged_attention, pool_width

    c = LingLinearConfig.tiny(dtype=jnp.float32)
    layer = MLAttention(c)
    n, ps = 21, 8
    u = jax.random.normal(jax.random.PRNGKey(0), (2, n, c.hidden_size))
    pos = jnp.broadcast_to(jnp.arange(n)[None], (2, n))
    p = layer.init(jax.random.PRNGKey(1), u, pos)["params"]
    want, none = layer.apply({"params": p}, u, pos)
    assert none is None
    same, (k_rows, v_rows) = layer.apply({"params": p}, u, pos, rows=True)
    np.testing.assert_array_equal(same, want)
    assert k_rows.shape == v_rows.shape == (2, n, 1, c.head_dim)
    assert c.head_dim == 40 and c.num_kv_heads == 1
    np.testing.assert_array_equal(v_rows[..., c.kv_lora_rank:], 0.0)
    # the first n - 1 rows into the pool, two sequences on pages of 8
    width = pool_width(1, c.head_dim)
    pages = -(-n // ps)
    table = jnp.arange(2 * pages).reshape(2, pages) + 1

    def pool(rows):
        flat = jnp.zeros((1, 2 * pages + 1, ps, width))
        for s in range(2):
            for t in range(n - 1):
                flat = flat.at[0, table[s, t // ps], t % ps,
                               :c.head_dim].set(rows[s, t, 0])
        return flat

    attend = functools.partial(
        paged_attention, k_pool=pool(k_rows), v_pool=pool(v_rows), layer=0,
        table=table, lengths=jnp.full((2,), n - 1))
    got, (k_new, v_new) = layer.apply({"params": p}, u[:, -1:], pos[:, -1:],
                                      kv=attend)
    np.testing.assert_allclose(got[:, 0], want[:, -1], atol=2e-5)
    np.testing.assert_allclose(k_new, k_rows[:, -1:], atol=1e-6)


# the router and the experts ---------------------------------------------------
def _plain_grouped_route(x, w, bias, k, n_group, topk_group, scaling):
    """Row by row in numpy: sigmoid, groups scored by their two best
    score + bias, the best groups kept, the k best inside them."""
    s = 1.0 / (1.0 + np.exp(-(x @ w)))
    biased = s + bias
    per = w.shape[1] // n_group
    weights, chosen = [], []
    for row_s, row_b in zip(s, biased):
        group = np.sort(row_b.reshape(n_group, per), -1)[:, -2:].sum(-1)
        kept = np.argsort(-group, kind="stable")[:topk_group]
        allowed = np.full_like(row_b, -np.inf)
        for g in kept:
            allowed[g * per:(g + 1) * per] = row_b[g * per:(g + 1) * per]
        idx = np.argsort(-allowed, kind="stable")[:k]
        chosen.append(idx)
        weights.append(row_s[idx] / row_s[idx].sum() * scaling)
    return np.asarray(weights), np.asarray(chosen)


def test_the_grouped_router_equals_a_plain_one_and_never_leaves_its_groups():
    import jax

    from ray_tpu.ops.moe import route_group_sigmoid_topk

    x = np.asarray(jax.random.normal(jax.random.PRNGKey(0), (64, 24)))
    w = np.asarray(jax.random.normal(jax.random.PRNGKey(1), (24, 32)))
    bias = np.asarray(jax.random.uniform(jax.random.PRNGKey(2), (32,),
                                         minval=-0.3, maxval=0.3))
    weights, chosen = route_group_sigmoid_topk(x, w, bias, 6, 8, 3, True, 2.5)
    want_w, want_i = _plain_grouped_route(x, w, bias, 6, 8, 3, 2.5)
    np.testing.assert_array_equal(np.sort(chosen, -1), np.sort(want_i, -1))
    np.testing.assert_allclose(np.sort(weights, -1), np.sort(want_w, -1),
                               rtol=1e-5)
    np.testing.assert_allclose(weights.sum(-1), 2.5, rtol=1e-5)
    groups = [set(row // 4) for row in np.asarray(chosen)]
    assert max(map(len, groups)) <= 3 and min(map(len, groups)) >= 2
    # the bias steers the choice and weighs nothing
    lifted = bias.copy()
    lifted[5] = 5.0
    w2, i2 = route_group_sigmoid_topk(x, w, lifted, 6, 8, 3, False, 1.0)
    assert bool(np.all(np.any(np.asarray(i2) == 5, axis=-1)))
    at = np.asarray(i2) == 5
    s5 = 1.0 / (1.0 + np.exp(-(x @ w)[:, 5]))
    np.testing.assert_allclose(np.asarray(w2)[at], s5, rtol=1e-5)
    # with every group kept it is the ungrouped router
    from ray_tpu.ops.moe import route_sigmoid_topk

    a = route_group_sigmoid_topk(x, w, bias, 6, 8, 8, True, 2.5)
    b = route_sigmoid_topk(x, w, bias, 6, True, 2.5)
    np.testing.assert_array_equal(a[1], b[1])
    np.testing.assert_allclose(a[0], b[0], rtol=1e-6)


LIVE = {"all": None, "some": [True, False, True, True, False, True, True]}


@pytest.mark.parametrize("live", list(LIVE))
@pytest.mark.parametrize("held,offset", [(4, 4), (16, 0), (4, 12)])
def test_hit_list_and_grouped_forms_of_the_swiglu_share_agree(
        monkeypatch, live, held, offset):
    """``experts_held_swiglu`` in its two forms against every held expert
    for every row, masked by the choice."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import moe

    n, d, f, e, k = 7, 24, 40, 16, 4
    ks = jax.random.split(jax.random.PRNGKey(held + offset), 6)
    x = jax.random.normal(ks[0], (n, d))
    experts = jnp.stack([jax.random.permutation(kk, e)[:k]
                         for kk in jax.random.split(ks[1], n)])
    weights = jax.random.uniform(ks[2], (n, k))
    w_gate, w_up = (0.3 * jax.random.normal(kk, (held, d, f))
                    for kk in ks[3:5])
    w_down = 0.3 * jax.random.normal(ks[5], (held, f, d))
    active = None if LIVE[live] is None else jnp.asarray(LIVE[live])
    rows_live = jnp.ones((n,), bool) if active is None else active
    want = jnp.zeros((n, d))
    for j in range(held):
        mine = jnp.sum(jnp.where((experts == offset + j)
                                 & rows_live[:, None], weights, 0.0), -1)
        want += mine[:, None] * ((jax.nn.silu(x @ w_gate[j])
                                  * (x @ w_up[j])) @ w_down[j])
    landed_want = int(jnp.sum((experts >= offset) & (experts < offset + held)
                              & rows_live[:, None]))
    hit, streamed, landed = moe.experts_held_swiglu(
        x, weights, experts, w_gate, w_up, w_down, offset, active=active)
    np.testing.assert_allclose(hit, want, atol=1e-4)
    assert int(landed) == landed_want and 0 <= int(streamed) <= held
    monkeypatch.setattr(moe, "DENSE_MAX_ROWS", 0)
    grouped, all_held, landed = moe.experts_held_swiglu(
        x, weights, experts, w_gate, w_up, w_down, offset, active=active)
    np.testing.assert_allclose(grouped, want, atol=1e-4)
    assert int(landed) == landed_want and int(all_held) == held


def test_the_four_shares_and_the_shared_expert_once_are_the_uncut_layer(ref):
    """16 experts in 4 shares of 4 (a share is a group): the routed parts of
    the four shares, plus the shared expert counted once, equal the layer
    that holds all 16; and that is the reference's uncut layer."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.ling_linear import GroupedMoE, LingLinearConfig

    whole_cfg = LingLinearConfig.tiny(experts_held=16, expert_offset=0,
                                      dtype=jnp.float32)
    u = jax.random.normal(jax.random.PRNGKey(0), (2, 9, 64))
    p = GroupedMoE(whole_cfg).init(jax.random.PRNGKey(1), u)["params"]
    p["expert_bias"] = jax.random.uniform(jax.random.PRNGKey(2), (16,),
                                          minval=-0.1, maxval=0.1)

    def parts(cfg, params):
        out, sown = GroupedMoE(cfg).apply({"params": params}, u,
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
        cut = slice(4 * share, 4 * share + 4)
        mine = dict(p, w_gate=p["w_gate"][cut], w_up=p["w_up"][cut],
                    w_down=p["w_down"][cut])
        _, part, again, n = parts(cfg, mine)
        np.testing.assert_allclose(again, shared, atol=1e-6)
        summed, landed_sum = summed + part, landed_sum + n
    assert landed_sum == landed  # every choice lands on exactly one share
    np.testing.assert_allclose(summed + shared, uncut, atol=2e-5)
    want_routed, want_shared, _, _ = ref._moe(
        p, u, offset=0, top_k=whole_cfg.num_experts_per_tok,
        n_group=whole_cfg.n_group, topk_group=whole_cfg.topk_group,
        norm_topk=True, scaling=whole_cfg.routed_scaling_factor)
    np.testing.assert_allclose(summed + shared, want_routed + want_shared,
                               atol=5e-5)


# the model against the reference -------------------------------------------
def test_forward_matches_the_reference_part_by_part(lm, ref):
    import jax.numpy as jnp

    from benchmark.drivers import serve_linear_moe

    model, params = lm
    ids = _ids(model.config.vocab_size, (2, 37), 1)  # three chunks of 16
    logits = model.apply({"params": params}, ids)
    want, parts, chosen, _ = ref.forward_with_parts(
        params, ids, published(model.config))
    np.testing.assert_allclose(logits, want, atol=3e-4)
    have, have_chosen = serve_linear_moe.program_parts(model, params, ids)
    assert {k: v.shape[0] for k, v in have.items()} == {
        "kda": 6, "mla": 1, "dense": 1, "routed": 6, "shared": 6}
    for name in serve_linear_moe.PARTS:
        np.testing.assert_allclose(have[name], parts[name], atol=3e-4)
    assert bool(jnp.all(jnp.sort(have_chosen, -1) == jnp.sort(chosen, -1)))
    assert int(chosen.max()) > 11 and int(chosen.min()) < 4  # all 16 routed
    per = model.config.num_experts // model.config.n_group
    groups = np.asarray(chosen) // per
    assert max(len(set(row)) for row in groups.reshape(-1, 4)) <= 2


def test_the_layers_are_what_the_rule_says(lm):
    model, _ = lm
    c = model.config
    assert [c.is_latent(i) for i in range(7)] == [
        False, False, False, False, False, True, False]
    assert [c.is_dense(i) for i in range(7)] == [True] + [False] * 6
    assert (model.kv_layers, model.state_layers, model.expert_layers) \
        == (1, 6, 6)
    assert (c.num_kv_heads, c.head_dim) == (1, 40)
    from ray_tpu.models.ling_linear import LingLinearConfig

    full = LingLinearConfig()
    assert (full.num_kv_heads, full.head_dim) == (1, 576)
    assert sum(full.is_latent(i) for i in range(42)) == 7


def test_the_config_refuses_what_it_cannot_build():
    from ray_tpu.models.ling_linear import LingLinearConfig

    with pytest.raises(ValueError, match="not among the layer's"):
        LingLinearConfig.tiny(experts_held=8, expert_offset=12)
    with pytest.raises(ValueError, match="n_group"):
        LingLinearConfig.tiny(n_group=3)
    with pytest.raises(ValueError, match="n_group"):
        LingLinearConfig.tiny(topk_group=5)


def test_given_choices_are_used_and_their_slack_is_told(lm, ref):
    """The reference given choices that are not its own computes the layer
    with them, and says how far they lie from ones it could have made: 0
    for its own, small and positive for the last place swapped inside a
    kept group, large for an expert of a group it did not keep."""
    import jax.numpy as jnp

    model, params = lm
    cfg = published(model.config)
    ids = _ids(model.config.vocab_size, (1, 9), 3)
    logits, parts, own, none = ref.forward_with_parts(params, ids, cfg)
    same = ref.forward_with_parts(params, ids, cfg, given=own)
    assert none == 0.0 and same[3] == 0.0
    np.testing.assert_array_equal(same[0], logits)
    k = own.shape[-1]
    groups = own // 4
    # an expert of the last choice's own group that was not taken
    peers = groups[..., k - 1:] * 4 + jnp.arange(4)
    free = jnp.all(peers[..., :, None] != own[..., None, :], axis=-1)
    swapped = own.at[..., k - 1].set(jnp.take_along_axis(
        peers, jnp.argmax(free, -1)[..., None], -1)[..., 0])
    moved = ref.forward_with_parts(params, ids, cfg, given=swapped)
    assert 0.0 < moved[3] < 1.0
    assert bool(jnp.all(moved[2][0] == own[0]))  # its own choice, as told
    assert float(jnp.max(jnp.abs(moved[0] - logits))) > 1e-4
    np.testing.assert_array_equal(moved[1]["kda"][0], parts["kda"][0])
    # an expert of a group the router did not keep
    unkept = jnp.argmax(jnp.all(
        groups[..., None] != jnp.arange(4), axis=-2), axis=-1)
    astray = own.at[..., k - 1].set(unkept * 4)
    assert ref.forward_with_parts(params, ids, cfg, given=astray)[3] > 0.0


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


@pytest.mark.parametrize("prompt_tokens", [3, 13, 19, 37])
def test_prefill_then_cached_decode_equals_the_full_forward(lm, ref,
                                                            prompt_tokens):
    """Logits, not tokens: the engine's log-probability of each token it
    chose against the reference's full forward over prompt + answer.
    Prompts that are no multiple of the chunk (16) nor of a bucket: the
    padding advances no state, writes no latent row and chooses no
    expert."""
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


def test_a_reused_slot_starts_from_a_reset_state(lm, ref):
    """More requests than slots, one after the other through the same two
    slots: each answer is the reference's for its own prompt alone, so
    admission's prefill overwrote what the slot's last sequence left (its
    state, its convolution rows, its latent rows)."""
    model, params = lm
    eng = _engine(model, params, chunk_tokens=1, max_slots=2)
    try:
        prompts = [_prompt(model.config.vocab_size, n, 50 + n)
                   for n in (17, 5, 9, 21, 6)]
        rids = [eng.submit(p, 6) for p in prompts]
        _drive(eng, rids, turns=800)
        got = [eng.rollout(r, timeout=5) for r in rids]
        state = eng._state
    finally:
        eng.close()
    assert len(state) == model.state_layers
    for prompt, answer in zip(prompts, got):
        err, same = _against_reference(ref, model, params, prompt, answer)
        assert same and err < 1e-4


def test_a_rollout_carries_the_experts_its_rows_chose(lm, ref):
    import jax
    import jax.numpy as jnp

    from benchmark.drivers import serve_linear_moe

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
    _, own = serve_linear_moe.program_parts(model, params, fed)
    given = jnp.moveaxis(jnp.asarray(got["experts"]), 0, 1)[:, None]
    assert bool(jnp.all(jnp.sort(given, -1) == jnp.sort(own, -1)))
    logits, _, _, slack = ref.forward_with_parts(
        params, fed, published(c), first_row=len(prompt) - 1, given=given)
    assert slack == 0.0
    logp = jnp.take_along_axis(jax.nn.log_softmax(logits[0], -1),
                               jnp.asarray(got["tokens"])[:, None], -1)
    np.testing.assert_allclose(logp[:, 0], got["logprobs"], atol=1e-4)


def test_the_stores_are_sized_by_the_model_s_own_counts(lm):
    """The pool has one layer (the one MLA layer) of ONE KV head as wide as
    a latent row; the state list has a set a KDA layer; a cached token
    costs its latent row twice, as stored."""
    from ray_tpu.ops.paged_attention import pool_width

    model, params = lm
    c = model.config
    eng = _engine(model, params)
    try:
        assert eng._k_pages.shape[0] == eng._v_pages.shape[0] == 1
        assert eng._k_pages.shape[-1] == pool_width(1, 40) == 128
        assert (eng.kv_heads, eng.head_dim) == (1, 40)
        assert len(eng._state) == 6
        assert eng._state[0]["S"].shape == (eng.max_slots, 4, 16, 16)
        assert eng._state[0]["conv"].shape == (eng.max_slots, 3, 3 * 64)
        assert eng._moe_experts == 6 * c.experts_held
        assert eng._moe_choices == 6 * c.num_experts_per_tok
        st = eng.stats()
        per_slot = 4 * 4 * 16 * 16 + 4 * 3 * 3 * 64
        assert st["state_pool_bytes"] == eng.max_slots * 6 * per_slot
        assert st["kv_bytes_per_token"] == 2 * 128 * 4
    finally:
        eng.close()


def test_kv_bytes_per_token_of_a_model_that_caches_heads():
    from ray_tpu.serve.llm_engine import build_model

    model, params = build_model("gpt2", None)
    c = model.config
    eng = _engine(model, params)
    try:
        width = -(-c.hidden_size // 128) * 128
        assert eng.stats()["kv_bytes_per_token"] == (
            2 * c.num_layers * width * eng._k_pages.dtype.itemsize)
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


def test_spans_and_stats_count_rows_slots_and_held_experts(lm):
    """Two requests decoding side by side: ``engine.decode.dispatch`` says
    the latent rows read and the slots whose state moves,
    ``engine.decode.fetch`` the experts held, hit and streamed and the
    choices that landed here, ``engine.prefill`` the rows scanned and
    padded; ``stats()`` holds the sums."""
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
    sent = [s["args"] for s in spans if s["name"] == "engine.decode.dispatch"]
    fills = [s["args"] for s in spans if s["name"] == "engine.prefill"]
    assert sorted((a["scanned_rows"], a["padded_rows"]) for a in fills) \
        == [(6, 2), (11, 5)]
    assert steps and len(steps) == len(sent)
    assert max(a["state_slots"] for a in sent) == 2
    assert max(a["kv_tokens"] for a in sent) >= 11 + 6
    for args, rows in zip(steps, (a["state_slots"] for a in sent)):
        assert args["experts_held"] == 6 * c.experts_held
        assert args["choices"] == rows * 6 * c.num_experts_per_tok
        assert 0 <= args["local_choices"] <= args["choices"]
        assert args["experts_hit"] == args["experts_streamed"] \
            <= min(args["experts_held"], args["local_choices"])
    assert st["moe_experts_held"] == 6 * c.experts_held
    for key in ("experts_hit", "experts_streamed", "local_choices",
                "choices"):
        assert st["moe_" + key] == sum(a[key] for a in steps)
    assert st["state_slots_moved"] == sum(a["state_slots"] for a in sent)


def test_no_other_kind_imports_the_new_model():
    """The file is imported where its kind is built and nowhere else: no
    other kind's set-up pays for it."""
    import subprocess
    import sys

    code = ("import sys; from ray_tpu.serve.llm_engine import build_model; "
            "import ray_tpu.models; build_model('gpt2', None); "
            "assert 'ray_tpu.models.ling_linear' not in sys.modules; "
            "assert 'ray_tpu.ops.kda' not in sys.modules")
    subprocess.run([sys.executable, "-c", code], check=True, timeout=300)
