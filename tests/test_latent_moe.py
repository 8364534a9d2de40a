"""The latent-MoE decoder (``models/latent_moe.py``: latent attention in
every layer under a YaRN-scaled rope, ONE cached row a token, a leading
dense layer, a sigmoid router's experts with a share held) and the serve
engine behind ``model_kind="latent_moe"`` (ISSUE 56), on the CPU at tiny
widths: hidden 64, 4 heads of 16 + 8 / 16, a latent row of 32 + 8, YaRN of
factor 8 over 32 original positions, 16 experts top-4 of which 4 are held
from the fifth on, 3 layers (one dense).

The yardstick is the benchmark's plain reference
(``benchmark/reference/sarvam_105b.py``: float32, attention expanded a head
at a time, every held expert for every token masked by the router's choice,
given the same share).
"""
import dataclasses
import math

import numpy as np
import pytest

from test_decode_lookahead import _drive, _engine, _prompt

PUBLISHED_YARN = dict(beta_fast=32, beta_slow=1, factor=40, mscale=1,
                      mscale_all_dim=1, original_max_position_embeddings=4096,
                      type="deepseek_yarn")


def published(c) -> dict:
    """The reference's configuration (the file's key names) of a program
    config."""
    keys = ("rms_norm_eps", "num_hidden_layers", "first_k_dense_replace",
            "num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "kv_lora_rank", "rope_theta",
            "num_experts_per_tok", "routed_scaling_factor", "expert_offset")
    scaling = c.rope_scaling
    return {**{k: getattr(c, k) for k in keys},
            "rope_scaling": None if scaling is None else {
                **dataclasses.asdict(scaling), "type": "deepseek_yarn"}}


@pytest.fixture(scope="module")
def ref():
    from benchmark.reference import sarvam_105b

    return sarvam_105b


@pytest.fixture(scope="module")
def lm():
    """The tiny decoder, its one-dimensional leaves (norm scales, the
    routers' bias) moved off their trivial initial values."""
    import jax

    from ray_tpu.serve.llm_engine import build_model

    model, params = build_model("latent_moe", {"dtype": "float32"})
    c = model.config
    assert (c.experts_held, c.expert_offset, c.num_experts) == (4, 4, 16)
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(5), len(leaves))
    leaves = [x + 0.1 * jax.random.normal(k, x.shape) if x.ndim == 1 else x
              for x, k in zip(leaves, keys)]
    return model, jax.tree_util.tree_unflatten(tree, leaves)


# ---------------------------------------------------------------- (b) YaRN
def test_yarn_at_the_published_numbers():
    """``low, high = 10, 23`` and ``mscale`` 1.3689 for factor 40 over 4,096
    original positions, base 10000, 64 rope columns; channels under ``low``
    keep their frequency, from ``high`` on it is divided by the factor, and
    between them it falls from the one to the other."""
    from ray_tpu.ops.rope import (YarnScaling, cos_sin_mscale, inv_freq,
                                  softmax_mscale, yarn_correction_range,
                                  yarn_mscale)

    s = YarnScaling.from_config(PUBLISHED_YARN)
    assert yarn_correction_range(s, 64, 10000.0) == (10, 23)
    assert yarn_mscale(40, 1) == pytest.approx(0.1 * math.log(40) + 1)
    assert yarn_mscale(40, 1) == pytest.approx(1.3689, abs=1e-4)
    assert softmax_mscale(s) == pytest.approx(1.3689 ** 2, rel=1e-4)
    assert cos_sin_mscale(s) == 1.0
    plain = np.asarray(inv_freq(64, 10000.0))
    np.testing.assert_allclose(
        plain, 10000.0 ** (-np.arange(0, 64, 2) / 64), rtol=1e-6)
    scaled = np.asarray(inv_freq(64, 10000.0, s))
    np.testing.assert_array_equal(scaled[:11], plain[:11])
    np.testing.assert_allclose(scaled[23:], plain[23:] / 40, rtol=1e-6)
    ratio = scaled[10:24] / plain[10:24]
    assert np.all(np.diff(ratio) < 0) and ratio[0] == 1.0


def test_yarn_of_factor_one_is_plain_rope(ref):
    """Factor 1 changes no frequency and no scale, in the program's rope
    and in the reference's."""
    from ray_tpu.ops.rope import (YarnScaling, inv_freq, softmax_mscale)

    one = YarnScaling(factor=1.0, original_max_position_embeddings=4096,
                      mscale=1.0, mscale_all_dim=1.0)
    np.testing.assert_allclose(inv_freq(64, 10000.0, one),
                               inv_freq(64, 10000.0), rtol=1e-7)
    assert softmax_mscale(one) == 1.0 and softmax_mscale(None) == 1.0
    block = {**PUBLISHED_YARN, "factor": 1}
    np.testing.assert_allclose(ref.yarn_inv_freq(64, 10000.0, block),
                               ref.yarn_inv_freq(64, 10000.0, None),
                               rtol=1e-7)
    assert ref.yarn_m(1, 1) == 1.0


def test_the_reference_s_yarn_is_the_program_s(ref):
    """Two writings of the same published functions agree, at the
    published numbers and at tiny's."""
    from ray_tpu.models.latent_moe import LatentMoEConfig
    from ray_tpu.ops.rope import (YarnScaling, inv_freq,
                                  yarn_correction_range)

    s = YarnScaling.from_config(PUBLISHED_YARN)
    np.testing.assert_allclose(ref.yarn_inv_freq(64, 10000.0, PUBLISHED_YARN),
                               inv_freq(64, 10000.0, s), rtol=1e-6)
    assert ref.yarn_range(64, 10000.0, 4096, 32, 1) == (10, 23)
    tiny = LatentMoEConfig.tiny().rope_scaling
    low, high = yarn_correction_range(tiny, 8, 10000.0)
    assert 0 <= low < high <= 7  # tiny's YaRN blends channels too
    with pytest.raises(ValueError, match="deepseek_yarn"):
        YarnScaling.from_config({"type": "linear", "factor": 2.0,
                                 "original_max_position_embeddings": 8})


def test_the_softmax_scale_carries_mscale_squared(lm, ref):
    """A model built without ``mscale_all_dim`` differs from the one with
    it exactly by the softmax scale: its attention equals the reference's
    only with the matching block."""
    import jax.numpy as jnp

    from ray_tpu.models.latent_moe import LatentMoE

    model, params = lm
    ids = jnp.asarray([_prompt(model.config.vocab_size, 40, 3)], jnp.int32)
    scaled = model.apply({"params": params}, ids)
    flat = dataclasses.replace(model.config, rope_scaling=dataclasses.replace(
        model.config.rope_scaling, mscale_all_dim=0.0, mscale=0.0))
    unscaled = LatentMoE(flat).apply({"params": params}, ids)
    assert float(jnp.max(jnp.abs(scaled - unscaled))) > 1e-3
    np.testing.assert_allclose(
        scaled, ref.forward(params, ids, published(model.config)),
        atol=2e-4)
    np.testing.assert_allclose(
        unscaled, ref.forward(params, ids, published(flat)), atol=2e-4)


# ------------------------------------------------- the forward, part by part
@pytest.mark.parametrize("length", [9, 33, 64, 100])
def test_forward_matches_the_reference_part_by_part(lm, ref, length):
    """Past 32 positions YaRN's interpolated channels are what is compared;
    at 100 rows the attention runs 2 heads at a time (``head_block``) and
    the feed-forwards in blocks of 16 rows."""
    import jax.numpy as jnp

    from ray_tpu.models import ling_linear

    model, params = lm
    ids = jnp.asarray([_prompt(model.config.vocab_size, length, length)],
                      jnp.int32)
    old = ling_linear.ROWS_ALL_HEADS
    ling_linear.ROWS_ALL_HEADS = 64  # tiny's stand-in for 2,048 rows
    try:
        logits, sown = model.apply({"params": params}, ids,
                                   mutable=["branches", "moe"])
    finally:
        ling_linear.ROWS_ALL_HEADS = old
    chosen = jnp.stack([sown["moe"][f"layer_{i}"]["moe"]["expert_idx"][0]
                        for i in (1, 2)])
    want, parts, own, slack = ref.forward_with_parts(
        params, ids, published(model.config), given=chosen)
    np.testing.assert_allclose(logits, want, atol=3e-4)
    assert slack < 1e-5 and ref.choice_overlap(chosen, own) > 0.99
    b = sown["branches"]
    for i in range(3):
        np.testing.assert_allclose(b[f"layer_{i}"]["attn_out"][0],
                                   parts["attn"][i], atol=1e-4)
    np.testing.assert_allclose(b["layer_0"]["dense_out"][0],
                               parts["dense"][0], atol=1e-4)
    for j, i in enumerate((1, 2)):
        np.testing.assert_allclose(b[f"layer_{i}"]["moe"]["routed_out"][0],
                                   parts["routed"][j], atol=1e-4)
        np.testing.assert_allclose(b[f"layer_{i}"]["moe"]["shared_out"][0],
                                   parts["shared"][j], atol=1e-4)


def test_the_reference_in_blocks_is_the_reference(lm, ref, monkeypatch):
    """Query blocks of 16 and feed-forward blocks of 8 rows and 32 hidden
    columns give what the whole arrays give, with parts and without."""
    import jax
    import jax.numpy as jnp

    model, params = lm
    ids = jnp.asarray([_prompt(model.config.vocab_size, 45, 1)], jnp.int32)
    whole = ref.forward(params, ids, published(model.config))
    monkeypatch.setattr(ref, "QUERY_BLOCK", 16)
    monkeypatch.setattr(ref, "ROW_BLOCK", 8)
    monkeypatch.setattr(ref, "HIDDEN_BLOCK", 32)  # the dense layer's 96
    jax.clear_caches()
    try:
        blocked = ref.forward(params, ids, published(model.config))
        with_parts = ref.forward_with_parts(params, ids,
                                            published(model.config))[0]
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    np.testing.assert_allclose(blocked, whole, atol=2e-5)
    np.testing.assert_allclose(with_parts, whole, atol=2e-5)


# -------------------------------------------------- (d) the shares add up
def test_the_four_shares_and_the_shared_expert_once_are_the_uncut_layer(ref):
    """16 experts in 4 shares of 4 (tiny's stand-in for 128 in 8 shares of
    16): the routed parts of the shares, plus the shared expert counted
    once, equal the layer that holds all 16; and that is the reference's
    uncut layer."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.glm_dsa import SigmoidMoE
    from ray_tpu.models.latent_moe import LatentMoEConfig

    whole_cfg = LatentMoEConfig.tiny(experts_held=16, expert_offset=0,
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
    summed, landed_sum = 0.0, 0
    for share in range(4):
        cfg = dataclasses.replace(whole_cfg, experts_held=4,
                                  expert_offset=4 * share)
        cut = slice(4 * share, 4 * share + 4)
        mine = dict(p, w_gate=p["w_gate"][cut], w_up=p["w_up"][cut],
                    w_down=p["w_down"][cut])
        _, part, again, n = parts(cfg, mine)
        np.testing.assert_allclose(again, shared, atol=1e-6)
        # the reference given the same share computes the same part
        want, _, _, _ = ref._moe(
            mine, u, offset=4 * share, top_k=cfg.num_experts_per_tok,
            norm_topk=True, scaling=cfg.routed_scaling_factor)
        np.testing.assert_allclose(part, want, atol=5e-5)
        summed, landed_sum = summed + part, landed_sum + n
    assert landed_sum == landed  # every choice lands on exactly one share
    np.testing.assert_allclose(summed + shared, uncut, atol=2e-5)
    want_routed, want_shared, _, _ = ref._moe(
        p, u, offset=0, top_k=whole_cfg.num_experts_per_tok, norm_topk=True,
        scaling=whole_cfg.routed_scaling_factor)
    np.testing.assert_allclose(summed + shared, want_routed + want_shared,
                               atol=5e-5)


# ------------------------------------------- (c) the one-row latent kernel
def _latent_case(seed=0, slots=3, heads=4, rank=32, rope=8, ps=8, pages=12,
                 layers=2):
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.paged_attention import pool_width

    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    width = pool_width(1, rank + rope)
    pool = jax.random.normal(k[0], (layers, pages, ps, width))
    q = jax.random.normal(k[1], (slots, 1, heads, rank + rope))
    row = jax.random.normal(k[2], (slots, 1, 1, rank + rope))
    table = jnp.asarray(np.random.default_rng(seed).permutation(
        pages - 1)[:slots * 3].reshape(slots, 3) + 1, jnp.int32)
    lengths = jnp.asarray([19, 0, 8], jnp.int32)[:slots]
    return pool, q, row, table, lengths


def test_the_latent_kernel_is_the_two_row_path_on_the_same_rows():
    """One DMA a page, V the first ``rank`` columns of the K block: the
    latent form returns what ``paged_attention`` returns (its first
    ``rank`` columns) when the V pool holds ``[c | 0]`` copies of the same
    rows; a free lane (length 0) attends to its own row alone."""
    import jax.numpy as jnp

    from ray_tpu.ops.paged_attention import (latent_paged_attention,
                                             paged_attention)

    rank = 32
    pool, q, row, table, lengths = _latent_case()
    v_pool = pool.at[..., rank:].set(0.0)
    v_row = row.at[..., rank:].set(0.0)
    for layer in (0, 1):
        two = paged_attention(q, row, v_row, pool, v_pool, layer, table,
                              lengths, sm_scale=0.3)[..., :rank]
        one = latent_paged_attention(q, row, None, pool, None, layer, table,
                                     lengths, sm_scale=0.3, rank=rank)
        assert one.shape == (3, 1, 4, rank)
        np.testing.assert_allclose(one, two, atol=1e-5)
    # a window of new tokens (a verify step's shape) is causal among them
    q3 = jnp.concatenate([q, q * 0.5, q * 2], axis=1)
    row3 = jnp.concatenate([row, row * 2, row * 0.5], axis=1)
    one = latent_paged_attention(q3, row3, None, pool, None, 0, table,
                                 lengths, sm_scale=0.3, rank=rank)
    two = paged_attention(q3, row3, row3.at[..., rank:].set(0.0), pool,
                          v_pool, 0, table, lengths,
                          sm_scale=0.3)[..., :rank]
    np.testing.assert_allclose(one, two, atol=1e-5)
    np.testing.assert_allclose(one[:, :1], latent_paged_attention(
        q, row, None, pool, None, 0, table, lengths, sm_scale=0.3,
        rank=rank), atol=1e-5)


def test_the_latent_kernel_through_mla_absorbed_is_mla_expanded():
    """A context's last row through ``mla_absorbed`` over a pool that holds
    the earlier rows (one row each, ``one_row``), against ``mla_expanded``
    over the whole context; more live pages than one step of the kernel's
    loop copies."""
    import functools

    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import paged_attention as pa
    from ray_tpu.ops.mla import latent_rows, mla_absorbed, mla_expanded

    h, nope, rope, rank, vd, ps = 4, 16, 8, 32, 16, 8
    n = pa.LATENT_PAGES_PER_STEP * ps + 21  # two turns of the loop
    k = jax.random.split(jax.random.PRNGKey(7), 5)
    q_nope = jax.random.normal(k[0], (1, n, h, nope))
    q_rope = jax.random.normal(k[1], (1, n, h, rope))
    c = jax.random.normal(k[2], (1, n, rank))
    k_rope = jax.random.normal(k[3], (1, n, rope))
    w_kvb = jax.random.normal(k[4], (rank, h, nope + vd)) * 0.2
    want = mla_expanded(q_nope, q_rope, c, k_rope, w_kvb, nope, 0.2)[:, -1:]

    pages = -(-n // ps)
    width = pa.pool_width(1, rank + rope)
    rows = latent_rows(c, k_rope)[0][0, :, 0]                   # [n, R + P]
    pool = jnp.zeros((1, pages + 1, ps, width)).at[0, 1:].set(jnp.pad(
        rows, ((0, pages * ps - n), (0, width - rank - rope))).reshape(
        pages, ps, width))
    table = jnp.arange(1, pages + 1, dtype=jnp.int32)[None]
    attend = functools.partial(
        pa.latent_paged_attention, k_pool=pool, v_pool=None, layer=0,
        table=table, lengths=jnp.asarray([n - 1], jnp.int32), rank=rank)
    last = lambda a: a[:, -1:]  # noqa: E731
    got, (row, none) = mla_absorbed(
        attend, last(q_nope), last(q_rope), last(c), last(k_rope), w_kvb,
        nope, 0.2, one_row=True)
    assert none is None and row.shape == (1, 1, 1, rank + rope)
    np.testing.assert_allclose(got, want, atol=2e-5)


# ----------------------------------- (a) prefill, then decode, on the logits
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


@pytest.mark.parametrize("prompt_tokens", [3, 13, 29, 40, 50])
def test_prefill_then_cached_decode_equals_the_full_forward(lm, ref,
                                                            prompt_tokens):
    """Logits, not tokens: the engine's log-probability of each token it
    chose against the reference's full forward over prompt + answer, left
    to its own experts.  Nine decode steps: every prompt crosses a page
    boundary (pages of 8 rows) in the prefill or in the steps, and from 29
    tokens on the steps run at positions past YaRN's 32 original ones.
    Tolerance 1e-4 on a log-probability: both sides are float32 here, and
    what is left is the order of sums (absorbed against expanded, the flash
    recurrence over pages against one softmax)."""
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
    """More requests than slots through the same two slots: each answer is
    the reference's for its own prompt alone (a reused slot's pages hold
    another sequence's rows until they are overwritten; a free lane's
    garbage reaches no softmax)."""
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


def test_plain_rope_in_place_of_yarn_is_another_model(lm, ref):
    """The planted fault of the chip's comparison, at tiny widths: the
    reference with no ``rope_scaling`` does not give the engine's
    log-probabilities once positions pass the original 32."""
    model, params = lm
    eng = _engine(model, params, chunk_tokens=1)
    try:
        prompt = _prompt(model.config.vocab_size, 45, 8)
        rid = eng.submit(prompt, 6)
        _drive(eng, [rid])
        got = eng.rollout(rid, timeout=5)
    finally:
        eng.close()
    assert _against_reference(ref, model, params, prompt, got)[0] < 1e-4
    plain = dataclasses.replace(model.config, rope_scaling=None)

    class Plain:
        config = plain

    assert _against_reference(ref, Plain, params, prompt, got)[0] > 1e-2


# ------------------------------------------------------------ (e) one pool
def test_the_cache_is_one_pool_of_latent_rows(lm):
    """One pool: a layer a model layer of ONE KV head as wide as a latent
    row, and a V pool with no page; a cached token costs one row a layer as
    stored; the decode step hands the V pool through."""
    from ray_tpu.ops.paged_attention import pool_width

    model, params = lm
    c = model.config
    eng = _engine(model, params, chunk_tokens=1)
    try:
        assert eng._latent and not eng._sparse and eng._ragged
        assert eng._k_pages.shape == (3, 4 * 8 + 1, 8, pool_width(1, 40))
        assert eng._v_pages.shape == (3, 0, 8, 128)
        assert eng._v_pages.nbytes == 0
        assert (eng.kv_heads, eng.head_dim) == (1, 40)
        assert eng._state is None
        assert eng._moe_experts == 2 * c.experts_held
        assert eng.stats()["kv_bytes_per_token"] == 3 * 128 * 4
        rid = eng.submit(_prompt(c.vocab_size, 11, 2), 4)
        _drive(eng, [rid])
        assert eng._v_pages.shape[1] == 0
        assert np.abs(np.asarray(eng._k_pages[..., :40])).sum() > 0
        assert not np.asarray(eng._k_pages[..., 40:]).any()  # the padding
    finally:
        eng.close()


def test_at_the_published_widths_a_token_costs_eight_rows_of_1280_bytes():
    """8 layers x ``pool_width(1, 576)`` = 640 columns x 2 bytes, ONE pool:
    the arithmetic ``stats()["kv_bytes_per_token"]`` does, on shapes
    alone."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.latent_moe import LatentMoEConfig
    from ray_tpu.ops.paged_attention import pool_width

    c = LatentMoEConfig(num_hidden_layers=8, experts_held=16)
    assert (c.num_kv_heads, c.head_dim, c.qk_head_dim) == (1, 576, 192)
    k = jax.ShapeDtypeStruct((8, 32 * 1024 + 1, 16, pool_width(1, 576)),
                             jnp.bfloat16)
    nbytes = math.prod(k.shape) * 2
    assert nbytes // (k.shape[1] * 16) == 8 * 1280
    assert nbytes == pytest.approx(5.37e9, rel=1e-3)


@pytest.mark.parametrize("kind,pools", [("ling_linear", 2), ("glm_dsa", 2),
                                        ("llama", 2)])
def test_the_other_kinds_keep_their_two_pools(kind, pools):
    """``ling_linear`` and ``glm_dsa`` store a latent row in the K pool and
    a second row in the V pool as before (GLM-5's carries the index key),
    and a model that caches heads its K and V."""
    from ray_tpu.serve.llm_engine import LLMEngine, build_model

    model, params = build_model(kind, {})
    eng = LLMEngine(model, params, start=False, max_slots=2, page_size=8,
                    max_ctx=64)
    try:
        assert not eng._latent
        assert eng._v_pages.shape == eng._k_pages.shape
        per_token = eng._k_pages.shape[0] * eng._k_pages.shape[-1] \
            * eng._k_pages.dtype.itemsize
        assert eng.stats()["kv_bytes_per_token"] == pools * per_token
    finally:
        eng.close()


@pytest.mark.parametrize("option", ["prefix_cache", "draft_model", "prefill",
                                    "prefix_directory"])
def test_options_that_hand_over_k_and_v_pages_are_refused(lm, option):
    from ray_tpu.serve.llm_engine import LLMEngine

    model, params = lm
    kw = {"prefix_cache": dict(prefix_cache=True),
          "draft_model": dict(draft_model=model, draft_params=params),
          "prefill": dict(prefill=object()),
          "prefix_directory": dict(prefix_directory=object())}[option]
    with pytest.raises(ValueError, match="one pool of latent rows"):
        LLMEngine(model, params, start=False, max_slots=2, page_size=8,
                  max_ctx=64, **kw)


# ------------------------------------------------------ spans and counters
def test_spans_say_rows_read_experts_hit_and_real_rows(lm):
    """``engine.decode.dispatch`` says the live rows the latent kernel
    reads, ``engine.decode.fetch`` the held experts hit and the choices
    that landed on them, ``engine.prefill`` the bucket and the real rows."""
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
    assert sorted((a["prompt_tokens"], a["bucket"]) for a in fills) \
        == [(6, 8), (29, 32)]
    assert steps and len(steps) == len(sent)
    for i, (args, out) in enumerate(zip(sent, steps)):
        assert args["kv_tokens"] == 29 + 6 + 2 * i
        assert out["experts_held"] == 2 * c.experts_held
        assert 0 <= out["experts_hit"] <= out["experts_held"]
        assert out["experts_hit"] == out["experts_streamed"]
        assert out["choices"] == 2 * 2 * c.num_experts_per_tok
        assert 0 <= out["local_choices"] <= out["choices"]
    assert st["moe_local_choices"] == sum(a["local_choices"] for a in steps)
    assert 0.0 < st["moe_local_choice_share"] < 1.0


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
    experts = np.asarray(got["experts"])
    assert experts.shape == (21 + 4, 2, c.num_experts_per_tok)
    ids = jnp.asarray([prompt + got["tokens"][:-1]], jnp.int32)
    given = jnp.moveaxis(jnp.asarray(experts), 0, 1)[:, None]
    logits, _, own, slack = ref.forward_with_parts(
        params, ids, published(c), first_row=20, given=given,
        each=ref.NOTHING)
    logp = jax.nn.log_softmax(logits[0], -1)
    err = jnp.abs(jnp.take_along_axis(
        logp, jnp.asarray(got["tokens"])[:, None], -1)[:, 0]
        - jnp.asarray(got["logprobs"]))
    assert float(jnp.max(err)) < 1e-4 and slack < 1e-5
    assert ref.choice_overlap(given, own) > 0.99


def test_the_config_refuses_what_it_cannot_build():
    from ray_tpu.models.latent_moe import LatentMoEConfig

    with pytest.raises(ValueError, match="experts_held"):
        LatentMoEConfig.tiny(experts_held=8, expert_offset=12)
    with pytest.raises(ValueError, match="head_block"):
        LatentMoEConfig.tiny(head_block=3)
    c = LatentMoEConfig(rope_scaling=dict(PUBLISHED_YARN))
    assert c.rope_scaling.factor == 40 and hash(c) is not None


def test_param_count_at_the_published_widths():
    """The issue's arithmetic, from shapes alone (``jax.eval_shape``: no
    weight is made): 94,634,688 in an attention, 295,969,472 in the dense
    layer, 120,333,120 outside the routed experts of an expert layer,
    25,165,824 a routed expert, 4,225,313,152 in the cut."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.latent_moe import LatentMoE, LatentMoEConfig

    c = LatentMoEConfig(num_hidden_layers=8, experts_held=16,
                        vocab_size=32768, rope_scaling=dict(PUBLISHED_YARN),
                        dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    shapes = jax.eval_shape(
        lambda: LatentMoE(c).init(jax.random.PRNGKey(0),
                                  jnp.zeros((1, 8), jnp.int32)))["params"]
    count = lambda t: sum(  # noqa: E731
        math.prod(x.shape) for x in jax.tree_util.tree_leaves(t))
    assert count(shapes["layer_0"]["attn"]) == 94_634_688
    assert count(shapes["layer_0"]) == 295_969_472
    moe = shapes["layer_1"]["moe"]
    routed = count([moe["w_gate"], moe["w_up"], moe["w_down"]])
    assert routed == 16 * 25_165_824 == 402_653_184
    assert count(shapes["layer_1"]) - routed == 120_333_120
    assert count(shapes) == 4_225_313_152
    assert moe["router"].dtype == jnp.float32
    assert moe["router"].shape == (4096, 128)


def test_ling_s_attention_is_what_it_was():
    """The shared ``MLAttention`` under Ling's config: no scaling, two
    rows, all heads at once; its rope's frequencies are the plain ones."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.ling_linear import LingLinearConfig, MLAttention

    c = LingLinearConfig.tiny(dtype=jnp.float32)
    u = jax.random.normal(jax.random.PRNGKey(0), (1, 9, 64))
    pos = jnp.arange(9)[None]
    attn = MLAttention(c)
    p = attn.init(jax.random.PRNGKey(1), u, pos)["params"]
    out, (k_row, v_row) = attn.apply({"params": p}, u, pos, rows=True)
    assert k_row.shape == v_row.shape == (1, 9, 1, 40)
    np.testing.assert_array_equal(v_row[..., :32], k_row[..., :32])
    assert not np.asarray(v_row[..., 32:]).any()
    assert not hasattr(c, "rope_scaling") and not hasattr(c, "latent_cache")
