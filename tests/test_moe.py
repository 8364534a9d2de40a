"""MoE / expert parallelism (SURVEY §2.4 EP — net-new TPU scope, no
reference equivalent): routing math, all_to_all dispatch equivalence on an
8-device CPU mesh, the MoE-GPT2 model end to end, and the dropless op's
``moe_hit`` kernel (interpreted here) against its two other forms."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops.moe import (
    MoEConfig,
    dispatch_combine_masks,
    init_moe_params,
    make_expert_parallel_moe,
    moe_apply,
    router_probs,
)
from ray_tpu.parallel.mesh import MeshSpec, make_mesh


def test_dispatch_masks_respect_capacity_and_gates():
    cfg = MoEConfig(num_experts=4, top_k=2, capacity_factor=1.0)
    probs = jax.nn.softmax(
        jax.random.normal(jax.random.PRNGKey(0), (16, 4)), -1)
    cap = cfg.capacity(16)  # ceil(2*16/4) = 8
    dispatch, combine = dispatch_combine_masks(probs, cfg, cap)
    # Each token occupies at most top_k slots, one per chosen expert.
    per_token = dispatch.sum(axis=(1, 2))
    assert (per_token <= cfg.top_k + 1e-6).all()
    # No expert exceeds capacity.
    per_slot = dispatch.sum(axis=0)  # [E, C]
    assert (per_slot <= 1 + 1e-6).all()
    # Combine weights for a token sum to ~1 when nothing dropped.
    sums = np.asarray(combine.sum(axis=(1, 2)))
    assert ((sums < 1 + 1e-5) & (sums >= 0)).all()


def test_moe_dense_k_equals_E_matches_full_mixture():
    """top_k == num_experts with ample capacity → output is exactly the
    softmax-weighted mixture of every expert MLP (nothing drops)."""
    d, f = 16, 32
    cfg = MoEConfig(num_experts=4, top_k=4, capacity_factor=4.0,
                    dtype=jnp.float32)
    params = init_moe_params(jax.random.PRNGKey(0), d, f, cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (8, d), jnp.float32)
    got = moe_apply(x, params["w_router"], params["w_in"], params["w_out"],
                    cfg)
    probs = router_probs(x, params["w_router"])
    ref = jnp.zeros_like(x)
    for e in range(cfg.num_experts):
        h = jax.nn.gelu(x @ params["w_in"][e])
        ref = ref + probs[:, e][:, None] * (h @ params["w_out"][e])
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


def test_expert_parallel_matches_dense_per_shard():
    """shard_map all_to_all path == dense moe_apply run per token shard."""
    if jax.device_count() < 4:
        pytest.skip("needs >=4 devices")
    mesh = make_mesh(MeshSpec({"expert": 4}))
    d, f = 16, 32
    n_per_shard = 8
    cfg = MoEConfig(num_experts=8, top_k=2, capacity_factor=2.0,
                    dtype=jnp.float32)
    params = init_moe_params(jax.random.PRNGKey(0), d, f, cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (4 * n_per_shard, d),
                          jnp.float32)
    ep_fn = make_expert_parallel_moe(mesh, cfg, n_per_shard)
    with mesh:
        got = ep_fn(x, params["w_router"], params["w_in"], params["w_out"])
    cap = cfg.capacity(n_per_shard)
    ref = jnp.concatenate([
        moe_apply(x[i * n_per_shard:(i + 1) * n_per_shard],
                  params["w_router"], params["w_in"], params["w_out"],
                  cfg, capacity=cap)
        for i in range(4)])
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


def test_moe_gpt2_trains():
    """MoE-GPT2 end to end: loss decreases under adam."""
    import optax

    from ray_tpu.models.gpt2 import GPT2, GPT2Config, gpt2_loss_fn

    cfg = GPT2Config.moe_tiny(num_experts=4, top_k=2, dtype=jnp.float32)
    model = GPT2(cfg)
    key = jax.random.PRNGKey(0)
    ids = jax.random.randint(key, (4, 32), 0, cfg.vocab_size)
    params = model.init(key, ids)["params"]
    assert any("moe_w_in" in str(p)
               for p, _ in jax.tree_util.tree_flatten_with_path(params)[0])
    tx = optax.adam(1e-3)
    opt = tx.init(params)

    @jax.jit
    def step(params, opt, ids):
        loss, grads = jax.value_and_grad(gpt2_loss_fn)(
            params, model.apply, {"input_ids": ids})
        updates, opt = tx.update(grads, opt, params)
        return optax.apply_updates(params, updates), opt, loss

    losses = []
    for _ in range(12):
        params, opt, loss = step(params, opt, ids)
        losses.append(float(loss))
    assert losses[-1] < losses[0] - 0.2, losses


def test_moe_gpt2_shards_over_expert_axis():
    """Params place on a data x expert mesh; one pjit step runs."""
    import optax

    from ray_tpu.models.gpt2 import (
        GPT2, GPT2Config, gpt2_loss_fn, param_logical_axes)
    from ray_tpu.parallel.sharding import ShardingRules, shard_params

    if jax.device_count() < 8:
        pytest.skip("needs 8 devices")
    mesh = make_mesh(MeshSpec({"data": 2, "expert": 4}))
    cfg = GPT2Config.moe_tiny(num_experts=4, top_k=2, dtype=jnp.float32)
    model = GPT2(cfg)
    key = jax.random.PRNGKey(0)
    ids = jax.random.randint(key, (4, 32), 0, cfg.vocab_size)
    params = model.init(key, ids)["params"]
    axes = param_logical_axes(params)
    params = shard_params(params, mesh, ShardingRules(), axes)
    # Expert dim really is partitioned over the expert axis.
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    w_in = next(v for p, v in flat if "moe_w_in" in str(p))
    assert "expert" in str(w_in.sharding.spec)

    @jax.jit
    def loss_fn(params, ids):
        return gpt2_loss_fn(params, model.apply, {"input_ids": ids})

    with mesh:
        loss = float(jax.device_get(loss_fn(params, ids)))
    assert np.isfinite(loss)


# ---- the dropless op's two forms (ops/moe.py) -----------------------------
def _dropless_inputs(seed, n, d, e, f, k, dtype=jnp.float32):
    from ray_tpu.ops import moe

    rng = np.random.default_rng(seed)
    mk = lambda *shape: jnp.asarray(  # noqa: E731
        rng.standard_normal(shape) / np.sqrt(shape[-2]), dtype)
    x = jnp.asarray(rng.standard_normal((n, d)), dtype)
    weights, experts = moe.route_topk(x, mk(d, e), k)
    return x, weights, experts, mk(e, d, f), mk(e, d, f), mk(e, f, d)


def _masked(x, weights, experts, w_gate, w_up, w_down, active):
    """The reference: every expert on every row, masked by the combine
    weights (what ``experts_dropless`` ran for few rows until PR 42).
    Products in x's dtype, sums in float32."""
    n, e, f32 = x.shape[0], w_gate.shape[0], jnp.float32
    weights = jnp.where(active[:, None], weights, 0.0)
    combine = jnp.zeros((n, e), f32).at[
        jnp.arange(n)[:, None], experts].add(weights)
    g = jnp.einsum("nd,edf->enf", x, w_gate, preferred_element_type=f32)
    u = jnp.einsum("nd,edf->enf", x, w_up, preferred_element_type=f32)
    h = (jax.nn.silu(g) * u * combine.T[:, :, None]).astype(x.dtype)
    return jnp.einsum("enf,efd->nd", h, w_down,
                      preferred_element_type=f32).astype(x.dtype)


def _form(monkeypatch, form, tile=512, itemsize=4, d=32):
    """Make ``experts_dropless`` take one form whatever the row count,
    the kernel in tiles of ``tile`` columns."""
    from ray_tpu.ops import moe

    monkeypatch.setattr(moe, "DENSE_MAX_ROWS",
                        {"hit": 1 << 20, "grouped": 0}[form])
    monkeypatch.setattr(moe, "HIT_TILE_BYTES", tile * d * itemsize)
    return moe.experts_dropless


LIVE = {"none": lambda n: np.zeros(n, bool),
        "one": lambda n: np.arange(n) == n // 2,
        "half": lambda n: np.arange(n) % 2 == 0,
        "all": lambda n: np.ones(n, bool)}


@pytest.mark.parametrize("live", list(LIVE))
@pytest.mark.parametrize("e,f,tile", [
    (8, 256, 128),   # two tiles of the width an expert
    (6, 192, 128),   # 128 does not divide 192: the width whole
    (64, 24, 512),   # the tile wider than the width; experts beyond a row's reach
])
def test_hit_kernel_agrees_with_the_masked_and_grouped_forms(
        monkeypatch, live, e, f, tile):
    """The same rows, choices and weights through the kernel, the grouped
    form and the masked reference, with the same rows live: the kernel
    sums the experts a live row chose and no other, which is what the
    other two compute by masking; a row that is not live gets zeros in all
    three.  float32: the orders of summation differ, nothing else."""
    from ray_tpu.ops import moe

    n, d, k = 12, 32, 2
    args = _dropless_inputs(e + f, n, d, e, f, k)
    active = jnp.asarray(LIVE[live](n))
    got, streamed = _form(monkeypatch, "hit", tile)(*args, active=active)
    assert moe._tile_of(f, tile) == (tile if f % tile == 0 else f)
    chosen = {int(v) for v in np.asarray(args[2])[np.asarray(active)].ravel()}
    assert int(streamed) == len(chosen)
    grouped, read = _form(monkeypatch, "grouped")(*args, active=active)
    assert int(read) == e
    for want in (_masked(*args, active), grouped):
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-5)
    assert not np.asarray(got)[~np.asarray(active)].any()
    if live == "all":  # no mask is all rows live
        whole, _ = _form(monkeypatch, "hit", tile)(*args)
        np.testing.assert_array_equal(whole, got)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_an_expert_no_live_row_chose_is_never_read(monkeypatch, dtype):
    """Every expert that no live row chose, NaN throughout: the kernel's
    answer is bit for bit its answer on clean weights, and the masked
    reference's on clean weights to rounding; the masked reference itself,
    which multiplies every expert, answers NaN."""
    n, d, e, f, k = 16, 32, 16, 256, 2
    x, weights, experts, *clean = _dropless_inputs(3, n, d, e, f, k, dtype)
    active = jnp.arange(n) % 4 == 0
    unhit = np.ones(e, bool)
    unhit[np.asarray(experts)[np.asarray(active)].ravel()] = False
    assert 0 < unhit.sum() < e
    poisoned = [jnp.where(unhit[:, None, None], jnp.nan, w) for w in clean]
    hit = _form(monkeypatch, "hit", 128, jnp.dtype(dtype).itemsize)
    got, streamed = hit(x, weights, experts, *poisoned, active=active)
    on_clean, _ = hit(x, weights, experts, *clean, active=active)
    np.testing.assert_array_equal(got, on_clean)
    assert int(streamed) == e - unhit.sum() and got.dtype == dtype
    tol = 2e-5 if dtype == jnp.float32 else 0.02
    np.testing.assert_allclose(
        np.asarray(got, np.float32),
        np.asarray(_masked(x, weights, experts, *clean, active), np.float32),
        atol=tol, rtol=tol)
    bad = _masked(x, weights, experts, *poisoned, active)
    assert np.isnan(np.asarray(bad, np.float32)).any()


def test_hit_order_lists_the_chosen_experts_first():
    from ray_tpu.ops import moe

    chosen = np.zeros((5, 9), bool)
    chosen[0, [7, 2]] = chosen[3, [2, 4]] = True
    order, n_hit = moe.hit_order(jnp.asarray(chosen))
    assert n_hit.tolist() == [3] and order.tolist() == [2, 4, 7] + [0] * 6
    order, n_hit = moe.hit_order(jnp.zeros((5, 9), bool))
    assert n_hit.tolist() == [0] and order.tolist() == [0] * 9
    order, n_hit = moe.hit_order(jnp.ones((1, 9), bool))
    assert n_hit.tolist() == [9] and order.tolist() == list(range(9))


def test_the_row_count_alone_picks_the_form(monkeypatch):
    """At most DENSE_MAX_ROWS rows follow the list, one more is grouped:
    told apart by what each says it streamed (two choices a row cannot
    reach every expert of so many, and the grouped form is counted as
    reading them all)."""
    from ray_tpu.ops import moe

    assert moe.DENSE_MAX_ROWS == 512
    monkeypatch.setattr(moe, "DENSE_MAX_ROWS", 12)  # a cheaper boundary
    for n, follows in ((12, True), (13, False)):
        args = _dropless_inputs(n, n, 16, 64, 24, 2)
        _, streamed = jax.jit(moe.experts_dropless)(*args)
        chosen = len(set(np.asarray(args[2]).ravel().tolist()))
        assert chosen <= 2 * n < 64
        assert int(streamed) == (chosen if follows else 64), n
