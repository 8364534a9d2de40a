"""Pallas flash attention, forward + custom-VJP backward, validated
against the XLA reference in interpreter mode (the CPU stand-in for the
TPU kernel; reference analogue for the pattern: the fused-kernel
parity tests any flash implementation carries).

Matmul precision is pinned to float32 for the comparisons: at default
precision the XLA einsums round through bf16 on some backends, which
would drown the kernel's actual error."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import attention
from ray_tpu.ops.attention import (_xla_attention, flash_attention,
                                   flash_attention_qkv, mha_attention)


def _rand_qkv(B, L, H, D, seed=0):
    key = jax.random.PRNGKey(seed)
    return tuple(jax.random.normal(k, (B, L, H, D), jnp.float32)
                 for k in jax.random.split(key, 3))


@pytest.fixture
def ran(monkeypatch):
    """What the forward kernels were given: (first operand's shape, heads a
    column block) a call."""
    calls = []

    def spy(ops, **kw):
        calls.append((ops[0].shape, kw["heads"]))
        return forward(ops, **kw)

    forward = attention._fwd_call
    monkeypatch.setattr(attention, "_fwd_call", spy)
    return calls


# (shape, heads a 128-lane column block): 0 where the operands go head-major
LAYOUTS = [
    ((2, 256, 3, 32), 0),   # three heads of 32 do not fill 128 lanes
    ((1, 384, 2, 64), 2),   # a pair of heads a block, parted by lane masks
                            # (causal: 6 tiles a head; not: 9, head-major)
    ((2, 256, 4, 64), 2),   # two such blocks
    ((1, 256, 3, 64), 0),   # an odd head count: no pair for the last one
    ((1, 256, 2, 128), 1),  # a head a block: no mask
    ((1, 256, 8, 32), 4),   # four heads a block
]


def _check_layout(ran, shape, heads):
    """The form that ran is the one the shape names."""
    b, l, h, d = shape
    assert ran and all(call == ran[0] for call in ran)
    assert ran[0] == (((b, l, h * d), heads) if heads
                      else ((b * h, l, d), 1))


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("shape,heads", LAYOUTS)
def test_flash_forward_matches_xla(causal, shape, heads, ran):
    q, k, v = _rand_qkv(*shape)
    with jax.default_matmul_precision("float32"):
        out_f = flash_attention(q, k, v, causal=causal, interpret=True)
        out_x = _xla_attention(q, k, v, causal, None)
    np.testing.assert_allclose(np.asarray(out_f), np.asarray(out_x),
                               atol=1e-5, rtol=1e-5)
    if shape == (1, 384, 2, 64) and not causal:
        heads = 0  # 18 tile bodies a pair: past what the kernels unroll
    _check_layout(ran, shape, heads)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("shape,heads", [LAYOUTS[0]] + LAYOUTS[2:])
def test_flash_gradients_match_xla(causal, shape, heads, ran):
    q, k, v = _rand_qkv(*shape)

    with jax.default_matmul_precision("float32"):
        def loss_f(q, k, v):
            return jnp.sum(jnp.sin(flash_attention(
                q, k, v, causal=causal, interpret=True)))

        def loss_x(q, k, v):
            return jnp.sum(jnp.sin(_xla_attention(q, k, v, causal, None)))

        gf = jax.grad(loss_f, argnums=(0, 1, 2))(q, k, v)
        gx = jax.grad(loss_x, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gx, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=2e-4, rtol=1e-3,
            err_msg=f"d{name} mismatch (causal={causal})")
    _check_layout(ran, shape, heads)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("blocks", [None, 256])  # 256: one tile, cut in two
@pytest.mark.parametrize("shape,heads", LAYOUTS[2:])
def test_fused_qkv_equals_the_three_operand_call(causal, shape, heads, blocks,
                                                 ran):
    """One ``[B, L, 3 * H * D]`` array read through the index maps, and the
    same columns as three arrays: the same kernels on the same numbers, so
    the same output, and a dqkv that is dq, dk and dv side by side.  Where
    the shape does not allow column blocks the array is split."""
    b, l, h, d = shape
    q, k, v = _rand_qkv(*shape)
    qkv = jnp.concatenate([x.reshape(b, l, h * d) for x in (q, k, v)], -1)
    w = _rand_qkv(*shape, seed=5)[0]
    kw = dict(causal=causal, block_q=blocks, block_k=blocks, interpret=True)

    def fused(qkv):
        return flash_attention_qkv(qkv, h, **kw)

    def three(q, k, v):
        return flash_attention(q, k, v, **kw)

    with jax.default_matmul_precision("float32"):
        out_1, vjp_1 = jax.vjp(fused, qkv)
        out_3, vjp_3 = jax.vjp(three, q, k, v)
        (dqkv,) = vjp_1(w.reshape(b, l, h * d))
        grads = vjp_3(w)
    np.testing.assert_array_equal(np.asarray(out_1).reshape(shape),
                                  np.asarray(out_3))
    np.testing.assert_array_equal(
        np.asarray(dqkv),
        np.concatenate([np.asarray(g).reshape(b, l, h * d) for g in grads],
                       -1))
    assert ran[0] == (((b, l, 3 * h * d), heads) if heads
                      else ((b * h, l, d), 1))
    np.testing.assert_allclose(
        np.asarray(out_1).reshape(shape),
        np.asarray(_xla_attention(q, k, v, causal, None)), atol=1e-5,
        rtol=1e-5)


@pytest.mark.parametrize("blocks", [(128, 64), (64, 128)])
def test_flash_mixed_block_sizes_stay_correct(blocks):
    """The causal diagonal-skip bounds round conservatively, so unequal
    q/k block sizes must still produce exact results."""
    bq, bk = blocks
    q, k, v = _rand_qkv(1, 256, 2, 32)
    with jax.default_matmul_precision("float32"):
        out_f = flash_attention(q, k, v, causal=True, block_q=bq,
                                block_k=bk, interpret=True)
        out_x = _xla_attention(q, k, v, True, None)
    np.testing.assert_allclose(np.asarray(out_f), np.asarray(out_x),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("lq,lk,block", [
    (256, 128, 64),
    (512, 256, 256),  # tiles that are cut: the tail q tile meets none
])
def test_flash_causal_lq_gt_lk_kernel_bounds(lq, lk, block):
    """lq > lk causal: the fwd/dq interior-block loop bound must clamp to
    num_k_blocks (matching the dkv kernel) — tail query blocks sit fully
    past the last K block, and an unclamped bound reads past K/V.  The
    kernels' mask convention is rows >= cols (top-left aligned), so the
    reference here builds that mask directly instead of _xla_attention's
    bottom-right alignment."""
    from ray_tpu.ops.attention import NEG_INF, _flash

    def flat(x):
        return x.reshape(x.shape[:2] + (-1,))

    q, _, _ = _rand_qkv(1, lq, 2, 32, seed=1)
    _, k, v = _rand_qkv(1, lk, 2, 32, seed=2)

    def ref(q):
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * (32 ** -0.5)
        rows = jnp.arange(lq)[:, None]
        cols = jnp.arange(lk)[None, :]
        s = jnp.where((rows >= cols)[None, None], s, NEG_INF)
        p = jax.nn.softmax(s.astype(jnp.float32), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v)

    def flash(q):
        return _flash((flat(q), flat(k), flat(v)), 2, True, None, block,
                      block, True).reshape(q.shape)

    with jax.default_matmul_precision("float32"):
        np.testing.assert_allclose(np.asarray(flash(q)), np.asarray(ref(q)),
                                   atol=2e-5, rtol=1e-4)
        gf = jax.grad(lambda q: jnp.sum(jnp.sin(flash(q))))(q)
        gx = jax.grad(lambda q: jnp.sum(jnp.sin(ref(q))))(q)
    np.testing.assert_allclose(np.asarray(gf), np.asarray(gx),
                               atol=2e-4, rtol=1e-3, err_msg="dq mismatch")


def test_flash_unaligned_seq_rejected():
    q, k, v = _rand_qkv(1, 200, 1, 32)
    with pytest.raises(ValueError, match="multiples"):
        flash_attention(q, k, v, interpret=True)


def test_auto_dispatch_uses_xla_on_cpu():
    """On the CPU test backend the auto path must take the XLA branch
    (flash compiles only for TPU); differentiating through
    mha_attention must therefore always work."""
    q, k, v = _rand_qkv(1, 256, 2, 32)
    g = jax.grad(lambda q: jnp.sum(mha_attention(q, k, v, causal=True)))(q)
    assert bool(jnp.all(jnp.isfinite(g)))


def test_explicit_flash_propagates_the_kernel_error():
    """use_flash=True is a request for the kernel: where it cannot compile
    (here: the CPU backend, compiled mode) the error must reach the caller,
    not be swallowed by a quiet switch to the XLA path."""
    q, k, v = _rand_qkv(1, 256, 2, 32)
    with pytest.raises(ValueError, match="interpret mode"):
        mha_attention(q, k, v, causal=True, use_flash=True)


@pytest.mark.parametrize("kernels,shape", [
    (False, (8, 128, 4, 32)),  # the XLA path inside the shard_map
    (True, (8, 128, 4, 64)),   # the kernels, a pair of heads a device
    (True, (8, 128, 2, 64)),   # one head a device: head-major there
])
def test_mesh_aware_attention_matches_unsharded(kernels, shape, monkeypatch):
    """mha_attention(mesh=...) under a plain jit with batch- and head-
    sharded inputs: the shard_map over the logical-axis rules gives every
    device its own rows, and the result and gradient equal the unsharded
    ones.  (On the chip this is what lets the Mosaic kernel run under a
    sharded jit at all; here the XLA path runs inside the shard_map, or
    the three-operand kernels interpreted.)"""
    from ray_tpu.parallel import MeshSpec, batch_sharding, make_mesh

    if kernels:
        monkeypatch.setattr(attention, "flash_attention", functools.partial(
            flash_attention, interpret=True))
    attend = functools.partial(mha_attention, use_flash=kernels or None)
    mesh = make_mesh(MeshSpec({"data": 4, "model": 2}))
    q, k, v = _rand_qkv(*shape)
    qs, ks, vs = (jax.device_put(x, batch_sharding(mesh, 4))
                  for x in (q, k, v))
    got = jax.jit(lambda q, k, v: attend(q, k, v, mesh=mesh))(qs, ks, vs)
    assert got.sharding.spec[0] == "data" and got.sharding.spec[2] == "model"
    np.testing.assert_allclose(np.asarray(got), np.asarray(attend(q, k, v)),
                               atol=1e-6, rtol=1e-6)
    g = jax.jit(jax.grad(lambda q: jnp.sum(
        attend(q, ks, vs, mesh=mesh) ** 2)))(qs)
    gw = jax.grad(lambda q: jnp.sum(attend(q, k, v) ** 2))(q)
    np.testing.assert_allclose(np.asarray(g), np.asarray(gw),
                               atol=1e-5, rtol=1e-5)


def test_flash_vjp_composes_with_jit_and_vmap():
    """jit(grad(...)) and vmap over the custom VJP both work and match
    the XLA reference (the residual plumbing must survive both
    transforms)."""
    q, k, v = _rand_qkv(2, 256, 2, 32)

    with jax.default_matmul_precision("float32"):
        gf = jax.jit(jax.grad(lambda q, k, v: jnp.sum(
            jnp.sin(flash_attention(q, k, v, interpret=True))),
            argnums=(0, 1, 2)))(q, k, v)
        gx = jax.grad(lambda q, k, v: jnp.sum(
            jnp.sin(_xla_attention(q, k, v, True, None))),
            argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gx):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-4, rtol=1e-3)
        # vmap over a leading ensemble axis.
        qs = jnp.stack([q, q * 0.5])
        vm = jax.vmap(lambda qq: flash_attention(qq, k, v,
                                                 interpret=True))(qs)
        ref = jnp.stack([_xla_attention(q, k, v, True, None),
                         _xla_attention(q * 0.5, k, v, True, None)])
    np.testing.assert_allclose(np.asarray(vm), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# The causal schedule and the two forms of the backward pass (PR 39).
# ---------------------------------------------------------------------------
def _grads(fn, q, k, v):
    return jax.grad(lambda q, k, v: jnp.sum(jnp.sin(
        fn(q, k, v).astype(jnp.float32))), argnums=(0, 1, 2))(q, k, v)


@pytest.mark.parametrize("shape,dtype", [
    ((1, 1024, 2, 64), jnp.bfloat16),   # the train cells' tile geometry
    ((1, 1024, 2, 64), jnp.float32),
    ((1, 1024, 1, 128), jnp.bfloat16),  # Llama-family heads
    ((1, 1024, 1, 128), jnp.float32),
])
def test_flash_auto_blocks_at_1k_match_xla(shape, dtype):
    """Forward and all three gradients with the auto tile choice at the
    length the train cells run: several tiles a head, skipped, unmasked
    and masked ones among them."""
    from ray_tpu.ops.attention import _auto_blocks, causal_tile_schedule

    sched = causal_tile_schedule(
        shape[1], shape[1], *_auto_blocks(shape[1], shape[1], shape[3],
                                          True))
    assert sched["skipped"] and sched["masked"] < sched["visited"]
    q, k, v = (x.astype(dtype) for x in _rand_qkv(*shape))
    ref = tuple(x.astype(jnp.float32) for x in (q, k, v))
    with jax.default_matmul_precision("float32"):
        out_f = flash_attention(q, k, v, causal=True, interpret=True)
        out_x = _xla_attention(*ref, True, None)
        gf = _grads(lambda q, k, v: flash_attention(
            q, k, v, causal=True, interpret=True), q, k, v)
        gx = _grads(lambda q, k, v: _xla_attention(q, k, v, True, None),
                    *ref)
    # bf16: p and ds are rounded to 8 bits before their matmuls, as the
    # kernels always did; the bound is PR 21's, 2^-6 of the largest value.
    for a, b, name in zip((out_f,) + gf, (out_x,) + gx,
                          ("out", "dq", "dk", "dv")):
        a, b = np.asarray(a, np.float32), np.asarray(b)
        bound = 2e-4 if dtype == jnp.float32 else 2 ** -6 * np.abs(b).max()
        assert np.abs(a - b).max() <= bound, (name, np.abs(a - b).max())


@pytest.mark.parametrize("entry", ["three", "qkv"])
@pytest.mark.parametrize("shape,block,heads", [
    ((1, 512, 2, 64), 256, 2),    # a pair of heads a block; two chunks a tile
    ((1, 512, 1, 128), 256, 1),   # a head a block
    ((1, 1024, 2, 64), 512, 2),   # the train cells' tiles: four chunks
    ((1, 1024, 1, 128), 512, 1),
    ((1, 512, 3, 32), 256, 0),    # head-major
])
def test_cut_tiles_match_xla(entry, shape, block, heads, ran):
    """Masked tiles cut into chunks (``_diagonal_chunks``), by both ways
    into the kernels and every layout: output and all three gradients."""
    from ray_tpu.ops.attention import causal_tile_schedule

    b, l, h, d = shape
    sched = causal_tile_schedule(l, l, block, block)
    assert sched["chunk"] == 128
    assert sched["multiplied_share"] < sched["visited_share"]
    q, k, v = _rand_qkv(*shape)
    kw = dict(causal=True, block_q=block, block_k=block, interpret=True)

    def by_qkv(q, k, v):
        qkv = jnp.concatenate([x.reshape(b, l, h * d) for x in (q, k, v)],
                              -1)
        return flash_attention_qkv(qkv, h, **kw).reshape(shape)

    attend = by_qkv if entry == "qkv" else functools.partial(
        flash_attention, **kw)
    with jax.default_matmul_precision("float32"):
        out = attend(q, k, v)
        gf = _grads(attend, q, k, v)
        gx = _grads(lambda q, k, v: _xla_attention(q, k, v, True, None),
                    q, k, v)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(_xla_attention(q, k, v, True, None)),
            atol=1e-5, rtol=1e-5)
    for a, b_, name in zip(gf, gx, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=2e-4,
                                   rtol=1e-3, err_msg=f"d{name}")
    width = (3 if entry == "qkv" and heads else 1) * h * d
    assert ran[0] == (((b, l, width), heads) if heads
                      else ((b * h, l, d), 1))


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("blocks", [(128, 128), (128, 256), (256, 128),
                                    (256, 256)])  # the last: cut tiles
def test_fused_backward_equals_the_two_kernel_form(blocks, causal):
    """One kernel that computes a tile's s, p, dp, ds once, and the dq and
    dk/dv kernels that each compute them: the same gradients."""
    from ray_tpu.ops.attention import _bwd_call, _fwd_call, _head_major

    bq, bk = blocks
    ops = tuple(_head_major(x.reshape(1, 512, 64), 2)
                for x in _rand_qkv(1, 512, 2, 32))
    dof = _head_major(_rand_qkv(1, 512, 2, 32, seed=3)[0].reshape(
        1, 512, 64), 2)
    kw = dict(d=32, heads=1, causal=causal, sm_scale=32 ** -0.5, block_q=bq,
              block_k=bk, interpret=True)
    with jax.default_matmul_precision("float32"):
        out, lse = _fwd_call(ops, whole=True, **kw)
        one = _bwd_call(ops, out, lse, dof, whole=True, **kw)
        two = _bwd_call(ops, out, lse, dof, whole=False, **kw)
    for a, b, name in zip(one, two, ("dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5,
                                   rtol=1e-5, err_msg=name)


@pytest.mark.parametrize("fused", [True, False])
@pytest.mark.parametrize("block", [128, 256])  # 256: the masked tiles cut
def test_both_backward_forms_match_xla(fused, block, monkeypatch):
    """The custom VJP takes whichever form ``_fused_bwd_fits`` names; both
    are held to the XLA reference here (not fused: the rolled forward and
    the dq and dk/dv kernels, their offsets traced)."""
    from ray_tpu.ops import attention

    monkeypatch.setattr(attention, "_whole_head_fits", lambda *a: fused)
    jax.clear_caches()  # the rule is read while tracing
    q, k, v = _rand_qkv(1, 512, 2, 32)
    with jax.default_matmul_precision("float32"):
        gf = _grads(lambda q, k, v: flash_attention(
            q, k, v, causal=True, block_q=block, block_k=block,
            interpret=True), q, k, v)
        gx = _grads(lambda q, k, v: _xla_attention(q, k, v, True, None),
                    q, k, v)
    for a, b, name in zip(gf, gx, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4,
                                   rtol=1e-3, err_msg=f"d{name}")


@pytest.mark.parametrize("blocks", [(128, 256), (256, 128), (512, 128),
                                    (128, 512)])
def test_explicit_blocks_are_honoured_in_the_gradients(blocks):
    """Unequal explicit tiles: the skipped / masked / unmasked bounds are
    taken from both sides (a q tile's k range in fwd and dq, a k tile's q
    range in dk/dv) and have to agree."""
    bq, bk = blocks
    q, k, v = _rand_qkv(1, 512, 1, 32)
    with jax.default_matmul_precision("float32"):
        gf = _grads(lambda q, k, v: flash_attention(
            q, k, v, causal=True, block_q=bq, block_k=bk, interpret=True),
            q, k, v)
        gx = _grads(lambda q, k, v: _xla_attention(q, k, v, True, None),
                    q, k, v)
    for a, b, name in zip(gf, gx, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4,
                                   rtol=1e-3, err_msg=f"d{name}")


def test_non_causal_keeps_its_schedule():
    """Non-causal calls visit the whole rectangle with the tiles they
    always had."""
    from ray_tpu.ops.attention import _auto_blocks

    for d in (64, 128):
        assert _auto_blocks(1024, 1024, d, False) == (256, 1024)
        assert _auto_blocks(4096, 4096, d, False) == (256, 1024)
        assert _auto_blocks(512, 512, d, False) == (128, 128)
        assert _auto_blocks(384, 384, d, False) == (128, 128)


@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("length", [256, 384, 512, 640, 1024, 1152, 1536,
                                    2048, 3072, 4096, 8192])
def test_auto_schedule_skips_what_the_mask_removes(length, d):
    """The auto tile choice never visits a tile wholly above the diagonal,
    and visits at most the causal half plus one diagonal band of tiles.
    (Until PR 39 the choice at 1,024 was one k tile as long as the
    sequence: visited share 1.0, all of it masked.)"""
    from ray_tpu.ops.attention import (_auto_blocks, _q_tile_bounds,
                                       causal_tile_schedule)

    bq, bk = _auto_blocks(length, length, d, True)
    assert length % bq == 0 and length % bk == 0
    nq, nk = length // bq, length // bk
    sched = causal_tile_schedule(length, length, bq, bk)
    # Every visited tile holds an entry on or under the diagonal...
    kinds = {}
    for i in range(nq):
        for j in range(nk):
            above = j * bk > i * bq + bq - 1     # first col past last row
            under = j * bk + bk - 1 <= i * bq    # last col at or before row
            kinds[i, j] = "skip" if above else ("full" if under else "mask")
    assert sched["visited"] == sum(k != "skip" for k in kinds.values())
    assert sched["masked"] == sum(k == "mask" for k in kinds.values())
    assert sched["skipped"] == sum(k == "skip" for k in kinds.values())
    # ... the k-side bounds (dk/dv, the fused backward) say the same ...
    for j in range(nk):
        first, full = (int(x) for x in _q_tile_bounds(j * bk, bk, bq, nq))
        assert [kinds[i, j] for i in range(nq)] == (
            ["skip"] * first + ["mask"] * (full - first)
            + ["full"] * (nq - full))
    # ... and the visited area is the causal half plus at most one band.
    band = max(bq, bk) * length
    assert sched["visited_share"] * length * length <= (
        length * length / 2 + band)
    if length >= 1024:
        assert sched["visited_share"] <= 0.75


def test_schedule_counter_on_the_parent_choice():
    """What PR 39 found: (256, 1024) at 1,024 visits the whole square, all
    of it under the mask; at 4,096 the same tiles do skip."""
    from ray_tpu.ops.attention import causal_tile_schedule

    assert causal_tile_schedule(1024, 1024, 256, 1024) == {
        "total": 4, "visited": 4, "masked": 4, "skipped": 0,
        "visited_share": 1.0, "chunk": 0, "multiplied_share": 1.0}
    assert causal_tile_schedule(4096, 4096, 256, 1024)["visited_share"] \
        == 0.625
    assert causal_tile_schedule(1024, 1024, 256, 256) == {
        "total": 16, "visited": 10, "masked": 4, "skipped": 6,
        "visited_share": 0.625, "chunk": 128, "multiplied_share": 0.5625}


@pytest.mark.parametrize("chunk,share", [(128, 0.5625), (256, 0.625),
                                         (512, 0.75)])
def test_schedule_counts_what_a_cut_tile_multiplies(chunk, share,
                                                    monkeypatch):
    """(512, 512) tiles at 1,024 tokens visit 0.75 of the square; cut into
    chunks of 128 columns the masked tiles multiply 10 of their 16
    sub-blocks and the square 0.5625 (0.625 at 256; the mask needs
    0.5005).  The counter and the kernels read the one ``_diagonal_chunks``:
    with another chunk width the counter follows, the kernels are called
    with the tiles' sizes in both orientations, and forward and gradients
    still equal the XLA path's."""
    from ray_tpu.ops.attention import causal_tile_schedule

    asked = []
    chunks = attention._diagonal_chunks

    def spy(block_q, block_k):
        asked.append((block_q, block_k))
        return chunks(block_q, block_k)

    monkeypatch.setattr(attention, "_CHUNK", chunk)
    monkeypatch.setattr(attention, "_diagonal_chunks", spy)
    jax.clear_caches()  # an earlier test's trace of these shapes
    sched = causal_tile_schedule(1024, 1024, 512, 512)
    assert sched["visited_share"] == 0.75
    assert sched["chunk"] == (chunk if chunk < 512 else 0)
    assert sched["multiplied_share"] == share
    # ... which is the sub-blocks at or under the diagonal, counted
    g, firsts = chunks(512, 512)
    under = (512 // chunk) * (512 // chunk + 1) // 2
    assert (g, len(firsts)) == ((chunk, 512 // chunk) if g else (0, 0))
    assert (512 * 512 + 2 * under * chunk * chunk) / 1024 ** 2 == share
    del asked[:]
    q, k, v = _rand_qkv(1, 1024, 2, 64)
    with jax.default_matmul_precision("float32"):
        out = flash_attention(q, k, v, causal=True, interpret=True)
        gf = _grads(lambda q, k, v: flash_attention(
            q, k, v, causal=True, interpret=True), q, k, v)
        gx = _grads(lambda q, k, v: _xla_attention(q, k, v, True, None),
                    q, k, v)
    assert asked and set(asked) == {(512, 512)}
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(_xla_attention(q, k, v, True, None)),
        atol=1e-5, rtol=1e-5)
    for a, b, name in zip(gf, gx, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4,
                                   rtol=1e-3, err_msg=f"d{name}")
    jax.clear_caches()  # the kernels traced here read the patched width


@pytest.mark.parametrize("length,d,itemsize,whole", [
    (1024, 64, 2, True),     # the train cells: one head's Q, K, V, dO fit
    (2048, 128, 2, True),
    (1024, 64, 4, True),
    (2048, 64, 4, False),    # float32 doubles the residency
    (3072, 64, 2, False),    # 21 tiles: past what the kernels unroll
    (4096, 64, 2, False),
    (8192, 128, 2, False),
])
def test_whole_head_form_follows_the_shape(length, d, itemsize, whole):
    """The unrolled forward and the fused backward are taken where one
    head's blocks fit the kernel's share of VMEM and the tiles are few;
    the grid-walking kernels stay for the rest.  Chosen from the length,
    the head size and the item size, by no option."""
    from ray_tpu.ops.attention import _auto_blocks, _whole_head_fits

    bq, bk = _auto_blocks(length, length, d, True)
    assert _whole_head_fits(length, length, d, itemsize, bq, bk,
                            True) is whole


# ---------------------------------------------------------------------------
# One trace of each kernel a program, whatever its depth (PR 49).
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("fused_qkv", [True, False])
def test_unrolled_blocks_trace_each_kernel_once(fused_qkv, monkeypatch):
    """A model of four unrolled ``Block``s, differentiated: the forward
    kernel's body and the backward's are each traced once, not once a
    layer, because the kernels sit behind one jitted call that the layers
    share (the lowering follows the trace: one function, four calls).  Both
    ways into the kernels: GPT-2's own fused qkv, and ``attn_fn=`` with
    three operands (the several-chip cells')."""
    from ray_tpu.models.gpt2 import GPT2, GPT2Config, gpt2_loss_fn

    traced = {"_flash_fwd_kernel": 0, "_flash_bwd_kernel": 0}

    def counting(name):
        body = getattr(attention, name)

        def counted(*refs, **kw):
            traced[name] += 1
            return body(*refs, **kw)
        return counted

    cfg = GPT2Config.tiny(num_layers=4, num_heads=4, hidden_size=256,
                          max_position_embeddings=256, use_flash=True)
    assert cfg.num_layers < cfg.scan_layers_threshold
    model = GPT2(cfg, attn_fn=None if fused_qkv else functools.partial(
        mha_attention, use_flash=True))
    ids = jnp.zeros((2, 256), jnp.int32)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0), ids)["params"]
    for name in traced:
        monkeypatch.setattr(attention, name, counting(name))
    jax.clear_caches()  # an earlier test's trace of these shapes
    # Traced and never lowered: compiled kernels do not lower for the CPU.
    text = str(jax.make_jaxpr(jax.grad(
        lambda p: gpt2_loss_fn(p, model.apply, {"input_ids": ids})))(params))
    for call in ("_fwd_call", "_bwd_call"):  # bound once, called four times
        assert text.count(f"jaxpr={call}") == 4, call
    assert traced == {"_flash_fwd_kernel": 1, "_flash_bwd_kernel": 1}


@pytest.mark.parametrize("attn_fn", [False, True])
def test_gpt2_through_the_kernels_equals_the_xla_path(attn_fn, monkeypatch):
    """GPT-2's block hands the kernels its fused qkv (or, with ``attn_fn=``,
    three operands split off it): loss and gradients equal those of the XLA
    path on the same weights."""
    from ray_tpu.models.gpt2 import GPT2, GPT2Config, gpt2_loss_fn

    for name in ("flash_attention", "flash_attention_qkv"):
        monkeypatch.setattr(attention, name, functools.partial(
            getattr(attention, name), interpret=True))

    def model(use_flash):
        cfg = GPT2Config.tiny(num_heads=2, hidden_size=128,
                              use_flash=use_flash, dtype=jnp.float32)
        return GPT2(cfg, attn_fn=functools.partial(
            mha_attention, use_flash=use_flash) if attn_fn else None)

    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 128), 0, 512)
    params = model(False).init(jax.random.PRNGKey(0), ids)["params"]
    with jax.default_matmul_precision("float32"):
        (lf, gf), (lx, gx) = (jax.value_and_grad(
            lambda p: gpt2_loss_fn(p, model(flash).apply,
                                   {"input_ids": ids}))(params)
            for flash in (True, False))
    np.testing.assert_allclose(float(lf), float(lx), rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(gf),
                    jax.tree_util.tree_leaves(gx)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5,
                                   rtol=1e-3)
