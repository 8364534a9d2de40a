"""Pallas flash attention, forward + custom-VJP backward, validated
against the XLA reference in interpreter mode (the CPU stand-in for the
TPU kernel; reference analogue for the pattern: the fused-kernel
parity tests any flash implementation carries).

Matmul precision is pinned to float32 for the comparisons: at default
precision the XLA einsums round through bf16 on some backends, which
would drown the kernel's actual error."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops.attention import _xla_attention, flash_attention, mha_attention


def _rand_qkv(B, L, H, D, seed=0):
    key = jax.random.PRNGKey(seed)
    return tuple(jax.random.normal(k, (B, L, H, D), jnp.float32)
                 for k in jax.random.split(key, 3))


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("shape", [(2, 256, 3, 32), (1, 384, 2, 64)])
def test_flash_forward_matches_xla(causal, shape):
    q, k, v = _rand_qkv(*shape)
    with jax.default_matmul_precision("float32"):
        out_f = flash_attention(q, k, v, causal=causal, interpret=True)
        out_x = _xla_attention(q, k, v, causal, None)
    np.testing.assert_allclose(np.asarray(out_f), np.asarray(out_x),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_gradients_match_xla(causal):
    q, k, v = _rand_qkv(2, 256, 3, 32)

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


def test_flash_causal_lq_gt_lk_kernel_bounds():
    """lq > lk causal: the fwd/dq interior-block loop bound must clamp to
    num_k_blocks (matching the dkv kernel) — tail query blocks sit fully
    past the last K block, and an unclamped bound reads past K/V.  The
    kernels' mask convention is rows >= cols (top-left aligned), so the
    reference here builds that mask directly instead of _xla_attention's
    bottom-right alignment."""
    from ray_tpu.ops.attention import NEG_INF, _flash

    q, _, _ = _rand_qkv(1, 256, 2, 32, seed=1)
    _, k, v = _rand_qkv(1, 128, 2, 32, seed=2)

    def ref(q):
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * (32 ** -0.5)
        rows = jnp.arange(256)[:, None]
        cols = jnp.arange(128)[None, :]
        s = jnp.where((rows >= cols)[None, None], s, NEG_INF)
        p = jax.nn.softmax(s.astype(jnp.float32), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v)

    def flash(q):
        return _flash(q, k, v, True, None, 64, 64, True)

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


def test_mesh_aware_attention_matches_unsharded():
    """mha_attention(mesh=...) under a plain jit with batch- and head-
    sharded inputs: the shard_map over the logical-axis rules gives every
    device its own rows, and the result and gradient equal the unsharded
    ones.  (On the chip this is what lets the Mosaic kernel run under a
    sharded jit at all; here the XLA path runs inside the shard_map.)"""
    from ray_tpu.parallel import MeshSpec, batch_sharding, make_mesh

    mesh = make_mesh(MeshSpec({"data": 4, "model": 2}))
    q, k, v = _rand_qkv(8, 128, 4, 32)
    qs, ks, vs = (jax.device_put(x, batch_sharding(mesh, 4))
                  for x in (q, k, v))
    got = jax.jit(lambda q, k, v: mha_attention(q, k, v, mesh=mesh))(
        qs, ks, vs)
    assert got.sharding.spec[0] == "data" and got.sharding.spec[2] == "model"
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(mha_attention(q, k, v)),
                               atol=1e-6, rtol=1e-6)
    g = jax.jit(jax.grad(lambda q: jnp.sum(
        mha_attention(q, ks, vs, mesh=mesh) ** 2)))(qs)
    gw = jax.grad(lambda q: jnp.sum(mha_attention(q, k, v) ** 2))(q)
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
