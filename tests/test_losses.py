"""``ops/losses.py``: the cross-entropy with its own backward pass against
the four lines it took the place of (``log_softmax`` + ``take_along_axis``,
left to autodiff), by value, by gradient and by what it keeps between the
passes."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.models import GPT2, GPT2Config
from ray_tpu.models.gpt2 import _stage_ce_loss, gpt2_loss_fn
from ray_tpu.ops.losses import next_token_cross_entropy, softmax_cross_entropy


def reference(logits, labels):
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], axis=-1))


def reference_lm(logits, ids):
    return reference(logits[:, :-1], ids[:, 1:])


def draw(shape, vocab, dtype, seed=0):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    logits = (4.0 * jax.random.normal(k1, shape + (vocab,))).astype(dtype)
    return logits, jax.random.randint(k2, shape, 0, vocab)


# A vocabulary that is no multiple of 128 is the case the train cells run.
@pytest.mark.parametrize("vocab", [131, 50257])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_value_matches_log_softmax(dtype, vocab):
    logits, labels = draw((2, 5), vocab, dtype)
    got = jax.jit(softmax_cross_entropy)(logits, labels)
    assert got.dtype == jnp.float32 and got.shape == ()
    np.testing.assert_allclose(got, reference(logits, labels), rtol=1e-6)


@pytest.mark.parametrize("vocab", [131, 50257])
@pytest.mark.parametrize("dtype,atol", [(jnp.float32, 1e-7),
                                        (jnp.bfloat16, 2e-3)])
def test_gradient_matches_autodiff_of_log_softmax(dtype, atol, vocab):
    logits, labels = draw((2, 5), vocab, dtype)
    got = jax.jit(jax.grad(softmax_cross_entropy))(logits, labels)
    want = jax.grad(reference)(logits.astype(jnp.float32), labels)
    assert got.dtype == dtype and got.shape == logits.shape
    # (softmax - onehot) / N: at most 1/N = 0.1 here, so bf16 resolves 4e-4.
    np.testing.assert_allclose(got.astype(jnp.float32), want, atol=atol)
    # every row's gradient sums to nothing: the one-hot's weight is known
    np.testing.assert_allclose(got.astype(jnp.float32).sum(-1), 0.0,
                               atol=40 * atol)


def test_the_cotangent_scales_the_gradient():
    logits, labels = draw((3, 4), 131, jnp.float32)
    got = jax.grad(lambda x: 2.5 * softmax_cross_entropy(x, labels))(logits)
    np.testing.assert_allclose(got, 2.5 * jax.grad(reference)(logits, labels),
                               atol=1e-7)


def test_rows_with_a_negative_label_carry_no_loss_and_no_gradient():
    logits, labels = draw((2, 6), 131, jnp.float32)
    masked = labels.at[:, -1].set(-1).at[0, 2].set(-100)
    keep = np.asarray(masked >= 0)
    loss, grad = jax.value_and_grad(softmax_cross_entropy)(logits, masked)
    want_loss, want_grad = jax.value_and_grad(reference)(
        logits[keep], labels[keep])
    np.testing.assert_allclose(loss, want_loss, rtol=1e-6)
    np.testing.assert_allclose(grad[keep], want_grad, atol=1e-7)
    assert not np.asarray(grad)[~keep].any()


def test_no_row_with_a_label_gives_zero_and_not_nan():
    logits, labels = draw((2, 3), 131, jnp.float32)
    loss, grad = jax.value_and_grad(softmax_cross_entropy)(
        logits, jnp.full_like(labels, -1))
    assert float(loss) == 0.0 and not np.asarray(grad).any()


def test_large_logits_do_not_overflow():
    logits, labels = draw((2, 3), 131, jnp.float32)
    loss, grad = jax.value_and_grad(softmax_cross_entropy)(
        logits * 1e4, labels)
    np.testing.assert_allclose(loss, reference(logits * 1e4, labels),
                               rtol=1e-6)
    assert np.isfinite(np.asarray(grad)).all()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_next_token_form_is_the_sliced_form(dtype):
    logits, ids = draw((2, 9), 131, dtype)
    loss, grad = jax.value_and_grad(next_token_cross_entropy)(logits, ids)
    want_loss, want_grad = jax.value_and_grad(reference_lm)(
        logits.astype(jnp.float32), ids)
    np.testing.assert_allclose(loss, want_loss, rtol=1e-6)
    np.testing.assert_allclose(grad.astype(jnp.float32), want_grad,
                               atol=1e-7 if dtype == jnp.float32 else 1e-3)
    assert not np.asarray(grad[:, -1].astype(jnp.float32)).any()


def test_next_token_form_takes_unsigned_ids():
    logits, ids = draw((2, 9), 131, jnp.float32)
    np.testing.assert_allclose(
        next_token_cross_entropy(logits, ids.astype(jnp.uint16)),
        reference_lm(logits, ids), rtol=1e-6)


def test_pipeline_last_stage_loss_is_the_same_objective():
    logits, ids = draw((2, 9), 131, jnp.float32)
    np.testing.assert_allclose(_stage_ce_loss(logits, ids),
                               reference_lm(logits, ids), rtol=1e-6)


def saved_between_the_passes(fn, *args):
    """Shapes and dtypes of what ``fn``'s forward pass hands its backward
    pass: the constants of the vjp's jaxpr."""
    _, pullback = jax.vjp(fn, *args)
    return [(x.shape, x.dtype) for x in jax.tree.leaves(pullback)
            if hasattr(x, "shape")]


def test_no_float32_array_of_the_logits_shape_is_kept_for_the_backward_pass():
    logits, labels = draw((2, 8), 131, jnp.bfloat16)
    big = [s for s in saved_between_the_passes(
        lambda x: softmax_cross_entropy(x, labels), logits)
        if s[0] == logits.shape]
    assert big == [(logits.shape, jnp.bfloat16)], big
    # ... where autodiff of the four lines keeps the float32 softmax
    old = saved_between_the_passes(lambda x: reference(x, labels), logits)
    assert (logits.shape, jnp.float32) in old, old


def test_the_backward_pass_makes_no_reduction_over_the_classes():
    logits, labels = draw((2, 8), 131, jnp.bfloat16)
    _, pullback = jax.vjp(lambda x: softmax_cross_entropy(x, labels), logits)
    text = str(jax.make_jaxpr(pullback)(jnp.float32(1.0)))
    assert "reduce" not in text, text


def old_gpt2_loss(params, apply_fn, batch):
    ids = batch["input_ids"]
    return reference_lm(apply_fn({"params": params}, ids), ids)


@pytest.mark.parametrize("dtype,rtol,atol", [(jnp.float32, 1e-4, 1e-5),
                                             (jnp.bfloat16, 5e-2, 1e-3)])
@pytest.mark.parametrize("moe", [False, True])
def test_gpt2_loss_and_gradients_match_the_four_lines(dtype, rtol, atol, moe):
    make = GPT2Config.moe_tiny if moe else GPT2Config.tiny
    cfg = make(dtype=dtype, vocab_size=131)
    model = GPT2(cfg)
    key = jax.random.PRNGKey(0)
    ids = jax.random.randint(key, (2, 24), 0, cfg.vocab_size)
    params = model.init(key, ids)["params"]
    batch = {"input_ids": ids}
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: gpt2_loss_fn(p, model.apply, batch)))(params)
    want_loss, want = jax.jit(jax.value_and_grad(
        lambda p: old_gpt2_loss(p, model.apply, batch)))(params)
    np.testing.assert_allclose(loss, want_loss, rtol=1e-6)
    for got_leaf, want_leaf in (
            (grads["wte"], want["wte"]),
            (grads["ln_f"]["scale"], want["ln_f"]["scale"]),
            (grads["ln_f"]["bias"], want["ln_f"]["bias"]),
            (grads["h_0"]["attn_qkv"]["kernel"],
             want["h_0"]["attn_qkv"]["kernel"])):
        np.testing.assert_allclose(got_leaf, want_leaf, rtol=rtol, atol=atol)


def test_llama_loss_matches_the_four_lines():
    from ray_tpu.models.llama import Llama, LlamaConfig, llama_loss_fn

    cfg = LlamaConfig.tiny(dtype=jnp.float32)
    model = Llama(cfg)
    key = jax.random.PRNGKey(0)
    ids = jax.random.randint(key, (2, 16), 0, cfg.vocab_size)
    params = model.init(key, ids)["params"]
    loss, grads = jax.value_and_grad(llama_loss_fn)(
        params, model.apply, {"input_ids": ids})
    want_loss, want = jax.value_and_grad(old_gpt2_loss)(
        params, model.apply, {"input_ids": ids})
    np.testing.assert_allclose(loss, want_loss, rtol=1e-6)
    for got_leaf, want_leaf in zip(jax.tree.leaves(grads),
                                   jax.tree.leaves(want)):
        np.testing.assert_allclose(got_leaf, want_leaf, rtol=1e-4, atol=1e-5)


def test_resnet_loss_matches_the_three_lines():
    from ray_tpu.models import ResNet, ResNetConfig
    from ray_tpu.models.resnet import resnet_loss_fn

    model = ResNet(ResNetConfig.tiny(dtype=jnp.float32))
    key = jax.random.PRNGKey(0)
    batch = {"image": jax.random.normal(key, (4, 32, 32, 3)),
             "label": jnp.array([0, 3, 1, 2])}
    variables = model.init(key, batch["image"], train=False)
    loss, _ = resnet_loss_fn(variables["params"], variables["batch_stats"],
                             model.apply, batch)
    logits, _ = model.apply(variables, batch["image"], train=True,
                            mutable=["batch_stats"])
    np.testing.assert_allclose(loss, reference(logits, batch["label"]),
                               rtol=1e-6)
