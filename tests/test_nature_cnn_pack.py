"""NatureCNN's first layer on packed frames (``models/nature_cnn.py``): the
8x8 convolution of stride 4 as a 2x2 one over frames folded four by four
pixels into channels, uint8 frames still uint8.  Against
``lax.conv_general_dilated`` on the raw frames, the parameter tree as it
was, both forms through ``DiscreteActorCritic``, and one anakin PPO
iteration that keeps its trajectory packed."""
import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.nature_cnn import (NatureCNN, PackedConv, pack_frames,
                                       packed_shape)
from ray_tpu.rllib.core.rl_module import RLModuleSpec

FRAMES = [((84, 84, 4), np.uint8), ((96, 96, 3), np.uint8),
          ((210, 160, 4), np.uint8), ((84, 84, 4), np.float32)]
IDS = ["84x84x4-uint8", "96x96x3-uint8", "210x160x4-uint8",
       "84x84x4-float32"]


def _frames(shape, dtype, n=3, seed=0):
    rng = np.random.default_rng(seed)
    if dtype == np.uint8:
        return jnp.asarray(rng.integers(0, 256, (n, *shape), dtype=np.uint8))
    return jnp.asarray(rng.normal(size=(n, *shape)).astype(dtype))


def _plain_first_layer(kernel, bias, frames):
    """What ``nn.Conv(32, (8, 8), strides=(4, 4))`` computes on raw frames."""
    x = frames.astype(jnp.float32)
    if frames.dtype == jnp.uint8:
        x = x / 255.0
    y = jax.lax.conv_general_dilated(
        x, kernel, (4, 4), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return y + bias


def _packed_first_layer(kernel, bias, frames):
    x = pack_frames(frames).astype(jnp.float32)
    if frames.dtype == jnp.uint8:
        x = x / 255.0
    return PackedConv(32).apply({"params": {"kernel": kernel, "bias": bias}},
                                x)


@pytest.mark.parametrize("what", ["outputs", "kernel_gradient"])
@pytest.mark.parametrize("shape,dtype", FRAMES, ids=IDS)
def test_packed_first_layer_is_the_strided_convolution(shape, dtype, what):
    """The same products summed in another order: float32 summation order
    alone at the highest precision, a unit-variance kernel."""
    frames = _frames(shape, dtype)
    rng = np.random.default_rng(1)
    kernel = jnp.asarray(rng.normal(size=(8, 8, shape[-1], 32)), jnp.float32)
    bias = jnp.asarray(rng.normal(size=32), jnp.float32)
    weigh = jnp.asarray(rng.normal(size=32), jnp.float32)

    def scalar(layer):
        return lambda k: jnp.mean(layer(k, bias, frames) * weigh)

    with jax.default_matmul_precision("highest"):
        if what == "outputs":
            got = _packed_first_layer(kernel, bias, frames)
            want = _plain_first_layer(kernel, bias, frames)
            assert got.shape == (3, -(-shape[0] // 4), -(-shape[1] // 4), 32)
        else:
            got = jax.grad(scalar(_packed_first_layer))(kernel)
            want = jax.grad(scalar(_plain_first_layer))(kernel)
            assert got.shape == kernel.shape
    assert float(jnp.max(jnp.abs(got - want))) < 1e-4


@pytest.mark.parametrize("shape,dtype", FRAMES, ids=IDS)
def test_pack_frames_pads_as_the_convolution_and_folds_by_four(shape, dtype):
    frames = _frames(shape, dtype, n=2)
    folded = pack_frames(frames)
    h, w, c = shape
    assert folded.shape[1:] == packed_shape(shape)
    assert folded.shape[1:] == (-(-h // 4) + 1, -(-w // 4) + 1, 16 * c)
    assert folded.dtype == frames.dtype  # uint8 in, uint8 out
    (top, _), (left, _) = jax.lax.padtype_to_pads(
        (h, w), (8, 8), (4, 4), "SAME")
    frames, folded = np.asarray(frames), np.asarray(folded)
    for y, x, ch in [(0, 0, 0), (h - 1, w - 1, c - 1), (h // 2, 7, 1),
                     (5, w // 3, c - 1)]:
        py, px = y + top, x + left
        at = ((py % 4) * 4 + px % 4) * c + ch
        assert np.all(folded[:, py // 4, px // 4, at] == frames[:, y, x, ch])
    # what the padding added is zero
    assert np.all(folded[:, 0, :, :c * 4 * top] == 0)


def test_parameter_tree_is_the_unpacked_kernels():
    """Paths, shapes and dtypes as before the packed layer (the reference of
    the PPO cell and every checkpoint read them), and the values
    ``nn.Conv``'s initialiser draws at those paths."""
    spec = RLModuleSpec(obs_shape=(84, 84, 4), num_actions=3, conv=True)
    params = spec.build().init(jax.random.PRNGKey(0),
                               jnp.asarray(spec.example_obs(2)))
    flat = {"/".join(k.key for k in path): (leaf.shape, str(leaf.dtype))
            for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]}
    assert flat == {
        "params/NatureCNN_0/Conv_0/bias": ((32,), "float32"),
        "params/NatureCNN_0/Conv_0/kernel": ((8, 8, 4, 32), "float32"),
        "params/NatureCNN_0/Conv_1/bias": ((64,), "float32"),
        "params/NatureCNN_0/Conv_1/kernel": ((4, 4, 32, 64), "float32"),
        "params/NatureCNN_0/Conv_2/bias": ((64,), "float32"),
        "params/NatureCNN_0/Conv_2/kernel": ((3, 3, 64, 64), "float32"),
        "params/NatureCNN_0/Dense_0/bias": ((256,), "float32"),
        "params/NatureCNN_0/Dense_0/kernel": ((7744, 256), "float32"),
        "params/pi/bias": ((3,), "float32"),
        "params/pi/kernel": ((256, 3), "float32"),
        "params/vf/bias": ((1,), "float32"),
        "params/vf/kernel": ((256, 1), "float32"),
    }

    class Plain(nn.Module):
        @nn.compact
        def __call__(self, x):
            return nn.Conv(32, (8, 8), strides=(4, 4))(x / 255.0)

    frames = jnp.zeros((1, 84, 84, 4), jnp.float32)
    trunk = NatureCNN().init(jax.random.PRNGKey(7), frames)["params"]
    plain = Plain().init(jax.random.PRNGKey(7), frames)["params"]
    assert np.array_equal(trunk["Conv_0"]["kernel"],
                          plain["Conv_0"]["kernel"])


@pytest.mark.parametrize("shape", [(84, 84, 4), (96, 96, 3)],
                         ids=["84x84x4", "96x96x3"])
def test_forward_train_takes_raw_and_packed_frames_alike(shape):
    spec = RLModuleSpec(obs_shape=shape, num_actions=4, conv=True)
    module = spec.build()
    frames = _frames(shape, np.uint8, n=5)
    actions = jnp.asarray([0, 3, 1, 2, 0])
    params = module.init(jax.random.PRNGKey(2), frames)
    packed = module.pack_obs(frames)
    assert packed.shape[1:] == spec.packed_obs_shape
    forward = jax.jit(module.forward_train)
    for raw, pre in zip(forward(params, frames, actions),
                        forward(params, packed, actions)):
        assert np.array_equal(raw, pre)
    with pytest.raises(ValueError, match="neither"):
        module.apply(params, frames[:, :80])


def test_other_trunks_take_their_observations_as_they_are():
    board = RLModuleSpec(obs_shape=(10, 10, 4), num_actions=3, conv=True)
    flat = RLModuleSpec(obs_dim=4, num_actions=2)
    assert board.packed_obs_shape is None and flat.packed_obs_shape is None
    obs = jnp.ones((2, 10, 10, 4), jnp.uint8)
    assert board.build().pack_obs(obs) is obs
    vec = jnp.ones((2, 4))
    assert flat.build().pack_obs(vec) is vec


@pytest.mark.timeout(240)
def test_anakin_ppo_keeps_its_trajectory_packed():
    """One iteration on a small Breakout84: finite losses, the env steps
    counted as before, the kernel in the state unpacked, and the trajectory
    inside the step held packed, an observation a run of 128-byte rows."""
    from ray_tpu.rllib import PPOConfig
    from ray_tpu.rllib.algorithms.ppo import make_anakin_ppo

    config = (PPOConfig().environment("Breakout-Atari84-v0")
              .anakin(num_envs=4, unroll_length=4)
              .training(num_sgd_iter=2, sgd_minibatch_size=8)
              .debugging(seed=3))
    algo = config.build()
    metrics = algo.train()
    assert metrics["num_env_steps_sampled_this_iter"] == 16
    assert all(np.isfinite(metrics[k]) for k in
               ("total_loss", "policy_loss", "vf_loss", "entropy"))
    state = algo._anakin_state
    kernel = state.params["params"]["NatureCNN_0"]["Conv_0"]["kernel"]
    assert kernel.shape == (8, 8, 4, 32) and kernel.dtype == jnp.float32
    assert state.obs.shape == (4, 84, 84, 4) and state.obs.dtype == jnp.uint8

    _module, init, step, total = make_anakin_ppo(config)
    assert total == 16
    assert step.attrs == {"frame_gather": "xla",  # no TPU here
                          "frame_pack": "xla"}
    text = str(jax.make_jaxpr(step)(jax.eval_shape(init, 3)))
    assert "u8[4,4,242,128]" in text      # [T, N, 22 * 22 * 64 / 128, 128]
    assert "u8[4,4,84,84,4]" not in text  # and no raw trajectory beside it


def _tiny_anakin(env, seed=3):
    from ray_tpu.rllib import PPOConfig

    return (PPOConfig().environment(env)
            .anakin(num_envs=8, unroll_length=4)
            .training(num_sgd_iter=2, sgd_minibatch_size=16)
            .debugging(seed=seed))


@pytest.mark.timeout(300)
@pytest.mark.parametrize("pack", ["fold_tiles", "xla"])
def test_anakin_ppo_gathers_frames_by_row_dma_to_the_same_losses(monkeypatch,
                                                                 pack):
    """Two iterations on a small Breakout84 with the minibatch's frames
    through ``ops.gather_rows`` (interpreted here; what a TPU backend
    chooses) against ``v[idx]`` on the same seed: the same samples, the
    same products, so the same losses; the trajectory held as word tiles,
    the minibatch batch-last, and the first call's span says which way.  A
    rollout step's frames reach the trajectory through ``fold_tiles``, one
    kernel from the env's raw bytes, which the trunk then reads batch-last
    (``pack == "fold_tiles"``: four channels of uint8 fold as words), and
    where frames do not fold so, packed by ``pack_frames`` and tiled by
    ``tile_columns`` (``"xla"``)."""
    from ray_tpu import observability
    from ray_tpu.ops import gather_rows as rows_op
    from ray_tpu.rllib.algorithms import ppo

    plain = _tiny_anakin("Breakout-Atari84-v0").build()
    assert plain._train_step.attrs == {"frame_gather": "xla",
                                       "frame_pack": "xla"}
    want = [plain.train() for _ in range(2)]

    monkeypatch.setattr(rows_op, "backend", lambda: "tpu")
    assert ppo._frames_by_dma(jax.ShapeDtypeStruct((1, 22, 22, 64),
                                                   jnp.uint8))
    # ... by shape: a board under a tile of words, rows off the lanes, floats
    assert not ppo._frames_by_dma(jax.ShapeDtypeStruct((1, 4, 4, 64),
                                                       jnp.uint8))
    assert not ppo._frames_by_dma(jax.ShapeDtypeStruct((1, 10, 10, 4),
                                                       jnp.uint8))
    assert not ppo._frames_by_dma(jax.ShapeDtypeStruct((1, 4), jnp.float32))
    monkeypatch.undo()

    # the shape rule as on a TPU, the kernels interpreted as on a CPU
    monkeypatch.setattr(ppo, "_frames_by_dma", lambda seen: True)
    if pack == "xla":
        monkeypatch.setattr(rows_op, "folds_frames", lambda x, pads: False)
    config = _tiny_anakin("Breakout-Atari84-v0")
    by_dma = config.build()
    assert by_dma._train_step.attrs == {"frame_gather": "rows_dma",
                                        "frame_pack": pack}
    got = [by_dma.train() for _ in range(2)]
    # the rollout's own forward pass reads batch-last frames where it
    # folds: the convolution's sums in another order, float32 round-off
    # (the total is a difference of its terms: absolute there)
    rel, tiny = (1e-4, 1e-6) if pack == "fold_tiles" else (1e-5, 1e-7)
    for g, w in zip(got, want):
        for k in ("total_loss", "policy_loss", "vf_loss", "entropy"):
            assert g[k] == pytest.approx(w[k], rel=rel, abs=tiny), k
    spans = [s for s in observability.session_spans()
             if s["name"] == "train.compile"
             and s.get("args", {}).get("program") == "anakin_ppo"]
    assert [(s["args"]["frame_gather"], s["args"]["frame_pack"])
            for s in spans[-2:]] == [("xla", "xla"), ("rows_dma", pack)]

    _module, init, step, _total = ppo.make_anakin_ppo(config)
    text = str(jax.make_jaxpr(step)(jax.eval_shape(init, 3)))
    assert "u32[32,64,128]" in text        # [T * N, 8 tiles of words]
    assert "u8[4,8,242,128]" not in text   # and no byte trajectory beside
    assert "u8[22,22,64,16]" in text       # a minibatch, the batch last
    assert "name=gather_rows" in text
    # one kernel a rollout step
    assert ("name=fold_tiles" in text) == (pack == "fold_tiles")
    assert ("name=tile_columns" in text) == (pack == "xla")


@pytest.mark.timeout(300)
def test_anakin_ppo_row_dma_runs_under_the_data_mesh(monkeypatch):
    """``num_devices=2``: inside ``shard_map`` the kernels see a device's
    own envs and its own half of a minibatch; the same losses as ``v[idx]``
    there."""
    from ray_tpu.rllib.algorithms import ppo

    def one_iteration():
        config = _tiny_anakin("Breakout-Atari84-v0").resources(num_devices=2)
        algo = config.build()
        return algo._train_step.attrs["frame_gather"], algo.train()

    way, want = one_iteration()
    assert way == "xla"
    monkeypatch.setattr(ppo, "_frames_by_dma", lambda seen: True)
    way, got = one_iteration()
    assert way == "rows_dma"
    # (the rollout reads batch-last frames: float32 round-off)
    for k in ("total_loss", "policy_loss", "vf_loss", "entropy"):
        assert got[k] == pytest.approx(want[k], rel=1e-4, abs=1e-6), k


@pytest.mark.timeout(240)
def test_anakin_ppo_on_a_vector_env_keeps_the_plain_gather(monkeypatch):
    """CartPole's four floats are no run of lane tiles: ``v[idx]`` whatever
    the backend."""
    from ray_tpu.ops import gather_rows as rows_op
    from ray_tpu.rllib.algorithms.ppo import make_anakin_ppo

    monkeypatch.setattr(rows_op, "backend", lambda: "tpu")
    config = _tiny_anakin("CartPole-v1")
    _module, init, step, _total = make_anakin_ppo(config)
    assert step.attrs == {"frame_gather": "xla", "frame_pack": "xla"}
    text = str(jax.make_jaxpr(step)(jax.eval_shape(init, 3)))
    assert "gather_rows" not in text and "tile_columns" not in text
    assert "fold_tiles" not in text
    algo = config.build()
    assert np.isfinite(algo.train()["total_loss"])
