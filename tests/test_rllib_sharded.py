"""Data-parallel (multi-device) anakin train step.

Reference shape: the learner DDP fan-out (one replica per GPU, grad
all-reduce) in rllib/core/rl_trainer/trainer_runner.py:75-90.  Here the
whole anakin step is one shard_map'd SPMD program over a `data` mesh
axis; these tests run it on the 8-device virtual CPU mesh (conftest sets
xla_force_host_platform_device_count=8):

- exact-parity: a full-batch SGD update (num_mb=1, so the permutation
  cannot reorder the gradient) computed on 8 devices must equal the
  single-device update on the same data to float tolerance — this pins
  the pmean-gradient + replicated-optimizer algebra.
- learning: 8-device PPO reaches the same CartPole reward floor as the
  single-device test at equal global batch, and its state is genuinely
  sharded (per-device env shard = N/8) with replicated params.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from ray_tpu.rllib.utils import mesh as mesh_util

DEVICES = 8


def _need_devices():
    if len(jax.devices()) < DEVICES:
        pytest.skip(f"needs {DEVICES} devices")


def _make_module(obs_dim=4, num_actions=2, hiddens=(32, 32)):
    from ray_tpu.rllib.core.rl_module import RLModuleSpec

    return RLModuleSpec(obs_dim=obs_dim, num_actions=num_actions,
                        hiddens=hiddens).build()


def test_normalize_global_matches_host():
    _need_devices()
    mesh = mesh_util.data_mesh(DEVICES)
    x = jnp.asarray(np.random.RandomState(0).randn(16, 24).astype(np.float32))

    out = jax.jit(jax.shard_map(
        lambda v: mesh_util.normalize_global(v, True),
        mesh=mesh, in_specs=(P("data"),), out_specs=P("data"),
        check_vma=False))(x)
    expect = (x - x.mean()) / (jnp.sqrt(jnp.mean((x - x.mean()) ** 2)) + 1e-8)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=2e-5, atol=2e-6)


def test_sharded_full_batch_update_matches_single_device():
    """The pmean'd 8-device gradient step == the single-device step on the
    same batch (full-batch minibatch so the local permutations are
    irrelevant), iterated twice so optimizer-state replication is also
    covered."""
    import optax

    from ray_tpu.rllib.algorithms.ppo import ppo_loss, run_ppo_sgd

    _need_devices()
    module = _make_module()
    rs = np.random.RandomState(1)
    total = 512
    batch = {
        "obs": rs.randn(total, 4).astype(np.float32),
        "actions": rs.randint(0, 2, size=total).astype(np.int32),
        "action_logp": rs.randn(total).astype(np.float32) * 0.1 - 0.7,
        "advantages": rs.randn(total).astype(np.float32),
        "value_targets": rs.randn(total).astype(np.float32),
    }
    batch = {k: jnp.asarray(v) for k, v in batch.items()}
    params = module.init(jax.random.PRNGKey(0), batch["obs"][:2])
    tx = optax.adam(3e-4)
    opt_state = tx.init(params)
    loss_fn = functools.partial(ppo_loss, clip_param=0.2, vf_clip_param=10.0,
                                vf_loss_coeff=0.5, entropy_coeff=0.01)
    rng = jax.random.PRNGKey(7)

    def single(params, opt_state, rng, batch):
        (p, o, _), _ = run_ppo_sgd(
            params, opt_state, rng,
            lambda pp, mb: loss_fn(pp, module, mb),
            lambda idx: {k: v[idx] for k, v in batch.items()},
            total, total, 1, 2, tx)
        return p, o

    p1, _ = jax.jit(single)(params, opt_state, rng, batch)

    mesh = mesh_util.data_mesh(DEVICES)
    loc = total // DEVICES

    def sharded(params, opt_state, rng, batch):
        (p, o, _), _ = run_ppo_sgd(
            params, opt_state, rng,
            lambda pp, mb: loss_fn(pp, module, mb),
            lambda idx: {k: v[idx] for k, v in batch.items()},
            loc, loc, 1, 2, tx, sharded=True)
        return p, o

    mapped = jax.jit(jax.shard_map(
        sharded, mesh=mesh,
        in_specs=(P(), P(), P(), P("data")), out_specs=(P(), P()),
        check_vma=False))
    p8, _ = mapped(params, opt_state, rng, batch)

    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p8)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-6)


def test_sharded_ppo_learns_cartpole_and_is_sharded():
    """Same global batch as the single-device north-star test
    (test_rllib.py::test_anakin_ppo_learns_cartpole): 8-device run must
    reach the same reward floor — VERDICT r4 item #1's loss-parity gate."""
    from ray_tpu.rllib import PPOConfig

    _need_devices()
    algo = (PPOConfig()
            .environment("CartPole-v1")
            .anakin(num_envs=32, unroll_length=64)
            .training(lr=3e-4, num_sgd_iter=4, sgd_minibatch_size=512,
                      entropy_coeff=0.01)
            .resources(num_devices=DEVICES)
            .debugging(seed=0)
            .build())
    st = algo._anakin_state
    # Envs genuinely sharded: per-device obs shard is N/D rows.
    assert st.obs.sharding.is_equivalent_to(
        NamedSharding(mesh_util.data_mesh(DEVICES), P("data")), st.obs.ndim)
    shard_rows = {s.data.shape[0] for s in st.obs.addressable_shards}
    assert shard_rows == {32 // DEVICES}
    # Params replicated on every device.
    leaf = jax.tree.leaves(st.params)[0]
    assert len({s.device for s in leaf.addressable_shards}) == DEVICES
    assert all(s.data.shape == leaf.shape for s in leaf.addressable_shards)

    best = -1.0
    for _ in range(120):
        result = algo.train()
        r = result.get("episode_reward_mean", float("nan"))
        if np.isfinite(r):
            best = max(best, r)
        if best >= 150:
            break
    assert best >= 150, f"sharded PPO failed to learn CartPole: best={best}"
    # After training the params must STILL be bitwise-replicated — a
    # broken pmean would drift the replicas apart.
    leaf = jax.tree.leaves(algo._anakin_state.params)[0]
    vals = [np.asarray(s.data) for s in leaf.addressable_shards]
    for v in vals[1:]:
        np.testing.assert_array_equal(vals[0], v)


def test_sharded_impala_runs_and_counts_episodes():
    from ray_tpu.rllib import IMPALAConfig

    _need_devices()
    algo = (IMPALAConfig()
            .environment("CartPole-v1")
            .anakin(num_envs=32, unroll_length=32)
            .resources(num_devices=DEVICES)
            .debugging(seed=0)
            .build())
    m = {}
    for _ in range(6):
        m = algo.train()
    assert np.isfinite(m["total_loss"])
    # Episode counters are psum'd across devices: with 32 envs x 32 steps
    # x 6 iters of random-ish CartPole play, episodes must have finished.
    assert algo._prev_counters[1] > 0


def test_num_devices_rejected_on_unsupported_paths():
    """Fail-closed: paths without a shard_map step refuse num_devices
    instead of silently running single-device."""
    from ray_tpu.rllib import DQNConfig, PPOConfig

    with pytest.raises(NotImplementedError, match="num_devices"):
        (DQNConfig().environment("CartPole-v1")
         .resources(num_devices=2).build())
    with pytest.raises(NotImplementedError, match="num_devices"):
        (PPOConfig().environment("CartPole-v1")
         .training(model={"use_lstm": True})
         .resources(num_devices=2).build())
    with pytest.raises(NotImplementedError, match="num_devices"):
        (PPOConfig().environment("CartPole-v1")
         .rollouts(num_rollout_workers=1, mode="actor")
         .resources(num_devices=2).build())


def test_num_devices_one_uses_spmd_path():
    """num_devices=1 must compile and run the shard_map path (the real
    chip bench runs exactly this shape)."""
    from ray_tpu.rllib import PPOConfig

    algo = (PPOConfig().environment("CartPole-v1")
            .anakin(num_envs=8, unroll_length=16)
            .resources(num_devices=1)
            .build())
    m = algo.train()
    assert np.isfinite(m["total_loss"])
    assert algo._anakin_state.rng.shape == (1, 2)
