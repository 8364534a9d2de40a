"""Train tests (modeled on python/ray/train/tests/: TestConfig no-op backend
executor tests + end-to-end trainer runs)."""
import numpy as np
import pytest

import ray_tpu
from ray_tpu.air import Checkpoint, RunConfig, ScalingConfig, session
from ray_tpu.air.config import CheckpointConfig, FailureConfig
from ray_tpu.train import (
    BackendExecutor,
    DataParallelTrainer,
    JaxTrainer,
    TestConfig,
)


def test_backend_executor_basic(ray_start_regular):
    ex = BackendExecutor(TestConfig(), ScalingConfig(num_workers=2))
    ex.start()

    def loop(config):
        session.report({"rank": session.get_world_rank(),
                        "world": session.get_world_size()})

    ex.start_training(loop, {})
    results = ex.get_next_results()
    ranks = sorted(r[1]["rank"] for r in results)
    assert ranks == [0, 1]
    assert all(r[1]["world"] == 2 for r in results)
    assert ex.get_next_results() is None
    ex.shutdown()


def test_scaling_config_elastic_range():
    assert ScalingConfig(num_workers=3).worker_range() == (3, 3)
    sc = ScalingConfig(num_workers=(1, 4))
    assert sc.min_workers == 1 and sc.max_workers == 4
    with pytest.raises(ValueError):
        ScalingConfig(num_workers=(3, 2)).worker_range()
    with pytest.raises(ValueError):
        ScalingConfig(num_workers=0).worker_range()


def test_scaling_config_use_tpu_needs_chips():
    """use_tpu with no chips reserved would start CPU workers and train
    there without saying so."""
    with pytest.raises(ValueError, match="chips_per_worker"):
        ScalingConfig(use_tpu=True, chips_per_worker=0)
    sc = ScalingConfig(use_tpu=True, chips_per_worker=2)
    assert sc.worker_resources()["TPU"] == 2.0


def test_backend_executor_elastic_range(ray_start_regular):
    """num_workers=(min, max): start() probes max->min and takes the
    largest gang the cluster can place now."""
    ex = BackendExecutor(TestConfig(), ScalingConfig(num_workers=(1, 2)))
    ex.start()

    def loop(config):
        session.report({"world": session.get_world_size()})

    try:
        assert ex.num_workers == 2  # 8-CPU head places the max size
        ex.start_training(loop, {})
        results = ex.get_next_results()
        assert all(r[1]["world"] == 2 for r in results)
        assert ex.get_next_results() is None
    finally:
        ex.shutdown()


def test_data_parallel_trainer_reports(ray_start_regular):
    def loop(config):
        for step in range(3):
            session.report({"step": step, "loss": 1.0 / (step + 1)})

    trainer = DataParallelTrainer(
        loop, backend_config=TestConfig(),
        scaling_config=ScalingConfig(num_workers=2))
    result = trainer.fit()
    assert result.error is None
    assert result.metrics["step"] == 2
    assert len(result.metrics_history) == 3


def test_trainer_checkpointing(ray_start_regular):
    def loop(config):
        ckpt = session.get_checkpoint()
        start = ckpt.to_dict()["step"] + 1 if ckpt else 0
        for step in range(start, 3):
            session.report({"step": step},
                           checkpoint=Checkpoint.from_dict({"step": step}))

    trainer = DataParallelTrainer(
        loop, backend_config=TestConfig(),
        scaling_config=ScalingConfig(num_workers=1),
        run_config=RunConfig(
            checkpoint_config=CheckpointConfig(num_to_keep=2)))
    result = trainer.fit()
    assert result.checkpoint.to_dict()["step"] == 2

    # Resume from the checkpoint: starts at step 3's absence → reports nothing
    trainer2 = DataParallelTrainer(
        loop, backend_config=TestConfig(),
        scaling_config=ScalingConfig(num_workers=1),
        resume_from_checkpoint=result.checkpoint)
    r2 = trainer2.fit()
    assert r2.error is None


def test_trainer_worker_failure_retry(ray_start_regular):
    import os

    marker = "/tmp/rtpu_train_fail_marker"
    if os.path.exists(marker):
        os.remove(marker)

    def loop(config):
        import os

        if not os.path.exists(marker):
            open(marker, "w").close()
            raise RuntimeError("simulated failure")
        session.report({"ok": 1},
                       checkpoint=Checkpoint.from_dict({"ok": 1}))

    trainer = DataParallelTrainer(
        loop, backend_config=TestConfig(),
        scaling_config=ScalingConfig(num_workers=1),
        run_config=RunConfig(failure_config=FailureConfig(max_failures=2)))
    result = trainer.fit()
    assert result.error is None
    assert result.metrics["ok"] == 1


def _run_gpt2_dp(num_workers: int, local_device_count: int):
    from ray_tpu.train.jax.config import JaxConfig

    # The loop is a nested function so cloudpickle captures it BY VALUE —
    # module-level test functions pickle by reference and worker processes
    # can't import the tests package.
    def gpt2_dp_loop(config):
        """Deterministic GPT-2 tiny training: same data/init on every
        worker, batch sharded over the global data axis, grads reduced
        in-graph."""
        import jax
        import jax.numpy as jnp
        import optax

        from ray_tpu.air import session
        from ray_tpu.models.gpt2 import GPT2, GPT2Config, gpt2_loss_fn
        from ray_tpu.train.jax import (
            get_mesh, prepare_batch, prepare_train_state)

        mesh = get_mesh()
        cfg = GPT2Config.tiny(dtype=jnp.float32)
        model = GPT2(cfg)
        key = jax.random.PRNGKey(0)
        ids = jax.random.randint(key, (8, 32), 0, cfg.vocab_size)
        params = model.init(key, ids)["params"]
        params = prepare_train_state(params, mesh)
        batch = prepare_batch({"input_ids": ids}, mesh)
        tx = optax.adam(1e-3)
        opt = tx.init(params)

        @jax.jit
        def step(params, opt, ids):
            loss, g = jax.value_and_grad(gpt2_loss_fn)(
                params, model.apply, {"input_ids": ids})
            upd, opt = tx.update(g, opt)
            return optax.apply_updates(params, upd), opt, loss

        losses = []
        for _ in range(5):
            params, opt, loss = step(params, opt, batch["input_ids"])
            losses.append(float(jax.device_get(loss)))
        session.report({"losses": losses,
                        "global_devices": jax.device_count()})

    trainer = JaxTrainer(
        gpt2_dp_loop,
        jax_config=JaxConfig(platform="cpu",
                             local_device_count=local_device_count),
        # No gloo headroom needed: collective-group init retries in place,
        # rendezvous warms the transport pairs up, and any abort that still
        # escapes is charged to fit()'s own transport budget rather than
        # FailureConfig.
        scaling_config=ScalingConfig(num_workers=num_workers))
    result = trainer.fit()
    assert result.error is None, result.error
    return result.metrics_history[-1]


@pytest.mark.slow  # ~30s: two gloo worlds + elastic retries under load
# inflate it to the suite's slowest test (see the max_failures note in
# _run_gpt2_dp); nightly covers it, PR 10's long-tail rule.
def test_gpt2_dp_two_workers_matches_single_process(ray_start_regular):
    """GPT-2 data-parallel across 2 worker processes produces the SAME loss
    trajectory as one process driving an equal-size mesh — the gradient
    allreduce rides XLA collectives across the process boundary without
    changing the math (reference methodology: Train-vs-native parity,
    doc/source/ray-air/benchmarks.rst:179-214)."""
    single = _run_gpt2_dp(num_workers=1, local_device_count=4)
    double = _run_gpt2_dp(num_workers=2, local_device_count=2)
    assert single["global_devices"] == double["global_devices"] == 4
    np.testing.assert_allclose(single["losses"], double["losses"],
                               rtol=1e-4, atol=1e-5)
    assert double["losses"][-1] < double["losses"][0]


def test_jax_trainer_mlp_learns(ray_start_regular):
    """End-to-end: JaxTrainer on a tiny regression problem (single worker
    = one host driving the full 8-device CPU mesh via pjit)."""

    def loop(config):
        import jax
        import jax.numpy as jnp
        import optax

        from ray_tpu.models import MLP
        from ray_tpu.train.jax import get_mesh, prepare_batch, prepare_train_state

        mesh = get_mesh()
        model = MLP(features=(32,), out_dim=1)
        key = jax.random.PRNGKey(0)
        x = jax.random.normal(key, (64, 4))
        y = jnp.sum(x, axis=1, keepdims=True)
        params = model.init(key, x)
        params = prepare_train_state(params, mesh)
        batch = prepare_batch({"x": x, "y": y}, mesh)
        tx = optax.adam(1e-2)
        opt = tx.init(params)

        @jax.jit
        def step(params, opt, batch):
            def loss_fn(p):
                pred = model.apply(p, batch["x"])
                return jnp.mean((pred - batch["y"]) ** 2)

            loss, g = jax.value_and_grad(loss_fn)(params)
            upd, opt = tx.update(g, opt)
            return optax.apply_updates(params, upd), opt, loss

        for i in range(30):
            params, opt, loss = step(params, opt, batch)
            if i % 10 == 9:
                session.report({"loss": float(loss), "iter": i})

    trainer = JaxTrainer(
        loop,
        jax_config=__import__("ray_tpu.train.jax.config", fromlist=["JaxConfig"]
                              ).JaxConfig(platform="cpu"),
        scaling_config=ScalingConfig(num_workers=1))
    result = trainer.fit()
    assert result.error is None
    losses = [m["loss"] for m in result.metrics_history]
    assert losses[-1] < losses[0]
