"""Train GPT-2 with JaxTrainer on synthetic tokens.

Run: python examples/train_gpt2.py  (add WORKERS=2 for multi-process DP
on a CPU mesh: WORKERS=2 JAX_PLATFORMS=cpu python examples/train_gpt2.py)

On a TPU host: CHIPS=1 python examples/train_gpt2.py (CHIPS=4 for all four
chips of a v5e host in one worker).  The worker reserves the chips with
``use_tpu=True, chips_per_worker=CHIPS``; a worker that reserves none is
started on the CPU, and ``use_tpu=True`` without chips is an error.
"""
import functools
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))  # run from a source tree
import ray_tpu
from ray_tpu.air import ScalingConfig, session
from ray_tpu.train import JaxTrainer
from ray_tpu.train.jax.config import JaxConfig


def train_loop(config):
    import jax
    import jax.numpy as jnp
    import optax

    from ray_tpu.models.gpt2 import GPT2, GPT2Config, gpt2_loss_fn
    from ray_tpu.ops.attention import mha_attention
    from ray_tpu.train.jax import get_mesh, prepare_batch, \
        prepare_train_state

    mesh = get_mesh()
    cfg = GPT2Config.tiny(dtype=jnp.float32)
    # The step below is a plain jit over arrays sharded on `mesh`: attention
    # is told the mesh, because the compiler will not partition the flash
    # kernel by itself (at this toy length the XLA path runs either way).
    model = GPT2(cfg, attn_fn=functools.partial(mha_attention, mesh=mesh))
    key = jax.random.PRNGKey(0)
    ids = jax.random.randint(key, (16, 64), 0, cfg.vocab_size)
    params = prepare_train_state(model.init(key, ids)["params"], mesh)
    batch = prepare_batch({"input_ids": ids}, mesh)
    tx = optax.adam(1e-3)
    opt = prepare_train_state(tx.init(params), mesh)
    print("training on", jax.devices()[0].platform,
          jax.devices()[0].device_kind, "x", len(jax.devices()))

    @jax.jit
    def step(params, opt, ids):
        loss, g = jax.value_and_grad(gpt2_loss_fn)(
            params, model.apply, {"input_ids": ids})
        upd, opt = tx.update(g, opt)
        return optax.apply_updates(params, upd), opt, loss

    for i in range(config.get("steps", 20)):
        params, opt, loss = step(params, opt, batch["input_ids"])
        session.report({"step": i, "loss": float(loss)})


if __name__ == "__main__":
    ray_tpu.init()
    workers = int(os.environ.get("WORKERS", "1"))
    chips = int(os.environ.get("CHIPS", "0"))
    jax_cfg = (JaxConfig(platform="cpu", local_device_count=4)
               if workers > 1 else None)
    scaling = (ScalingConfig(num_workers=workers, use_tpu=True,
                             chips_per_worker=chips)
               if chips else ScalingConfig(num_workers=workers))
    trainer = JaxTrainer(train_loop, train_loop_config={"steps": 20},
                         jax_config=jax_cfg, scaling_config=scaling)
    result = trainer.fit()
    print("final:", result.metrics)
    ray_tpu.shutdown()
