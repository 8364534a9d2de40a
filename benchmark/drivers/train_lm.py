"""Driver ``train_lm``: language-model training through Ray Train.

The parent (``run``) never touches jax.  It makes the token data set from the
seed, starts ``JaxTrainer`` with one worker that owns every chip of the cell,
and takes back one record.  The worker (``train_loop``) builds GPT-2 from the
configuration file, makes the weights on the device in one jitted call from
the seed, compares the program's loss with the plain reference, warms the one
step program up, and then measures a fixed number of optimizer steps fed by
``iter_device_batches``.
"""
from __future__ import annotations

import functools
import sys
import time


def model_config(config: dict, traffic: dict):
    import jax.numpy as jnp

    from ray_tpu.models import GPT2Config

    t = config["train"]
    return GPT2Config(
        vocab_size=config["vocab_size"],
        max_position_embeddings=max(config["n_positions"], traffic["seq"]),
        num_layers=config["n_layer"], num_heads=config["n_head"],
        hidden_size=config["n_embd"], dtype=getattr(jnp, t["dtype"]),
        scan_layers_threshold=t["scan_layers_threshold"],
        use_flash=t.get("use_flash"))  # None: the program's own dispatch


def build_step(config: dict, traffic: dict, mesh, chips: int):
    """(model, init, (loss_of, token_logp), step): the program's own pieces, put together
    as ``examples/train_gpt2.py`` and ``chip_smoke.py`` do."""
    import jax
    import jax.numpy as jnp
    import optax

    from ray_tpu.models import GPT2
    from ray_tpu.models.gpt2 import gpt2_loss_fn
    from ray_tpu.ops.attention import mha_attention
    from ray_tpu.train.jax import compile_donated_step

    cfg = model_config(config, traffic)
    # Several chips: the step is a plain jit over a sharded batch, and the
    # Mosaic kernel has to be told the mesh (PERF.md section 0).
    model = GPT2(cfg, attn_fn=functools.partial(
        mha_attention, mesh=mesh, use_flash=cfg.use_flash)
        if chips > 1 else None)
    tx = optax.adamw(config["train"]["lr"])

    def init(key):
        params = model.init(key, jnp.zeros((chips, 8), jnp.int32))["params"]
        return params, tx.init(params)

    def loss_of(params, ids):
        return gpt2_loss_fn(params, model.apply, {"input_ids": ids})

    def token_logp(params, ids):
        """The log-probability the program gives each next token: the terms
        whose mean ``gpt2_loss_fn`` returns."""
        logp = jax.nn.log_softmax(
            model.apply({"params": params}, ids)[:, :-1], axis=-1)
        return jnp.take_along_axis(logp, ids[:, 1:, None], axis=-1)[..., 0]

    def step_impl(params, opt, ids):
        loss, grads = jax.value_and_grad(loss_of)(params, ids)
        updates, opt = tx.update(grads, opt, params)
        return optax.apply_updates(params, updates), opt, loss

    return model, init, (loss_of, token_logp), compile_donated_step(
        step_impl, carry_argnums=(0, 1))


def reference_check(program, ref, config, traffic, params, sample,
                    program_params=None) -> dict:
    """The program (bf16, its kernels) against the plain reference (float32,
    ``highest``) on the same weights and sequences: the loss, and every
    token's log-probability by its largest and its root-mean-square error.
    The loss alone is a mean near ln(V) in which rounding averages out; the
    per-token errors are what a lower precision moves.  ``program_params``:
    other weights for the program's side only (the precision probe's)."""
    import jax
    import jax.numpy as jnp

    loss_of, token_logp = program
    mine = params if program_params is None else program_params
    loss_prog = float(jax.jit(loss_of)(mine, sample))
    got = jax.jit(token_logp)(mine, sample)
    want = -jax.jit(functools.partial(ref.token_nll, cfg=config))(
        params, sample)
    err = jnp.abs(got.astype(jnp.float32) - want)
    out = {"loss_program": loss_prog, "loss_reference": float(-jnp.mean(want)),
           "token_logprob_max_err": float(jnp.max(err)),
           "token_logprob_rms_err": float(jnp.sqrt(jnp.mean(err ** 2))),
           "tokens_compared": int(err.size),
           "loss_tolerance": traffic["loss_tolerance"],
           "token_logprob_max_tolerance":
               traffic["token_logprob_max_tolerance"],
           "token_logprob_rms_tolerance":
               traffic["token_logprob_rms_tolerance"]}
    out["loss_matches"] = bool(
        abs(loss_prog - out["loss_reference"]) <= out["loss_tolerance"]
        and out["token_logprob_max_err"] <= out["token_logprob_max_tolerance"]
        and out["token_logprob_rms_err"] <= out["token_logprob_rms_tolerance"])
    return out


def train_loop(c):
    """Runs inside the Train worker, the process that holds the chips."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec

    from benchmark import common
    from ray_tpu.air import session
    from ray_tpu.parallel.mesh import MeshSpec
    from ray_tpu.parallel.sharding import batch_sharding
    from ray_tpu.train.jax import get_mesh

    config, traffic, chips = c["config"], c["traffic"], c["chips"]
    device = common.device_record(c["allow_cpu"])
    if device["count"] != chips:
        raise RuntimeError(f"the cell asks for {chips} chips, jax sees "
                           f"{device['count']}")
    compiles = common.CompileCounter()
    batch, seq = traffic["per_chip_batch"] * chips, traffic["seq"]
    mesh = get_mesh(MeshSpec({"data": chips}))
    _model, init, program, step = build_step(config, traffic, mesh, chips)
    replicated = NamedSharding(mesh, PartitionSpec())
    # Weights and optimizer state made on the device, in one call, placed as
    # the step hands them back (anything else compiles the step twice).
    params, opt = jax.jit(init, out_shardings=replicated)(
        jax.random.PRNGKey(common.jax_seed(c["seed"])))

    def stream():
        shard = session.get_dataset_shard("train")
        while True:  # the data set is a ring: the window may outlast it
            yield from shard.iter_device_batches(
                batch, sharding=batch_sharding(mesh, 2))

    batches = stream()
    ids = next(batches)["tokens"]
    if ids.shape != (batch, seq):
        raise RuntimeError(f"batch of shape {ids.shape}, not {(batch, seq)}")

    # correct, part 1: the program against the plain reference, on the
    # seeded weights, on a sample of this batch's sequences.
    sample = np.asarray(ids)[:traffic["reference_sequences"] * chips]
    sample = jax.device_put(sample, batch_sharding(mesh, 2))
    checks = reference_check(
        program, common.load_module("reference", c["config_name"]), config,
        traffic, params, sample)
    print("[bench] reference check:", checks, file=sys.stderr, flush=True)

    # Warm-up: the one compilation, then a few timed steps that fix how many
    # steps fill the window.
    params, opt, loss = step(params, opt, ids)
    jax.block_until_ready(loss)
    t0 = time.perf_counter()
    for _ in range(traffic["calibration_steps"]):
        params, opt, loss = step(params, opt, next(batches)["tokens"])
    jax.block_until_ready(loss)
    step_s = (time.perf_counter() - t0) / traffic["calibration_steps"]
    steps = max(traffic["calibration_steps"], int(c["seconds"] / step_s))

    spans, losses, traced, window = common.SpanTimes(), [], None, None
    first, last = traffic["trace_first_step"], (
        traffic["trace_first_step"] + traffic["trace_steps"])
    compiles.arm()
    window_start = time.time()
    t0 = time.perf_counter()
    for i in range(steps):
        if c["trace"] and i == first:
            jax.block_until_ready(loss)
            window = common.TracedWindow("train")
        with spans("ingest"):
            ids = next(batches)["tokens"]
        with common.span("dispatch:step"):
            params, opt, loss = step(params, opt, ids)
        losses.append(loss)
        if c["trace"] and i == last - 1:
            with common.span("fetch"):
                jax.block_until_ready(loss)
            traced = window.close()
    with common.span("fetch"):
        jax.block_until_ready(loss)
    window_s = time.perf_counter() - t0
    checks["compiles_in_window"] = compiles.disarm()

    losses = [float(x) for x in jax.device_get(losses)]
    checks["losses_finite"] = bool(np.all(np.isfinite(losses)))
    checks["step_programs"] = step._cache_size()
    if chips > 1:
        # Replicated parameters must still be the same bytes on every chip.
        flat = jax.tree_util.tree_leaves(params)
        small = sorted(flat, key=lambda x: x.size)
        checks["replicas_equal"] = all(
            len({np.asarray(s.data).tobytes()
                 for s in leaf.addressable_shards}) == 1
            for leaf in small[:4] + small[len(small) // 2:][:2])
    correct = (checks["loss_matches"] and checks["losses_finite"]
               and checks["compiles_in_window"] == 0
               and checks["step_programs"] == 1
               and checks.get("replicas_equal", True)
               and device["platform"] == "tpu")
    tokens = steps * batch * seq
    rate = tokens / window_s / chips
    if traced:  # starting and stopping the profiler is not the program's time
        rate = (traffic["trace_steps"] * batch * seq / traced["window_s"]
                / chips)
    session.report({"bench": {
        "device": common.memory_record(device),
        "correct": bool(correct), "checks": checks,
        "attempted": steps, "failed": 0 if checks["losses_finite"] else steps,
        "window_start": window_start, "window_s": window_s,
        "end_to_end": {"tokens_per_s_chip": rate},
        "counters": {"steps": steps, "tokens": tokens, "chips": chips,
                     "batch": batch, "seq": seq,
                     "calibration_step_s": step_s,
                     "first_loss": losses[0], "last_loss": losses[-1]},
        "samples": {"ingest_ms": spans.ms.get("ingest", [])},
        "trace": traced,
        "trace_steps": traffic["trace_steps"] if traced else 0,
    }})


def run(cell, config, traffic, seed, seconds, trace, allow_cpu=False):
    import numpy as np

    import ray_tpu
    import ray_tpu.data as rdata
    from ray_tpu.air.config import ScalingConfig
    from ray_tpu.train import JaxTrainer
    from ray_tpu.train.jax.config import JaxConfig

    chips = cell["chips"]
    ray_tpu.init(**({"num_tpus": chips} if allow_cpu else {}))
    try:
        rows = traffic["per_chip_batch"] * chips * traffic["dataset_batches"]
        tokens = np.random.default_rng(int(seed)).integers(
            0, config["vocab_size"], size=(rows, traffic["seq"]),
            dtype=np.int32)
        result = JaxTrainer(
            train_loop,
            train_loop_config={
                "config": config, "config_name": cell["config"],
                "traffic": traffic, "chips": chips, "seed": seed,
                "seconds": seconds, "trace": trace, "allow_cpu": allow_cpu},
            datasets={"train": rdata.from_numpy({"tokens": tokens})},
            jax_config=(JaxConfig(platform="cpu", local_device_count=chips)
                        if allow_cpu else JaxConfig()),
            scaling_config=ScalingConfig(num_workers=1, use_tpu=True,
                                         chips_per_worker=chips),
        ).fit()
        if result.error is not None:
            raise result.error
        return result.metrics["bench"]
    finally:
        ray_tpu.shutdown()
