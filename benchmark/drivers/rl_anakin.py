"""Driver ``rl_anakin``: fully on-device PPO (``PPOConfig().anakin``), built
and stepped in this process, which therefore holds the chip itself.

One ``train()`` is one compiled program (rollout, GAE, SGD epochs) and one
fetch of its metrics.  The window is a fixed number of iterations, set from
a few timed ones after the warm-up.
"""
from __future__ import annotations

import time


def build_algo(config, chips, seed):
    from benchmark import common
    from ray_tpu.rllib import PPOConfig

    return (PPOConfig().environment(config["env"])
            .anakin(num_envs=config["num_envs"],
                    unroll_length=config["unroll_length"])
            .training(num_sgd_iter=config["num_sgd_iter"],
                      sgd_minibatch_size=config["sgd_minibatch_size"],
                      lr=config["lr"], entropy_coeff=config["entropy_coeff"],
                      clip_param=config["clip_param"],
                      vf_clip_param=config["vf_clip_param"],
                      vf_loss_coeff=config["vf_loss_coeff"],
                      grad_clip=config["grad_clip"], gamma=config["gamma"],
                      lambda_=config["lambda_"])
            .resources(num_devices=chips)
            .debugging(seed=common.jax_seed(seed))
            .build())


def reference_check(algo, config, traffic, seed, ref,
                    program_params=None) -> dict:
    """The program against the plain reference, on one seeded minibatch of
    frames and one seeded block of rewards: every sample's action
    log-probability and value by the largest error, the loss terms, and
    GAE.  The loss terms are means over the minibatch, in which rounding
    averages out, so they get a tolerance of their own; the per-sample
    errors are what a lower precision moves.  ``program_params``: other
    weights for the program's side only (the precision probe's)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.rllib.algorithms.ppo import ppo_loss
    from ray_tpu.rllib.evaluation.postprocessing import gae_jax

    r, c = traffic["reference"], algo.config
    rng = np.random.default_rng([int(seed), 2])
    n = r["minibatch"]
    params = algo._anakin_state.params
    mine = params if program_params is None else program_params
    obs = jnp.asarray(rng.integers(
        0, 256, (n, *algo.module.spec.obs_shape), dtype=np.uint8))
    actions = jnp.asarray(rng.integers(0, algo.module.spec.num_actions, n))
    forward = jax.jit(algo.module.forward_train)
    logp, value, _ = forward(params, obs, actions)
    batch = {
        "obs": obs, "actions": actions,
        "action_logp": logp + jnp.asarray(
            rng.normal(0, 0.2, n), jnp.float32),
        "advantages": jnp.asarray(rng.normal(0, 1, n), jnp.float32),
        "value_targets": value + jnp.asarray(
            rng.normal(0, 1, n), jnp.float32),
    }
    _, got = jax.jit(lambda p, b: ppo_loss(
        p, algo.module, b, clip_param=c.clip_param,
        vf_clip_param=c.vf_clip_param, vf_loss_coeff=c.vf_loss_coeff,
        entropy_coeff=c.entropy_coeff))(mine, batch)
    want = jax.jit(lambda p, b: ref.loss_terms(
        p, b, c.clip_param, c.vf_clip_param))(params, batch)
    errs = {k: abs(float(got[k]) - float(want[k])) for k in want}
    logp_prog, value_prog, _ = forward(mine, obs, actions)
    logits_ref, value_ref = jax.jit(ref.forward)(params, obs)
    logp_ref = jnp.take_along_axis(
        jax.nn.log_softmax(logits_ref, -1), actions[:, None], -1)[:, 0]
    per_sample = {
        "logp": float(jnp.max(jnp.abs(logp_prog - logp_ref))),
        "value": float(jnp.max(jnp.abs(value_prog - value_ref)))}

    t, m = r["gae_steps"], r["gae_envs"]
    rew = rng.normal(0, 1, (t, m)).astype(np.float32)
    val = rng.normal(0, 1, (t, m)).astype(np.float32)
    done = rng.random((t, m)) < 0.05
    last = rng.normal(0, 1, m).astype(np.float32)
    adv, targ = jax.jit(lambda *a: gae_jax(*a, c.gamma, c.lambda_))(
        rew, val, done, last)
    adv_ref, targ_ref = ref.gae(rew, val, done, last, c.gamma, c.lambda_)
    errs["gae"] = float(max(np.max(np.abs(np.asarray(adv) - adv_ref)),
                            np.max(np.abs(np.asarray(targ) - targ_ref))))
    return {"max_abs_err": errs, "tolerance": r["tolerance"],
            "per_sample_max_err": per_sample,
            "per_sample_tolerance": r["per_sample_tolerance"],
            "matches": all(e <= r["tolerance"] for e in errs.values())
            and all(e <= r["per_sample_tolerance"]
                    for e in per_sample.values()),
            "reference_terms": {k: float(v) for k, v in want.items()}}


def run(cell, config, traffic, seed, seconds, trace, allow_cpu=False):
    import jax
    import numpy as np

    from benchmark import common

    chips = cell["chips"]
    device = common.device_record(allow_cpu)
    if device["count"] != chips:
        raise RuntimeError(f"the cell asks for {chips} chips, jax sees "
                           f"{device['count']}")
    compiles = common.CompileCounter()
    algo = build_algo(config, chips, seed)
    check = reference_check(algo, config, traffic, seed,
                            common.load_module("reference", cell["config"]))

    algo.train()  # compiles
    t0 = time.perf_counter()
    for _ in range(traffic["calibration_iters"]):
        algo.train()
    iter_s = (time.perf_counter() - t0) / traffic["calibration_iters"]
    iters = max(traffic["calibration_iters"], int(seconds / iter_s))

    first = traffic["trace_first_iter"]
    last = first + traffic["trace_iters"]
    iter_ms, losses, env_steps, traced = [], [], 0, None
    compiles.arm()
    window_start = time.time()
    t0 = time.perf_counter()
    for i in range(iters):
        if trace and i == first:
            window = common.TracedWindow("rl")
        ta = time.perf_counter()
        with common.span("dispatch:step"):
            m = algo.train()
        iter_ms.append((time.perf_counter() - ta) * 1e3)
        losses.append(m["total_loss"])
        env_steps += m["num_env_steps_sampled_this_iter"]
        if trace and i == last - 1:
            traced = window.close()
    window_s = time.perf_counter() - t0
    n_compiles = compiles.disarm()

    finite = bool(np.all(np.isfinite(losses)))
    leaf = jax.tree.leaves(algo._anakin_state.params)[0]
    replicas_equal = len({np.asarray(s.data).tobytes()
                          for s in leaf.addressable_shards}) == 1
    checks = {**check, "losses_finite": finite,
              "compiles_in_window": n_compiles,
              "step_programs": algo._train_step._cache_size(),
              "replicas_equal": replicas_equal}
    correct = (check["matches"] and finite and n_compiles == 0
               and replicas_equal
               and env_steps == iters * config["num_envs"]
               * config["unroll_length"] and device["platform"] == "tpu")
    return {
        "device": common.memory_record(device),
        "correct": bool(correct), "checks": checks,
        "attempted": iters, "failed": 0 if finite else iters,
        "window_start": window_start, "window_s": window_s,
        "end_to_end": {"env_steps_per_s": env_steps / window_s},
        "counters": {"iters": iters, "env_steps": env_steps,
                     "calibration_iter_s": iter_s,
                     "first_loss": losses[0], "last_loss": losses[-1]},
        "samples": {"ppo_iter_ms": iter_ms},
        "trace": traced,
    }
