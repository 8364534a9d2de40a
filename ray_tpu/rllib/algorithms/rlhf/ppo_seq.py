"""PPO on sequences: the RLHF learner step.

The objective is classic clipped-surrogate PPO (`ppo.ppo_surrogate`'s
math) applied to LM token sequences: each *sampled* token is one action,
its behavior logprob came from the serving engine's decode step (exact —
no recomputation drift), and the reward is terminal per sequence (a
scalar from the reward scorer).  With gamma=1 and a terminal reward the
Monte-Carlo return of every response position is the sequence reward, so

- ``value_targets[t] = R`` on response positions,
- ``advantages[t] = R - V_pre(s_t)`` (pre-update critic, the standard
  PPO bootstrap-free estimator), whitened over the masked positions,

both computed ONCE per batch inside the train step, followed by the
shared ``run_ppo_sgd`` permute->minibatch->epoch scaffolding — the same
scaffolding every PPO variant in this repo uses, with the
gradient-application recipe (plain adam / int8 collectives / ZeRO)
resolved by ``mesh.build_update_plan`` exactly as the anakin steps do.
The whole step (advantage pass + all SGD epochs) is ONE jit (one compile
per fixed ``[B, L]`` batch shape; the loop keeps shapes constant).
"""
from __future__ import annotations

import types
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax

from ray_tpu.rllib.algorithms.ppo import run_ppo_sgd
from ray_tpu.rllib.utils import mesh as mesh_util


def _masked_mean(x, mask):
    return (x * mask).sum() / jnp.maximum(mask.sum(), 1.0)


def _masked_mean_global(x, mask, sharded):
    s = mesh_util.psum_if((x * mask).sum(), sharded)
    n = mesh_util.psum_if(mask.sum(), sharded)
    return s / jnp.maximum(n, 1.0)


def sequence_logprobs(logits, tokens):
    """``[B, L-1]`` log-softmax of ``tokens[:, 1:]`` under
    ``logits[:, :-1]`` — position t's logit row predicts token t+1."""
    lp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32), axis=-1)
    labels = tokens[:, 1:]
    return jnp.take_along_axis(lp, labels[..., None], axis=-1)[..., 0]


def sequence_ppo_loss(params, model, batch, *, clip_param, vf_coeff,
                      entropy_coeff):
    """Clipped-surrogate PPO over one minibatch of sequences.

    ``batch``: tokens [B, L] int32, response_mask [B, L] (1.0 on sampled
    tokens), behavior_logp [B, L], advantages [B, L], value_targets
    [B, L].  Mask/logp/adv/targets are indexed by the position of the
    sampled token; the value prediction for token t is the critic at
    t-1 (the state *before* emitting it)."""
    logits, values = model.apply({"params": params}, batch["tokens"])
    new_logp = sequence_logprobs(logits, batch["tokens"])  # [B, L-1]
    mask = batch["response_mask"][:, 1:]
    behavior = batch["behavior_logp"][:, 1:]
    adv = batch["advantages"][:, 1:]
    vt = batch["value_targets"][:, 1:]
    v_pred = values[:, :-1]

    ratio = jnp.exp(new_logp - behavior)
    surr = jnp.minimum(
        ratio * adv,
        jnp.clip(ratio, 1 - clip_param, 1 + clip_param) * adv)
    policy_loss = -_masked_mean(surr, mask)
    vf_loss = 0.5 * _masked_mean((v_pred - vt) ** 2, mask)
    lp_full = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32),
                                 axis=-1)
    ent = _masked_mean(-(jnp.exp(lp_full) * lp_full).sum(-1), mask)
    # One-sample KL(behavior || current) estimate — drift telemetry.
    kl = _masked_mean(behavior - new_logp, mask)
    total = policy_loss + vf_coeff * vf_loss - entropy_coeff * ent
    return total, {"policy_loss": policy_loss, "vf_loss": vf_loss,
                   "entropy": ent, "approx_kl": kl}


class SeqPPOLearner:
    """Jitted PPO-on-sequences learner for a ``GPT2WithValue`` module.

    ``update(batch_dict)`` runs advantage estimation plus
    ``num_sgd_iter`` shuffled-minibatch epochs in one compiled call and
    returns host metrics.  ``num_devices`` switches to the SPMD path
    (sequences sharded over the ``data`` mesh axis, params replicated)
    where ``zero_sharding``/``quantized_collectives`` select the PR 9
    gradient-application plans via ``mesh.build_update_plan``; without
    it both knobs fail loudly, exactly like the anakin steps."""

    def __init__(self, model, params, *, batch_size: int, pad_to: int,
                 lr: float = 1e-4, clip_param: float = 0.2,
                 vf_coeff: float = 0.5, entropy_coeff: float = 0.01,
                 grad_clip: Optional[float] = 1.0, num_sgd_iter: int = 2,
                 minibatch_size: Optional[int] = None,
                 num_devices: Optional[int] = None,
                 zero_sharding: str = "off",
                 quantized_collectives: str = "off", seed: int = 0):
        self._model = model
        self.batch_size = int(batch_size)
        self.pad_to = int(pad_to)
        D, sharded, mesh = mesh_util.setup_data_mesh(
            types.SimpleNamespace(num_devices=num_devices),
            self.batch_size)
        mb = int(minibatch_size or self.batch_size)
        if mb > self.batch_size or self.batch_size % mb:
            raise ValueError(
                f"minibatch_size={mb} must divide batch_size="
                f"{self.batch_size}")
        if sharded and (self.batch_size % D or mb % D):
            raise ValueError(
                f"batch_size={self.batch_size} and minibatch_size={mb} "
                f"must be divisible by num_devices={D}")
        B_loc = self.batch_size // D if sharded else self.batch_size
        mb_loc = mb // D if sharded else mb
        num_mb = B_loc // mb_loc

        plan_cfg = types.SimpleNamespace(
            zero_sharding=zero_sharding,
            quantized_collectives=quantized_collectives)
        params_tmpl = jax.eval_shape(lambda: params)
        update_fn, opt_init, opt_specs = mesh_util.build_update_plan(
            plan_cfg, lr, grad_clip, params_tmpl, D, sharded)

        def loss_fn(p, mb_batch):
            return sequence_ppo_loss(
                p, model, mb_batch, clip_param=clip_param,
                vf_coeff=vf_coeff, entropy_coeff=entropy_coeff)

        def train_step(p, opt_state, rng, batch):
            # Advantages from the PRE-update critic, once per batch.
            _, values0 = model.apply({"params": p}, batch["tokens"])
            mask = batch["response_mask"]
            vt = batch["rewards"][:, None] * mask
            v_pre = jnp.concatenate(
                [jnp.zeros_like(values0[:, :1]), values0[:, :-1]], axis=1)
            adv_raw = (batch["rewards"][:, None] - v_pre) * mask
            m = _masked_mean_global(adv_raw, mask, sharded)
            var = _masked_mean_global((adv_raw - m) ** 2, mask, sharded)
            adv = (adv_raw - m) / (jnp.sqrt(var) + 1e-8) * mask
            flat = {"tokens": batch["tokens"], "response_mask": mask,
                    "behavior_logp": batch["behavior_logp"],
                    "advantages": adv, "value_targets": vt}
            (p, opt_state, rng), (losses, auxes) = run_ppo_sgd(
                p, opt_state, rng, loss_fn,
                lambda idx: {k: v[idx] for k, v in flat.items()},
                B_loc, mb_loc, num_mb, num_sgd_iter, None,
                sharded=sharded, update_fn=update_fn)
            metrics = {"total_loss": losses.mean()}
            metrics.update({k: v.mean() for k, v in auxes.items()})
            return p, opt_state, rng, metrics

        if sharded:
            from jax.sharding import PartitionSpec as P

            batch_specs = {"tokens": P(mesh_util.DATA_AXIS),
                           "response_mask": P(mesh_util.DATA_AXIS),
                           "behavior_logp": P(mesh_util.DATA_AXIS),
                           "rewards": P(mesh_util.DATA_AXIS)}
            mapped = jax.shard_map(
                train_step, mesh=mesh,
                in_specs=(P(), opt_specs, P(), batch_specs),
                out_specs=(P(), opt_specs, P(), P()), check_vma=False)
            self._step = jax.jit(mapped)
            init_sh = mesh_util.state_sharding(mesh, opt_specs)
            self._opt_state = jax.jit(
                opt_init, out_shardings=init_sh)(params)
        else:
            self._step = jax.jit(train_step)
            self._opt_state = opt_init(params)
        self._params = params
        self._rng = jax.random.PRNGKey(seed)
        self._sharded = sharded

    @property
    def params(self):
        return self._params

    @property
    def lm_params(self):
        """The policy subtree — exactly what ``LLMEngine.swap_weights``
        installs (the value head never ships to the serving plane)."""
        return self._params["lm"]

    def update(self, batch: Dict[str, np.ndarray]) -> Dict[str, Any]:
        if batch["tokens"].shape != (self.batch_size, self.pad_to):
            raise ValueError(
                f"batch shape {batch['tokens'].shape} != compiled "
                f"({self.batch_size}, {self.pad_to}) — keep rollout batch "
                "shapes constant so the learner compiles once")
        step_batch = {k: batch[k] for k in
                      ("tokens", "response_mask", "behavior_logp",
                       "rewards")}
        self._params, self._opt_state, self._rng, metrics = self._step(
            self._params, self._opt_state, self._rng, step_batch)
        return {k: float(v) for k, v in jax.device_get(metrics).items()}
