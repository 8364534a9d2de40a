"""PPO, two execution modes.

Reference: rllib/algorithms/ppo/ppo.py:350 (training_step: sample →
multi_gpu_train_one_step SGD → sync weights).  The TPU-first redesign:

- **anakin** (default): the Podracer/Anakin architecture (PAPERS.md) — env
  dynamics, rollout, GAE and the full minibatch-SGD epoch loop live inside
  ONE jitted train step; envs are a batched state pytree on device.  There
  is no sample transport at all: the [T, N] trajectory never leaves HBM.
  This is what makes ≥1M env-steps/s reachable — the reference's path
  (python envs → SampleBatch → GPU load) is bandwidth-bound at ~1e4/s/core.
- **actor**: reference-shaped path for envs that can't be jitted — CPU
  RolloutWorker actors sample fragments (with per-worker GAE like the
  reference's postprocessing), driver concatenates and the JaxLearner does
  the clipped-surrogate SGD on the mesh.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

import ray_tpu
from ray_tpu.ops import gather_rows as rows_op
from ray_tpu.rllib.algorithms.algorithm import Algorithm
from ray_tpu.rllib.algorithms.algorithm_config import AlgorithmConfig
from ray_tpu.rllib.core.rl_module import RLModuleSpec
from ray_tpu.rllib.evaluation.postprocessing import gae_jax
from ray_tpu.rllib.env.jax_envs import make_jax_env, vector_reset, vector_step


class PPOConfig(AlgorithmConfig):
    def __init__(self):
        super().__init__(algo_class=PPO)


def ppo_surrogate(logp, value, entropy, batch, *, clip_param, vf_clip_param,
                  vf_loss_coeff, entropy_coeff):
    """The clipped-surrogate objective from already-computed forward
    outputs — shared by the feedforward and recurrent paths."""
    ratio = jnp.exp(logp - batch["action_logp"])
    adv = batch["advantages"]
    surr = jnp.minimum(
        ratio * adv,
        jnp.clip(ratio, 1 - clip_param, 1 + clip_param) * adv)
    vf_err = jnp.clip((value - batch["value_targets"]) ** 2,
                      0.0, vf_clip_param ** 2)
    policy_loss = -jnp.mean(surr)
    vf_loss = 0.5 * jnp.mean(vf_err)
    ent = jnp.mean(entropy)
    total = policy_loss + vf_loss_coeff * vf_loss - entropy_coeff * ent
    return total, {"policy_loss": policy_loss, "vf_loss": vf_loss,
                   "entropy": ent}


def ppo_loss(params, module, batch, *, clip_param, vf_clip_param,
             vf_loss_coeff, entropy_coeff):
    logp, value, entropy = module.forward_train(
        params, batch["obs"], batch["actions"])
    return ppo_surrogate(logp, value, entropy, batch,
                         clip_param=clip_param,
                         vf_clip_param=vf_clip_param,
                         vf_loss_coeff=vf_loss_coeff,
                         entropy_coeff=entropy_coeff)


def run_ppo_sgd(params, opt_state, rng, loss_fn, make_mb, total, mb_size,
                num_mb, num_sgd_iter, tx, sharded: bool = False,
                update_fn=None):
    """The shared permute→minibatch→update scaffolding for every PPO
    variant (feedforward, recurrent, attention): `make_mb(idx)` maps an
    index vector over `total` items (steps or env sequences) to a loss
    batch; `loss_fn(params, mb) -> (loss, aux)`.  One copy so fixes to
    the minibatch loop (e.g. the perm remainder drop) land everywhere.

    With `sharded=True` the caller runs inside a shard_map over the
    `data` mesh axis: `total`/`mb_size` are per-device, each device
    permutes its own shard, and the gradient (plus loss metrics) is
    pmean'd across the axis before the optimizer update — params stay
    replicated because every device applies the identical update.

    `update_fn(grads, opt_state, params) -> (params, opt_state)` swaps
    the reduce+apply half (the ZeRO / int8-collective plans from
    mesh.build_update_plan); it receives the RAW local grads and owns the
    cross-replica reduction.  None keeps the classic pmean + tx.update."""
    from ray_tpu.rllib.utils.mesh import pmean_if

    if update_fn is None:
        def update_fn(grads, opt_state, params):
            updates, opt_state = tx.update(pmean_if(grads, sharded),
                                           opt_state, params)
            return optax.apply_updates(params, updates), opt_state

    def sgd_epoch(carry, _):
        params, opt_state, rng = carry
        rng, k = jax.random.split(rng)
        perm = jax.random.permutation(k, total)

        def mb_step(carry, idx):
            params, opt_state = carry
            (loss, aux), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, make_mb(idx))
            loss = pmean_if(loss, sharded)
            aux = pmean_if(aux, sharded)
            params, opt_state = update_fn(grads, opt_state, params)
            return (params, opt_state), (loss, aux)

        idxs = perm[: num_mb * mb_size].reshape(num_mb, mb_size)
        (params, opt_state), (losses, auxes) = jax.lax.scan(
            mb_step, (params, opt_state), idxs)
        return (params, opt_state, rng), (losses.mean(),
                                          {k_: v.mean() for k_, v in
                                           auxes.items()})

    return jax.lax.scan(sgd_epoch, (params, opt_state, rng), None,
                        length=num_sgd_iter)


def _tile_rows(x):
    """``[n, ...]`` -> ``[n, k, 128]`` where an observation holds a multiple
    of 128 values, ``[n, -1]`` otherwise.  In a sample-major buffer an
    observation is then a run of whole lane tiles of its own, and ``v[idx]``
    copies tiles; a row of 30,976 bytes shares its tiles with its
    neighbours and is picked out of them byte by byte (PERF.md, PR 55).
    The form of the trajectory where ``_frames_by_dma`` says no: there the
    compiler gathers a minibatch's observations and then lays them out for
    the trunk, two passes."""
    x = x.reshape(x.shape[0], -1)
    if x.shape[1] % 128:
        return x
    return x.reshape(x.shape[0], -1, 128)


def _frames_by_dma(seen) -> bool:
    """Whether a minibatch's observations come through ``ops.gather_rows``
    (one pass: a DMA a row, written batch-minor as the trunk's first
    convolution reads them) and not through ``v[idx]``: packed frames, which
    the module reads batch-last, where an observation fills whole tiles of
    words (under one it would be mostly padding) and the backend is a TPU.
    ``seen``: the shape and dtype of observations as the trunk reads them."""
    return (rows_op.backend() == "tpu" and len(seen.shape) == 4
            and rows_op.tiles_rows(seen)
            and math.prod(seen.shape[1:]) * seen.dtype.itemsize
            >= rows_op.TILE_BYTES)


class AnakinState(NamedTuple):
    params: Any
    opt_state: Any
    env_states: Any
    obs: jax.Array
    rng: jax.Array
    ep_return: jax.Array      # per-env running return
    done_return_sum: jax.Array
    done_count: jax.Array


def anakin_state_specs(opt_specs=None):
    """PartitionSpec prefix for AnakinState on the `data` mesh: params +
    optimizer replicated, env batch (states/obs/rng/returns) sharded on
    the axis, episode counters replicated (psum'd deltas).

    `opt_specs` overrides the optimizer subtree — the ZeRO plane passes
    `ZeroSharder.opt_specs` so each replica carries a 1/N state block."""
    from jax.sharding import PartitionSpec as P

    from ray_tpu.rllib.utils.mesh import DATA_AXIS

    return AnakinState(P(), opt_specs if opt_specs is not None else P(),
                       P(DATA_AXIS), P(DATA_AXIS), P(DATA_AXIS),
                       P(DATA_AXIS), P(), P())


def make_anakin_ppo(config: AlgorithmConfig):
    """Builds (init_fn, jitted train_step) for fully-on-device PPO.

    With ``config.num_devices`` set, the step is one SPMD program over a
    1-D ``data`` mesh (reference DP shape: one replica per GPU with grad
    all-reduce, rllib/core/rl_trainer/trainer_runner.py:75-90): each
    device rolls out N/D envs and runs the minibatch scan on its shard,
    with gradients/moments pmean'd across the axis — the only cross-chip
    traffic is the grad all-reduce riding ICI."""
    from ray_tpu._private.jax_env import (FirstCallSpan,
                                          ensure_compile_listener)
    from ray_tpu.rllib.utils import mesh as mesh_util

    ensure_compile_listener()
    env = make_jax_env(config.env) if isinstance(config.env, str) \
        else config.env
    spec = RLModuleSpec.for_env(env, tuple(config.hiddens))
    module = spec.build()

    N, T = config.num_envs, config.unroll_length
    batch_total = N * T
    mb_size = min(config.sgd_minibatch_size, batch_total)
    num_mb = batch_total // mb_size

    D, sharded, mesh = mesh_util.setup_data_mesh(config, N)
    if sharded:
        if mb_size % D:
            raise ValueError(f"sgd_minibatch_size={mb_size} not divisible "
                             f"by num_devices={D}")
        N_loc, mb_loc = N // D, mb_size // D
    else:
        N_loc, mb_loc = N, mb_size
    batch_loc = N_loc * T

    # The gradient-application plan (pmean / int8 collectives / ZeRO) —
    # shapes only, so the sharder is built before any init compiles.
    params_tmpl = jax.eval_shape(module.init, jax.random.PRNGKey(0),
                                 jnp.asarray(spec.example_obs()))
    update_fn, opt_init, opt_specs = mesh_util.build_update_plan(
        config, config.lr, config.grad_clip, params_tmpl, D, sharded)
    state_specs = anakin_state_specs(opt_specs)

    def _init(seed) -> AnakinState:
        rng = jax.random.PRNGKey(seed)
        rng, k_init, k_env = jax.random.split(rng, 3)
        env_states, obs = vector_reset(env, k_env, N)
        params = module.init(k_init, obs)
        return AnakinState(params, opt_init(params), env_states, obs,
                           mesh_util.split_rng(rng, D, sharded),
                           jnp.zeros(N), jnp.zeros(()), jnp.zeros(()))

    if sharded:
        out_sh = mesh_util.state_sharding(mesh, state_specs)
        init_fn = jax.jit(_init, out_shardings=out_sh)
    else:
        init_fn = _init

    # An observation as the trunk reads it (packed, where frames are), and
    # which way a minibatch of them is gathered: settled here, by shape.
    seen_as = jax.eval_shape(module.pack_obs, spec.example_obs())
    by_dma = _frames_by_dma(seen_as)
    # ... and whether a rollout step's frames go from the environment to the
    # trajectory through one kernel, the fold done on the way.
    by_fold = by_dma and spec.packs_tiled
    frame, frame_size = seen_as.shape[1:], math.prod(seen_as.shape[1:])

    loss_fn = functools.partial(
        ppo_loss, clip_param=config.clip_param,
        vf_clip_param=config.vf_clip_param,
        vf_loss_coeff=config.vf_loss_coeff,
        entropy_coeff=config.entropy_coeff)

    def rollout_step(carry, t):
        params, env_states, obs, rng, ep_ret, dsum, dcnt, frames = carry
        rng, k_act, k_step = jax.random.split(rng, 3)
        # Frames are packed here, once, and the trajectory holds that form:
        # SGD gathers and reads it as it is (models/nature_cnn.py).
        if by_fold:
            # One pass over the env's bytes: into the trajectory where it
            # lies, a frame a run of word tiles, and batch-minor for the
            # trunk, which is how the chip holds a convolution's frames.
            frames, seen = module.pack_obs_tiled(obs, into=frames,
                                                 at=t * obs.shape[0])
        else:
            seen = module.pack_obs(obs)
            if by_dma:
                frames = rows_op.tile_columns(
                    seen.reshape(seen.shape[0], -1).T, into=frames,
                    at=t * seen.shape[0])
        action, logp, value = module.forward_exploration(params, seen, k_act)
        env_states, next_obs, reward, done, _ = vector_step(
            env, env_states, action, k_step)
        ep_ret = ep_ret + reward
        dsum = dsum + jnp.sum(jnp.where(done, ep_ret, 0.0))
        dcnt = dcnt + jnp.sum(done)
        ep_ret = jnp.where(done, 0.0, ep_ret)
        out = (None if by_dma else _tile_rows(seen), action, logp, value,
               reward, done)
        return (params, env_states, next_obs, rng, ep_ret, dsum, dcnt,
                frames), out

    def train_step(state: AnakinState) -> Tuple[AnakinState, Dict[str, jax.Array]]:
        # Inside shard_map every array is the per-device block: N_loc envs,
        # a [1, 2] rng row (unwrapped to this device's key), and the
        # replicated params/opt/counters.
        rng_in = mesh_util.unwrap_rng(state.rng, sharded)
        frames = rows_op.empty_tiles(batch_loc, frame_size, seen_as.dtype) \
            if by_dma else None
        carry = (state.params, state.env_states, state.obs, rng_in,
                 state.ep_return, jnp.zeros(()), jnp.zeros(()), frames)
        with jax.named_scope("rollout"):
            carry, traj = jax.lax.scan(rollout_step, carry, jnp.arange(T))
        params, env_states, obs, rng, ep_ret, dsum_d, dcnt_d, frames = carry
        obs_t, act_t, logp_t, val_t, rew_t, done_t = traj  # [T, N_loc, ...]

        dsum = state.done_return_sum + mesh_util.psum_if(dsum_d, sharded)
        dcnt = state.done_count + mesh_util.psum_if(dcnt_d, sharded)

        with jax.named_scope("gae"):
            _, last_value = module.apply(params, obs)
            adv, vtarg = gae_jax(rew_t, val_t, done_t, last_value,
                                 config.gamma, config.lambda_)
            adv = mesh_util.normalize_global(adv, sharded)

        flat = {
            "obs": frames if by_dma
            else obs_t.reshape(batch_loc, *obs_t.shape[2:]),
            "actions": act_t.reshape(batch_loc),
            "action_logp": logp_t.reshape(batch_loc),
            "advantages": adv.reshape(batch_loc),
            "value_targets": vtarg.reshape(batch_loc),
        }

        def make_mb(idx):
            mb = {k_: v[idx] for k_, v in flat.items() if k_ != "obs"}
            if by_dma:
                # [*frame, mb]: the batch last, as the chip holds the frames
                # of a convolution; the module reads them so
                mb["obs"] = rows_op.gather_rows(
                    flat["obs"], idx, width=frame_size,
                    dtype=seen_as.dtype).reshape(*frame, -1)
            else:
                mb["obs"] = flat["obs"][idx].reshape(-1, *frame)
            return mb

        with jax.named_scope("sgd"):
            (params, opt_state, rng), (losses, auxes) = run_ppo_sgd(
                params, state.opt_state, rng,
                lambda p, mb: loss_fn(p, module, mb), make_mb,
                batch_loc, mb_loc, num_mb, config.num_sgd_iter, None,
                sharded=sharded, update_fn=update_fn)

        new_state = AnakinState(params, opt_state, env_states, obs,
                                mesh_util.wrap_rng(rng, sharded),
                                ep_ret, dsum, dcnt)
        metrics = {
            "total_loss": losses.mean(),
            "policy_loss": auxes["policy_loss"].mean(),
            "vf_loss": auxes["vf_loss"].mean(),
            "entropy": auxes["entropy"].mean(),
            "episode_return_sum": dsum,
            "episode_count": dcnt,
        }
        return new_state, metrics

    # No donate_argnums: freshly-inited zero leaves (opt mu/nu, counters) can
    # share deduped buffers, which XLA rejects as double-donation.  The state
    # here is tiny; donation pays off in the LM train step, not this one.
    if sharded and config.zero_sharding != "off":
        step = mesh_util.zero_train_step(train_step, mesh, state_specs)
    elif sharded:
        step = mesh_util.shard_train_step(train_step, mesh, state_specs)
    else:
        step = jax.jit(train_step)
    # The first call inside a lifecycle span that says which way the frames
    # of a minibatch go and what packs a rollout step's: the run's record of
    # choices made by shape.
    step = FirstCallSpan(step, "train.compile", "anakin_ppo",
                         frame_gather="rows_dma" if by_dma else "xla",
                         frame_pack="fold_tiles" if by_fold else "xla")
    return module, init_fn, step, batch_total


class PPO(Algorithm):
    _default_config_cls = PPOConfig
    _data_mesh_capable = True  # feedforward anakin only; guarded below

    # ---- anakin mode ----
    def _setup_anakin(self):
        if self.config.use_lstm and self.config.use_attention:
            raise ValueError("use_lstm and use_attention are exclusive")
        if self.config.use_lstm or self.config.use_attention:
            from ray_tpu.rllib.utils.mesh import reject_data_mesh

            reject_data_mesh(self.config, "recurrent/attention PPO")
        if self.config.use_lstm:
            from ray_tpu.rllib.algorithms.ppo_rnn import make_anakin_ppo_rnn

            (self.module, init_fn, self._train_step,
             self._steps_per_iter) = make_anakin_ppo_rnn(self.config)
        elif self.config.use_attention:
            from ray_tpu.rllib.algorithms.ppo_attn import make_anakin_ppo_attn

            (self.module, init_fn, self._train_step,
             self._steps_per_iter) = make_anakin_ppo_attn(self.config)
        else:
            (self.module, init_fn, self._train_step,
             self._steps_per_iter) = make_anakin_ppo(self.config)
        self._anakin_state = init_fn(self.config.seed)

    def evaluate(self, num_steps: int = 1000) -> Dict[str, Any]:
        """Extends the generic evaluator to the memory policies: the
        LSTM/attention modules need their carry/window threaded through
        the greedy rollout."""
        if self.config.mode == "anakin" and (self.config.use_lstm
                                             or self.config.use_attention):
            import jax

            from ray_tpu.rllib.env.jax_envs import make_jax_env

            if getattr(self, "_eval_rollout_fn", None) is None:
                env = make_jax_env(self.config.env) \
                    if isinstance(self.config.env, str) else self.config.env
                if self.config.use_lstm:
                    from ray_tpu.rllib.algorithms.ppo_rnn import \
                        make_rnn_eval_rollout

                    self._eval_rollout_fn = make_rnn_eval_rollout(
                        env, self.module, self.config.lstm_cell_size)
                else:
                    from ray_tpu.rllib.algorithms.ppo_attn import \
                        make_attn_eval_rollout

                    self._eval_rollout_fn = make_attn_eval_rollout(
                        env, self.module, self.config.attention_window)
                self._eval_rollout_key = jax.random.PRNGKey(
                    self.config.seed + 1)
            self._eval_rollout_key, k = jax.random.split(
                self._eval_rollout_key)
            r = self._eval_rollout_fn(self._anakin_state.params, k,
                                      num_steps)
            return {"episode_reward_mean": float(r)}
        return super().evaluate(num_steps)

    def _training_step_anakin(self) -> Dict[str, Any]:
        self._anakin_state, metrics = self._train_step(self._anakin_state)
        # ONE host fetch for every metric: each separate device->host read
        # costs a full transfer round-trip (~0.1s on some backends), so
        # per-scalar float() here would dominate the whole train step.  The
        # previous counter values are remembered host-side from last iter.
        metrics = {k: float(v) for k, v in jax.device_get(metrics).items()}
        prev_sum, prev_cnt = getattr(self, "_prev_counters", (0.0, 0.0))
        cum_sum = metrics.pop("episode_return_sum")
        cum_cnt = metrics.pop("episode_count")
        self._prev_counters = (cum_sum, cum_cnt)
        dsum, dcnt = cum_sum - prev_sum, cum_cnt - prev_cnt
        if dcnt > 0:
            self._ep_reward_ema = dsum / dcnt
        metrics["episode_reward_mean"] = getattr(self, "_ep_reward_ema",
                                                 float("nan"))
        metrics["num_env_steps_sampled_this_iter"] = self._steps_per_iter
        return metrics

    # ---- actor mode ----
    def _setup_actor_mode(self):
        from ray_tpu.rllib.core.learner import JaxLearner
        from ray_tpu.rllib.evaluation.worker_set import WorkerSet
        from ray_tpu.rllib.env.py_envs import make_py_env

        if self.config.use_lstm or self.config.use_attention:
            # Silently training a memoryless MLP on a memory task is the
            # worst failure mode — refuse loudly instead.
            raise NotImplementedError(
                "use_lstm/use_attention policies run in anakin mode only; "
                "the actor-path sampling stack is feedforward")
        probe = make_py_env(self.config.env)
        # for_env is the one place pixel-vs-flat trunk selection lives:
        # pixel envs get the CNN trunk fed raw uint8 frames (the rollout
        # workers keep the dtype; NatureCNN does the /255).
        spec = RLModuleSpec.for_env(probe, tuple(self.config.hiddens))
        example = spec.example_obs()
        self.module = spec.build()
        if hasattr(probe, "close"):  # dimension probe only — release now
            probe.close()
        tx = optax.chain(optax.clip_by_global_norm(self.config.grad_clip or 1e9),
                         optax.adam(self.config.lr))
        self.learner = JaxLearner(
            self.module,
            functools.partial(ppo_loss,
                              clip_param=self.config.clip_param,
                              vf_clip_param=self.config.vf_clip_param,
                              vf_loss_coeff=self.config.vf_loss_coeff,
                              entropy_coeff=self.config.entropy_coeff),
            optimizer=tx, example_obs=example, seed=self.config.seed)
        self.workers = WorkerSet(self.config, spec)
        self._stream = None
        if self.config.sample_streaming:
            from ray_tpu.rllib.evaluation.sample_stream import SampleStream

            self._stream = SampleStream(
                self.workers, kind="gae",
                max_in_flight_per_worker=self.config.max_in_flight_per_worker,
                max_weight_staleness=self.config.max_weight_staleness)
            # Version 1 lands before the first fragment dispatch (FIFO
            # mailboxes), so no worker ever samples with params=None.
            self._stream.publish_weights(self.learner.get_weights())
        else:
            self.workers.sync_weights(self.learner.get_weights())

    def _run_ppo_epochs(self, train_batch) -> Dict[str, Any]:
        """The shared SGD half of both actor paths: advantage
        normalization + shuffled minibatch epochs on the learner."""
        adv = train_batch["advantages"]
        train_batch["advantages"] = (adv - adv.mean()) / (adv.std() + 1e-8)
        metrics: Dict[str, Any] = {}
        for _ in range(self.config.num_sgd_iter):
            shuffled = train_batch.shuffle()
            for mb in shuffled.minibatches(
                    min(self.config.sgd_minibatch_size, len(shuffled))):
                metrics = self.learner.update(dict(mb))
        if metrics:
            from ray_tpu.rllib.core.learner import metrics_to_host

            metrics = metrics_to_host(metrics)
        return metrics

    def _training_step_actor(self) -> Dict[str, Any]:
        from ray_tpu.rllib.policy.sample_batch import SampleBatch

        if self._stream is None:
            # Legacy lockstep path (sample_streaming=False): barrier
            # sample -> train -> blocking weight sync.
            batches, ep_returns = self.workers.sample_sync()
            train_batch = SampleBatch.concat_samples(batches)
            metrics = self._run_ppo_epochs(train_batch)
            self.workers.sync_weights(self.learner.get_weights())
        else:
            # Streaming path: consume one fragment per worker slot as
            # they land — while the SGD epochs below run, every worker
            # still holds queued fragment work (the overlap the smoke
            # guards), and the new weights broadcast asynchronously.
            target = max(1, self.config.num_rollout_workers)
            batches, ep_returns = [], []
            for _ in range(target):
                frag = self._stream.next_fragment(timeout=120.0)
                if frag is None:
                    break
                batches.append(frag.batch)
                ep_returns.extend(frag.episode_returns)
            if not batches:
                raise ray_tpu.exceptions.RayTpuError(
                    "rollout stream produced no fragments within timeout")
            # Reuse last iteration's concat buffer (the learner consumed
            # it during the previous SGD epochs) — one batch-sized
            # allocation less per iteration.
            train_batch = SampleBatch.concat_samples_into(
                batches, getattr(self, "_train_buf", None))
            self._train_buf = train_batch
            metrics = self._run_ppo_epochs(train_batch)
            self._stream.publish_weights(self.learner.get_weights())
            st = self._stream.stats()
            metrics.update({
                "rollout_fragments_per_s": st["fragments_per_s"],
                "rollout_weight_lag_mean": st["weight_lag_mean"],
                "rollout_weight_lag_max": st["weight_lag_max"],
                "rollout_worker_idle_frac": st["worker_idle_frac"],
                "rollout_queue_depth": st["inflight"],
                "rollout_stale_dropped": st["stale_dropped"],
            })
        if ep_returns:
            self._ep_reward_ema = float(np.mean(ep_returns))
        metrics["episode_reward_mean"] = getattr(self, "_ep_reward_ema",
                                                 float("nan"))
        metrics["num_env_steps_sampled_this_iter"] = len(train_batch)
        return metrics
