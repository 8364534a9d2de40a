"""The asynchronous rollout plane (ISSUE 5): streaming sampler liveness
under worker death, the weight-staleness consumption gate, parallel
VectorEnv step-equivalence, and the preallocated-buffer fragment loop's
byte-identity with the legacy append+stack path."""
import os
import signal
import time

import numpy as np
import pytest

import ray_tpu


@pytest.fixture(scope="module")
def ray_cluster():
    ray_tpu.init(num_cpus=8, object_store_memory=256 * 1024**2,
                 ignore_reinit_error=True)
    yield
    ray_tpu.shutdown()


def _make_stream(num_workers=2, num_envs=2, fragment=8, k=2, staleness=None):
    from ray_tpu.rllib import PPOConfig
    from ray_tpu.rllib.core.rl_module import RLModuleSpec
    from ray_tpu.rllib.env.py_envs import make_py_env
    from ray_tpu.rllib.evaluation.sample_stream import SampleStream
    from ray_tpu.rllib.evaluation.worker_set import WorkerSet

    config = (PPOConfig().environment("CartPole-v1")
              .rollouts(num_rollout_workers=num_workers,
                        num_envs_per_worker=num_envs,
                        rollout_fragment_length=fragment, mode="actor")
              .training(model={"fcnet_hiddens": [16]}))
    spec = RLModuleSpec.for_env(make_py_env("CartPole-v1"),
                                tuple(config.hiddens))
    workers = WorkerSet(config, spec)
    stream = SampleStream(workers, kind="gae",
                          max_in_flight_per_worker=k,
                          max_weight_staleness=staleness)
    import jax

    module = spec.build()
    params = module.init(jax.random.PRNGKey(0), spec.example_obs())
    return workers, stream, params


def test_stream_liveness_under_worker_sigkill(ray_cluster):
    """A worker SIGKILLed mid-fragment must not stall the stream: the
    failed futures feed the WorkerSet strike/replace path and fragments
    keep flowing.  Episode returns ride the fragment that observed them,
    so every consumed fragment satisfies sum(dones) == len(returns) —
    a double-counted (or replayed) harvest would break the equality."""
    workers, stream, params = _make_stream(fragment=8)
    try:
        stream.publish_weights(params)
        for _ in range(2):
            frag = stream.next_fragment(timeout=60.0)
            assert frag is not None
            assert int(frag.batch["dones"].sum()) == \
                len(frag.episode_returns)
        victim_pid = ray_tpu.get(workers.workers[0].pid.remote())
        os.kill(victim_pid, signal.SIGKILL)
        # Until six more fragments have come AND the death has been seen:
        # how many fragments the survivor turns out before the head
        # notices the dead process is the host's doing, not the stream's.
        consumed = 0
        deadline = time.monotonic() + 120.0
        while (consumed < 6 or stream.failures_seen < 1) \
                and time.monotonic() < deadline:
            frag = stream.next_fragment(timeout=60.0)
            if frag is None:
                break
            assert int(frag.batch["dones"].sum()) == \
                len(frag.episode_returns)
            consumed += 1
        assert consumed >= 6, (
            f"stream stalled after SIGKILL: {consumed} fragments, "
            f"stats={stream.stats()}")
        assert stream.failures_seen >= 1
    finally:
        stream.close()
        workers.stop()


@pytest.mark.timeout(120)
def test_stream_staleness_bound_enforced(ray_cluster):
    """With max_weight_staleness=1, fragments produced under weights more
    than one version behind the latest publish are dropped before the
    learner sees them.  The actor mailbox is FIFO, so the three fragments
    dispatched before the v2/v3 publishes (four went out, one came back)
    carry v1 whenever they run, and everything dispatched after carries
    v3: the stale set is those three, however far the workers had got
    when the publishes landed.  The consumer takes the oldest dispatch
    first, so it reaches all three however fast the other worker is."""
    workers, stream, params = _make_stream(fragment=4, k=2, staleness=1)
    try:
        stream.publish_weights(params)           # v1
        first = stream.next_fragment(timeout=60.0)
        assert first is not None and first.weights_version == 1
        assert stream.inflight == 3
        stream.publish_weights(params)           # v2
        stream.publish_weights(params)           # v3
        seen = {0: 0, 1: 0}
        while stream.stale_dropped < 3 or min(seen.values()) < 2:
            frag = stream.next_fragment(timeout=60.0)
            assert frag is not None, stream.stats()
            # The gate: nothing older than current - 1 is ever consumed.
            assert stream.weights_version - frag.weights_version <= 1, \
                stream.stats()
            seen[frag.worker_index] += 1
        # Every drop is counted against the version it carried: the three
        # v1 fragments and nothing else, and both workers kept being read.
        assert stream.stats()["stale_dropped_versions"] == {1: 3}, \
            stream.stats()
    finally:
        stream.close()
        workers.stop()


def test_stream_broadcast_is_one_put_per_version(ray_cluster):
    """Versioned broadcast cost model: K workers borrow ONE object-store
    ref per published version (not one put per worker)."""
    workers, stream, params = _make_stream(num_workers=2, fragment=4)
    try:
        puts = []
        orig_put = ray_tpu.put

        def counting_put(value):
            puts.append(1)
            return orig_put(value)

        ray_tpu.put = counting_put
        try:
            for _ in range(3):
                stream.publish_weights(params)
        finally:
            ray_tpu.put = orig_put
        assert len(puts) == 3, f"{len(puts)} puts for 3 versions"
        frag = stream.next_fragment(timeout=60.0)
        assert frag is not None and frag.weights_version >= 1
    finally:
        stream.close()
        workers.stop()


# ---- parallel VectorEnv ---------------------------------------------------

def _rollout_trajectory(mode, steps=40, num_envs=5, seed=11):
    from ray_tpu.rllib.env.py_envs import PyCartPole, VectorEnv

    v = VectorEnv(lambda: PyCartPole(), num_envs, seed=seed, mode=mode,
                  num_workers=2)
    try:
        out = [v.reset_all()]
        rng = np.random.default_rng(3)
        for _ in range(steps):
            a = rng.integers(0, 2, num_envs)
            obs, rew, done, _ = v.step(a)
            out.append((obs, rew, done))
        return out
    finally:
        v.close()


def test_threaded_vector_env_step_equivalence():
    serial = _rollout_trajectory("serial")
    threaded = _rollout_trajectory("thread")
    assert np.array_equal(serial[0], threaded[0])
    for s, t in zip(serial[1:], threaded[1:]):
        for a, b in zip(s, t):
            assert a.dtype == b.dtype
            assert np.array_equal(a, b)


def test_subprocess_vector_env_step_equivalence():
    serial = _rollout_trajectory("serial", steps=25)
    sub = _rollout_trajectory("subprocess", steps=25)
    assert np.array_equal(serial[0], sub[0])
    for s, t in zip(serial[1:], sub[1:]):
        for a, b in zip(s, t):
            assert a.dtype == b.dtype
            assert np.array_equal(a, b)


def test_vector_env_close_reaps_subprocesses():
    from ray_tpu.rllib.env.py_envs import PyCartPole, VectorEnv

    v = VectorEnv(lambda: PyCartPole(), 4, seed=0, mode="subprocess",
                  num_workers=2)
    v.reset_all()
    procs = list(v._procs)
    v.close()
    for p in procs:
        assert not p.is_alive()


# ---- preallocated fragment buffers ---------------------------------------

def _fake_act(obs, key):
    """Deterministic numpy policy: ignores the key, exercises every
    column dtype (int actions, float32 logp/values)."""
    s = obs.sum(axis=-1)
    action = (s > 0).astype(np.int32)
    logp = np.full(obs.shape[0], -0.69, np.float32)
    value = s.astype(np.float32)
    return action, logp, value


def test_prealloc_fragment_byte_identical_to_append_stack():
    from ray_tpu.rllib.env.py_envs import PyCartPole, VectorEnv
    from ray_tpu.rllib.evaluation.worker_set import (
        FragmentBuffers,
        collect_fragment,
    )

    T, N = 12, 4
    keys = [None] * T

    def run(bufs):
        env = VectorEnv(lambda: PyCartPole(), N, seed=5)
        obs = env.reset_all().astype(np.float32)
        ep = np.zeros(N)
        completed = []
        last_obs, cols = collect_fragment(
            env, _fake_act, obs, keys, ep, completed, bufs=bufs,
            cast=lambda o: o.astype(np.float32))
        env.close()
        return last_obs, cols, completed

    obs_a, legacy, comp_a = run(None)
    obs_b, prealloc, comp_b = run(FragmentBuffers(T))
    assert comp_a == comp_b
    assert obs_a.tobytes() == obs_b.tobytes()
    assert set(legacy) == set(prealloc)
    for k in legacy:
        assert legacy[k].dtype == prealloc[k].dtype, k
        assert legacy[k].shape == prealloc[k].shape, k
        assert legacy[k].tobytes() == prealloc[k].tobytes(), \
            f"column {k} differs between prealloc and append+stack"


def test_fragment_buffers_reused_across_fragments():
    """The second fragment writes into the SAME arrays (no per-fragment
    allocation) — the halved-copies claim."""
    from ray_tpu.rllib.env.py_envs import PyCartPole, VectorEnv
    from ray_tpu.rllib.evaluation.worker_set import (
        FragmentBuffers,
        collect_fragment,
    )

    env = VectorEnv(lambda: PyCartPole(), 3, seed=1)
    obs = env.reset_all().astype(np.float32)
    bufs = FragmentBuffers(6)
    ep, completed = np.zeros(3), []
    obs, cols1 = collect_fragment(env, _fake_act, obs, [None] * 6, ep,
                                  completed, bufs=bufs,
                                  cast=lambda o: o.astype(np.float32))
    ids1 = {k: id(v) for k, v in cols1.items()}
    obs, cols2 = collect_fragment(env, _fake_act, obs, [None] * 6, ep,
                                  completed, bufs=bufs,
                                  cast=lambda o: o.astype(np.float32))
    assert {k: id(v) for k, v in cols2.items()} == ids1
    env.close()


def test_concat_samples_into_reuses_buffers():
    from ray_tpu.rllib.policy.sample_batch import SampleBatch

    def frags():
        return [SampleBatch({"obs": np.arange(8, dtype=np.float32
                                              ).reshape(4, 2) + i,
                             "rewards": np.full(4, float(i), np.float32)})
                for i in range(3)]

    a = SampleBatch.concat_samples_into(frags(), None)
    ref = SampleBatch.concat_samples(frags())
    for k in ref:
        assert np.array_equal(a[k], ref[k])
    ids = {k: id(v) for k, v in a.items()}
    b = SampleBatch.concat_samples_into(frags(), a)
    assert {k: id(v) for k, v in b.items()} == ids  # arrays reused
    for k in ref:
        assert np.array_equal(b[k], ref[k])
    # Shape change falls back to fresh allocation, correctly.
    bigger = [SampleBatch({"obs": np.ones((6, 2), np.float32),
                           "rewards": np.ones(6, np.float32)})]
    c = SampleBatch.concat_samples_into(bigger, b)
    assert len(c) == 6 and id(c["obs"]) != ids["obs"]
