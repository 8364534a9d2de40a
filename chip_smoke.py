#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that ray_tpu still starts on the chip.

    python chip_smoke.py              one chip: legs train, serve, rl
    python chip_smoke.py --chips 4    a four-chip host: the same legs over
                                      four chips, plus a four-process gang
    python chip_smoke.py --tiny       toy widths, Pallas interpreted, runs on
                                      a CPU to check the control flow; still
                                      exits non-zero, because no chip was used

Each leg drives one main path through the entry points a user calls, at the
full width of GPT-2-small (124M) or the Atari-resolution PPO, with weights
made from a seed:

  train  ray_tpu.init() -> JaxTrainer(ScalingConfig(use_tpu=True,
         chips_per_worker=N)) -> a donated AdamW step fed by
         get_dataset_shard("train").iter_device_batches, at 16x1024 and at
         4x4096.  The train worker also checks the compiled flash-attention
         kernels, forward and backward, against the XLA reference.
  serve  serve.run(serve.deployment(LLMServer, ray_actor_options=
         {"num_tpus": 1})) -> generate_many; greedy decode must repeat.
  rl     PPOConfig().anakin(...).resources(num_devices=N).build().train()
  gang   (--chips > 1 only) MeshGroup(N, resources_per_host={"TPU": 1}).

One process owns the chip at a time.  This parent never imports jax: it runs
each leg as a child in a process group of its own, and does not start the
next leg before every process of that group is gone.  In the train and serve
legs the child is the driver and a worker holds the chip; the rl leg's child
holds it itself.  A leg that fails, or that ran anywhere but on a TPU, makes
the exit code non-zero and no result line is printed.  The numbers printed
on the way are set-up facts, not metrics.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import re
import signal
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))

# The whole run must end inside 1200 s, compilation included.
BUDGET_S = 1100.0
LEG_CAP_S = {"train": 600.0, "serve": 400.0, "rl": 400.0, "gang": 300.0}

SIZES = {
    False: {
        # Steps are counted after one warm-up step each.
        "train_fits": [{"batch": 16, "seq": 1024, "steps": 5},
                       {"batch": 4, "seq": 4096, "steps": 2}],
        "vocab": 50257,
        "kernel_shapes": [(2, 1024, 12, 64), (1, 4096, 12, 64)],
        # tiny=False with no widths given IS gpt2_small: 12 layers, 12
        # heads, hidden 768, vocab 50257.
        "serve_model": {"tiny": False, "dtype": "bfloat16"},
        "serve_engine": {"max_slots": 8, "page_size": 16, "max_ctx": 1024},
        "prompt_lens": (32, 512, 8), "new_tokens": 32,
        "rl_env": "Breakout-Atari84-v0", "num_envs": 2048, "unroll": 64,
        "sgd_iters": 2, "minibatch": 8192,
    },
    True: {
        "train_fits": [{"batch": 4, "seq": 128, "steps": 2},
                       {"batch": 4, "seq": 256, "steps": 1}],
        "vocab": 512,
        "kernel_shapes": [(1, 256, 2, 64)],
        "serve_model": {"tiny": True, "dtype": "float32"},
        "serve_engine": {"max_slots": 4, "page_size": 16, "max_ctx": 128},
        "prompt_lens": (8, 48, 4), "new_tokens": 8,
        "rl_env": "CartPole-v1", "num_envs": 16, "unroll": 8,
        "sgd_iters": 1, "minibatch": 32,
    },
}


# ---------------------------------------------------------------------------
# Code that runs inside the process that holds the chip
# ---------------------------------------------------------------------------
def device_facts() -> dict:
    """What JAX reports in THIS process, printed here and handed back."""
    from importlib.metadata import version

    import jax

    devs = jax.devices()
    facts = {
        "platform": devs[0].platform,
        "device_kind": devs[0].device_kind,
        "device_count": len(devs),
        "jax": version("jax"), "jaxlib": version("jaxlib"),
        "libtpu": version("libtpu"),
        "pid": os.getpid(),
        "visible_chips": os.environ.get("TPU_VISIBLE_CHIPS"),
        "compile_cache": jax.config.jax_compilation_cache_dir,
    }
    print("[chip_smoke] " + " ".join(f"{k}={v}" for k, v in facts.items()),
          flush=True)
    return facts


def kernel_check(tiny: bool) -> list:
    """flash_attention against _xla_attention, forward and backward, bf16,
    causal.  Compiled (interpret=False) unless tiny."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.attention import _xla_attention, flash_attention

    def compare(name, got, want):
        got, want = got.astype(jnp.float32), want.astype(jnp.float32)
        err = float(jnp.max(jnp.abs(got - want)))
        ref = float(jnp.max(jnp.abs(want)))
        # Four bf16 ulps at the reference's largest magnitude: both sides
        # round their probabilities and outputs to bf16.
        tol = 2.0 ** -6 * max(1.0, ref)
        if not err <= tol:
            raise AssertionError(
                f"flash {name} off the XLA reference: max|diff|={err:.4g} "
                f"> {tol:.4g} (reference max {ref:.4g})")
        return err

    out = []
    for shape in SIZES[tiny]["kernel_shapes"]:
        keys = jax.random.split(jax.random.PRNGKey(0), 4)
        q, k, v, g = (jax.random.normal(kk, shape, jnp.bfloat16)
                      for kk in keys)

        def flash(q, k, v):
            return flash_attention(q, k, v, causal=True, interpret=tiny)

        def xla(q, k, v):
            return _xla_attention(q, k, v, True, None)

        def scalar(fn):
            return lambda q, k, v: jnp.sum(
                fn(q, k, v).astype(jnp.float32) * g.astype(jnp.float32))

        errs = {"fwd": compare("fwd", jax.jit(flash)(q, k, v),
                               jax.jit(xla)(q, k, v))}
        got = jax.jit(jax.grad(scalar(flash), argnums=(0, 1, 2)))(q, k, v)
        want = jax.jit(jax.grad(scalar(xla), argnums=(0, 1, 2)))(q, k, v)
        for name, a, b in zip(("dq", "dk", "dv"), got, want):
            errs[name] = compare(name, a, b)
        out.append({"shape": list(shape), "interpret": tiny,
                    "max_abs_err": errs})
        print(f"[chip_smoke] flash vs xla at {shape}: {errs}", flush=True)
    return out


def train_loop(config):
    """Runs inside the Train worker: GPT-2, AdamW, one donated step."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from ray_tpu.air import session
    from ray_tpu.models import GPT2, GPT2Config
    from ray_tpu.models.gpt2 import gpt2_loss_fn
    from ray_tpu.ops.attention import mha_attention
    from ray_tpu.parallel.mesh import MeshSpec
    from ray_tpu.parallel.sharding import batch_sharding
    from ray_tpu.train.jax import (compile_donated_step, get_mesh,
                                   prepare_train_state)

    tiny, chips = config["tiny"], config["chips"]
    B, S, steps = config["batch"], config["seq"], config["steps"]
    report = {"facts": device_facts(), "batch": B, "seq": S}
    if config["kernel_check"]:
        report["kernels"] = kernel_check(tiny)

    if tiny:
        cfg = GPT2Config.tiny(dtype=jnp.float32, max_position_embeddings=S)
    else:
        cfg = GPT2Config.gpt2_small(dtype=jnp.bfloat16,
                                    max_position_embeddings=max(1024, S))
    mesh = get_mesh(MeshSpec({"data": chips}))
    # One chip: the plain dispatch, as a one-chip user has it.  Several: the
    # step is a plain jit over a sharded batch, where the compiler refuses
    # to partition a Mosaic kernel by itself — attention is told the mesh.
    model = GPT2(cfg, attn_fn=functools.partial(mha_attention, mesh=mesh)
                 if chips > 1 else None)
    key = jax.random.PRNGKey(0)
    # Parameter shapes do not depend on the batch: init on a sliver.
    params = jax.jit(lambda k: model.init(
        k, jnp.zeros((chips, 8), jnp.int32))["params"])(key)
    params = prepare_train_state(params, mesh)
    report["n_params"] = int(sum(
        x.size for x in jax.tree_util.tree_leaves(params)))
    tx = optax.adamw(3e-4)
    # Placed like the parameters: the step hands its carry back committed to
    # the mesh, and a carry that went in any other way compiles twice.
    opt = prepare_train_state(jax.jit(tx.init)(params), mesh)

    def loss_of(params, ids, model=model):
        return gpt2_loss_fn(params, model.apply, {"input_ids": ids})

    def step_impl(params, opt, ids):
        loss, grads = jax.value_and_grad(loss_of)(params, ids)
        updates, opt = tx.update(grads, opt, params)
        return optax.apply_updates(params, updates), opt, loss

    step = compile_donated_step(step_impl, carry_argnums=(0, 1))
    # The same placement prepare_batch does, applied by the prefetcher.
    batches = iter(session.get_dataset_shard("train").iter_device_batches(
        B, sharding=batch_sharding(mesh, 2)))
    ids = next(batches)["tokens"]
    assert ids.shape == (B, S), ids.shape

    # The flash path must be IN the step, not its XLA stand-in.
    lowered = step.lower(params, opt, ids)
    report["mosaic_in_step"] = "tpu_custom_call" in lowered.as_text()
    if not tiny:
        assert report["mosaic_in_step"], \
            "the lowered train step has no Mosaic custom call"
    if chips > 1:
        # What the partitioner made of the kernel under a data-sharded
        # batch: result shapes of its calls, per device (B*H rows lead).
        report["flash_call_shapes"] = sorted(set(re.findall(
            r"= (\S+) custom-call\([^\n]*custom_call_target=\"tpu_custom_call\"",
            lowered.compile().as_text())))[:8]
        one = jax.devices()[0]
        report["loss_one_chip"] = float(jax.jit(
            functools.partial(loss_of, model=GPT2(cfg)))(
                jax.device_put(params, one),
                jax.device_put(np.asarray(ids), one)))

    t0 = time.perf_counter()
    params, opt, loss = step(params, opt, ids)  # warm-up: compiles
    losses = [loss]
    jax.block_until_ready(loss)
    report["first_step_s"] = round(time.perf_counter() - t0, 2)
    t0 = time.perf_counter()
    for _ in range(steps):
        ids = next(batches)["tokens"]
        params, opt, loss = step(params, opt, ids)
        losses.append(loss)
    losses = [float(x) for x in jax.device_get(losses)]  # the barrier
    report["steps_s"] = round(time.perf_counter() - t0, 2)
    report["losses"] = losses
    assert all(np.isfinite(losses)), f"non-finite loss: {losses}"
    assert step._cache_size() == 1, \
        f"the step compiled {step._cache_size()} times"

    leaf = jax.tree_util.tree_leaves(params)[0]
    report["batch_devices"] = len({s.device for s in ids.addressable_shards})
    report["param_devices"] = len({s.device for s in leaf.addressable_shards})
    assert report["batch_devices"] == report["param_devices"] == chips, report
    if chips > 1:
        assert abs(losses[0] - report["loss_one_chip"]) < 2e-2, \
            (losses[0], report["loss_one_chip"])
        if not tiny:  # the CPU backend keeps no memory statistics
            in_use = [d.memory_stats()["bytes_in_use"]
                      for d in jax.local_devices()]
            report["bytes_in_use"] = in_use
            assert all(b > 0 for b in in_use), in_use
    session.report({"smoke": report})


# ---------------------------------------------------------------------------
# Legs: each runs in a child process of its own
# ---------------------------------------------------------------------------
def _init_cluster(args):
    import ray_tpu

    # The real run lets init() find the chips.  A CPU has none to find, so
    # --tiny declares them: the workers are started the same way and come
    # up on the CPU, which the legs report.
    ray_tpu.init(**({"num_tpus": args.chips} if args.tiny else {}))
    print(f"[chip_smoke] cluster: {ray_tpu.cluster_resources()}", flush=True)


def _assert_driver_off_chip():
    from jax._src import xla_bridge

    assert not xla_bridge.backends_are_initialized(), \
        "the driver initialised a JAX backend: it would hold the chip"


def leg_train(args) -> dict:
    import numpy as np

    import ray_tpu.data as rdata
    from ray_tpu.air.config import ScalingConfig
    from ray_tpu.train import JaxTrainer
    from ray_tpu.train.jax.config import JaxConfig

    z = SIZES[args.tiny]
    _init_cluster(args)
    jax_config = (JaxConfig(platform="cpu", local_device_count=args.chips)
                  if args.tiny else JaxConfig())
    fits = []
    for i, shape in enumerate(z["train_fits"]):
        rows = shape["batch"] * (shape["steps"] + 1)
        tokens = np.random.default_rng(i).integers(
            0, z["vocab"], size=(rows, shape["seq"]), dtype=np.int32)
        # One fit per shape, as two users would: the first fit's worker has
        # let go of the chip before the second fit's worker asks for it.
        result = JaxTrainer(
            train_loop,
            train_loop_config={**shape, "tiny": args.tiny,
                               "chips": args.chips, "kernel_check": i == 0},
            datasets={"train": rdata.from_numpy({"tokens": tokens})},
            jax_config=jax_config,
            scaling_config=ScalingConfig(num_workers=1, use_tpu=True,
                                         chips_per_worker=args.chips),
        ).fit()
        if result.error is not None:
            raise result.error
        fits.append(result.metrics["smoke"])
    _assert_driver_off_chip()
    return {"facts": fits[0]["facts"], "fits": fits}


def leg_serve(args) -> dict:
    import numpy as np

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve.llm_engine import LLMServer, generate_many

    class SmokeLLMServer(LLMServer):
        def device_facts(self):
            return device_facts()

    z = SIZES[args.tiny]
    n = args.chips  # one one-chip replica per chip
    _init_cluster(args)
    handle = serve.run(serve.deployment(
        SmokeLLMServer, name="llm", num_replicas=n,
        ray_actor_options={"num_tpus": 1},
    ).bind("gpt2", z["serve_model"], **z["serve_engine"]))

    rng = np.random.default_rng(0)
    lo, hi, count = z["prompt_lens"]
    prompts = [list(map(int, rng.integers(0, z["vocab"], size=int(p))))
               for p in np.linspace(lo, hi, count)]
    new = z["new_tokens"]
    outs = generate_many(handle, prompts, max_new_tokens=new, timeout=300.0)
    assert [len(o) for o in outs] == [new] * len(prompts), \
        [len(o) for o in outs]
    again = generate_many(handle, prompts[:1], max_new_tokens=new,
                          timeout=120.0)
    assert again[0] == outs[0], "a repeated greedy request changed its tokens"

    # Idle replicas are picked round-robin: n calls in a row reach each once.
    def each(method, *a):
        return [ray_tpu.get(handle.method(method).remote(*a), timeout=300.0)
                for _ in range(n)]

    if n > 1:  # every replica answers, whatever the affinity routing chose
        for got in each("generate_batch", prompts[:1], new, None, False):
            assert got[0] == outs[0], "replicas disagree on a greedy decode"
    facts, stats = each("device_facts"), each("stats")
    assert len({f["pid"] for f in facts}) == n, facts
    if not args.tiny and n > 1:
        assert len({f["visible_chips"] for f in facts}) == n, facts
    for f, s in zip(facts, stats):
        assert s["platform"] == f["platform"], (s["platform"], f)
        assert s["device_kind"] == f["device_kind"], (s["device_kind"], f)
        assert s["decode_cache_size"] == 1, s["decode_cache_size"]
        assert s["completed"] >= 1, s
    serve.shutdown()
    _assert_driver_off_chip()
    return {"facts": facts[0], "replicas": facts,
            "requests": len(prompts), "new_tokens": new,
            "completed": [s["completed"] for s in stats],
            "prefill_buckets": [s["prefill_buckets"] for s in stats]}


def leg_rl(args) -> dict:
    import numpy as np

    if args.tiny:  # a CPU has one device unless told otherwise
        from ray_tpu.parallel.mesh_group import force_host_device_count

        os.environ["XLA_FLAGS"] = force_host_device_count(
            os.environ.get("XLA_FLAGS", ""), args.chips)
    facts = device_facts()
    if not args.tiny and facts["platform"] != "tpu":
        # Before build(): 2048 Atari-resolution envs on a CPU do not end.
        raise RuntimeError(
            f"no TPU: jax.devices() reports platform={facts['platform']}")
    import jax

    from ray_tpu.rllib import PPOConfig

    z = SIZES[args.tiny]
    algo = (PPOConfig().environment(z["rl_env"])
            .anakin(num_envs=z["num_envs"], unroll_length=z["unroll"])
            .training(num_sgd_iter=z["sgd_iters"],
                      sgd_minibatch_size=z["minibatch"])
            .resources(num_devices=args.chips)
            .build())
    iters = []
    for _ in range(3):
        m = algo.train()
        assert np.isfinite(m["total_loss"]), f"non-finite RL loss: {m}"
        iters.append({"total_loss": m["total_loss"],
                      "seconds": round(m["time_this_iter_s"], 2)})
    want = 3 * z["num_envs"] * z["unroll"]
    assert m["num_env_steps_sampled"] == want, \
        (m["num_env_steps_sampled"], want)
    # Parameters are replicated over the data axis: after sharded steps
    # every device must still hold the same bytes.
    leaf = jax.tree.leaves(algo._anakin_state.params)[0]
    assert len(leaf.addressable_shards) == args.chips
    copies = {np.asarray(s.data).tobytes() for s in leaf.addressable_shards}
    assert len(copies) == 1, "params drifted across devices"
    return {"facts": facts, "iters": iters, "env_steps": want}


def leg_gang(args) -> dict:
    """N chip-owning processes on one host as ONE jax world: either it
    forms, or the rendezvous refuses with a message.  It must not hang."""
    import ray_tpu
    from ray_tpu.parallel.mesh_group import MeshGroup

    _init_cluster(args)
    t0 = time.monotonic()
    try:
        group = MeshGroup(args.chips, resources_per_host={"TPU": 1},
                          platform="cpu" if args.tiny else None,
                          local_device_count=1 if args.tiny else None,
                          bootstrap_timeout=120.0)
    except RuntimeError as e:
        if "did not form one jax world" not in str(e):
            raise
        return {"outcome": "refused at rendezvous", "message": str(e),
                "seconds": round(time.monotonic() - t0, 1)}
    info = group.device_info
    group.shutdown()
    assert all(i["global_devices"] == args.chips for i in info), info
    return {"outcome": "one world", "device_info": info,
            "seconds": round(time.monotonic() - t0, 1)}


LEGS = {"train": leg_train, "serve": leg_serve, "rl": leg_rl,
        "gang": leg_gang}


def run_leg_here(args) -> int:
    """Child entry: run one leg in this process, hand the result up."""
    sys.path.insert(0, HERE)
    # Before jax is imported anywhere: the compile cache is placed through
    # the environment, and the leg's workers inherit it.
    from ray_tpu._private.jax_env import ensure_compile_cache

    ensure_compile_cache()
    import ray_tpu

    try:
        result = LEGS[args.leg](args)
    finally:
        if ray_tpu.is_initialized():
            ray_tpu.shutdown()  # returns once the workers are reaped
    with os.fdopen(args.result_fd, "w") as f:
        json.dump(result, f)
    return 0


# ---------------------------------------------------------------------------
# Parent: never imports jax, owns no chip
# ---------------------------------------------------------------------------
def _group_alive(pgid: int) -> bool:
    """Any process of the group that is not a zombie (a zombie holds no
    chip, and whether one gets reaped is up to its adoptive parent)."""
    for pid in os.listdir("/proc"):
        if not pid.isdigit():
            continue
        try:
            with open(f"/proc/{pid}/stat") as f:
                state, _ppid, pgrp = f.read().rsplit(")", 1)[1].split()[:3]
        except OSError:
            continue  # exited while we looked
        if int(pgrp) == pgid and state != "Z":
            return True
    return False


def _end_group(pgid: int) -> None:
    """Kill what is left of a leg's process group and wait until it is
    gone: the next leg's chip owner must find the chip free."""
    deadline = time.monotonic() + 60.0
    while _group_alive(pgid):
        try:
            os.killpg(pgid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        if time.monotonic() > deadline:
            raise RuntimeError(f"process group {pgid} would not die")
        time.sleep(0.1)


def run_leg(name: str, args, deadline: float):
    """Run one leg as a child in its own process group.  Returns its result
    dict, or None when it failed, was killed at its time limit, or left no
    result."""
    timeout = min(LEG_CAP_S[name], deadline - time.monotonic())
    if timeout <= 0:
        print(f"[chip_smoke] leg {name}: no time left", flush=True)
        return None
    r, w = os.pipe()
    cmd = [sys.executable, os.path.abspath(__file__), "--leg", name,
           "--chips", str(args.chips), "--result-fd", str(w)]
    if args.tiny:
        cmd.append("--tiny")
    t0 = time.monotonic()
    proc = subprocess.Popen(cmd, pass_fds=(w,), start_new_session=True,
                            cwd=HERE)
    os.close(w)
    try:
        rc = proc.wait(timeout=timeout)
    except subprocess.TimeoutExpired:
        rc = f"killed at its {timeout:.0f}s limit"
    finally:
        if proc.poll() is None:
            proc.kill()
        proc.wait()
        _end_group(proc.pid)
    with os.fdopen(r) as f:
        raw = f.read()
    took = time.monotonic() - t0
    if rc != 0 or not raw:
        print(f"[chip_smoke] leg {name}: FAILED (exit {rc}) after "
              f"{took:.0f}s", flush=True)
        return None
    result = json.loads(raw)
    print(f"[chip_smoke] leg {name}: done in {took:.0f}s: "
          f"{json.dumps(result)}", flush=True)
    return result


def result_line(facts: dict) -> str:
    """The last line of standard output: exactly these keys, nothing else
    (the driver's check reads it)."""
    return json.dumps({"ok": True,
                       "device": {"platform": facts["platform"],
                                  "kind": facts["device_kind"],
                                  "count": facts["device_count"]}})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1,
                    help="chips the legs spread over (1, or 4 on a "
                         "four-chip host)")
    ap.add_argument("--tiny", action="store_true",
                    help="toy widths on whatever platform there is; never "
                         "exits 0 without a TPU")
    ap.add_argument("--leg", choices=sorted(LEGS), help=argparse.SUPPRESS)
    ap.add_argument("--result-fd", type=int, help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.leg:
        return run_leg_here(args)

    t0 = time.monotonic()
    names = ["train", "serve", "rl"] + (["gang"] if args.chips > 1 else [])
    results = {n: run_leg(n, args, t0 + BUDGET_S) for n in names}
    assert "jax" not in sys.modules, "the parent imported jax"
    print(f"[chip_smoke] total {time.monotonic() - t0:.0f}s", flush=True)

    failed = [n for n in names if results[n] is None]
    if failed:
        print(f"[chip_smoke] FAILED legs: {failed}", flush=True)
        return 1
    devices = {n: results[n]["facts"] for n in ("train", "serve", "rl")}
    off_chip = {n: f["platform"] for n, f in devices.items()
                if f["platform"] != "tpu"}
    if off_chip:
        print(f"[chip_smoke] FAILED: no TPU — legs ran on {off_chip}",
              flush=True)
        return 1
    # Set-up facts, not metrics; this PR claims no gain.
    print("[chip_smoke] summary: " + json.dumps(
        {"legs": {n: f["platform"] for n, f in devices.items()},
         "claim": None}), flush=True)
    # The device as a plain process on this machine sees it (the rl leg's):
    # the train and serve workers see only their share of the host's chips.
    print(result_line(devices["rl"]), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
