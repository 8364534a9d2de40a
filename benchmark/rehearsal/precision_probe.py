#!/usr/bin/env python3
"""Does ``correct`` notice a lower precision?  For the train and the RL
configuration, the driver's own comparison with the plain reference, run
three times in the process that holds the chip: on the program as it is, on
the program with its weights rounded to bfloat16, and with its weights rounded
to 8 bits (float8_e4m3fn, scaled per tensor to the type's range).  The
reference keeps the true weights each time.  The tolerances in the traffic
files are set from what this prints: some times the first line's errors, and
under the last line's.  Not a measurement of speed; prints no result line.

    python3 benchmark/rehearsal/precision_probe.py [--tiny] [cell ...]
"""
import argparse
import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)
SEED = 3000000011


def lowered(params, how):
    import jax
    import jax.numpy as jnp

    def leaf(x):
        if how == "bfloat16":
            return x.astype(jnp.bfloat16).astype(x.dtype)
        scale = jnp.max(jnp.abs(x)) / 448.0 + 1e-30  # e4m3's largest finite
        return ((x / scale).astype(jnp.float8_e4m3fn).astype(x.dtype)
                * scale)

    return jax.tree.map(leaf, params)


def probe_train_lm(cell, config, traffic, tiny):
    import jax
    import numpy as np

    from benchmark import common
    from benchmark.drivers import train_lm
    from ray_tpu.parallel.mesh import MeshSpec
    from ray_tpu.train.jax import get_mesh

    mesh = get_mesh(MeshSpec({"data": 1}))
    _m, init, program, _s = train_lm.build_step(config, traffic, mesh, 1)
    params, _opt = jax.jit(init)(jax.random.PRNGKey(common.jax_seed(SEED)))
    sample = np.random.default_rng(SEED).integers(
        0, config["vocab_size"],
        (traffic["reference_sequences"], traffic["seq"]), dtype=np.int32)
    ref = common.load_module("reference", cell["config"])
    return lambda p: train_lm.reference_check(
        program, ref, config, traffic, params, sample, program_params=p
    ), params


def probe_rl_anakin(cell, config, traffic, tiny):
    from benchmark import common
    from benchmark.drivers import rl_anakin

    algo = rl_anakin.build_algo(config, 1, SEED)
    ref = common.load_module("reference", cell["config"])
    return lambda p: rl_anakin.reference_check(
        algo, config, traffic, SEED, ref, program_params=p
    ), algo._anakin_state.params


def one(name: str, tiny: bool) -> int:
    from benchmark import common
    from benchmark.rehearsal import rehearse

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cell = next(w for w in manifest["workloads"] if w["name"] == name)
    entry = next(c for c in manifest["configs"] if c["name"] == cell["config"])
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    traffic = common.load_traffic(cell["traffic"])
    if tiny:
        common.merge({"config": config, "traffic": traffic},
                     rehearse.tiny_overrides(name))
    device = common.device_record(allow_cpu=tiny)
    probe = globals().get("probe_" + traffic["driver"])
    if probe is None:
        print(f"[probe] no probe for driver {traffic['driver']}")
        return 1
    check, params = probe(cell, config, traffic, tiny)
    for how in ("as it is", "bfloat16", "float8_e4m3fn"):
        out = check(None if how == "as it is" else lowered(params, how))
        out.pop("reference_terms", None)
        print("PROBE " + json.dumps({
            "cell": name, "platform": device["platform"],
            "program_weights": how, **out}), flush=True)
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("cells", nargs="*",
                    default=["gpt2m_train_1k", "ppo_atari84_anakin"])
    ap.add_argument("--tiny", action="store_true",
                    help="the rehearsal's toy sizes, on the CPU")
    ap.add_argument("--one", help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.one:
        return one(args.one, args.tiny)
    rc = 0
    for cell in args.cells:  # a process each: each holds the chip alone
        env = dict(os.environ)
        if args.tiny:
            env["JAX_PLATFORMS"] = "cpu"
        rc |= subprocess.call(
            [sys.executable, os.path.abspath(__file__), "--one", cell]
            + (["--tiny"] if args.tiny else []), env=env, cwd=ROOT)
    return rc


if __name__ == "__main__":
    sys.exit(main())
