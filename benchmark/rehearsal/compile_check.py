#!/usr/bin/env python3
"""Compile the train step at the real size for a described v5e:2x2, without
a chip (on-chip-measurement guide, section 2): what the TPU's compiler
refuses here costs no chip time, and ``memory_analysis()`` says which
per-chip batch fits.  Nothing runs, so this gives no time and no result.

    JAX_PLATFORMS=cpu python3 benchmark/rehearsal/compile_check.py \
        --cell gpt2m_train_1k --batches 8 12 16
"""
import argparse
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cell", default="gpt2m_train_1k")
    ap.add_argument("--batches", type=int, nargs="*")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from benchmark import common
    from benchmark.drivers import train_lm

    jax.config.update("jax_enable_compilation_cache", False)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cell = next(w for w in manifest["workloads"] if w["name"] == args.cell)
    config = common.load_json("configs", cell["config"] + ".json")
    traffic = common.load_json("traffic", cell["traffic"] + ".json")
    chips = cell["chips"]
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    mesh = Mesh(np.array(topo.devices[:chips]), ("data",))
    rep = NamedSharding(mesh, PartitionSpec())
    rows = NamedSharding(mesh, PartitionSpec("data"))
    _m, init, _l, _s = train_lm.build_step(config, traffic, mesh, chips)
    params, opt = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=rep),
        jax.eval_shape(init, jax.random.PRNGKey(0)))
    # jax.default_backend() is the CPU here, so the program's own dispatch
    # would pick the XLA path: name the flash path, which the chip takes.
    config["train"]["use_flash"] = True
    _m, _i, _l, step = train_lm.build_step(config, traffic, mesh, chips)
    for b in args.batches or [traffic["per_chip_batch"]]:
        ids = jax.ShapeDtypeStruct((b * chips, traffic["seq"]), jnp.int32,
                                   sharding=rows)
        try:
            compiled = step.lower(params, opt, ids).compile()
        except Exception as e:  # noqa: BLE001 — the compiler's refusal
            print(f"per-chip batch {b} on {chips} chip(s): REFUSED: "
                  f"{str(e)[:300]}", flush=True)
            continue
        ma, text = compiled.memory_analysis(), compiled.as_text()
        total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                 + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
        print(f"per-chip batch {b} on {chips} chip(s): compiled; per device "
              f"args {ma.argument_size_in_bytes / 2**30:.2f} GiB, temp "
              f"{ma.temp_size_in_bytes / 2**30:.2f} GiB, aliased "
              f"{ma.alias_size_in_bytes / 2**30:.2f} GiB, total "
              f"{total / 2**30:.2f} GiB; rematerialised instructions "
              f"{text.count('.remat')}, mosaic calls "
              f"{text.count('tpu_custom_call')}, all-reduce "
              f"{text.count('all-reduce(') + text.count('all-reduce-start(')}",
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
