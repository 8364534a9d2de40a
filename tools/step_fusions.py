"""What a train cell's or the anakin PPO cell's step is made of, read off the
compiled program without a chip: the step compiled for a described
``v5e:2x2`` (the route of ``benchmark/rehearsal/compile_check.py``), the
operations of its entry computation and of its ``while`` bodies (each as
often as the loops around it turn) grouped as a profile's breakdown names
them (``benchmark/trace_reduce.py::op_key``), and its collectives with
their place in the schedule.

    JAX_PLATFORMS=cpu python3 tools/step_fusions.py --cell gpt2m_train_1k \
        [--min-ms 1] [--shape 8,1023,50257] [--save step.hlo.txt]
    JAX_PLATFORMS=cpu python3 tools/step_fusions.py --cell ppo_atari84_anakin
    python3 tools/step_fusions.py --text step.hlo.txt

A line a group: how many a step, the compiler's cost model for all of them
(``estimated_cycles`` at 1.5 GHz: an estimate and never a measurement; 0-35%
above what traced runs read on most operations, several times above on a
few, and on the PPO cell's byte copies four times off either way, a
transposition too high and a gather too low: PERF.md section 5; a Pallas
kernel, ``tpu_custom_call``, has no estimate at all, so the PPO cell's frame
path, ``gather_rows`` a minibatch and ``fold_tiles`` a rollout step, is
listed by name after the groups, each kernel with the loop body it stands
in, and the anakin step is built as on a TPU backend, which is what chooses
those kernels),
whether a matmul (``convolution``) is fused inside, and the ``op_name`` of
the first.  ``--shape`` lists instead, in schedule order, every operation
with that shape among its results.  Nothing runs, so this gives no time.
"""
import argparse
import collections
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CLOCK_HZ = 1.5e9
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")
_INSTRUCTION = re.compile(
    r"^\s+(?:ROOT )?%(?P<name>[\w.\-]+) = (?P<type>.*?) (?P<op>[\w\-]+)\(")
_SHAPE = re.compile(r"(\w+\[[\d,]*\])")


def _benchmark():
    """The benchmark's package, also where this file runs as a script."""
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    import benchmark
    return benchmark


def compile_step(cell_name: str, described=None):
    """The cell's step program, compiled for the described chips:
    ``described``, or a ``v5e:2x2`` described here (a test hands in its
    fixture's; the persistent cache is then the caller's to keep out)."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or it logs under /tmp
    _benchmark()
    from benchmark import common

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cell = next(w for w in json.load(f)["workloads"]
                    if w["name"] == cell_name)
    config = common.load_json("configs", cell["config"] + ".json")
    traffic = common.load_traffic(cell["traffic"])
    if described is None:
        import jax
        from jax.experimental import topologies

        jax.config.update("jax_enable_compilation_cache", False)
        described = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices
    devices = described[:cell["chips"]]
    if traffic["driver"] == "rl_anakin":
        return _compile_anakin_step(config, devices)
    return _compile_train_step(config, traffic, devices)


def _compile_anakin_step(config, devices):
    """``make_anakin_ppo``'s step from the configuration that
    ``drivers/rl_anakin.py::build_algo`` makes, on the shapes of its state.
    The algorithm's constructor would run ``init`` on ``jax.devices()``:
    ``build`` hands back the configuration instead, and the data mesh is
    laid over the described chips."""
    from unittest import mock

    import jax
    import numpy as np
    from jax.sharding import Mesh

    from benchmark.drivers import rl_anakin
    from ray_tpu.ops import gather_rows
    from ray_tpu.rllib.algorithms.algorithm_config import AlgorithmConfig
    from ray_tpu.rllib.algorithms.ppo import make_anakin_ppo
    from ray_tpu.rllib.utils import mesh as mesh_util

    def described_mesh(n):
        return Mesh(np.array(devices[:n]), (mesh_util.DATA_AXIS,))

    # jax.default_backend() is the CPU here: name the path the chip takes.
    with mock.patch.object(AlgorithmConfig, "build", lambda self: self), \
            mock.patch.object(mesh_util, "data_mesh", described_mesh), \
            mock.patch.object(gather_rows, "backend", lambda: "tpu"):
        algo_config = rl_anakin.build_algo(config, len(devices), 0)
        _module, init, step, _total = make_anakin_ppo(algo_config)
        return step.lower(jax.eval_shape(init, 0)).compile()


def _compile_train_step(config, traffic, devices):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from benchmark.drivers import train_lm

    chips = len(devices)
    mesh = Mesh(np.array(devices), ("data",))
    rep = NamedSharding(mesh, PartitionSpec())
    _m, init, _p, _s = train_lm.build_step(config, traffic, mesh, chips)
    params, opt = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=rep),
        jax.eval_shape(init, jax.random.PRNGKey(0)))
    # jax.default_backend() is the CPU here: name the path the chip takes.
    config["train"]["use_flash"] = True
    _m, _i, _p, step = train_lm.build_step(config, traffic, mesh, chips)
    ids = jax.ShapeDtypeStruct(
        (traffic["per_chip_batch"] * chips, traffic["seq"]), jnp.int32,
        sharding=NamedSharding(mesh, PartitionSpec("data")))
    return step.lower(params, opt, ids).compile()


def computations(text: str) -> dict:
    """``{name: lines}`` of every computation; the entry's under "ENTRY"."""
    found, name = {}, None
    for line in text.splitlines():
        if line.endswith("{") and not line.startswith(" "):
            head = line.split()
            name = "ENTRY" if head[0] == "ENTRY" else head[0].lstrip("%")
            found[name] = []
        elif line.startswith("}"):
            name = None
        elif name is not None:
            found[name].append(line)
    return found


def _trips(condition: list) -> int:
    """How often a ``while`` turns, where its condition is ``lax.scan``'s: a
    counter from 0 held under the one integer constant there; 1 otherwise."""
    bounds = [int(n) for x in condition
              for n in re.findall(r"s32\[\]\S* constant\((\d+)\)", x)]
    return bounds[0] if len(bounds) == 1 else 1


def entry_operations(text: str) -> list:
    """The entry computation's instructions in schedule order, a ``while``
    followed by its body's (and theirs): ``at`` (its place in the entry
    computation, 0 to 1), ``name``, ``key`` (what a profile's breakdown
    calls it), ``shapes`` of its results, ``op``, ``op_name``, ``cycles`` by
    the cost model, ``times`` it runs a step (the trips of the loops around
    it), whether a ``convolution`` is fused inside, ``retries``."""
    _benchmark()
    from benchmark.trace_reduce import op_key

    comps = computations(text)
    matmul = {n for n, lines in comps.items()
              if any(" convolution(" in x for x in lines)}
    out = []

    def walk(lines, times, at):
        for i, line in enumerate(lines):
            m = _INSTRUCTION.match(line)
            if not m:
                continue
            here = i / len(lines) if at is None else at
            called = re.search(r"calls=%([\w.\-]+)", line)
            cycles = re.search(r'"estimated_cycles":"(\d+)"', line)
            op_name = re.search(r'op_name="([^"]*)"', line)
            retries = re.search(r'"retry_count":"(\d+)"', line)
            out.append({
                "at": here, "name": m["name"],
                "key": op_key(line.strip().removeprefix("ROOT ")),
                "shapes": _SHAPE.findall(m["type"]), "op": m["op"],
                "op_name": op_name[1] if op_name else "",
                "cycles": int(cycles[1]) if cycles else 0, "times": times,
                "matmul": bool(called and called[1] in matmul)
                or m["op"] == "convolution",
                "retries": int(retries[1]) if retries else 0})
            loop = re.search(
                r"condition=%([\w.\-]+), body=%([\w.\-]+)", line)
            if m["op"] == "while" and loop:
                walk(comps[loop[2]], times * _trips(comps[loop[1]]), here)

    walk(comps["ENTRY"], 1, None)
    return out


def _ms(cycles: int) -> float:
    return cycles / CLOCK_HZ * 1e3


def report(text: str, min_ms: float, shape: str | None) -> None:
    ops = entry_operations(text)
    timed = [o for o in ops if o["cycles"]]
    print(f"entry computation and loop bodies: {len(ops)} instructions, "
          f"{len(timed)} with a cost, "
          f"{_ms(sum(o['cycles'] * o['times'] for o in timed)):.2f} ms by the "
          f"cost model, each as often as its loops turn "
          f"(custom calls, the flash kernels among them, have none); "
          f"rematerialised {text.count('.remat')}, mosaic calls "
          f"{text.count('tpu_custom_call')}")
    if shape:
        want = f"[{shape}]"
        for o in ops:
            if o["op"] != "get-tuple-element" and any(
                    s.endswith(want) for s in o["shapes"]):
                print(f"  {o['at']:.3f} {o['name']} ({', '.join(o['shapes'])})"
                      f" {_ms(o['cycles']):.2f} ms x {o['times']}"
                      f"{' matmul' if o['matmul'] else ''}"
                      f"{' retries ' + str(o['retries']) if o['retries'] else ''}"
                      f" {o['op_name']}")
        return
    groups = collections.defaultdict(list)
    for o in timed:
        groups[o["key"]].append(o)
    print("  count  est.ms  matmul  group: op_name of the first")
    def cost(members):
        return sum(o["cycles"] * o["times"] for o in members)

    for key, members in sorted(groups.items(), key=lambda kv: -cost(kv[1])):
        total = _ms(cost(members))
        if total < min_ms:
            continue
        inside = sum(o["matmul"] for o in members)
        print(f"  {sum(o['times'] for o in members):5d} {total:7.2f}  "
              f"{inside:3d}/{len(members):<3d} {key}: "
              f"{members[0]['op_name']}")
    print("kernels (Pallas: no estimate), as often as their loops turn:")
    for o in ops:
        if o["op"] == "custom-call" and "tpu_custom_call" in o["key"]:
            print(f"  {o['times']:5d}  {o['name'].rsplit('.', 1)[0]} -> "
                  f"{', '.join(o['shapes'])}: {o['op_name']}")
    print("collectives, by their place in the schedule (0 first, 1 last):")
    for o in ops:
        if o["op"].startswith(COLLECTIVES):
            print(f"  {o['at']:.3f} {o['op']} {o['name']} "
                  f"{len(o['shapes'])} result(s), first {o['shapes'][0]}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cell", default="gpt2m_train_1k")
    ap.add_argument("--text", help="a saved compiled.as_text(), not a compile")
    ap.add_argument("--save", help="write compiled.as_text() here")
    ap.add_argument("--min-ms", type=float, default=0.5)
    ap.add_argument("--shape", help="e.g. 8,1023,50257: list, do not group")
    args = ap.parse_args()
    if args.text:
        with open(args.text) as f:
            text = f.read()
    else:
        compiled = compile_step(args.cell)
        ma, text = compiled.memory_analysis(), compiled.as_text()
        print(f"{args.cell}: per device args "
              f"{ma.argument_size_in_bytes / 2**30:.2f} GiB, temp "
              f"{ma.temp_size_in_bytes / 2**30:.2f} GiB, aliased "
              f"{ma.alias_size_in_bytes / 2**30:.2f} GiB")
        if args.save:
            with open(args.save, "w") as f:
                f.write(text)
    report(text, args.min_ms, args.shape)
    return 0


if __name__ == "__main__":
    sys.exit(main())
