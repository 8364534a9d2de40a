#!/usr/bin/env python3
"""A CPU rehearsal of every cell at toy sizes: the whole control flow of
``run.py`` (drivers, reference comparison, window, trace, readers) with the
presets of ``tiny/`` laid over the real files (``config.<config>.json`` and
``driver.<driver>.json``: a new cell of a known configuration and driver needs
no new preset, and where there is none the real size runs).  It finds wrong paths,
arguments and control flow before a chip call is spent on them.  It prints
records, never a result line, and always exits non-zero: nothing it sees is
a measurement.

    python3 benchmark/rehearsal/rehearse.py [cell ...] [--trace 0|1]
"""
import argparse
import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


SECONDS = 3


def tiny_overrides(cell: str) -> dict:
    """The toy sizes for this cell's configuration and for its driver."""
    from benchmark import common

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(w for w in json.load(f)["workloads"]
                     if w["name"] == cell)
    driver = common.load_traffic(entry["traffic"])["driver"]
    out = {}
    for key, name in (("config", f"config.{entry['config']}.json"),
                      ("traffic", f"driver.{driver}.json")):
        path = os.path.join(HERE, "tiny", name)
        if os.path.exists(path):
            with open(path) as f:
                out[key] = json.load(f)
        else:
            print(f"[rehearsal] no {name}: the real sizes run", flush=True)
    return out


def one(cell: str, trace: int) -> int:
    sys.path.insert(0, ROOT)
    from benchmark import run

    result, record = run.measure(cell, 2 ** 31 + 7, SECONDS, bool(trace),
                                 allow_cpu=True,
                                 overrides=tiny_overrides(cell))
    record.pop("samples", None)
    print(f"[rehearsal] {cell} trace={trace} on "
          f"{result['device']['platform']}: correct-but-for-the-device="
          f"{all_but_device(record)} metrics={sorted(result['metrics'])}")
    print("[rehearsal] record:", json.dumps(record, default=str))
    return 0 if all_but_device(record) else 1


def all_but_device(record) -> bool:
    c = record["checks"]
    return (record["failed"] == 0 and c.get("compiles_in_window") == 0
            and c.get("loss_matches", c.get("matches", True))
            and c.get("losses_finite", True))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("cells", nargs="*")
    ap.add_argument("--trace", type=int, default=None)
    ap.add_argument("--one", help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.one:
        return one(args.one, args.trace or 0)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        chips = {w["name"]: w["chips"] for w in json.load(f)["workloads"]}
    bad = []
    for cell in args.cells or chips:
        env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS=(
            f"--xla_force_host_platform_device_count={chips[cell]}"))
        for trace in ((0, 1) if args.trace is None else (args.trace,)):
            rc = subprocess.call([sys.executable, os.path.abspath(__file__),
                                  "--one", cell, "--trace", str(trace)],
                                 env=env, cwd=ROOT)
            print(f"[rehearsal] {cell} trace={trace}: "
                  f"{'ok' if rc == 0 else 'FAILED'}", flush=True)
            if rc:
                bad.append((cell, trace))
    print(f"[rehearsal] {len(bad)} failed: {bad}; no TPU was used, so this "
          "is not a measurement and the exit code is 1")
    return 1


if __name__ == "__main__":
    sys.exit(main())
