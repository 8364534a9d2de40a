#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything about a cell is found by name at run time: the cell in
``BENCHMARK.json`` names a configuration (``configs/<config>.json``) and a
traffic mix (``traffic/<traffic>.json``); the traffic file names the driver
that plays it (``drivers/<driver>.py``); a per-layer metric is read by
``layer_metrics/<name up to its first dot>.py``; the configuration's plain
reference is ``reference/<config>.py``.  A new cell, configuration, traffic
mix, driver or metric is a new file and a new entry, and no edit here.

The last line of standard output is the result; everything else goes to
standard error.  Without a TPU the run fails and prints no result.
"""
import time

T_PROCESS = time.time()  # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)


def log(*a):
    print("[bench]", *a, file=sys.stderr, flush=True)


def compared(checks):
    """The numbers a driver compared and their limits, without the prose that
    a traffic file keeps beside them."""
    if isinstance(checks, dict):
        return {k: compared(v) for k, v in checks.items()
                if not isinstance(v, str)}
    return checks


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(workload, overrides=None):
    """The manifest, the cell's entry, its configuration and its traffic, by
    the names in ``BENCHMARK.json``; ``overrides`` are the rehearsal's toy
    sizes and the sweep's rates, never a measurement's."""
    from benchmark import common

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cell = next(w for w in manifest["workloads"] if w["name"] == workload)
    entry = next(c for c in manifest["configs"] if c["name"] == cell["config"])
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    traffic = common.load_traffic(cell["traffic"])
    common.merge({"config": config, "traffic": traffic}, overrides or {})
    return manifest, cell, config, traffic


def measure(workload, seed, seconds, trace, allow_cpu=False, overrides=None):
    """One run of one cell: the result object, and the driver's record."""
    from benchmark import common
    from ray_tpu._private.jax_env import ensure_compile_cache

    log("compile cache:", ensure_compile_cache())
    manifest, cell, config, traffic = load_cell(workload, overrides)
    driver = common.load_module("drivers", traffic["driver"])
    record = driver.run(cell, config, traffic, seed, seconds, trace,
                        allow_cpu=allow_cpu)
    record["end_to_end"]["setup_s"] = record["window_start"] - T_PROCESS

    device = record["device"]
    ctx = {"cell": cell, "config": config, "traffic": traffic}
    if device["platform"] == "tpu":
        ctx["peak"] = common.peak_for(device["kind"])
    metrics = {}
    for m in manifest["per_layer" if trace else "end_to_end"]:
        if not applies(m, workload):
            continue
        if trace:
            reader = common.load_module("layer_metrics", m["name"])
            value = reader.read(record, ctx) if reader else None
        else:
            value = record["end_to_end"].get(m["name"])
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result = {"correct": record["correct"], "attempted": record["attempted"],
              "failed": record["failed"], "metrics": metrics,
              "device": {k: device[k] for k in (
                  "platform", "kind", "count", "memory_peak_bytes")}}
    t = record.get("trace")
    if trace and t:
        result["device"]["busy_s"] = t["busy_s"]
        result["device"]["window_s"] = t["window_s"]
        result["breakdown"] = {"device_ops": t["device_ops"],
                               "idle_gaps": t["idle_gaps"]}
    result["checks"] = compared(record["checks"])  # last in the line
    return result, record


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    result, record = measure(args.workload, args.seed, args.seconds,
                             bool(args.trace))
    record.pop("samples", None)
    (record.get("trace") or {}).pop("op_s", None)
    print("[bench] record:", json.dumps(record, default=str), flush=True)
    if result["device"]["platform"] != "tpu":
        log("no TPU: no result")
        return 1
    print(json.dumps(result, default=str), flush=True)
    log("correct:", result["correct"], "compared:",
        json.dumps(result["checks"], default=str))
    return 0


if __name__ == "__main__":
    sys.exit(main())
