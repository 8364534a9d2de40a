#!/usr/bin/env python3
"""Does ``BENCHMARK.json`` still stand for the files it names?  ``faults``
counts what the manifest holds and knows no cell by name: every cell's
configuration, traffic, driver and reference file exist; at least one cell
asks for four chips and no more than the contract's quarter; every name on a
metric's ``workloads`` list is a cell; every cell reports ``setup_s``,
another end-to-end metric and a per-layer metric whose ``moves`` it reports;
every per-layer metric has its reader; and no name of ``retired.txt`` is
left anywhere in the file.  No chip, no model, no jax:
``benchmark/tests/test_steady_cells.py`` runs it, and any other test may (a
list of strings, empty where all is well).

    python3 benchmark/manifest_check.py
"""
import json
import os
import re
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)


def retired() -> list:
    """The names of ``retired.txt``: cells and traffic mixes that were taken
    away, one a line before its ``#`` remark.  Such a name may not come back
    for something that reads differently."""
    with open(os.path.join(HERE, "retired.txt")) as f:
        names = [line.split("#")[0].strip() for line in f]
    return [name for name in names if name]


def load() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def faults(manifest: dict) -> list:
    from benchmark import common
    from benchmark.run import applies

    out = []
    cells = [w["name"] for w in manifest["workloads"]]
    configs = {c["name"]: c for c in manifest["configs"]}
    ends = manifest["end_to_end"]
    four = [w["chips"] for w in manifest["workloads"]].count(4)
    if not 1 <= four <= max(1, len(cells) // 4):
        out.append(f"{four} of {len(cells)} cells on four chips")
    for w in manifest["workloads"]:
        cell = w["name"]
        entry = configs.get(w["config"])
        if entry is None or not os.path.exists(
                os.path.join(ROOT, entry["file"])):
            out.append(f"{cell}: no configuration file for {w['config']}")
        try:
            traffic = common.load_traffic(w["traffic"])
        except OSError:
            out.append(f"{cell}: no traffic file {w['traffic']}")
            continue
        if common.load_module("drivers", traffic["driver"]) is None:
            out.append(f"{cell}: no driver {traffic['driver']}")
        if common.load_module("reference", w["config"]) is None:
            out.append(f"{cell}: no reference for {w['config']}")
        reported = {m["name"] for m in ends if applies(m, cell)}
        if not {"setup_s"} < reported:
            out.append(f"{cell}: reports {sorted(reported)} end to end")
        # without a list a per-layer metric is due wherever its ``moves`` is
        layers = [m for m in manifest["per_layer"]
                  if (cell in m["workloads"] if "workloads" in m
                      else m["moves"] in reported)]
        if not layers:
            out.append(f"{cell}: no per-layer metric")
        out += [f"{cell}: {m['name']} moves {m['moves']}, which the cell "
                "does not report" for m in layers
                if m["moves"] not in reported]
    for c in manifest["configs"]:
        if not any(w["config"] == c["name"] for w in manifest["workloads"]):
            out.append(f"configuration {c['name']} is used by no cell")
    for m in ends + manifest["per_layer"]:
        out += [f"{m['name']} lists {name}, which is no cell"
                for name in m.get("workloads", []) if name not in cells]
    for m in manifest["per_layer"]:
        if common.load_module("layer_metrics", m["name"]) is None:
            out.append(f"{m['name']}: no reader")
    text = json.dumps(manifest)
    out += [f"the retired name {name} is still in the manifest"
            for name in retired()
            if re.search(rf"(?<![A-Za-z0-9_]){name}(?![A-Za-z0-9_])", text)]
    return out


if __name__ == "__main__":
    found = faults(load())
    print("\n".join(found) or "the manifest stands")
    sys.exit(1 if found else 0)
