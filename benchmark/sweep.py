#!/usr/bin/env python3
"""Find a serving cell's knee, once, when the cell is defined: deploy the
cell's replica once and play its traffic at several arrival rates one after
the other, lowest first, each with a pre-roll and a window of its own on an
engine that has drained, up to the first whose queue grows by more than five
requests through its window or that completes under 85% of the tokens offered
in it, and print for each how the queue grew through the
window, the tokens completed of those offered, the token gaps, the share of
them that something cut, the time to first token, the engine's step and how
many of its slots decoded.  The knee is the highest rate at which the queue
does not grow and every offered token completes; the cell's traffic file
records it with these lines and fixes the cell's rate at four fifths of it
(PERF.md section 4 has the rule).  With ``--schedule-seed`` it plays another
realisation of the same traffic (other arrival instants, another draw of
lengths), which says how far the cell's numbers belong to the one realisation
its file fixes.  Not part of a measurement: ``run.py`` never searches.

    python3 benchmark/sweep.py --workload gpt2m_serve_steady --rates 8 16 24 32
    python3 benchmark/sweep.py --workload gpt2m_serve_steady --rates 16 \\
        --schedule-seed 7 --seconds 45 --preroll 30
"""
import argparse
import copy
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def line(rate, schedule_seed, rec) -> dict:
    from benchmark import common

    c = rec["counters"]

    def layer(name):  # the per-layer readers that need no trace
        return common.load_module("layer_metrics", name).read(rec, {})

    return {
        "rate_per_s": rate, "schedule_seed": schedule_seed,
        "clients": c["clients"], "window_s": rec["window_s"],
        "preroll_s": c["preroll_s"], "correct": rec["correct"],
        "attempted": rec["attempted"], "failed": rec["failed"],
        "waiting_at_start": c["waiting_at_window_start"],
        "waiting_at_end": c["waiting_at_window_end"],
        "pending_at_end": c["engine"].get("pending"),
        "tokens_per_s": c["tokens_per_s"],
        "offered_tokens_per_s": c["offered_tokens_per_s"],
        "live_streams_mean": c["live_streams_mean"],
        "gap_p50_ms": layer("gap_p50_ms"),
        "gap_p95_ms": layer("gap_p95_ms"),
        "ttft_p50_ms": layer("ttft_p50_ms"),
        "ttft_p95_ms": layer("ttft_p95_ms"),
        "gap_cut_share": layer("gap_cut_share"),
        "engine_step_ms": layer("engine_step_ms"),
        "window_occupancy": c["window_occupancy"],
        "memory_peak_bytes": rec["device"]["memory_peak_bytes"],
        "lateness_p95_ms": c["lateness_p95_ms"],
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seed", type=int, default=3000000011)
    ap.add_argument("--schedule-seed", type=int, default=None)
    ap.add_argument("--seconds", type=float, default=15)
    ap.add_argument("--preroll", type=float, default=8)
    ap.add_argument("--tiny", action="store_true",
                    help="the rehearsal's toy sizes on the CPU: the control "
                         "flow, no measurement")
    args = ap.parse_args()

    from benchmark import common, run
    from ray_tpu._private.jax_env import ensure_compile_cache

    overrides = None
    if args.tiny:
        from benchmark.rehearsal import rehearse
        overrides = rehearse.tiny_overrides(args.workload)
    run.log("compile cache:", ensure_compile_cache())
    _, cell, config, traffic = run.load_cell(args.workload, overrides)
    traffic["preroll_s"] = args.preroll
    if args.schedule_seed is not None:
        traffic["schedule_seed"] = args.schedule_seed
    driver = common.load_module("drivers", traffic["driver"])
    with driver.session(cell, config, traffic, args.seed,
                        allow_cpu=args.tiny) as window:
        for rate in sorted(args.rates):
            played = copy.deepcopy(traffic)
            played["arrivals"]["rate_per_s"] = rate
            rec = window(played, args.seconds, False)
            got = line(rate, args.schedule_seed, rec)
            print("SWEEP " + json.dumps(got), flush=True)
            if (got["waiting_at_end"] > got["waiting_at_start"] + 5
                    or got["tokens_per_s"] < 0.85 * got["offered_tokens_per_s"]):
                break  # above the knee: a higher rate says nothing more
    return 0


if __name__ == "__main__":
    sys.exit(main())
