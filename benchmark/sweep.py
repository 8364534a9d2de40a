#!/usr/bin/env python3
"""Find a serving cell's knee, once, when the cell is defined: play its
traffic at several arrival rates, one process per rate, and print for each
how the queue grew through the window, the token gaps and the time to first
token.  The knee is the highest rate at which the queue does not grow; the
cell's traffic file then fixes its rate at about four fifths of it.  With
``--schedule-seed`` it plays another realisation of the same traffic (other
arrival instants, another draw of lengths), which says how far the cell's
numbers belong to the one realisation its file fixes.  Not part of a
measurement: ``run.py`` never searches.

    python3 benchmark/sweep.py --workload gpt2m_serve_chat --rates 0.8 1.0 1.2
    python3 benchmark/sweep.py --workload gpt2m_serve_chat --rates 1.0 \
        --schedule-seed 7 --seeds 3000000011 3000000029 3000000047
"""
import argparse
import json
import os
import subprocess
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def one(workload, rate, seed, seconds, schedule_seed):
    from benchmark import loadgen, run

    traffic = {"arrivals": {"rate_per_s": rate}}
    if schedule_seed is not None:
        traffic["schedule_seed"] = schedule_seed
    result, rec = run.measure(workload, seed, seconds, False,
                              overrides={"traffic": traffic})
    s, c = rec["samples"], rec["counters"]
    print("SWEEP " + json.dumps({
        "rate_per_s": rate, "seed": seed, "schedule_seed": schedule_seed,
        "setup_s": result["metrics"]["setup_s"]["value"],
        "correct": rec["correct"],
        "attempted": rec["attempted"], "failed": rec["failed"],
        "waiting_at_start": c["waiting_at_window_start"],
        "waiting_at_end": c["waiting_at_window_end"],
        "pending_at_end": c["engine"].get("pending"),
        "gaps": c["gaps"], "tokens_per_s": c["tokens_per_s"],
        "gap_p50_ms": rec["end_to_end"].get("token_gap_p50_ms"),
        "gap_p95_ms": rec["end_to_end"].get("token_gap_p95_ms"),
        "ttft_p50_ms": loadgen.percentile(s["ttft_ms"], 50),
        "ttft_p95_ms": loadgen.percentile(s["ttft_ms"], 95),
        "occupancy": c["engine"].get("avg_batch_occupancy"),
        "lateness_p95_ms": loadgen.percentile(s["lateness_ms"], 95),
    }), flush=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", default=[3000000011])
    ap.add_argument("--schedule-seed", type=int, default=None)
    ap.add_argument("--seconds", type=float, default=45)
    ap.add_argument("--one", type=float, help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.one is not None:
        one(args.workload, args.one, args.seeds[0], args.seconds,
            args.schedule_seed)
        return 0
    for rate in args.rates:  # one process per run: each owns the chip alone
        for seed in args.seeds:
            subprocess.call(
                [sys.executable, __file__, "--workload", args.workload,
                 "--seeds", str(seed), "--seconds", str(args.seconds),
                 "--rates", "0", "--one", str(rate)]
                + ([] if args.schedule_seed is None else
                   ["--schedule-seed", str(args.schedule_seed)]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
