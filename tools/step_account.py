"""One run of a serve cell with the loop thread's step accounted for.

    python3 tools/step_account.py --workload gpt2m_serve_steady --seed 7 \
        --seconds 45 --trace 1

Runs ``benchmark/run.py``'s ``measure`` in this process, where the head of
the session lives, so that the ``span_batch`` requests its workers send can
be counted as they arrive (``Head.req_span_batch``: every tree since PR 24
has it, so the parent commit is counted the same way; run it from the root
of the checkout it is to measure).  Prints the result line ``run.py`` would
print, then one line ``[account] {...}``:

- ``batches``: span batches the head took, their spans, and the batches a
  second over the stretch the engine's iterations span (the profile);
- ``per_step_ms``: the stretch divided among the engine's spans, each total
  over the steps dispatched, so the parts add up to the mean step with the
  remainder named (``iteration_self``: inside an iteration and under no
  child; ``turnaround``: between two iterations; ``idle``: ``engine.idle``);
- ``cpu_per_step_ms`` / ``offcpu_per_step_ms`` / ``reply_calls_per_step``
  from the iterations' ``cpu_ms`` and ``reply_calls``, and ``offcpu_vs_calls``,
  the correlation of an iteration's off-CPU time with the calls answered
  during it;
- ``median_ms`` of every engine span, ``spans`` (how many the session
  holds), ``dropped`` (``session_spans_dropped()``).
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.getcwd())


def count_batches():
    """Wrap the head's handler; returns the list it appends
    ``(time.time(), spans in the batch)`` to."""
    from ray_tpu._private.head import Head

    seen = []
    handler = Head.req_span_batch

    def counted(self, payload, reply, caller):
        seen.append((time.time(), len(payload.get("spans") or [])))
        return handler(self, payload, reply, caller)

    Head.req_span_batch = counted
    return seen


def ms(s) -> float:
    return (s["end"] - s["start"]) * 1e3


def account(spans, batches) -> dict:
    engine = [s for s in spans if s["name"].startswith("engine.")
              and s["name"] not in ("engine.init", "engine.compile")]
    its = sorted((s for s in engine if s["name"] == "engine.iteration"),
                 key=lambda s: s["start"])
    out = {"spans": len(spans),
           "batches": {"n": len(batches),
                       "spans": sum(n for _, n in batches)}}
    if not its:
        return out
    t0, t1 = its[0]["start"], its[-1]["end"]
    out["profile_s"] = t1 - t0
    in_profile = [t for t, _ in batches if t0 <= t <= t1 + 0.5]
    out["batches"]["in_profile"] = len(in_profile)
    out["batches"]["per_s"] = len(in_profile) / (t1 - t0)
    by_name, children = {}, {}
    for s in engine:
        by_name.setdefault(s["name"], []).append(s)
        children.setdefault(s.get("parent_id"), []).append(s)
    out["median_ms"] = {n: statistics.median(map(ms, v))
                        for n, v in sorted(by_name.items())}
    steps = len(by_name.get("engine.decode.dispatch", ())) or 1
    out["steps"] = steps
    total = {n: sum(map(ms, v)) for n, v in by_name.items()}
    per = {n.replace("engine.", ""): total[n] / steps for n in total
           if n not in ("engine.iteration", "engine.prefill")}
    # a dispatch's own: under it and under none of its three parts
    parts = sum(total.get("engine.decode." + p, 0.0)
                for p in ("stage", "call", "readback"))
    if parts:
        per["decode.dispatch_self"] = (
            total["engine.decode.dispatch"] - parts) / steps
        del per["decode.dispatch"]
    inside = sum(ms(c) for it in its for c in children.get(it["span_id"], ()))
    per["iteration_self"] = (total["engine.iteration"] - inside) / steps
    idle_in = sum(ms(s) for s in by_name.get("engine.idle", ())
                  if t0 <= s["start"] and s["end"] <= t1)
    per["idle"] = idle_in / steps
    per["turnaround"] = ((t1 - t0) * 1e3 - total["engine.iteration"]
                         - idle_in) / steps
    out["per_step_ms"] = dict(sorted(per.items()))
    out["mean_step_ms"] = (t1 - t0) * 1e3 / steps
    args = [s.get("args") or {} for s in its]
    if all("cpu_ms" in a for a in args):
        fetch = {}
        for s in by_name.get("engine.decode.fetch", ()):
            fetch[s.get("parent_id")] = fetch.get(s["parent_id"], 0) + ms(s)
        off = [max(0.0, ms(s) - a["cpu_ms"] - fetch.get(s["span_id"], 0.0))
               for s, a in zip(its, args)]
        calls = [a["reply_calls"] for a in args]
        out["cpu_per_step_ms"] = sum(a["cpu_ms"] for a in args) / steps
        out["offcpu_per_step_ms"] = sum(off) / steps
        out["reply_calls_per_step"] = sum(calls) / steps
        if len(set(calls)) > 1 and len(set(off)) > 1:
            out["offcpu_vs_calls"] = statistics.correlation(off, calls)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    ap.add_argument("--tag", default="")
    ap.add_argument("--dump", default="",
                    help="write the engine's spans to this file, one a "
                         "line: for a question the account does not answer")
    ap.add_argument("--tiny", action="store_true",
                    help="rehearse on the CPU at rehearse.py's toy sizes")
    args = ap.parse_args(argv)
    batches = count_batches()
    from benchmark import run
    from ray_tpu import observability as obs

    tiny = {}
    if args.tiny:
        from benchmark.rehearsal import rehearse

        tiny = {"allow_cpu": True,
                "overrides": rehearse.tiny_overrides(args.workload)}
    result, record = run.measure(args.workload, args.seed, args.seconds,
                                 bool(args.trace), **tiny)
    print(json.dumps(result, default=str), flush=True)
    spans = obs.session_spans()
    if args.dump:
        os.makedirs(os.path.dirname(args.dump) or ".", exist_ok=True)
        with open(args.dump, "w") as f:
            for s in spans:
                if s["name"].startswith(("engine.", "request.")):
                    f.write(json.dumps({k: s[k] for k in (
                        "name", "start", "end", "span_id", "parent_id",
                        "os_pid", "args")}, default=str) + "\n")
    out = account(spans, batches)
    dropped = getattr(obs, "session_spans_dropped", None)
    out.update(tag=args.tag, workload=args.workload, seed=args.seed,
               trace=args.trace, correct=result["correct"],
               dropped=dropped() if dropped else None,
               step_stamps_ms=statistics.median(
                   record.get("samples", {}).get("engine_step_ms") or [0]),
               end_to_end=record["end_to_end"],
               metrics={k: v["value"] for k, v in result["metrics"].items()})
    print("[account]", json.dumps(out, default=str), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
