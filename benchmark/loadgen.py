"""The load generator and the arithmetic on its stamps.

A traffic file fixes the load once and for all: the arrival instants and the
(prompt length, output length) pair of every request are drawn from the
file's own ``schedule_seed``, so every run meets the same lengths at the same
instants in the same order.  ``--seed`` draws only the token ids (and the
weights): the schedule's shape is a function of the traffic file alone.

What a traffic file may say, all of it data: ``arrivals.process`` names a file
of ``benchmark/arrivals/`` (its other keys are that process's parameters);
``prompt_tokens`` and ``output_tokens`` are clipped log-normals;
``shared_prefix`` (optional) makes prompts begin with one of a few seeded
prefixes, so that a prefix cache has something to find.
"""
from __future__ import annotations

import threading
import time

import numpy as np

from benchmark import common


def percentile(values, q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation between the two
    nearest ranks, as numpy's default does."""
    v = sorted(values)
    if not v:
        raise ValueError("percentile of nothing")
    pos = (len(v) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(v) - 1)
    return float(v[lo] + (v[hi] - v[lo]) * (pos - lo))


def gaps_in_window(stamps, w0: float, w1: float) -> list:
    """Gaps between consecutive output tokens of one request, both of whose
    stamps lie inside [w0, w1].  A gap that straddles an edge of the window is
    cut off, whichever side the request began or ends on."""
    out = []
    for a, b in zip(stamps, stamps[1:]):
        if a >= w0 and b <= w1:
            out.append(b - a)
    return out


def _lognormal_clipped(rng, median, sigma, lo, hi, n):
    x = median * np.exp(sigma * rng.standard_normal(n))
    return np.clip(np.rint(x), lo, hi).astype(np.int64)


def build_schedule(traffic: dict, seed: int, vocab_size: int,
                   horizon_s: float) -> list:
    """Requests due inside [0, horizon_s): dicts with ``due_s``, ``prompt``
    (a list of token ids) and ``max_new_tokens``."""
    shape = np.random.default_rng(int(traffic["schedule_seed"]))
    arr = traffic["arrivals"]
    process = common.load_module("arrivals", arr["process"])
    if process is None:
        raise ValueError(f"no benchmark/arrivals/{arr['process']}.py")
    # More than enough gaps for any horizon, always the same count, so that
    # a longer horizon extends the schedule and does not redraw it.
    n = int(arr["rate_per_s"] * float(traffic["max_horizon_s"]) * 2) + 16
    due = np.cumsum(process.gaps(shape, arr, n))
    p, o = traffic["prompt_tokens"], traffic["output_tokens"]
    prompt = _lognormal_clipped(shape, p["median"], p["sigma"],
                                p["min"], p["max"], n)
    out = _lognormal_clipped(shape, o["median"], o["sigma"],
                             o["min"], o["max"], n)
    prompt = np.minimum(prompt, traffic["max_total_tokens"] - out)
    keep = int(np.searchsorted(due, horizon_s))
    ids = np.random.default_rng([int(seed), 1])
    schedule = [{"due_s": float(due[i]),
                 "prompt": ids.integers(0, vocab_size,
                                        int(prompt[i])).tolist(),
                 "max_new_tokens": int(out[i])}
                for i in range(keep)]
    share = traffic.get("shared_prefix")
    if share:
        # Request i begins with prefix i mod pool, as far as its length goes.
        pool = np.random.default_rng([int(seed), 3]).integers(
            0, vocab_size, (int(share["pool"]), int(share["tokens"])))
        for i, req in enumerate(schedule):
            head = pool[i % len(pool)][:len(req["prompt"])].tolist()
            req["prompt"][:len(head)] = head
    return schedule


class OpenLoop:
    """Sends request i at ``t0 + due_s[i]`` from a thread of its own, whatever
    the system does with the earlier ones, and records how late each left."""

    def __init__(self, schedule, send):
        self._schedule, self._send = schedule, send
        self.sent_at = [None] * len(schedule)
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, name="loadgen",
                                        daemon=True)

    def start(self, t0: float):
        self.t0 = t0
        self._thread.start()

    def _run(self):
        for i, req in enumerate(self._schedule):
            delay = self.t0 + req["due_s"] - time.perf_counter()
            if delay > 0 and self._stop.wait(delay):
                return
            if self._stop.is_set():
                return
            self.sent_at[i] = time.perf_counter()
            self._send(i, req)

    def stop(self):
        self._stop.set()
        self._thread.join()

    def lateness_ms(self, w0: float, w1: float) -> list:
        """Send time minus due time of the requests due inside [w0, w1]."""
        return [(s - (self.t0 + r["due_s"])) * 1e3
                for s, r in zip(self.sent_at, self._schedule)
                if s is not None and w0 <= self.t0 + r["due_s"] <= w1]
