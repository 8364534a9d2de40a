"""Driver ``serve_lm``: open-loop chat traffic against one ``LLMServer``
replica, deployed through the normal ``serve`` API.

The parent never touches jax: the replica's worker owns the chip.  Every
request goes ``submit_stream`` -> ``next_chunk`` ... through the serve handle,
one token to a chunk, from one of the traffic file's ``clients`` processes
(``benchmark/clients.py``), which stamps each chunk as its reply arrives.
The generators start during set-up and play a pre-roll at the cell's rate,
so the measured window opens on an engine at its steady occupancy.
``deployed``, ``play`` and ``record`` are what ``serve_decoder`` shares with
this driver; ``session`` is what ``sweep.py`` shares with ``run``.

``BenchLLMServer`` adds to ``LLMServer`` only what a measurement needs inside
the process that holds the chip: the device's facts, the comparison with the
plain reference, the compile counter, the profiler, and host spans around the
engine's two dispatches.
"""
from __future__ import annotations

import contextlib
import functools
import time

from benchmark import clients, common, loadgen
from ray_tpu.serve.llm_engine import LLMServer


class BenchLLMServer(LLMServer):
    def __init__(self, *args, allow_cpu=False, **kw):
        self._device = common.device_record(allow_cpu)
        self._compiles = common.CompileCounter()
        super().__init__(*args, **kw)
        self._name_dispatches()

    def _name_dispatches(self):
        """Host spans around the decode and prefill dispatches, so that an
        idle gap of the device can be named.  The engine has no hook for
        this (PERF.md lists one for the tracing issue), so its two private
        callables are wrapped; where they are gone, the spans are too."""
        eng = self.engine
        decode = getattr(eng, "_decode", None)
        if callable(decode):
            @functools.wraps(decode)
            def traced_decode(*a, **k):
                with common.span("dispatch:step"):
                    return decode(*a, **k)

            traced_decode._cache_size = getattr(decode, "_cache_size", None)
            eng._decode = traced_decode
        prefill_fn = getattr(eng, "_prefill_fn", None)
        if callable(prefill_fn):
            def traced_prefill_fn(bucket):
                fn = prefill_fn(bucket)

                def call(*a, **k):
                    with common.span("dispatch:prefill"):
                        return fn(*a, **k)

                return call

            eng._prefill_fn = traced_prefill_fn

    def facts(self) -> dict:
        import jax

        rec = common.memory_record(self._device)
        rec["param_count"] = int(sum(
            x.size for x in jax.tree_util.tree_leaves(self.engine._params)))
        return rec

    def reference_check(self, config_name, config, prompt, new_tokens):
        """One greedy request through prefill and the cached decode, against
        the reference's one full forward pass over prompt + answer: the
        engine's log-probability of each token it chose against the
        reference's at the same place, and how far below the reference's
        best logit the chosen token's lies (0 unless rounding flipped an
        argmax)."""
        import jax
        import jax.numpy as jnp

        ref = common.load_module("reference", config_name)
        rid = self.engine.submit(prompt, new_tokens)
        got = self.engine.rollout(rid, timeout=600.0)
        ids = jnp.asarray([list(prompt) + got["tokens"]], jnp.int32)
        logits = jax.jit(functools.partial(ref.forward, cfg=config))(
            self.engine._params, ids)[0, len(prompt) - 1:-1]
        logp = jax.nn.log_softmax(logits, -1)
        chosen = jnp.asarray(got["tokens"])[:, None]
        ref_lp = jnp.take_along_axis(logp, chosen, -1)[:, 0]
        margin = jnp.max(logits, -1) - jnp.take_along_axis(
            logits, chosen, -1)[:, 0]
        return {"tokens": len(got["tokens"]),
                "logprob_max_err": float(jnp.max(jnp.abs(
                    ref_lp - jnp.asarray(got["logprobs"])))),
                "argmax_margin_max": float(jnp.max(margin))}

    def warm(self, prompts, new_tokens):
        """Every prefill program and the decode step, compiled before the
        window.  ``generate_batch`` would do, but for its fixed 120 s wait,
        which a cold compile of the longest bucket outlasts."""
        rids = [self.engine.submit(p, new_tokens) for p in prompts]
        return [len(self.engine.result(r, timeout=1100.0)) for r in rids]

    def arm(self):
        self._compiles.arm()
        return True

    def disarm(self) -> int:
        return self._compiles.disarm()

    def trace_start(self):
        self._trace_dir = common.trace_dir("serve")
        common.start_trace(self._trace_dir)
        return True

    def trace_stop(self) -> dict:
        return common.stop_trace(self._trace_dir)

    def step_stamps(self):
        return self.engine.recent_step_stamps()


def warm_prompts(traffic: dict, vocab: int) -> list:
    """One prompt at every power of two between the shortest and the longest
    prompt, and at both ends: every prefill program this traffic can reach."""
    p = traffic["prompt_tokens"]
    lens = {p["min"], p["max"]} | {
        1 << k for k in range(32) if p["min"] <= 1 << k <= p["max"]}
    return [[(7 * j + n) % vocab for j in range(n)] for n in sorted(lens)]


@contextlib.contextmanager
def deployed(server, bind_args, config, seed, allow_cpu):
    """One replica of ``server`` on the chip behind the serve handle, for the
    length of the block: the handle, and a blocking call of one of the
    replica's methods."""
    import ray_tpu
    from ray_tpu import serve

    s = config["serve"]
    ray_tpu.init(**({"num_tpus": 1} if allow_cpu else {}))
    try:
        handle = serve.run(serve.deployment(
            server, name="llm", num_replicas=1,
            ray_actor_options={"num_tpus": 1,
                               "max_concurrency": s["max_concurrency"]},
        ).bind(*bind_args, seed=common.jax_seed(seed), allow_cpu=allow_cpu,
               max_slots=s["max_slots"], page_size=s["page_size"],
               max_ctx=s["max_ctx"], chunk_tokens=s["chunk_tokens"]))

        def call(method, *args):
            return ray_tpu.get(handle.method(method).remote(*args),
                               timeout=1100.0)

        yield handle, call
    finally:
        serve.shutdown()
        ray_tpu.shutdown()


def play(handle, call, traffic, seed, vocab, seconds, trace,
         engine_keys=()) -> dict:
    """One pre-roll and one window of the traffic through ``clients``
    processes (``benchmark/clients.py``), and the arithmetic on their stamps:
    the record's parts that do not depend on the model.  Times are seconds
    since ``t0``; the window is [preroll, preroll + seconds]."""
    preroll = float(traffic["preroll_s"])
    schedule = loadgen.build_schedule(traffic, seed, vocab, preroll + seconds)
    fleet = clients.Fleet(handle, schedule, int(traffic["clients"]))
    while True:  # an engine that holds nothing: so set-up leaves it
        held = call("stats")
        if not held["active"] + held["pending"]:
            break
        time.sleep(0.2)
    t0 = time.time() + 0.25
    w0, w1 = preroll, preroll + seconds

    def sleep_until(t):
        time.sleep(max(0.0, t0 + t - time.time()))

    fleet.start(t0)
    sleep_until(w0)
    call("arm")
    at_start = call("stats")
    traced = None
    if trace:
        sleep_until(w0 + traffic["trace_offset_s"])
        call("trace_start")
        time.sleep(traffic["trace_s"])
        traced = call("trace_stop")
    sleep_until(w1)
    played = fleet.stop()
    compiles = call("disarm")
    stats, stamps = call("stats"), call("step_stamps")

    gaps, ttft, late, in_window_tokens = [], [], [], 0
    attempted = failed = 0
    live_s = 0.0  # stream-seconds inside the window, first token to last
    offered = 0  # tokens of the answers due in the window
    waiting = [0, 0]  # due, but no first token yet, at the window's edges
    errors = []
    for req, got in zip(schedule, played):
        if got["sent_at"] is None:
            continue
        st, due = got["stamps"], req["due_s"]
        attempted += 1
        if got["error"] is not None:
            errors.append(got["error"])
        if got["error"] is not None or len(st) > req["max_new_tokens"] or (
                got["done_at"] is not None
                and len(st) != req["max_new_tokens"]):
            failed += 1
        gaps += [g * 1e3 for g in loadgen.gaps_in_window(st, w0, w1)]
        in_window_tokens += sum(1 for t in st if w0 <= t <= w1)
        if st:
            live_s += max(0.0, min(st[-1], w1) - max(st[0], w0))
        if w0 <= due <= w1:
            offered += req["max_new_tokens"]
            late.append((got["sent_at"] - due) * 1e3)
            if st:
                ttft.append((st[0] - due) * 1e3)
        for k, edge in enumerate((w0, w1)):
            if due <= edge and (not st or st[0] > edge):
                waiting[k] += 1

    recent = [t for t in stamps if t >= stamps[-1] - seconds] if stamps else []
    step_ms = [(b - a) * 1e3 for a, b in zip(recent, recent[1:])]
    steps = stats["steps"] - at_start["steps"]
    end_to_end = {}
    if gaps:
        end_to_end = {"token_gap_p50_ms": loadgen.percentile(gaps, 50),
                      "serve_tokens_per_s": in_window_tokens / seconds}
    return {
        "attempted": attempted, "failed": failed,
        "window_start": t0 + w0, "window_s": float(seconds),
        "end_to_end": end_to_end,
        "checks": {"compiles_in_window": compiles,
                   "decode_programs": stats.get("decode_cache_size"),
                   "errors": sorted(errors)[:3]},
        "counters": {
            "gaps": len(gaps), "tokens_in_window": in_window_tokens,
            "tokens_per_s": in_window_tokens / seconds,
            "offered_tokens_per_s": offered / seconds,
            "requests_due_in_window": len(late),
            "waiting_at_window_start": waiting[0],
            "waiting_at_window_end": waiting[1],
            "live_streams_mean": live_s / seconds,
            "lateness_p95_ms": loadgen.percentile(late, 95) if late else None,
            "rate_per_s": traffic["arrivals"]["rate_per_s"],
            "clients": int(traffic["clients"]),
            "preroll_s": preroll,
            # the window's own share of the slots that were decoding, from
            # the engine's running mean at the window's two ends
            "window_occupancy": (
                (stats["avg_batch_occupancy"] * stats["steps"]
                 - at_start["avg_batch_occupancy"] * at_start["steps"])
                / steps if steps else None),
            "engine": {k: stats[k] for k in (
                "steps", "tokens_generated", "avg_batch_occupancy",
                "admitted", "completed", "pending", "active", "preemptions",
                "prefill_tokens", "prefill_buckets", "pages_in_use",
                *engine_keys) if k in stats}},
        "samples": {"gap_ms": gaps, "ttft_ms": ttft, "lateness_ms": late,
                    "engine_step_ms": step_ms},
        "trace": traced,
    }


def record(played, device, found, limits, sound) -> dict:
    """``play``'s parts with the device's facts and the comparison with the
    reference: ``correct`` where that comparison held (``sound``), nothing
    compiled in the window, no request failed and there were gaps to read."""
    checks = {**limits, **found, **played.pop("checks")}
    correct = (sound and checks["compiles_in_window"] == 0
               and played["failed"] == 0 and bool(played["samples"]["gap_ms"])
               and device["platform"] == "tpu")
    played["counters"]["param_count"] = device.get("param_count")
    return {"device": device, "correct": bool(correct), "checks": checks,
            **played}


@contextlib.contextmanager
def session(cell, config, traffic, seed, allow_cpu=False):
    """The replica deployed, warmed and compared with the reference, for the
    length of the block: yields ``window(traffic, seconds, trace)``, which
    plays one pre-roll and window and returns the run's record (``run``
    plays one; ``sweep.py`` several, at other rates)."""
    model = {
        "tiny": False, "vocab_size": config["vocab_size"],
        "max_position_embeddings": config["n_positions"],
        "num_layers": config["n_layer"], "num_heads": config["n_head"],
        "hidden_size": config["n_embd"], "dtype": config["serve"]["dtype"],
        "scan_layers_threshold": config["serve"]["scan_layers_threshold"]}
    with deployed(BenchLLMServer, ("gpt2", model), config, seed,
                  allow_cpu) as (handle, call):
        vocab = config["vocab_size"]
        call("warm", warm_prompts(traffic, vocab), 2)
        ref = traffic["reference"]
        check = call("reference_check", cell["config"], config,
                     [(3 * j + seed) % vocab
                      for j in range(ref["prompt_tokens"])],
                     ref["new_tokens"])
        sound = (check["tokens"] == ref["new_tokens"]
                 and check["logprob_max_err"] <= ref["logprob_tolerance"]
                 and check["argmax_margin_max"] <= ref["logprob_tolerance"])

        def window(traffic, seconds, trace):
            played = play(handle, call, traffic, seed, vocab, seconds, trace)
            return record(played, call("facts"), check, ref, sound)

        yield window


def run(cell, config, traffic, seed, seconds, trace, allow_cpu=False):
    with session(cell, config, traffic, seed, allow_cpu) as window:
        return window(traffic, seconds, trace)
