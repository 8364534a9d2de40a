"""Driver ``serve_lm``: open-loop chat traffic against one ``LLMServer``
replica, deployed through the normal ``serve`` API.

The parent never touches jax: the replica's worker owns the chip.  Every
request goes ``submit_stream`` -> ``next_chunk`` ... through the serve handle,
one token to a chunk, and the client stamps each chunk as its reply arrives.
The generator starts during set-up and plays a pre-roll at the cell's rate,
so the measured window opens on an engine at its steady occupancy.

``BenchLLMServer`` adds to ``LLMServer`` only what a measurement needs inside
the process that holds the chip: the device's facts, the comparison with the
plain reference, the compile counter, the profiler, and host spans around the
engine's two dispatches.
"""
from __future__ import annotations

import functools
import queue
import threading
import time

from benchmark import common, loadgen
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


class Client:
    """Submits through the handle and stamps every chunk.  Replies land on a
    queue from whichever thread completes the call, with the time taken
    there; one collector thread does the book-keeping and asks for the next
    chunk."""

    def __init__(self, handle, schedule):
        self._handle, self.schedule = handle, schedule
        n = len(schedule)
        self.stamps = [[] for _ in range(n)]
        self.done_at = [None] * n
        self.errors = {}
        self._rid = [None] * n
        self._events = queue.SimpleQueue()
        self._thread = threading.Thread(target=self._collect, name="collect",
                                        daemon=True)
        self._thread.start()

    def _call(self, kind, i, method, *args):
        fut = self._handle.method(method).remote(*args).future()
        fut.add_done_callback(
            lambda f: self._events.put((kind, i, time.perf_counter(), f)))

    def send(self, i, req):
        self._call("rid", i, "submit_stream", req["prompt"],
                   req["max_new_tokens"])

    def _collect(self):
        while True:
            ev = self._events.get()
            if ev is None:
                return
            kind, i, t, fut = ev
            try:
                val = fut.result()
            except Exception as e:  # noqa: BLE001 — counted as failed
                self.errors[i] = repr(e)
                continue
            if kind == "rid":
                self._rid[i] = val
            elif val is None:
                self.done_at[i] = t
                continue
            else:
                self.stamps[i].extend([t] * len(val))
            self._call("chunk", i, "next_chunk", self._rid[i], 300.0)

    def close(self):
        self._events.put(None)
        self._thread.join()


def warm_prompts(traffic: dict, vocab: int) -> list:
    """One prompt at every power of two between the shortest and the longest
    prompt, and at both ends: every prefill program this traffic can reach."""
    p = traffic["prompt_tokens"]
    lens = {p["min"], p["max"]} | {
        1 << k for k in range(32) if p["min"] <= 1 << k <= p["max"]}
    return [[(7 * j + n) % vocab for j in range(n)] for n in sorted(lens)]


def run(cell, config, traffic, seed, seconds, trace, allow_cpu=False):
    import ray_tpu
    from ray_tpu import serve

    s = config["serve"]
    ray_tpu.init(**({"num_tpus": 1} if allow_cpu else {}))
    try:
        handle = serve.run(serve.deployment(
            BenchLLMServer, name="llm", num_replicas=1,
            ray_actor_options={"num_tpus": 1,
                               "max_concurrency": s["max_concurrency"]},
        ).bind("gpt2", {
            "tiny": False, "vocab_size": config["vocab_size"],
            "max_position_embeddings": config["n_positions"],
            "num_layers": config["n_layer"], "num_heads": config["n_head"],
            "hidden_size": config["n_embd"], "dtype": s["dtype"],
            "scan_layers_threshold": s["scan_layers_threshold"],
        }, seed=common.jax_seed(seed), allow_cpu=allow_cpu,
            max_slots=s["max_slots"], page_size=s["page_size"],
            max_ctx=s["max_ctx"], chunk_tokens=s["chunk_tokens"]))

        def call(method, *args):
            return ray_tpu.get(handle.method(method).remote(*args),
                               timeout=1100.0)

        vocab = config["vocab_size"]
        call("warm", warm_prompts(traffic, vocab), 2)
        ref = traffic["reference"]
        check = call("reference_check", cell["config"], config,
                     [(3 * j + seed) % vocab
                      for j in range(ref["prompt_tokens"])],
                     ref["new_tokens"])

        preroll = float(traffic["preroll_s"])
        schedule = loadgen.build_schedule(traffic, seed, vocab,
                                          preroll + seconds)
        client = Client(handle, schedule)
        gen = loadgen.OpenLoop(schedule, client.send)
        t0 = time.perf_counter() + 0.05
        w0, w1 = t0 + preroll, t0 + preroll + seconds
        gen.start(t0)
        time.sleep(max(0.0, w0 - time.perf_counter()))
        call("arm")
        window_start = time.time() - (time.perf_counter() - w0)
        traced, t_mid = None, None
        if trace:
            time.sleep(max(0.0, w0 + traffic["trace_offset_s"]
                           - time.perf_counter()))
            ta = time.perf_counter()
            call("trace_start")
            time.sleep(traffic["trace_s"])
            traced = call("trace_stop")
            t_mid = (ta + time.perf_counter()) / 2
        time.sleep(max(0.0, w1 - time.perf_counter()))
        gen.stop()
        compiles = call("disarm")
        stats, stamps = call("stats"), call("step_stamps")
        device = call("facts")
        client.close()
    finally:
        serve.shutdown()
        ray_tpu.shutdown()

    gaps, ttft, in_window_tokens = [], [], 0
    attempted = failed = live_tokens = 0
    waiting = [0, 0]  # due, but no first token yet, at the window's edges
    for i, req in enumerate(schedule):
        if gen.sent_at[i] is None:
            continue
        st, due = client.stamps[i], t0 + req["due_s"]
        attempted += 1
        done = client.done_at[i]
        if i in client.errors or len(st) > req["max_new_tokens"] or (
                done is not None and len(st) != req["max_new_tokens"]):
            failed += 1
        gaps += [g * 1e3 for g in loadgen.gaps_in_window(st, w0, w1)]
        in_window_tokens += sum(1 for t in st if w0 <= t <= w1)
        if w0 <= due <= w1 and st:
            ttft.append((st[0] - due) * 1e3)
        for k, edge in enumerate((w0, w1)):
            if due <= edge and (not st or st[0] > edge):
                waiting[k] += 1
        if t_mid is not None and st and st[0] <= t_mid and (
                done is None or done > t_mid):
            live_tokens += len(req["prompt"]) + sum(
                1 for t in st if t <= t_mid)

    recent = [t for t in stamps if t >= stamps[-1] - seconds] if stamps else []
    step_ms = [(b - a) * 1e3 for a, b in zip(recent, recent[1:])]
    checks = {**check, **traffic["reference"],
              "compiles_in_window": compiles,
              "decode_programs": stats.get("decode_cache_size"),
              "errors": sorted(client.errors.values())[:3]}
    correct = (check["tokens"] == ref["new_tokens"]
               and check["logprob_max_err"] <= ref["logprob_tolerance"]
               and check["argmax_margin_max"] <= ref["logprob_tolerance"]
               and compiles == 0 and failed == 0 and bool(gaps)
               and device["platform"] == "tpu")
    end_to_end = {}
    if gaps:
        end_to_end = {"token_gap_p50_ms": loadgen.percentile(gaps, 50),
                      "token_gap_p95_ms": loadgen.percentile(gaps, 95)}
    return {
        "device": device, "correct": bool(correct), "checks": checks,
        "attempted": attempted, "failed": failed,
        "window_start": window_start, "window_s": float(seconds),
        "end_to_end": end_to_end,
        "counters": {
            "gaps": len(gaps), "tokens_in_window": in_window_tokens,
            "tokens_per_s": in_window_tokens / seconds,
            "requests_due_in_window": len(ttft),
            "waiting_at_window_start": waiting[0],
            "waiting_at_window_end": waiting[1],
            "rate_per_s": traffic["arrivals"]["rate_per_s"],
            "preroll_s": preroll, "live_tokens_at_trace": live_tokens,
            "param_count": device.get("param_count"),
            "engine": {k: stats[k] for k in (
                "steps", "tokens_generated", "avg_batch_occupancy",
                "admitted", "completed", "pending", "active", "preemptions",
                "prefill_tokens", "prefill_buckets", "pages_in_use")
                if k in stats}},
        "samples": {"gap_ms": gaps, "ttft_ms": ttft,
                    "lateness_ms": gen.lateness_ms(w0, w1),
                    "engine_step_ms": step_ms},
        "trace": traced,
    }
