"""Driver ``serve_decoder``: ``serve_lm``'s open-loop chat traffic against one
``LLMServer`` replica, for a configuration that says itself which model of
the program it is.

``serve_lm.run`` binds ``"gpt2"`` and reads GPT-2's key names.  This one
binds ``config["serve"]["model_kind"]`` with ``config["serve"]["model_kw"]``,
in which a value ``"$key"`` stands for the configuration's published
``key``: the program's arguments are built from the published numbers, and a
rehearsal that shrinks a published number shrinks the model.  Everything else
is ``serve_lm``'s: its ``Client``, its ``warm_prompts``, its server's
counters and profiler, the same window and the same arithmetic on the
stamps.  The body of ``run`` is that file's, copied, because a PR that adds a
cell may edit no file the benchmark has; PERF.md section 7 lists the two for
folding into one.

What differs besides the binding: the comparison with the plain reference
runs the reference as it is (it jits layer by layer, so that it fits beside
a 7 GB model and its page pool; one ``jax.jit`` around the whole of it would
hold every layer in float32 at once) and, where the reference says which
experts it chose, reports ``router_agreement``; it is made a second time
where the traffic file has a ``reference.long`` (a short prompt reaches only
the smallest prefill program, a few pages of context and, in a routed model,
only the few-rows form of the expert FFN; the long one has a limit of its
own); the record carries the engine's ``moe_*`` shares; and
``counters.live_tokens_at_trace`` is gone (PERF.md section 7: readers take
``kv_tokens`` from the engine's spans).  A
program that cannot build the model (the parent of the PR that brought a
configuration) raises in the replica's constructor; ``serve.run`` hands that
error on and the run ends non-zero within seconds.
"""
from __future__ import annotations

import time

from benchmark import common, loadgen
from benchmark.drivers.serve_lm import BenchLLMServer, Client, warm_prompts


def model_kw(config: dict) -> dict:
    """``serve.model_kw`` with every ``"$key"`` replaced by the published
    value of ``key``."""
    return {k: config[v[1:]] if isinstance(v, str) and v.startswith("$")
            else v for k, v in config["serve"]["model_kw"].items()}


def comparisons(reference: dict) -> list:
    """The traffic file's ``reference`` as the comparisons to make, each
    with ``prompt_tokens``, ``new_tokens``, ``logprob_tolerance`` and, for
    a routed model, ``router_agreement_min``: the block itself, then its
    ``long`` where it has one."""
    return [reference] + ([reference["long"]] if "long" in reference else [])


def reference_prompt(n: int, seed: int, vocab: int) -> list:
    return [(3 * j + seed) % vocab for j in range(n)]


def within(check: dict, limits: dict) -> bool:
    """Every token answered, both the largest log-probability error and
    the largest argmax margin inside the comparison's limit, and, where the
    comparison sets a floor for it, the experts chosen as the reference
    chose them in at least that share of (layer, token) pairs."""
    return (check["tokens"] == limits["new_tokens"]
            and check["logprob_max_err"] <= limits["logprob_tolerance"]
            and check["argmax_margin_max"] <= limits["logprob_tolerance"]
            and check.get("router_agreement", 1.0)
            >= limits.get("router_agreement_min", 0.0))


def _ids(prompt, got):
    import jax.numpy as jnp

    return jnp.asarray([list(prompt) + got["tokens"]], jnp.int32)


def compare(ref, config, params, prompt, got, program_experts=None) -> dict:
    """One greedy answer (``got``: the engine's tokens and the
    log-probability it gave each) against the reference's one full forward
    over prompt + answer on ``params``: the error of each chosen token's
    log-probability, how far below the reference's best logit the chosen
    token's lies (0 unless rounding flipped an argmax), and, for a routed
    model, the share of (layer, token) pairs in which the program (its
    ``program_experts``, [layers, 1, tokens, k]) and the reference chose
    the same set of experts."""
    import jax
    import jax.numpy as jnp

    ids = _ids(prompt, got)
    routed = program_experts is not None
    out = (ref.forward_with_experts if routed else ref.forward)(
        params, ids, config)
    logits = (out[0] if routed else out)[0, len(prompt) - 1:-1]
    logp = jax.nn.log_softmax(logits, -1)
    chosen = jnp.asarray(got["tokens"])[:, None]
    ref_lp = jnp.take_along_axis(logp, chosen, -1)[:, 0]
    margin = jnp.max(logits, -1) - jnp.take_along_axis(
        logits, chosen, -1)[:, 0]
    check = {"tokens": len(got["tokens"]),
             "logprob_max_err": float(jnp.max(jnp.abs(
                 ref_lp - jnp.asarray(got["logprobs"])))),
             "argmax_margin_max": float(jnp.max(margin))}
    if routed:
        check["router_agreement"] = ref.router_agreement(
            program_experts, out[1])
    return check


def program_experts(model, params, prompt, got):
    """The experts the program's own forward chooses on prompt + answer
    ([layers, 1, tokens, k]), or None for a model that routes nothing: what
    its expert layers sow into the ``moe`` collection."""
    import jax
    import jax.numpy as jnp

    if not getattr(model.config, "num_experts", 0):
        return None
    _, sown = jax.jit(lambda p, i: model.apply(
        {"params": p}, i, mutable=["moe"]))(params, _ids(prompt, got))
    return jnp.stack([
        sown["moe"][f"layer_{i}"]["moe"]["expert_idx"][0]
        for i in range(model.config.num_layers)])


class BenchDecoderServer(BenchLLMServer):
    def reference_check(self, config_name, config, prompt, new_tokens):
        """Prefill and the cached decode of one greedy request against the
        reference's full forward (``compare``)."""
        eng = self.engine
        got = eng.rollout(eng.submit(prompt, new_tokens), timeout=600.0)
        return compare(common.load_module("reference", config_name), config,
                       eng._params, prompt, got,
                       program_experts(eng._model, eng._params, prompt, got))


def run(cell, config, traffic, seed, seconds, trace, allow_cpu=False):
    import ray_tpu
    from ray_tpu import serve

    s = config["serve"]
    ray_tpu.init(**({"num_tpus": 1} if allow_cpu else {}))
    try:
        handle = serve.run(serve.deployment(
            BenchDecoderServer, name="llm", num_replicas=1,
            ray_actor_options={"num_tpus": 1,
                               "max_concurrency": s["max_concurrency"]},
        ).bind(s["model_kind"], model_kw(config),
               seed=common.jax_seed(seed), allow_cpu=allow_cpu,
               max_slots=s["max_slots"], page_size=s["page_size"],
               max_ctx=s["max_ctx"], chunk_tokens=s["chunk_tokens"]))

        def call(method, *args):
            return ray_tpu.get(handle.method(method).remote(*args),
                               timeout=1100.0)

        vocab = config["vocab_size"]
        call("warm", warm_prompts(traffic, vocab), 2)
        refs = comparisons(traffic["reference"])
        found = [call("reference_check", cell["config"], config,
                      reference_prompt(r["prompt_tokens"], seed, vocab),
                      r["new_tokens"]) for r in refs]

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
        traced = None
        if trace:
            time.sleep(max(0.0, w0 + traffic["trace_offset_s"]
                           - time.perf_counter()))
            call("trace_start")
            time.sleep(traffic["trace_s"])
            traced = call("trace_stop")
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
    attempted = failed = 0
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

    recent = [t for t in stamps if t >= stamps[-1] - seconds] if stamps else []
    step_ms = [(b - a) * 1e3 for a, b in zip(recent, recent[1:])]
    checks = {**found[0], **traffic["reference"],
              "compiles_in_window": compiles,
              "decode_programs": stats.get("decode_cache_size"),
              "errors": sorted(client.errors.values())[:3]}
    if len(found) > 1:
        checks["long"] = {**refs[1], **found[1]}
    correct = (all(within(c, r) for c, r in zip(found, refs))
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
            "preroll_s": preroll,
            "param_count": device.get("param_count"),
            "engine": {k: stats[k] for k in (
                "steps", "tokens_generated", "avg_batch_occupancy",
                "admitted", "completed", "pending", "active", "preemptions",
                "prefill_tokens", "prefill_buckets", "pages_in_use",
                "moe_experts_hit_share", "moe_max_expert_share")
                if k in stats}},
        "samples": {"gap_ms": gaps, "ttft_ms": ttft,
                    "lateness_ms": gen.lateness_ms(w0, w1),
                    "engine_step_ms": step_ms},
        "trace": traced,
    }
