"""The one span recorder (ray_tpu.observability) and the spans the serve
engine and the train ingest make with it (ISSUE 24).

Structure, not time: which spans exist, what lies inside what, which
counts agree with the engine's own, and that the same spans are in the
JAX profiler's trace on a host line.  The names checked here are the
contract PERF.md lists.
"""
import glob
import subprocess
import sys
import time
from collections import Counter

import numpy as np
import pytest

from ray_tpu import observability as obs

ENGINE_CHILDREN = ("engine.admit", "engine.decode.dispatch",
                   "engine.decode.fetch", "engine.emit")


@pytest.fixture(scope="module")
def gpt2():
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import GPT2, GPT2Config

    cfg = GPT2Config.tiny(dtype=jnp.float32)
    model = GPT2(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params, cfg


@pytest.fixture(scope="module")
def traced_engine(gpt2, tmp_path_factory):
    """A tiny engine serves three requests while a ``jax.profiler`` trace
    runs on the CPU backend, the tracing flag off: what the ring holds,
    the engine's counts over the same stretch, the queue waits its
    histogram was given, and the profile."""
    import jax

    from ray_tpu.serve.llm_engine import LLMEngine

    model, params, cfg = gpt2
    obs.drain_spans()  # lifecycle spans of whatever file this worker ran last
    eng = LLMEngine(model, params, max_slots=4, page_size=8, max_ctx=64)
    rng = np.random.default_rng(0)
    prompts = [list(map(int, rng.integers(0, cfg.vocab_size, size=n)))
               for n in (5, 11, 19)]
    try:
        for r in [eng.submit(p, 3) for p in prompts]:  # compile
            eng.result(r, timeout=300)
        setup = obs.drain_spans()  # lifecycle spans: recorded, flag off
        # the off path where it is hot: 200 iterations after set-up
        steps0 = eng.stats()["steps"]
        while eng.stats()["steps"] - steps0 < 200:
            for r in [eng.submit(p, 40) for p in prompts]:
                eng.result(r, timeout=300)
        off = {"span": obs.span("engine.iteration", active=1),
               "steps": eng.stats()["steps"] - steps0,
               "ring": len(obs.drain_spans())}
        observed = []
        eng._observe_queue_wait = observed.append
        trace_dir = str(tmp_path_factory.mktemp("profile"))
        before = eng.stats()
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        try:
            rids = [eng.submit(p, 6) for p in prompts]
            for r in rids:
                eng.result(r, timeout=300)
            time.sleep(0.3)  # the last iteration closes inside the profile
        finally:
            jax.profiler.stop_trace()
        after = eng.stats()
        lowered = {
            "decode": eng._decode.lower(
                eng._params, eng._k_pages, eng._v_pages, eng._table,
                eng._lengths, eng._last_tok, eng._active, eng._temps,
                eng._top_ps, eng._seeds).as_text(debug_info=True),
            "prefill": eng._prefill_fn(8).lower(
                eng._params, eng._k_pages, eng._v_pages, eng._table[0],
                np.zeros((8,), np.int32), np.int32(5), np.float32(0),
                np.float32(1), np.int32(0)).as_text(debug_info=True)}
        profiled, observed = obs.drain_spans(), list(observed)
        # the same loop under the flag, its one request through the
        # server's streaming calls, which the engine counts
        from ray_tpu.serve.llm_engine import LLMServer
        from ray_tpu.util import tracing

        server = LLMServer.__new__(LLMServer)
        server.engine = eng
        calls0 = eng.stats()["reply_calls"]
        tracing.enable_tracing()
        try:
            rid = server.submit_stream(prompts[0], 6)
            calls = 1
            while server.next_chunk(rid) is not None:
                calls += 1
            server.request_stats(rid)
            calls += 2  # the chunk that said it was over, and the stats
            time.sleep(0.3)  # the last iteration closes under the flag
        finally:
            tracing.disable_tracing()
        flagged = {"spans": obs.drain_spans(), "calls": calls,
                   "counted": eng.stats()["reply_calls"] - calls0}
    finally:
        eng.close()
        obs.drain_spans()  # the idle span that the flag opened
    return {"spans": profiled, "flagged": flagged, "rids": rids, "off": off,
            "setup": setup,
            "counts": {k: after[k] - before[k] for k in (
                "steps", "admitted", "lookahead_steps", "drained_steps")},
            "observed": observed, "lowered": lowered,
            "xplane": glob.glob(trace_dir + "/plugins/profile/*/*.xplane.pb")}


def named(spans, name):
    return [s for s in spans if s["name"] == name]


# (a) ----------------------------------------------------------------------
def test_off_is_one_shared_noop_and_an_empty_ring(traced_engine):
    off = traced_engine["off"]
    assert off["span"] is obs.NO_SPAN and off["ring"] == 0
    assert off["steps"] >= 200  # iterations after set-up left nothing
    assert obs.span("x", a=1) is obs.NO_SPAN and not obs.on()
    with obs.span("x") as sp:
        sp.set(b=2)
    assert obs.record("x", 0.0, 1.0) is None
    assert len(obs.ring()) == 0


def test_off_imports_no_jax():
    code = ("import sys; from ray_tpu import observability as obs; "
            "assert obs.span('x', a=1) is obs.NO_SPAN; "
            "assert obs._ring is None and 'jax' not in sys.modules")
    subprocess.run([sys.executable, "-c", code], check=True, timeout=120)


def test_spans_lie_on_a_host_line_of_the_profile(traced_engine):
    from jax.profiler import ProfileData

    assert traced_engine["xplane"], "the profiler wrote no .xplane.pb"
    ring = Counter(s["name"] for s in traced_engine["spans"]
                   if s["name"] != "engine.idle")
    assert {"engine.iteration", "engine.prefill", "request.queued",
            "request.decode", *ENGINE_CHILDREN} <= set(ring)
    host = Counter()
    for plane in ProfileData.from_file(traced_engine["xplane"][0]).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                host.update(e.name for e in line.events
                            if e.name.startswith(("engine.", "request.")))
    host.pop("engine.idle", None)  # open when the profile started or ended
    assert host == ring


# (b) ----------------------------------------------------------------------
def test_children_lie_inside_their_iteration(traced_engine):
    spans = traced_engine["spans"]
    iterations = {s["span_id"]: s for s in named(spans, "engine.iteration")}
    assert iterations
    for name in ENGINE_CHILDREN:
        for child in named(spans, name):
            it = iterations[child["parent_id"]]
            assert it["start"] <= child["start"] <= child["end"] <= it["end"]
    admits = {s["span_id"]: s for s in named(spans, "engine.admit")}
    for prefill in named(spans, "engine.prefill"):
        a = admits[prefill["parent_id"]]
        assert a["start"] <= prefill["start"] <= prefill["end"] <= a["end"]
    assert all(s["parent_id"] is None for s in iterations.values())


def test_counts_equal_the_engines_own(traced_engine):
    spans, counts = traced_engine["spans"], traced_engine["counts"]
    assert len(named(spans, "engine.decode.dispatch")) == counts["steps"]
    assert len(named(spans, "engine.decode.fetch")) == counts["steps"]
    assert len(named(spans, "engine.emit")) == counts["steps"]
    assert len(named(spans, "engine.prefill")) == counts["admitted"] == 3
    assert len(named(spans, "request.queued")) == len(traced_engine["rids"])
    assert sum(s["args"]["admitted"]
               for s in named(spans, "engine.admit")) == counts["admitted"]
    assert sum(s["args"]["tokens"] for s in named(spans, "engine.emit")) \
        == 3 * 5  # six tokens a request, the first from its prefill


# the step's account (ISSUE 59) ---------------------------------------------
DISPATCH_PARTS = ("engine.decode.stage", "engine.decode.call",
                  "engine.decode.readback")
BOOK = ("engine.grow", "engine.decode.prepare", "engine.decode.settle")


def recorded(traced_engine, how):
    return (traced_engine["spans"] if how == "profile"
            else traced_engine["flagged"]["spans"])


@pytest.mark.parametrize("how", ["profile", "flag"])
def test_a_decode_iteration_yields_the_account(traced_engine, how):
    """Under a profile and under the flag alike: a dispatch is made of
    stage, call and readback, one after the other; grow, prepare and
    settle are the iteration's own children."""
    spans = recorded(traced_engine, how)
    by_id = {s["span_id"]: s for s in spans}
    dispatches = named(spans, "engine.decode.dispatch")
    assert dispatches
    for d in dispatches:
        parts = sorted((s for s in spans if s["parent_id"] == d["span_id"]),
                       key=lambda s: s["start"])
        assert tuple(p["name"] for p in parts) == DISPATCH_PARTS
        edges = [d["start"]] + [t for p in parts
                                for t in (p["start"], p["end"])] + [d["end"]]
        assert edges == sorted(edges)  # disjoint, in order, inside
        assert by_id[d["parent_id"]]["name"] == "engine.iteration"
    for name in BOOK:
        found = named(spans, name)
        assert found, name
        for s in found:
            it = by_id[s["parent_id"]]
            assert it["name"] == "engine.iteration"
            assert it["start"] <= s["start"] <= s["end"] <= it["end"]
    # a step settles three times: as it is dispatched (the donated
    # arrays die there), as it is read, and as it is let go after the emit
    assert len(named(spans, "engine.decode.prepare")) == len(dispatches)
    assert len(named(spans, "engine.decode.settle")) == 3 * len(dispatches)
    assert len(named(spans, "engine.grow")) == len(
        named(spans, "engine.iteration"))


@pytest.mark.parametrize("how", ["profile", "flag"])
def test_the_accounts_arguments_are_there_and_of_their_type(
        traced_engine, how):
    spans = recorded(traced_engine, how)
    for s in named(spans, "engine.decode.stage"):
        a = s["args"]
        assert type(a["uploads"]) is int and type(a["upload_bytes"]) is int
        assert (a["uploads"] == 0) == (a["upload_bytes"] == 0)
        assert 0 <= a["uploads"] <= 8
    for s in named(spans, "engine.decode.readback"):
        assert s["args"] == {"arrays": 2}  # tokens and their log-probs
    grown = [s["args"]["pages"] for s in named(spans, "engine.grow")]
    assert all(type(n) is int and n >= 0 for n in grown)
    for s in named(spans, "engine.iteration"):
        a = s["args"]
        assert type(a["cpu_ms"]) is float and a["cpu_ms"] >= 0.0
        assert type(a["reply_calls"]) is int and a["reply_calls"] >= 0
        assert {"active", "pending"} <= set(a)


def test_a_slots_first_step_sends_its_state_and_a_later_one_does_not(
        traced_engine):
    """``uploads`` follows ``_on_device``: the step after an admission
    sends the arrays the admission changed, a step between two sends
    none but what every step changes."""
    stages = sorted(named(traced_engine["spans"], "engine.decode.stage"),
                    key=lambda s: s["start"])
    sent = [s["args"]["uploads"] for s in stages]
    assert max(sent) >= 3 and sent[0] == max(sent)  # table, tokens, rows...
    assert min(sent) < max(sent)
    # a page is 8 tokens: prompts of 5, 11 and 19 with 6 new tokens each
    # cross one page boundary between them while decoding
    assert sum(s["args"]["pages"] for s in named(
        traced_engine["spans"], "engine.grow")) >= 1


def test_reply_calls_count_the_servers_calls(traced_engine):
    flagged = traced_engine["flagged"]
    assert flagged["counted"] == flagged["calls"] >= 4
    told = sum(s["args"]["reply_calls"]
               for s in named(flagged["spans"], "engine.iteration"))
    # an iteration is told the calls since the one before it closed:
    # all of them but those after the last iteration
    assert 1 <= told <= flagged["calls"]


def test_off_the_accounts_spans_are_the_shared_noop(traced_engine):
    """Flag off, no profile: over 100 iterations the ring did not grow,
    and every new name is the one no-op."""
    off = traced_engine["off"]
    assert off["steps"] >= 100 and off["ring"] == 0
    for name in DISPATCH_PARTS + BOOK:
        assert obs.span(name, pages=0) is obs.NO_SPAN


def test_the_loop_runs_a_step_ahead_and_the_span_says_so(traced_engine):
    """``in_flight`` on ``engine.decode.dispatch``: 1 where the step was
    dispatched while the one before it was still unread (ISSUE 34)."""
    counts = traced_engine["counts"]
    flights = [s["args"]["in_flight"] for s in named(
        traced_engine["spans"], "engine.decode.dispatch")]
    assert set(flights) == {0, 1}  # every burst starts drained
    assert sum(flights) == counts["lookahead_steps"] > 0
    assert flights.count(0) == counts["drained_steps"] > 0
    assert len(flights) == counts["steps"]


def test_a_requests_three_spans_share_its_id(traced_engine):
    spans = traced_engine["spans"]
    for name in ("request.queued", "engine.prefill", "request.decode"):
        assert sorted(s["args"]["request_id"] for s in named(spans, name)) \
            == sorted(traced_engine["rids"])
    for s in named(spans, "request.decode"):
        assert s["args"] == {"request_id": s["args"]["request_id"],
                             "tokens": 6, "preemptions": 0}


# (c) ----------------------------------------------------------------------
def test_queue_wait_ends_where_prefill_starts(traced_engine):
    spans = traced_engine["spans"]
    prefill = {s["args"]["request_id"]: s
               for s in named(spans, "engine.prefill")}
    queued = named(spans, "request.queued")
    for q in queued:
        assert q["start"] <= q["end"] <= prefill[
            q["args"]["request_id"]]["start"]
    # serve_queue_wait_s is given that same interval, not submit to first
    # token
    assert traced_engine["observed"] == pytest.approx(
        [q["end"] - q["start"] for q in queued], abs=1e-6)


# (d) ----------------------------------------------------------------------
def test_engine_programs_carry_scope_names(traced_engine, gpt2):
    decode = traced_engine["lowered"]["decode"]
    assert "llm_decode" in decode
    for scope in ("attend", "sample", "scatter"):
        assert f"llm_decode)/{scope}" in decode or f"/{scope}/" in decode
    # attention reads the pool in place: the kernel, and no gather before it
    assert "paged_attn" in decode and "/gather/" not in decode
    prefill = traced_engine["lowered"]["prefill"]
    assert "llm_prefill_8" in prefill
    for scope in ("attend", "sample", "scatter"):
        assert f"/{scope}" in prefill


def test_anakin_ppo_step_carries_scope_names():
    from ray_tpu.rllib import PPOConfig

    algo = (PPOConfig().environment("CartPole-v1")
            .anakin(num_envs=8, unroll_length=4).debugging(seed=0).build())
    text = algo._train_step.lower(algo._anakin_state).as_text(
        debug_info=True)
    for scope in ("rollout", "gae", "sgd"):
        assert f"/{scope}/" in text


@pytest.mark.parametrize("whole_head,kernels", [
    (True, ("flash_fwd", "flash_bwd")),            # one backward kernel
    (False, ("flash_fwd", "flash_dq", "flash_dkv")),  # past its residency
])
def test_flash_kernels_carry_their_names(whole_head, kernels, monkeypatch):
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import attention
    from ray_tpu.ops.attention import flash_attention

    monkeypatch.setattr(attention, "_whole_head_fits",
                        lambda *a: whole_head)

    q = jnp.ones((1, 128, 2, 64), jnp.float32)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True, interpret=True).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, q, q).as_text(debug_info=True)
    for kernel in ("flash_fwd", "flash_bwd", "flash_dq", "flash_dkv"):
        assert (kernel in text) == (kernel in kernels), kernel


# (e) ----------------------------------------------------------------------
def test_prefetcher_spans():
    from ray_tpu.data.prefetch import DevicePrefetcher
    from ray_tpu.util import tracing

    batches = [{"x": np.ones((4, 8), np.float32)} for _ in range(6)]
    obs.drain_spans()
    tracing.enable_tracing()
    try:
        assert len(list(DevicePrefetcher(batches))) == 6
    finally:
        tracing.disable_tracing()
    spans = obs.drain_spans()
    produce = {s["span_id"]: s for s in named(spans, "ingest.produce")}
    h2d = named(spans, "ingest.h2d")
    assert len(produce) == len(h2d) == 6
    assert all(s["args"] == {"rows": 4} for s in produce.values())
    for s in h2d:
        p = produce[s["parent_id"]]
        assert p["start"] <= s["start"] <= s["end"] <= p["end"]
        assert s["args"] == {"bytes": 4 * 8 * 4}
    waits = named(spans, "ingest.wait")
    assert len(waits) >= 6
    assert all(0 <= s["args"]["depth"] <= 2 for s in waits)


# the recorder itself ---------------------------------------------------------
def test_nesting_context_and_one_clock():
    from ray_tpu._private import profiling
    from ray_tpu.util import tracing

    obs.drain_spans()
    tracing.enable_tracing()
    try:
        with obs.span("outer", k=1) as outer:
            with obs.span("inner"):
                pass
            outer.set(n=2)
            t = time.perf_counter()
            profiling.record_span("posthoc", t - 0.5, t, rid=3)
        with tracing.span("root", a=1):
            with obs.span("child"):
                pass
    finally:
        tracing.disable_tracing()
    got = {s["name"]: s for s in profiling.recorded_spans(clear=True)}
    assert got["outer"]["args"] == {"k": 1, "n": 2}
    assert got["outer"]["trace_id"] is None
    assert got["inner"]["parent_id"] == got["outer"]["span_id"]
    assert got["posthoc"]["parent_id"] == got["outer"]["span_id"]
    assert got["posthoc"]["end"] - got["posthoc"]["start"] == \
        pytest.approx(0.5)
    assert got["outer"]["start"] <= got["posthoc"]["end"] <= \
        got["outer"]["end"]  # perf_counter stamps land on the same clock
    assert abs(got["outer"]["start"] - time.time()) < 5.0  # wall clock
    assert got["root"]["trace_id"] and got["root"]["parent_id"] is None
    assert got["child"]["trace_id"] == got["root"]["trace_id"]
    assert got["child"]["parent_id"] == got["root"]["span_id"]
    assert obs.get_context() is None


def test_session_spans_outlive_shutdown(shutdown_only):
    """The head's store stays readable after ``shutdown`` until the next
    ``init``, and a worker's spans reach it though only the worker had
    the flag on."""
    import ray_tpu

    ray_tpu.init(num_cpus=1)

    @ray_tpu.remote
    def work():
        from ray_tpu import observability as o
        from ray_tpu.util import tracing

        tracing.enable_tracing()
        try:
            with o.span("worker.side", n=1):
                pass
        finally:
            tracing.disable_tracing()
        return True

    assert ray_tpu.get(work.remote())
    ray_tpu.shutdown()
    assert [s["args"] for s in obs.session_spans("worker.side")] == [{"n": 1}]
    ray_tpu.init(num_cpus=1)
    assert obs.session_spans("worker.side") == []


# lifecycle spans: set-up, recorded whatever the flag says (ISSUE 41) ---------
LIFECYCLE_ONCE = ("runtime.init", "serve.deploy", "serve.replica_init",
                  "model.build", "engine.init")


def inside(inner, outer):
    return outer["start"] <= inner["start"] and inner["end"] <= outer["end"]


def compiles_under(spans, parent):
    return [s for s in named(spans, "jax.compile")
            if s["parent_id"] == parent["span_id"]]


def test_a_lifecycle_span_is_recorded_with_everything_off():
    obs.drain_spans()
    assert not obs.on()
    with obs.span("life", _lifecycle=True, k=1) as life:
        with obs.span("plain"):  # per-call: not recorded while off
            pass
        t = time.perf_counter()
        obs.record("life.after", t - 0.25, t, _lifecycle=True, n=2)
        assert obs.record("plain.after", t - 0.25, t) is None
        life.set(m=3)
    got = {s["name"]: s for s in obs.drain_spans()}
    assert set(got) == {"life", "life.after"}
    assert got["life"]["args"] == {"k": 1, "m": 3}
    assert got["life"]["trace_id"] is None  # pools under UNTRACED
    assert got["life.after"]["parent_id"] == got["life"]["span_id"]
    assert got["life.after"]["end"] - got["life.after"]["start"] == \
        pytest.approx(0.25)
    assert abs(got["life"]["start"] - time.time()) < 5.0  # time.time()'s clock
    assert obs.get_context() is None


def test_engine_set_up_is_one_init_and_one_compile_a_program(traced_engine):
    """The fixture's set-up, flag off and no profile: three prompt buckets
    and the decode step, each compiled in its first call and never again
    (the 200 iterations and the traced requests add none)."""
    setup = traced_engine["setup"]
    [init] = named(setup, "engine.init")
    assert init["args"]["slots"] == 4 and init["args"]["pool_bytes"] > 0
    assert init["args"]["state_pool_bytes"] == 0
    programs = Counter(s["args"]["program"]
                       for s in named(setup, "engine.compile"))
    assert programs == {"llm_prefill_8": 1, "llm_prefill_16": 1,
                        "llm_prefill_32": 1, "llm_decode": 1}
    assert not named(traced_engine["spans"], "engine.compile")
    for comp in named(setup, "engine.compile"):
        under = compiles_under(setup, comp)
        assert under and all(inside(c, comp) for c in under)
        assert f"jit({comp['args']['program']})" in {
            c["args"]["program"] for c in under}
    for c in named(setup, "jax.compile"):
        assert c["args"]["event"] in ("compile", "cache_hit")
        assert c["end"] - c["start"] == pytest.approx(c["args"]["seconds"])


def test_a_late_compile_warns_with_the_programs_name(gpt2, caplog):
    import logging

    from ray_tpu.serve.llm_engine import LLMEngine

    model, params, _cfg = gpt2
    eng = LLMEngine(model, params, max_slots=2, page_size=8, max_ctx=64)
    try:
        obs.drain_spans()
        with caplog.at_level(logging.WARNING,
                             logger="ray_tpu.serve.llm_engine"):
            # the first request: its prefill and decode programs are set-up
            eng.result(eng.submit([1, 2, 3, 4, 5], 3), timeout=300)
            first = obs.drain_spans()
            assert not caplog.records
            before = eng.stats()
            # the same bucket again: nothing compiles, nothing is recorded
            eng.result(eng.submit([5, 4, 3, 2, 1, 0], 3), timeout=300)
            assert eng.stats()["compiles"] == before["compiles"]
            assert obs.drain_spans() == []
            # a bucket first reached after decode steps have emitted
            eng.result(eng.submit(list(range(19)), 3), timeout=300)
            late = obs.drain_spans()
        after = eng.stats()
    finally:
        eng.close()
    assert Counter(s["args"]["program"] for s in named(
        first, "engine.compile")) == {"llm_prefill_8": 1, "llm_decode": 1}
    [comp] = named(late, "engine.compile")
    assert comp["args"]["program"] == "llm_prefill_32"
    [warning] = [r.getMessage() for r in caplog.records]
    assert "llm_prefill_32" in warning
    # stats() counts the process's compile requests, and their seconds
    assert after["compiles"] - before["compiles"] == \
        len(named(late, "jax.compile")) >= 1
    assert after["compile_s"] - before["compile_s"] == pytest.approx(
        sum(s["args"]["seconds"] for s in named(late, "jax.compile")))


def test_the_train_steps_first_call_is_one_train_compile_span():
    import jax.numpy as jnp

    from ray_tpu._private import jax_env
    from ray_tpu.train.jax import compile_donated_step

    def sgd_step(w, x):
        return w - 0.1 * x.sum(), (w * w).sum()

    step = compile_donated_step(sgd_step, carry_argnums=(0,))
    jax_env.ensure_compile_listener()  # a second time: still one listener
    w, x = jnp.ones((4,)), jnp.ones((3,))
    obs.drain_spans()
    w, loss = step(w, x)
    first = obs.drain_spans()
    w, loss = step(w, x)
    assert obs.drain_spans() == [] and float(loss) > 0
    [comp] = named(first, "train.compile")
    assert comp["args"] == {"program": "sgd_step"}
    [under] = compiles_under(first, comp)
    assert under["args"]["program"] == "jit(sgd_step)"
    # the jitted step's own attributes, which the benchmark reads
    assert step._cache_size() == 1
    assert step.lower(w, x).as_text()


def test_each_compile_request_is_one_span_and_one_count(shutdown_only):
    import jax

    import ray_tpu
    from ray_tpu._private import jax_env
    from ray_tpu.util.metrics import Counter as MetricCounter

    ray_tpu.init(num_cpus=1)
    jax_env.ensure_compile_listener()
    requests = MetricCounter("jax_compiles_total")
    seconds = MetricCounter("jax_compile_seconds_total")

    def counted():
        return (requests.value({"cache": "hit"})
                + requests.value({"cache": "miss"}), seconds.value())

    @jax.jit
    def twice_compiled(x):
        return x * 2 + 1

    obs.drain_spans()
    n0, s0 = counted()
    t0 = jax_env.compile_totals()
    for n in (3, 5, 5):  # two shapes: two compile requests
        twice_compiled(np.ones((n,), np.float32))
    spans = named(obs.drain_spans(), "jax.compile")
    assert [s["args"]["program"] for s in spans] == \
        ["jit(twice_compiled)"] * 2
    n1, s1 = counted()
    assert n1 - n0 == 2
    took = sum(s["args"]["seconds"] for s in spans)
    assert s1 - s0 == pytest.approx(took)
    t1 = jax_env.compile_totals()
    assert t1["compiles"] - t0["compiles"] == 2
    assert t1["compile_s"] - t0["compile_s"] == pytest.approx(took)


def test_put_get_and_tasks_after_set_up_record_nothing(shutdown_only):
    """The off path stays free where it is hot: with the flag off, 200
    put/get pairs and a task after set-up append nothing to the driver's
    ring, the worker's or the head's store."""
    import ray_tpu
    from ray_tpu.util.testing import wait_for_condition

    ray_tpu.init(num_cpus=1)

    @ray_tpu.remote
    def ring_length():
        from ray_tpu import observability as o

        return len(o.ring())

    ray_tpu.get(ring_length.remote())  # set-up: the worker is up
    wait_for_condition(lambda: obs.session_spans("runtime.worker_start"))
    obs.drain_spans()
    store = ray_tpu._head.trace_store
    ingested = store.spans_ingested
    for i in range(200):
        assert ray_tpu.get(ray_tpu.put(i)) == i
    assert ray_tpu.get(ring_length.remote()) == 0
    assert len(obs.ring()) == 0 and store.spans_ingested == ingested


@pytest.mark.timeout(300)
def test_a_served_model_leaves_its_set_up_in_the_session(shutdown_only):
    """A tiny ``LLMServer`` behind ``serve.run``, the flag off: what
    ``session_spans()`` holds after ``shutdown()``."""
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve.llm_engine import LLMServer
    from ray_tpu.util.testing import wait_for_condition

    ray_tpu.init(num_cpus=2)
    handle = serve.run(serve.deployment(
        LLMServer, name="tiny_llm", num_replicas=1).bind(
            "gpt2", {}, seed=0, max_slots=2, page_size=8, max_ctx=64))

    def call(method, *args):
        return ray_tpu.get(handle.method(method).remote(*args), timeout=240)

    for prompt in ([1, 2, 3], [3, 2, 1, 0]):  # twice the same bucket
        assert len(call("__call__", {"tokens": prompt,
                                     "max_new_tokens": 3})["tokens"]) == 3
    stats = call("stats")
    # the worker's flusher has handed everything over
    wait_for_condition(lambda: len(obs.session_spans(
        "jax.compile")) >= stats["compiles"], timeout=30)
    serve.shutdown()
    ray_tpu.shutdown()

    spans = obs.session_spans()
    once = {name: named(spans, name) for name in LIFECYCLE_ONCE}
    assert {name: len(found) for name, found in once.items()} == \
        dict.fromkeys(LIFECYCLE_ONCE, 1)
    assert all(s["trace_id"] is None for s in spans)
    assert once["runtime.init"][0]["args"] == {"mode": "head"}
    assert once["serve.deploy"][0]["args"] == {
        "deployment": "tiny_llm", "replicas": 1}
    build = once["model.build"][0]
    assert build["args"]["model_kind"] == "gpt2"
    assert build["args"]["param_bytes"] == 4 * build["args"]["param_count"]
    started = named(spans, "runtime.worker_start")
    assert any(s["args"]["for"] == "_Replica.__init__" for s in started)
    # what lies inside what, across processes, on one clock
    replica = once["serve.replica_init"][0]
    assert inside(replica, once["serve.deploy"][0])
    assert inside(build, replica)
    assert inside(once["engine.init"][0], replica)
    assert build["end"] <= once["engine.init"][0]["start"]
    assert replica["proc"] != once["serve.deploy"][0]["proc"]
    # one engine.compile a program, none for the second request
    programs = Counter(s["args"]["program"]
                       for s in named(spans, "engine.compile"))
    assert programs == {"llm_prefill_8": 1, "llm_decode": 1}
    for comp in named(spans, "engine.compile"):
        assert compiles_under(spans, comp)
    in_worker = [s for s in named(spans, "jax.compile")
                 if s["proc"] == replica["proc"]]
    assert len(in_worker) == stats["compiles"]
    assert obs.session_spans_dropped() == 0


@pytest.mark.timeout(300)
def test_a_train_gang_leaves_its_set_up_in_the_session(shutdown_only):
    import ray_tpu
    from ray_tpu.air.config import ScalingConfig
    from ray_tpu.train import JaxTrainer
    from ray_tpu.train.jax.config import JaxConfig

    ray_tpu.init(num_cpus=4, object_store_memory=256 * 1024**2)

    def loop(config):
        from ray_tpu.air import session

        session.report({"rank": session.get_world_rank()})

    result = JaxTrainer(
        loop, jax_config=JaxConfig(platform="cpu", local_device_count=1),
        scaling_config=ScalingConfig(num_workers=2)).fit()
    assert result.error is None
    ray_tpu.shutdown()
    [gang] = obs.session_spans("train.worker_group_start")
    [meet] = obs.session_spans("train.rendezvous")
    assert gang["args"] == {"workers": 2}
    assert meet["args"] == {"world": 2, "platform": "cpu"}
    assert inside(meet, gang)
    assert len(obs.session_spans("runtime.worker_start")) >= 2
