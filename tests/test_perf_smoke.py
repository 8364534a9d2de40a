"""Tier-1 wrapper for tools/perf_smoke.py: the pipelined hot path must
dispatch step N+1 before step N's result is fetched (overlap), with zero
blocking driver↔worker syncs — so an overlap regression fails the normal
test pass instead of only surfacing in the full bench.

Every gate here compares counts, orderings and byte sizes; the clock
appears only as a timeout (tools/perf_smoke.py's header says why)."""
import ray_tpu  # noqa: F401 — conftest sets the virtual-device env first

from tools.perf_smoke import (
    run_3d_smoke,
    run_broadcast_smoke,
    run_checkpoint_smoke,
    run_elastic_smoke,
    run_flow_smoke,
    run_locality_smoke,
    run_mpmd_smoke,
    run_node_loss_smoke,
    run_object_plane_smoke,
    run_replay_smoke,
    run_rlhf_smoke,
    run_rollout_smoke,
    run_rpc_chaos_smoke,
    run_serving_smoke,
    run_smoke,
    run_tracing_smoke,
    run_zero_smoke,
)


def test_pipeline_overlap_smoke(shutdown_only):
    out = run_smoke(steps=8, depth=2)
    assert out["results_ok"], out
    assert out["driver_syncs"] == 0, out
    assert out["overlap_ok"], f"lockstep regression: {out}"
    assert out["ok"]


def test_checkpoint_overlap_smoke(shutdown_only):
    """An async sharded save riding the step pipeline must not stall it:
    overlap invariant intact, zero blocking driver syncs, and the save
    still commits its manifest (restorable state) — the tier-1 guard for
    the distributed checkpoint subsystem's 'off the step path' promise."""
    out = run_checkpoint_smoke(steps=8, depth=2)
    assert out["results_ok"], out
    assert out["driver_syncs"] == 0, out
    assert out["overlap_ok"], f"checkpoint stalled the pipeline: {out}"
    assert out["committed_step"] == 1, out
    assert out["restore_ok"], out
    assert out["ok"]


def test_rollout_plane_smoke(shutdown_only):
    """The streaming rollout plane must overlap sampling with learning
    (whenever a fragment is consumed, every other slot of every worker's
    window is still queued at its worker) and broadcast weights as ONE
    put per version — the tier-1 guard for ISSUE 5's async rollout
    plane."""
    out = run_rollout_smoke()
    assert out["one_put_per_version"], f"broadcast fan-out regressed: {out}"
    assert out["inflight_ok"], f"stream drained at consume time: {out}"
    assert out["ok"], out


def test_rpc_chaos_smoke(shutdown_only):
    """One dropped reply on the submit path must be invisible to the
    workload: the call times out its attempt, retries under the same
    idempotency key, and completes with exact results — the tier-1 guard
    for ISSUE 6's deadline-enforced RPC plane (no call may hang)."""
    out = run_rpc_chaos_smoke()
    assert out["exact_results"], out
    assert out["net_faults_injected"] >= 1, f"no fault injected: {out}"
    assert out["retries"] >= 1, f"dropped reply never retried: {out}"
    assert out["no_hang"], f"no-hang invariant violated: {out}"
    assert out["ok"], out


def test_object_plane_smoke(shutdown_only):
    """Steady-state large puts must hit the segment pool (no new shm
    segment per put) and a put_many burst must reach the head as at most
    one coalesced notify — no timing assertions, tier-1 safe."""
    out = run_object_plane_smoke()
    assert out["pool_reuse_ok"], f"pool regression: {out}"
    assert out["batching_ok"], f"notify batching regression: {out}"
    assert out["roundtrip_ok"], out
    assert out["ok"]


def test_serving_smoke():
    """The continuous-batching engine must decode token-identically to
    the uncached per-request reference with at least one admission
    landing mid-batch and the fixed-slot decode step compiled exactly
    once — the tier-1 guard for ISSUE 8's inference plane."""
    out = run_serving_smoke()
    assert out["token_identical"], f"paged decode diverged: {out}"
    assert out["admitted_mid_batch"] >= 1, f"batch drained to admit: {out}"
    assert out["decode_cache_size"] == 1, f"decode step recompiled: {out}"
    assert out["pages_leaked"] == 0, out
    # Serving tier (ISSUE 13): a prefix-cache hit must skip prefill
    # work, speculative decoding must accept tokens without changing
    # the stream, and the disaggregated handoff must leak zero pages.
    assert out["prefix_hit_pages"] >= 1, out
    assert out["prefix_tail_tokens"] < 17, out  # tail-only prefill
    assert out["spec_accepted"] >= 1, out
    assert out["spec_token_identical"], out
    assert out["prefill_offloaded"] >= 2, out
    assert out["disagg_pages_leaked"] == 0, out
    assert out["ok"], out


def test_zero_smoke(shutdown_only):
    """The ZeRO+int8 train step must hold 1/N optimizer bytes per
    replica, ride the step pipeline with zero extra driver syncs (and
    the overlap invariant intact), and never recompile across steps —
    the tier-1 guard for ISSUE 9's memory/bandwidth-efficient data
    parallelism."""
    out = run_zero_smoke()
    assert out["results_ok"], out
    assert out["driver_syncs"] == 0, out
    assert out["overlap_ok"], f"ZeRO step reintroduced lockstep: {out}"
    assert out["opt_bytes_ok"], f"opt-state bytes not 1/N: {out}"
    assert out["no_recompile"], f"ZeRO step recompiled: {out}"
    assert out["ok"], out


def test_mpmd_smoke(shutdown_only):
    """The MPMD pipeline must run every stage's ops in 1F1B order (stage
    0 forwards microbatch m+1 before it takes m's backward, so it has
    work while stage 1 holds m), stream steps with zero driver syncs,
    hold the 1F1B residual bound, and never retrace its compiled stage
    programs — the tier-1 guard for ISSUE 10."""
    out = run_mpmd_smoke()
    assert out["results_ok"], out
    assert out["driver_syncs_steady"] == 0, f"lockstep regression: {out}"
    assert out["schedule_order_ok"], f"a stage left the 1F1B order: {out}"
    assert out["jit_cache_constant"], f"stage program retraced: {out}"
    assert out["inflight_bound_ok"], f"1F1B bound violated: {out}"
    assert out["ok"], out


def test_3d_smoke(shutdown_only):
    """The composed 3D plane — interleaved MPMD pipeline x intra-stage
    SPMD x ZeRO with the int8 inter-stage wire, on a tiny GQA Llama —
    must stream with zero mid-step driver syncs, compile each chunk's
    programs exactly once, ship >= 3x fewer wire bytes than fp32, stay
    inside the quantization loss envelope, and hold 1/N optimizer bytes
    (the tier-1 guard for ISSUE 12)."""
    out = run_3d_smoke()
    assert out["results_ok"], out
    assert out["driver_syncs_steady"] == 0, f"lockstep regression: {out}"
    assert out["jit_cache_constant"], f"chunk program retraced: {out}"
    assert out["wire_ok"], f"int8 wire under 3x: {out}"
    assert out["loss_envelope_ok"], f"int8 numerics drifted: {out}"
    assert out["zero_ok"], f"opt state not sharded: {out}"
    assert out["ok"], out


def test_flow_smoke(shutdown_only):
    """Streaming Dataset execution on the flow substrate must genuinely
    stream — later blocks' reads are submitted, a window's worth, before
    an earlier block is handed to the consumer — while the RefStream
    holds at most `window` blocks in flight, results byte-match the
    eager engine, and the loop performs zero driver syncs (the tier-1
    guard for ISSUE 11's async dataflow substrate)."""
    out = run_flow_smoke()
    assert out["exact_results"], f"streaming diverged from eager: {out}"
    assert out["residency_ok"], f"window bound violated: {out}"
    assert out["produce_consume_overlap"], f"stage barrier regression: {out}"
    assert out["driver_syncs"] == 0, out
    assert out["ok"], out


def test_rlhf_smoke():
    """The RLHF loop must keep its two planes genuinely concurrent: the
    engine's count of decode steps goes up while an SGD update runs
    (generation of batch i+1 overlaps training on batch i), >= 2 hot
    weight swaps apply with the decode step compiled exactly once and
    zero dropped/errored rollouts, and the engine-captured behavior logprobs
    match a full-context forward pass (the tier-1 guard for ISSUE 14)."""
    out = run_rlhf_smoke()
    assert out["overlap_windows"] >= 1, f"drain-then-train regression: {out}"
    assert out["swaps"] >= 2, out
    assert out["decode_cache_size"] == 1, f"swap recompiled decode: {out}"
    assert out["rollouts_full"] and out["pages_leaked"] == 0, out
    assert out["logp_parity_err"] < 1e-3, f"logprob capture drifted: {out}"
    assert out["ok"], out


def test_flow_usage_static_check():
    """No NEW hand-rolled threading.Thread+queue.Queue pipeline outside
    flow.py/_private, and the not-yet-migrated allowlist only shrinks —
    the CI guard that keeps the dataflow substrate the single copy."""
    from tools.check_flow_usage import scan

    result = scan()
    assert not result["violations"], (
        "hand-rolled pipeline outside flow.py — build it on "
        f"ray_tpu.parallel.flow instead: {result['violations']}")
    assert not result["stale_allowlist"], (
        "allowlist entries no longer hand-roll pipelines — remove them "
        f"from tools/check_flow_usage.py: {result['stale_allowlist']}")


def test_tracing_smoke(shutdown_only):
    """The tracing plane must be free when off (zero spans recorded before
    and after an enable→disable cycle, which leaves the switch off and
    no trace context on the thread) and assemble when on: one driver
    boundary produces a single trace whose spans span >= 3 processes on
    >= 2 virtual nodes, with the chrome dump json-clean and carrying
    cross-process flow edges — the tier-1 guard for the observability
    PR."""
    out = run_tracing_smoke()
    assert out["off_zero_spans"] and out["off_still_zero_spans"], out
    assert out["off_path_restored"], f"the cycle left tracing on: {out}"
    assert out["assembled_ok"], f"trace did not assemble: {out}"
    assert out["flow_edges"] >= 1, f"no cross-process flow edges: {out}"
    assert out["chrome_json_ok"], out
    assert out["ok"], out


def test_trace_context_static_check():
    """No NEW record_span call site may ignore trace context (orphan
    spans never join a distributed trace), and the context-inheriting
    allowlist only shrinks — the CI guard that keeps the span families
    assembling into cross-process timelines."""
    from tools.check_trace_context import scan

    result = scan()
    assert not result["violations"], (
        "record_span call site without _trace_ctx — thread the "
        f"step/request context through: {result['violations']}")
    assert not result["stale_allowlist"], (
        "allowlist entries no longer call record_span bare — remove "
        f"them from tools/check_trace_context.py: "
        f"{result['stale_allowlist']}")


def test_node_loss_smoke(shutdown_only):
    """One scheduled node kill mid-run must be survivable: the job
    completes with exact results, every get inside its timeout,
    replicated puts restore from a surviving holder, sealed outputs
    reconstruct from lineage — and the recovery counters prove it (the
    tier-1 guard for ISSUE 7's node-loss survivability plane)."""
    out = run_node_loss_smoke()
    assert out["killed"], out
    assert out["exact_results"], out
    assert out["node_deaths"] >= 1, out
    assert out["objects_restored"] >= 1, f"no replica restore: {out}"
    assert out["objects_reconstructed"] >= 1, f"no reconstruction: {out}"
    assert out["objects_lost"] == 0, out
    assert out["no_hang"], f"node-loss recovery hung: {out}"
    assert out["ok"], out


def test_locality_smoke(shutdown_only):
    """Locality-aware scheduling must place a DEFAULT-strategy consumer
    on its producer's host and read the arg with zero demand wire bytes
    (zero-copy segment attach), and a forced-remote consumer must find
    its arg prefetched into the target host's store WHILE the task was
    still queued (one prefetch, started at that task's placement; wire
    counter flat) — the tier-1
    guard for ISSUE 17's place-compute-where-the-bytes-live plane."""
    out = run_locality_smoke()
    assert out["local_on_producer_host"], f"compute left the bytes: {out}"
    assert out["local_wire_bytes"] == 0, f"local read hit the wire: {out}"
    assert out["local_hit_counted"], out
    assert out["remote_on_b"], out
    assert out["remote_wire_bytes"] == 0, f"prefetch missed demand: {out}"
    assert out["prefetch_completed"], out
    assert out["prefetch_overlapped_queue"], \
        f"prefetch did not overlap the queue: {out}"
    assert out["values_ok"], out
    assert out["ok"], out


def test_elastic_smoke(shutdown_only):
    """A scripted grow (spare capacity) + notice shrink (preemption)
    must both land at step boundaries with zero steps lost, exactly one
    versioned weight broadcast per gang incarnation, and a final state
    BITWISE-equal to an uninterrupted single-host run — the tier-1 guard
    for the elastic data-parallel plane."""
    out = run_elastic_smoke()
    assert out["grows"] == 1, out
    assert out["notice_shrinks"] == 1, out
    assert out["steps_lost"] == 0, out
    assert out["weight_puts"] == out["version"], \
        f"weight broadcast fan-out regressed: {out}"
    assert out["bitwise_parity"], f"elastic resize perturbed the run: {out}"
    assert out["ok"], out


def test_replay_smoke(shutdown_only):
    """The distributed replay plane's three perf invariants: steady-state
    inserts are zero-copy (ring eviction recycles pooled segments — no
    new shm segments while the ring churns), sampling resolves each batch
    with exactly ONE batched get_many gather, and the flow prefetcher
    issues the next gather while the learner still holds a batch."""
    out = run_replay_smoke()
    assert out["zero_copy_ok"], \
        f"insert path copied or leaked segments: {out}"
    assert out["gather_ok"], f"sampling issued extra gathers: {out}"
    assert out["overlap_ok"], f"the prefetcher did not run ahead: {out}"
    assert out["ok"], out


def test_broadcast_smoke(shutdown_only):
    """One put broadcast to 3 real node agents must stripe every pull,
    serve at least one chunk range from a NON-owner peer (the receivers
    formed a dissemination tree instead of all draining the owner),
    land byte-identical copies, and create zero new segments on the
    owner's store — the tier-1 guard for ISSUE 20's multi-source
    cooperative-broadcast transfer plane."""
    out = run_broadcast_smoke()
    assert out["byte_identity"], out
    assert out["striped_pulls"] >= out["receivers"], \
        f"a pull fell back to single-stream: {out}"
    assert out["ranges_from_partial"] >= 1, \
        f"no range pulled from a partial holder: {out}"
    assert out["peer_served_ranges"] >= 1, \
        f"no peer served a range: {out}"
    assert out["owner_new_segments"] == 0, \
        f"broadcast created segments on the owner: {out}"
    assert out["no_hang"], out
    assert out["ok"], out
