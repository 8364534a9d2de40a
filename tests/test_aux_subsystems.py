"""Autoscaler, workflow, timeline, chaos tests (SURVEY.md §5 subsystems)."""
import os
import time

import pytest

import ray_tpu


def test_autoscaler_scales_up_and_down(shutdown_only):
    from ray_tpu.autoscaler import StandardAutoscaler

    ray_tpu.init(num_cpus=1)

    @ray_tpu.remote(num_cpus=1)
    def busy():
        time.sleep(1.5)
        return ray_tpu.get_runtime_context().get_node_id()

    refs = [busy.remote() for _ in range(3)]
    time.sleep(0.2)  # let two of them queue
    scaler = StandardAutoscaler(
        {"cpu_node": {"resources": {"CPU": 1}, "max_workers": 4}},
        idle_timeout_s=0.5)
    launched = scaler.update()
    assert sum(launched.values()) >= 1
    nodes = {n for n in ray_tpu.get(refs)}
    assert len(nodes) >= 2  # work actually spread onto the new node(s)
    # Idle nodes get reclaimed.
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline and scaler.provider.non_terminated_nodes():
        scaler.update()
        time.sleep(0.3)
    assert not scaler.provider.non_terminated_nodes()


def test_workflow_resume_skips_done_steps(shutdown_only, tmp_path):
    import ray_tpu.workflow as workflow

    ray_tpu.init(num_cpus=4)
    workflow.init(str(tmp_path))
    counter_file = str(tmp_path / "exec_count")

    def bump_and_add(a, b):
        with open(counter_file, "a") as f:
            f.write("x")
        return a + b

    def double(x):
        return x * 2

    from ray_tpu.workflow import StepNode

    node = StepNode(double, (StepNode(bump_and_add, (1, 2), {}),), {})
    assert workflow.run(node, "wf1") == 6
    assert len(open(counter_file).read()) == 1
    # Re-run: all steps cached, no re-execution.
    node2 = StepNode(double, (StepNode(bump_and_add, (1, 2), {}),), {})
    assert workflow.run(node2, "wf1") == 6
    assert len(open(counter_file).read()) == 1
    assert len(workflow.list_steps("wf1")) == 2


def test_timeline_chrome_trace(shutdown_only, tmp_path):
    ray_tpu.init(num_cpus=2)

    @ray_tpu.remote
    def work():
        time.sleep(0.05)
        return 1

    ray_tpu.get([work.remote() for _ in range(3)])
    path = str(tmp_path / "trace.json")
    events = ray_tpu.timeline(path)
    done = [e for e in events if e["name"] == "work"]
    assert len(done) == 3
    assert all(e["dur"] >= 40_000 for e in done)  # >= 40ms in microseconds
    assert os.path.exists(path)


def test_chaos_delay_injection(shutdown_only):
    ray_tpu.init(num_cpus=2)

    @ray_tpu.remote
    def f():
        return 1

    os.environ["RAY_TPU_TESTING_DELAY_MS"] = "submit:30:40"
    try:
        t0 = time.monotonic()
        ray_tpu.get([f.remote() for _ in range(5)])
        assert time.monotonic() - t0 >= 0.15  # 5 × ≥30ms injected
    finally:
        del os.environ["RAY_TPU_TESTING_DELAY_MS"]


def test_chaos_kill_random_worker_recovers(shutdown_only):
    from ray_tpu._private.chaos import kill_random_worker

    ray_tpu.init(num_cpus=4)

    @ray_tpu.remote(max_retries=3)
    def slow(i):
        time.sleep(1.0)
        return i

    refs = [slow.remote(i) for i in range(4)]
    deadline = time.monotonic() + 20
    killed = False
    while time.monotonic() < deadline and not killed:
        killed = kill_random_worker()
        time.sleep(0.2)
    assert killed
    # Retries recover every result despite the crash.
    assert sorted(ray_tpu.get(refs)) == [0, 1, 2, 3]


def test_tracing_spans_recorded(shutdown_only):
    """OTel-API instrumentation (reference: ray.util.tracing): spans record
    locally (and flow to any TracerProvider the app wires)."""
    import ray_tpu
    from ray_tpu.util import tracing

    tracing.enable_tracing()
    try:
        ray_tpu.init(num_cpus=2)

        @ray_tpu.remote
        def traced(x):
            return x + 1

        assert ray_tpu.get(traced.remote(1)) == 2
        # Driver-side spans: the driver executes no task; worker spans live
        # in the worker process.  Exercise span() directly too.
        with tracing.span("custom.op", foo="bar"):
            pass
        spans = tracing.pop_local_spans()
        assert any(s["name"] == "custom.op" for s in spans)
        s = next(s for s in spans if s["name"] == "custom.op")
        assert s["args"]["foo"] == "bar" and s["end"] >= s["start"]
    finally:
        tracing.disable_tracing()


def test_tune_syncer_mirrors_experiment_dir(tmp_path):
    import os

    from ray_tpu.tune.syncer import Syncer

    exp = tmp_path / "exp"
    (exp / "sub").mkdir(parents=True)
    (exp / "experiment_state.pkl").write_bytes(b"state1")
    (exp / "sub" / "ckpt.bin").write_bytes(b"x" * 100)
    (exp / ".experiment_state.tmp").write_bytes(b"partial")

    dst = tmp_path / "durable"
    s = Syncer(str(dst))
    s.sync_now(str(exp))
    assert (dst / "exp" / "experiment_state.pkl").read_bytes() == b"state1"
    assert (dst / "exp" / "sub" / "ckpt.bin").stat().st_size == 100
    assert not (dst / "exp" / ".experiment_state.tmp").exists()
    # Incremental: update one file, sync again.
    (exp / "experiment_state.pkl").write_bytes(b"state2-longer")
    s.sync_now(str(exp))
    assert (dst / "exp" / "experiment_state.pkl").read_bytes() \
        == b"state2-longer"


def test_tracing_submit_spans_on_driver(shutdown_only):
    import ray_tpu
    from ray_tpu.util import tracing

    tracing.enable_tracing()
    try:
        ray_tpu.init(num_cpus=2)

        @ray_tpu.remote
        def t(x):
            return x

        assert ray_tpu.get(t.remote(5)) == 5
        names = {s["name"] for s in tracing.pop_local_spans()}
        assert "task.submit" in names
    finally:
        tracing.disable_tracing()
