"""Test fixtures (modeled on the reference's python/ray/tests/conftest.py:
ray_start_regular :294, ray_start_cluster :375, shutdown_only :223).

JAX tests run on a virtual 8-device CPU mesh: the env vars MUST be set before
jax is imported anywhere in the process (fake-accelerator mode, the JAX
equivalent of the reference's _fake_gpus)."""
import os
import signal
import sys
import threading

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest  # noqa: E402

import ray_tpu  # noqa: E402
from ray_tpu._private.jax_env import ensure_compile_cache  # noqa: E402

# One persistent XLA compilation cache for the test process AND every
# worker it spawns (they inherit the environment): gang workers otherwise
# recompile identical programs on every spawn/rebuild, which dominates
# suite wall-clock.  Must run before anything imports jax.
ensure_compile_cache()


# Every test has a limit of its own: ``@pytest.mark.timeout(seconds)`` where
# it carries one, this otherwise.  The slowest test of a whole tier-1 run
# took 50.5 s (PR 52, six workers on a loaded sandbox; the junit file's
# times); a test still going after six times that is waiting on something
# that will not come.
DEFAULT_TIMEOUT_S = 300.0


def _limited(phase):
    """A hook wrapper that gives one phase of a test (its fixtures' set-up,
    its body, their tear-down) the test's limit (the installation has no
    pytest-timeout).  A test that hangs fails after its limit instead of
    running the whole suite into the driver's clock (PR 29 ended in exit
    code 124; one run of PR 43 sat in a tear-down, waiting on a defunct
    child, until the run's own limit cut it).  SIGALRM interrupts the main
    thread, where pytest and every xdist worker run their tests."""

    def wrapper(item):
        if threading.current_thread() is not threading.main_thread():
            yield
            return
        marker = item.get_closest_marker("timeout")
        seconds = float(marker.args[0]) if marker else DEFAULT_TIMEOUT_S

        def on_alarm(signum, frame):
            raise TimeoutError(
                f"{item.nodeid} ({phase}) passed its limit of {seconds} s")

        previous = signal.signal(signal.SIGALRM, on_alarm)
        signal.setitimer(signal.ITIMER_REAL, seconds)
        try:
            yield
        finally:
            signal.setitimer(signal.ITIMER_REAL, 0)
            signal.signal(signal.SIGALRM, previous)

    return pytest.hookimpl(hookwrapper=True)(wrapper)


pytest_runtest_setup = _limited("set-up")
pytest_runtest_call = _limited("call")
pytest_runtest_teardown = _limited("tear-down")


@pytest.fixture
def ray_start_regular():
    ray_tpu.init(num_cpus=8, object_store_memory=256 * 1024**2)
    yield
    ray_tpu.shutdown()


@pytest.fixture
def shutdown_only():
    yield
    ray_tpu.shutdown()


@pytest.fixture
def ray_start_cluster():
    from ray_tpu.cluster_utils import Cluster

    cluster = Cluster(initialize_head=False)
    yield cluster
    cluster.shutdown()
