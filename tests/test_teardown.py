"""A runtime that cannot leave a worker process behind (ISSUE 31, ROADMAP
D13).  PRs 28 and 29 were refused unmeasured because a process outlived a
benchmark run.  Each scenario below runs as a driver script in a process
of its own, under a ``subprocess`` limit of its own, and reports the pids
it created; the assertions are on those pids and never on a ``pgrep`` of
the machine (tier-1 runs six workers side by side).
"""
import inspect
import json
import os
import signal
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Beside each scenario's own subprocess limit (tests/conftest.py).
pytestmark = pytest.mark.timeout(150)

def _alive(pid):
    """A zombie counts as gone: where pid 1 reaps late, an orphan's entry
    stays a while after the process has ended."""
    try:
        with open(f"/proc/{pid}/stat") as f:
            return f.read().rsplit(")", 1)[1].split()[0] != "Z"
    except OSError:
        return False


PRELUDE = f"""
import json, os, signal, socket, subprocess, sys, threading, time
sys.path.insert(0, {REPO!r})
import ray_tpu
from ray_tpu._private import raylet as raylet_mod

""" + inspect.getsource(_alive) + """
alive = _alive


def gone_within(pids, seconds):
    deadline = time.monotonic() + seconds
    while time.monotonic() < deadline and any(alive(p) for p in pids):
        time.sleep(0.02)
    return [p for p in pids if alive(p)]


@ray_tpu.remote
class Stubborn:
    def pid(self):
        return os.getpid()

    def hang_on_exit_then_cut_the_connection(self):
        # An exit that never finishes (interpreter finalisation with a
        # daemon thread still busy is the real case), then the control
        # connection closes under a process that lives on.
        os._exit = sys.exit = lambda *a: time.sleep(3600)
        from ray_tpu._private.worker import global_worker
        conn = global_worker.transport.conn
        sock = socket.socket(fileno=os.dup(conn.fileno()))
        threading.Timer(0.3, sock.shutdown, (socket.SHUT_RDWR,)).start()
        return os.getpid()

    def spawn_child(self):
        self.child = subprocess.Popen(
            [sys.executable, "-c", "import time; time.sleep(3600)"])
        return self.child.pid

    def sleep(self, seconds):
        time.sleep(seconds)
"""


def _kill(pids):
    for pid in pids:
        try:
            os.kill(pid, signal.SIGKILL)
        except OSError:
            pass


def _scenario(body, limit=90.0):
    """Run PRELUDE + body as a driver script; its last stdout line is a
    JSON object.  The script's own session is killed whatever happens."""
    proc = subprocess.Popen(
        [sys.executable, "-c", PRELUDE + body], stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True, start_new_session=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    try:
        out, err = proc.communicate(timeout=limit)
    finally:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except OSError:
            pass
        proc.wait()
    assert proc.returncode == 0, err[-3000:]
    return json.loads(out.strip().splitlines()[-1])


def test_worker_whose_connection_closed_is_gone_after_shutdown():
    """A worker without chips whose control connection closes while its
    process lives was forgotten by ``on_worker_lost`` and never killed."""
    got = _scenario("""
ray_tpu.init(num_cpus=2)
a = Stubborn.remote()
pid = ray_tpu.get(a.hang_on_exit_then_cut_the_connection.remote())
head = ray_tpu._head
deadline = time.monotonic() + 20
while time.monotonic() < deadline and any(
        h.proc.pid == pid for r in head.raylets.values()
        for h in list(r.workers.values())):
    time.sleep(0.05)
forgotten = not any(h.proc.pid == pid for r in head.raylets.values()
                    for h in list(r.workers.values()))
ray_tpu.shutdown()
print(json.dumps({"pid": pid, "forgotten": forgotten,
                  "left": gone_within([pid], 1.0)}))
""")
    try:
        assert got["forgotten"], "the head never saw the connection close"
        assert got["left"] == []
    finally:
        _kill([got["pid"]])


def test_child_of_a_worker_is_gone_after_shutdown():
    """Workers lead a session of their own, and the raylet kills what is
    left of the group: a helper process a worker started does not outlive
    ``ray_tpu.shutdown()``."""
    got = _scenario("""
ray_tpu.init(num_cpus=2)
a = Stubborn.remote()
worker, child = ray_tpu.get([a.pid.remote(), a.spawn_child.remote()])
same_group = os.getpgid(child) == os.getpgid(worker) == worker
ray_tpu.shutdown()
print(json.dumps({"pids": [worker, child], "same_group": same_group,
                  "left": gone_within([worker, child], 1.0)}))
""")
    try:
        assert got["same_group"], "the worker does not lead its own group"
        assert got["left"] == []
    finally:
        _kill(got["pids"])


def test_one_reap_that_raises_does_not_spare_the_next_worker():
    """Two workers busy in a call that never returns, so neither leaves
    in the grace period; the first ``_reap`` raises.  The second worker
    is killed all the same."""
    got = _scenario("""
ray_tpu.init(num_cpus=2)
actors = [Stubborn.remote() for _ in range(2)]
pids = ray_tpu.get([a.pid.remote() for a in actors])
for a in actors:
    a.sleep.remote(3600)
time.sleep(0.5)
real, calls = raylet_mod._reap, []

def reap(proc):
    calls.append(proc.pid)
    if len(calls) == 1:
        raise RuntimeError("injected: the first reap fails")
    real(proc)

raylet_mod._reap = reap
ray_tpu.shutdown()
spared = calls[0]
rest = [p for p in pids if p != spared]
print(json.dumps({"pids": pids, "calls": len(calls), "spared": spared,
                  "spared_alive": alive(spared),
                  "left": gone_within(rest, 1.0)}))
""")
    try:
        assert got["calls"] >= 2 and got["spared"] in got["pids"]
        assert got["spared_alive"], "the injected fault killed it anyway"
        assert got["left"] == []
    finally:
        _kill(got["pids"])


def test_worker_exits_within_2s_of_its_parents_sigkill():
    """The head process is killed with SIGKILL while a worker sits in a
    call that never returns (its main thread cannot notice anything).
    The worker leads its own session, so no signal reaches it; it leaves
    by itself."""
    driver = subprocess.Popen(
        [sys.executable, "-c", PRELUDE + """
ray_tpu.init(num_cpus=2)
a = Stubborn.remote()
pid = ray_tpu.get(a.pid.remote())
a.sleep.remote(3600)
time.sleep(0.5)
print(json.dumps({"pid": pid}), flush=True)
time.sleep(3600)
"""], stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
        start_new_session=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    pid = None
    try:
        import select

        ready, _, _ = select.select([driver.stdout], [], [], 60.0)
        assert ready, "the driver script never reported its worker"
        pid = json.loads(driver.stdout.readline())["pid"]
        assert _alive(pid)
        driver.kill()
        driver.wait(timeout=10)
        t0 = time.monotonic()
        while _alive(pid) and time.monotonic() - t0 < 5.0:
            time.sleep(0.02)
        took = time.monotonic() - t0
        assert not _alive(pid), "the worker outlived its parent"
        assert took < 2.0, f"the worker took {took:.2f} s to leave"
    finally:
        try:
            os.killpg(driver.pid, signal.SIGKILL)
        except OSError:
            pass
        driver.wait()
        if pid is not None:
            _kill([pid])
