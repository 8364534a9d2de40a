"""Raylet: per-node manager — worker pool, local dispatch, node object store.

Equivalent of the reference's NodeManager + WorkerPool + LocalTaskManager
(src/ray/raylet/node_manager.h:115, worker_pool.h:156,
local_task_manager.h:58).  One Raylet instance per (possibly virtual) node;
all raylets of a local cluster live in the head process, workers are real
subprocesses.  Virtual multi-node is the test fixture the reference builds
with ray.cluster_utils.Cluster (python/ray/cluster_utils.py:99).
"""
from __future__ import annotations

import contextlib
import os
import signal
import subprocess
import sys
import threading
import time
from collections import deque
from typing import Dict, Optional

from ray_tpu._private.ids import NodeID, WorkerID
from ray_tpu._private.jax_env import CHIP_WORKER_ENV, pin_platform
from ray_tpu._private.object_store import SharedMemoryStore
from ray_tpu._private.task_spec import TaskSpec, TaskType

DEFAULT_MAX_WORKERS = 64
IDLE_WORKER_TTL_S = 300.0


def _reap(proc) -> None:
    """Kill a worker process, wait until it is gone, then kill what is left
    of its process group.  libtpu hands a chip to one process at a time, and
    the chip stays taken until its owner has exited — not merely been
    signalled.  A local worker leads a session of its own (``spawn_worker``),
    so its group is the worker and whatever it started: a resource tracker
    or a helper process does not outlive it.  Harmless on a process that
    has already exited."""
    try:
        proc.kill()
        proc.wait(timeout=30.0)
    finally:
        pid = getattr(proc, "pid", None)  # a _RemoteProc has none
        if pid is not None:
            try:
                os.killpg(pid, signal.SIGKILL)
            except (ProcessLookupError, PermissionError):
                pass  # nothing left in the group


class WorkerHandle:
    __slots__ = ("worker_id", "proc", "conn", "busy", "actor_id", "node_id",
                 "current_task", "idle_since", "tpu_visible", "tpu_chips",
                 "task_started_at", "direct_addr", "leased_to", "lease_spec",
                 "blocked", "asked_at", "asked_for")

    def __init__(self, worker_id: WorkerID, proc, node_id: NodeID):
        self.worker_id = worker_id
        self.proc = proc  # subprocess.Popen (None until registered? no: set at spawn)
        self.conn = None  # set on register
        self.busy = False
        self.actor_id = None
        self.node_id = node_id
        self.current_task: Optional[TaskSpec] = None
        self.idle_since = time.monotonic()
        self.tpu_visible = False
        self.tpu_chips: tuple = ()  # chip indices this worker may touch
        self.task_started_at = 0.0  # dispatch time of current_task
        self.direct_addr = None  # the worker's own direct listener address
        self.leased_to = None    # caller worker id holding a lease on us
        self.lease_spec = None   # synthetic spec whose resources the lease holds
        self.blocked = False     # blocked in get(): resources released
        # the ``runtime.worker_start`` lifecycle span: when the process
        # was asked for (span clock), and the task or actor class it was
        # asked for (None: the pool's own spare)
        self.asked_at = time.perf_counter()
        self.asked_for: Optional[str] = None


class Raylet:
    """Node-local state. Thread-safety provided by the Head's single dispatch
    lock (all mutation happens under head._lock)."""

    def __init__(self, node_id: NodeID, head, store_capacity: int,
                 labels: Optional[dict] = None, max_workers: int = DEFAULT_MAX_WORKERS,
                 tpu_chips: int = 0):
        self.node_id = node_id
        self.head = head
        self.store = SharedMemoryStore(
            store_capacity,
            spill_dir=os.path.join(head.session_dir, "spill",
                                   node_id.hex()[:12]))
        self.labels = labels or {}
        self.max_workers = max_workers
        self.workers: Dict[WorkerID, WorkerHandle] = {}
        self.idle: deque = deque()  # WorkerIDs of registered idle workers
        self.queued: deque = deque()  # TaskSpecs waiting for a free worker
        self.num_starting = 0
        self.consecutive_start_failures = 0
        self.dead = False
        # Chip partitioning: libtpu grabs every visible chip exclusively, so
        # two TPU-visible processes on one host MUST see disjoint chip sets
        # (TPU_VISIBLE_CHIPS) or the second hangs/fails at backend init.
        self.tpu_chips_total = int(tpu_chips)
        self._free_chips = list(range(self.tpu_chips_total))
        # Every process this raylet started and has not yet waited for,
        # whatever became of its handle: what shutdown() answers for.
        self._procs: list = []

    # ---- worker pool ----
    @staticmethod
    def _chips_needed(spec: TaskSpec) -> int:
        """Exclusive chips for a TPU spec: whole-number requests partition
        (ceil); fractional requests return 0 = *shared* mode — the worker
        is TPU-visible with no exclusive chip claim, because sharing is the
        declared intent and an exclusive grant would deadlock the peers the
        scheduler co-packed onto the same chip."""
        req = spec.resources.get("TPU", 0)
        if req < 1:
            return 0

        import math

        return int(math.ceil(req))

    @staticmethod
    def _needs_tpu(spec: TaskSpec) -> bool:
        return spec.resources.get("TPU", 0) > 0

    def ensure_worker(self, spec: Optional[TaskSpec] = None):
        """Spawn a new worker process if needed for `spec` (or any task)."""
        needs_tpu = spec is not None and self._needs_tpu(spec)
        needs_chips = self._chips_needed(spec) if needs_tpu else 0
        if needs_tpu:
            # TPU tasks need a TPU-visible worker whose chip share covers
            # the request.  A worker that is busy or permanently pinned to
            # an actor can never serve this spec, so "some TPU worker
            # exists" is not enough — that silently deadlocked a second TPU
            # actor on the same node.  Spawn another as long as none with
            # enough chips is *available or starting*; each TPU worker is
            # spawned onto a disjoint chip partition (TPU_VISIBLE_CHIPS) so
            # concurrent TPU workers never contend for the exclusive libtpu.
            for w in self.workers.values():
                if not w.tpu_visible:
                    continue
                # With an unknown topology (total == 0) every TPU worker
                # sees all chips, so chip-count matching is moot (same
                # guard as _pop_idle); shared-mode specs (needs_chips == 0)
                # are satisfied by any TPU-visible worker.
                if self.tpu_chips_total > 0 and len(w.tpu_chips) < needs_chips:
                    continue
                if w.conn is None:  # still starting — wait for it
                    return
                if not w.busy and w.actor_id is None:  # idle and claimable
                    return
            if len(self.workers) < self.max_workers:
                if needs_chips:
                    chips = self._allocate_chips(needs_chips)
                    if chips is None:
                        # No free chips: every chip is held by a live TPU
                        # worker.  The spec waits until one dies/releases
                        # (the scheduler already capped grants to the
                        # node's TPU total, so this only happens while a
                        # pinned worker is shutting down).
                        return
                else:
                    chips = ()  # shared mode: all chips visible, none owned
                wid = self.spawn_worker(tpu_visible=True, tpu_chips=chips)
                self.workers[wid].asked_for = spec.name
            return
        if self.idle or self.num_starting > 0:
            return
        if len(self.workers) + self.num_starting >= self.max_workers:
            return
        wid = self.spawn_worker()
        self.workers[wid].asked_for = spec.name if spec is not None else None

    def _allocate_chips(self, n: int) -> Optional[tuple]:
        """Reserve n chip indices for a new TPU worker (None if unavailable).
        With an unknown topology (tpu_chips_total == 0, e.g. fake-TPU CPU
        test nodes) partitioning is moot: return an empty share."""
        if self.tpu_chips_total == 0:
            return ()
        if len(self._free_chips) < n:
            return None
        chips = tuple(self._free_chips[:n])
        del self._free_chips[:n]
        return chips

    def _worker_env(self, worker_id: WorkerID, tpu_visible: bool,
                    tpu_chips: tuple) -> Dict[str, str]:
        """Env-var *overlay* every worker needs, local or remote (transport
        vars are added by the spawner — head socket locally, head TCP on
        agents; the spawner applies this on top of its inherited environ,
        then calls ``pin_platform``)."""
        env = {
            "RAY_TPU_AUTHKEY": self.head.authkey.hex(),
            "RAY_TPU_NODE_ID": self.node_id.hex(),
            "RAY_TPU_WORKER_ID": worker_id.hex(),
            CHIP_WORKER_ENV: "1" if tpu_visible else "0",
            # Host identity for the direct transport's endpoint selection
            # (same host => unix socket; cross-host => TCP).  RemoteRaylet
            # overrides with its agent's host key.
            "RAY_TPU_HOST_KEY": getattr(self, "host_key", None)
                                 or self.head.host_key,
        }
        # Tracing plane: ship the driver's RESOLVED tracing switch — the
        # flag may have been set via _system_config or enable_tracing(),
        # which a fresh subprocess's CONFIG would never see.
        try:
            from ray_tpu.util.tracing import tracing_enabled

            if tracing_enabled():
                env["RAY_TPU_TRACING_ENABLED"] = "1"
        except Exception:
            pass
        if tpu_visible and tpu_chips and len(tpu_chips) < self.tpu_chips_total:
            # Strict-subset chip share: partition via TPU_VISIBLE_CHIPS so
            # concurrent TPU workers on this host never contend for libtpu.
            # A worker granted ALL host chips keeps the default env — the
            # proven whole-host path (and the only case libtpu's default
            # topology handling needs).
            env["TPU_VISIBLE_CHIPS"] = ",".join(str(c) for c in tpu_chips)
            env["TPU_PROCESS_BOUNDS"] = "1,1,1"
            env["TPU_CHIPS_PER_PROCESS_BOUNDS"] = f"{len(tpu_chips)},1,1"
        return env

    def spawn_worker(self, tpu_visible: bool = False,
                     tpu_chips: tuple = ()) -> WorkerID:
        worker_id = WorkerID.from_random()
        env = dict(os.environ)
        env.update(self._worker_env(worker_id, tpu_visible, tpu_chips))
        pin_platform(env)
        from ray_tpu._private import inject_pkg_pythonpath

        inject_pkg_pythonpath(env)
        env["RAY_TPU_HEAD_SOCKET"] = self.head.socket_path
        env["RAY_TPU_SESSION_DIR"] = self.head.session_dir
        # Per-worker log files, tailed by the head's LogMonitor and echoed
        # to the driver (reference: log_monitor.py:104).
        logs_dir = os.path.join(self.head.session_dir, "logs")
        os.makedirs(logs_dir, exist_ok=True)
        stem = os.path.join(logs_dir, f"worker-{worker_id.hex()[:16]}")
        out_f = open(stem + ".out", "ab")
        err_f = open(stem + ".err", "ab")
        try:
            proc = subprocess.Popen(
                [sys.executable, "-m", "ray_tpu._private.default_worker"],
                env=env,
                stdout=out_f,
                stderr=err_f,
                # Its own session, so its own process group: _reap can
                # take the worker's children with it.  The worker watches
                # for its parent's death itself (default_worker.py).
                start_new_session=True,
            )
        finally:
            out_f.close()
            err_f.close()
        self._procs = [p for p in self._procs if p.returncode is None]
        self._procs.append(proc)
        h = WorkerHandle(worker_id, proc, self.node_id)
        h.tpu_visible = tpu_visible
        h.tpu_chips = tuple(tpu_chips)
        self.workers[worker_id] = h
        self.num_starting += 1
        return worker_id

    def on_worker_registered(self, worker_id: WorkerID, conn,
                             direct_addr=None) -> Optional[WorkerHandle]:
        h = self.workers.get(worker_id)
        if h is None:
            return None
        h.conn = conn
        h.direct_addr = direct_addr
        from ray_tpu import observability as obs

        obs.record("runtime.worker_start", h.asked_at, time.perf_counter(),
                   _lifecycle=True, worker_id=worker_id.hex(),
                   **{"for": h.asked_for})
        self.num_starting = max(0, self.num_starting - 1)
        self.consecutive_start_failures = 0
        self.idle.append(worker_id)
        h.idle_since = time.monotonic()
        return h

    def on_worker_lost(self, worker_id: WorkerID) -> Optional[WorkerHandle]:
        h = self.workers.pop(worker_id, None)
        if h is None:
            return None
        try:
            self.idle.remove(worker_id)
        except ValueError:
            pass
        # This runs when the control connection closes (a killed actor, a
        # crash, an exit that hangs), which a dying process does before it
        # is gone and a hung one without ever going: nothing will name the
        # handle again, so its process ends here.
        with contextlib.suppress(Exception):
            _reap(h.proc)  # on a fault it stays in _procs, for shutdown()
        if h.tpu_chips:
            # Return the chip partition to the free pool — now that its
            # owner is gone; the next owner is spawned from the free pool
            # and must find the chips released.
            self._free_chips.extend(h.tpu_chips)
            self._free_chips.sort()
            h.tpu_chips = ()
        return h

    # ---- dispatch ----
    def try_dispatch(self):
        """Hand queued task specs to idle workers; spawn workers as needed.
        Scans the whole queue so one spec waiting for a special worker
        (e.g. TPU-visible) doesn't block runnable work behind it.
        Called under the head lock whenever state changes."""
        progress = True
        while progress and self.queued:
            progress = False
            for spec in list(self.queued):
                worker = self._pop_idle(spec)
                if worker is None:
                    self.ensure_worker(spec)
                    continue
                self.queued.remove(spec)
                progress = True
                worker.busy = True
                worker.current_task = spec
                worker.task_started_at = time.monotonic()
                if spec.task_type == TaskType.ACTOR_CREATION:
                    worker.actor_id = spec.actor_id
                self.head.send_to_worker(worker, {"type": "execute", "spec": spec})

    def _pop_idle(self, spec: TaskSpec) -> Optional[WorkerHandle]:
        needs_tpu = self._needs_tpu(spec)
        needs_chips = self._chips_needed(spec) if needs_tpu else 0
        for _ in range(len(self.idle)):
            wid = self.idle.popleft()
            h = self.workers.get(wid)
            if h is None or h.conn is None:
                continue
            if needs_tpu and (
                    not h.tpu_visible
                    or (self.tpu_chips_total > 0
                        and len(h.tpu_chips) < needs_chips)):
                self.idle.append(wid)
                continue
            return h
        return None

    def queue_task(self, spec: TaskSpec):
        self.queued.append(spec)
        self.try_dispatch()

    def release_worker(self, worker: WorkerHandle):
        """Task finished: return worker to the idle pool (actors stay pinned)."""
        worker.busy = False
        worker.current_task = None
        if worker.actor_id is None:
            self.idle.append(worker.worker_id)
            worker.idle_since = time.monotonic()
        self.try_dispatch()

    def shutdown(self, keep_spilled: bool = False):
        self.dead = True
        # Registered workers are asked to leave and given a grace period:
        # they may hold something to flush.  One that is still starting
        # never registered, was sent nothing and holds nothing — waiting
        # the grace out for it is 2 s for nothing.
        asked = set()
        for h in list(self.workers.values()):
            try:
                if h.conn is not None:
                    h.conn.send({"type": "shutdown"})
                    asked.add(h.proc)
            except Exception:
                pass
        deadline = time.monotonic() + 2.0
        try:
            # Every process ever started and not yet waited for, not only
            # the handles still in self.workers.  The chip must be free
            # when ray_tpu.shutdown() returns (the caller's next step may
            # be its next owner), and no process may outlive it.
            for proc in self._procs:
                # One process's fault does not spare the others.
                with contextlib.suppress(Exception):
                    if proc in asked:
                        with contextlib.suppress(subprocess.TimeoutExpired):
                            proc.wait(timeout=max(
                                0.05, deadline - time.monotonic()))
                    _reap(proc)
            self._procs = [p for p in self._procs if p.returncode is None]
        finally:
            self.store.shutdown(keep_spilled=keep_spilled)


# ---------------------------------------------------------------------------
# Remote nodes (multi-host): head-side proxies for a node agent process
# ---------------------------------------------------------------------------
class RemoteStoreProxy:
    """Head-side handle for a store that lives in a node agent process.

    Mutations are forwarded over the agent connection; reads return None —
    the head never reads remote bytes, it hands out pull resolutions against
    the agent's ObjectTransferServer instead (the reference's raylet↔object
    manager split, src/ray/object_manager/object_manager.h:117)."""

    def __init__(self, raylet: "RemoteRaylet"):
        self._raylet = raylet
        self.evict_callback = None  # agents report via "object_evicted" msgs
        # Spill records reported by the agent ("object_spilled"): lets the
        # head hand same-host callers a direct spill-file resolution.
        self._spilled: Dict = {}

    def adopt(self, object_id, data_size: int, metadata: bytes,
              segment=None):
        self._raylet.send_agent({"type": "store_adopt",
                                 "oid": object_id.binary(),
                                 "size": data_size, "meta": metadata,
                                 "segment": segment})

    def segment_of(self, object_id):
        return None

    def delete(self, object_id, evicted: bool = False):
        self._spilled.pop(object_id, None)
        self._raylet.send_agent({"type": "store_delete",
                                 "oid": object_id.binary()})

    def note_spilled(self, object_id, path: str, meta: bytes, size: int):
        self._spilled[object_id] = (path, meta, size)

    def meta(self, object_id):
        return None

    def spilled_lookup(self, object_id):
        rec = self._spilled.get(object_id)
        if rec is None:
            return None
        path, meta, size = rec
        return {"kind": "spilled", "path": path, "meta": meta, "size": size}

    def get(self, object_id):
        return None

    def contains(self, object_id):
        return False

    def pin(self, object_id):
        pass

    def unpin(self, object_id):
        pass

    def stats(self):
        return {}

    def shutdown(self):
        pass


class _RemoteProc:
    """Popen stand-in for a worker subprocess living on another host.
    Liveness comes from the agent's worker_exit reports + the worker's own
    control connection, not from local polling."""

    def __init__(self, raylet: "RemoteRaylet", worker_id: WorkerID):
        self._raylet = raylet
        self._worker_id = worker_id
        self.returncode = None

    def poll(self):
        return self.returncode

    def wait(self, timeout=None):
        return self.returncode

    def kill(self):
        self._raylet.send_agent({"type": "kill_worker",
                                 "worker_id": self._worker_id.binary()})


class RemoteRaylet(Raylet):
    """A raylet whose store + worker processes live on another host, driven
    through a NodeAgent connection (reference: the remote raylet the GCS
    talks to via NodeManagerService, src/ray/raylet/node_manager.h:115)."""

    def __init__(self, node_id: NodeID, head, agent_conn, host_key: str,
                 transfer_addr, labels: Optional[dict] = None,
                 max_workers: int = DEFAULT_MAX_WORKERS, tpu_chips: int = 0):
        # Deliberately NOT calling super().__init__: no local store.
        self.node_id = node_id
        self.head = head
        self.agent_conn = agent_conn
        self.host_key = host_key
        self.transfer_addr = tuple(transfer_addr)
        self.store = RemoteStoreProxy(self)
        self.labels = labels or {}
        self.max_workers = max_workers
        self.workers: Dict[WorkerID, WorkerHandle] = {}
        self.idle: deque = deque()
        self.queued: deque = deque()
        self.num_starting = 0
        self.consecutive_start_failures = 0
        self.dead = False
        self.tpu_chips_total = int(tpu_chips)
        self._free_chips = list(range(self.tpu_chips_total))
        self._agent_lock = threading.Lock()

    def send_agent(self, msg: dict):
        try:
            with self._agent_lock:
                self.agent_conn.send(msg)
        except Exception:
            pass  # agent death is handled by its conn-close path

    def spawn_worker(self, tpu_visible: bool = False,
                     tpu_chips: tuple = ()) -> WorkerID:
        worker_id = WorkerID.from_random()
        env = self._worker_env(worker_id, tpu_visible, tpu_chips)
        self.send_agent({"type": "spawn_worker",
                         "worker_id": worker_id.binary(), "env": env})
        h = WorkerHandle(worker_id, _RemoteProc(self, worker_id), self.node_id)
        h.tpu_visible = tpu_visible
        h.tpu_chips = tuple(tpu_chips)
        self.workers[worker_id] = h
        self.num_starting += 1
        return worker_id

    def shutdown(self, keep_spilled: bool = False):
        self.dead = True
        self.send_agent({"type": "shutdown"})
        try:
            self.agent_conn.close()
        except Exception:
            pass
