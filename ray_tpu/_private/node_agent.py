"""Remote-node agent: the per-host raylet process for multi-host clusters.

The reference runs one raylet binary per node (src/ray/raylet/main.cc) that
owns the node's plasma store, spawns workers, and serves object transfer.
This agent is that process for ray_tpu: it

- connects to the head over TCP (same authkey-HMAC framing as workers),
- registers the node (resources, host key, transfer address),
- owns the host's SharedMemoryStore + an ObjectTransferServer for pulls,
- spawns/kills worker subprocesses on command from the head's RemoteRaylet
  proxy (workers connect *directly* to the head over TCP for control; only
  store ownership and object bytes stay host-local),
- reports child exits so the head's health monitor sees remote deaths.

Start programmatically (cluster_utils.Cluster.add_remote_node) or:
    python -m ray_tpu._private.node_agent --address HOST:PORT \
        --authkey HEX --num-cpus 8 [--num-tpus 4] [--store-capacity BYTES]
"""
from __future__ import annotations

import argparse
import os
import subprocess
import sys
import threading
import time
import traceback
from multiprocessing.connection import Client
from typing import Dict

from ray_tpu._private.ids import NodeID, ObjectID, WorkerID
from ray_tpu._private.object_store import SharedMemoryStore
from ray_tpu._private.transfer import (
    ObjectTransferServer,
    wire_store_reporting,
)


class NodeAgent:
    def __init__(self, head_addr, authkey: bytes, resources: Dict[str, float],
                 store_capacity: int = 2 * 1024**3, max_workers: int = 64,
                 labels=None):
        self.head_addr = head_addr
        self.authkey = authkey
        self.resources = resources
        self.labels = labels or {}
        self.max_workers = max_workers
        self.host_key = os.urandom(8).hex()
        import tempfile

        self._spill_dir = tempfile.mkdtemp(prefix="rtpu_spill_")
        self.store = SharedMemoryStore(store_capacity,
                                       spill_dir=self._spill_dir)
        # should_spill stays None: without refcount visibility, spilling
        # everything evicted is the safe default.
        wire_store_reporting(self.store, self.send)
        self.xfer = ObjectTransferServer(self.store, authkey)
        from ray_tpu._private.chaos import wrap_net_faults

        # Fault-injection wrapper (identity no-op without a net schedule):
        # agent notifies label as notify:<type>, head pushes as
        # push:<type> (spawn_worker, store_adopt, ...).
        self.conn = wrap_net_faults(Client(tuple(head_addr), family="AF_INET",
                                           authkey=authkey))
        self._send_lock = threading.Lock()
        self._children: Dict[bytes, subprocess.Popen] = {}
        self._children_lock = threading.Lock()
        self._shutdown = threading.Event()
        self.node_id = None  # assigned by head in register reply
        self._stats_period = None  # head-resolved, set in register reply
        self._xfer_client = None  # lazy: durability replica pulls

    def send(self, msg: dict):
        with self._send_lock:
            self.conn.send(msg)

    def _register_msg(self) -> dict:
        msg = {
            "type": "register_node",
            "resources": self.resources,
            "labels": self.labels,
            "host_key": self.host_key,
            "transfer_addr": list(self.xfer.address),
            "store_capacity": self.store.capacity,
            "max_workers": self.max_workers,
            "pid": os.getpid(),
        }
        if self.node_id is not None:
            # Re-registration after a head restart: keep our identity and
            # hand over the surviving worker processes for adoption.
            msg["node_id"] = self.node_id.binary()
            with self._children_lock:
                msg["workers"] = [
                    {"worker_id": wid,
                     "tpu_chips": getattr(p, "_rtpu_chips", [])}
                    for wid, p in self._children.items()]
        return msg

    def _reconnect(self) -> bool:
        """Head connection died: retry within the reconnect window (the
        head may be restarting from its snapshot — reference: the GCS
        reconnect window, ray_config_def.h:58-62)."""
        from ray_tpu._private.config import CONFIG

        deadline = time.monotonic() + CONFIG.reconnect_window_s
        while not self._shutdown.is_set() and time.monotonic() < deadline:
            time.sleep(1.0)
            try:
                from ray_tpu._private.chaos import wrap_net_faults

                conn = wrap_net_faults(
                    Client(tuple(self.head_addr), family="AF_INET",
                           authkey=self.authkey))
            except Exception:
                continue
            with self._send_lock:
                try:
                    conn_old, self.conn = self.conn, conn
                except Exception:
                    continue
            try:
                conn_old.close()
            except Exception:
                pass
            try:
                self.send(self._register_msg())
            except Exception:
                continue  # head died again mid-handshake: keep retrying
            return True
        return False

    def run(self):
        self.send(self._register_msg())
        threading.Thread(target=self._reap_loop, name="rtpu-agent-reap",
                         daemon=True).start()
        threading.Thread(target=self._memory_loop, name="rtpu-agent-mem",
                         daemon=True).start()
        threading.Thread(target=self._stats_loop, name="rtpu-agent-stats",
                         daemon=True).start()
        threading.Thread(target=self._heartbeat_loop, name="rtpu-agent-hb",
                         daemon=True).start()
        try:
            while not self._shutdown.is_set():
                try:
                    msg = self.conn.recv()
                except (EOFError, OSError):
                    if self._shutdown.is_set() or not self._reconnect():
                        break
                    continue
                self._handle(msg)
        finally:
            self.shutdown()

    def _chaos_site(self, op: str):
        """Node-level kill site: a schedule match SIGKILLs the agent AND
        every worker child — whole-node loss, no cleanup, exactly what a
        preempted/OOM-killed host looks like to the head."""
        from ray_tpu._private.chaos import check_die

        if not check_die(op):
            return
        import signal

        with self._children_lock:
            procs = list(self._children.values())
        for p in procs:
            try:
                p.kill()
            except Exception:
                pass
        os.kill(os.getpid(), signal.SIGKILL)

    def _handle(self, msg: dict):
        t = msg.get("type")
        self._chaos_site("node_agent_msg")
        try:
            if t == "node_registered":
                self.node_id = NodeID(msg["node_id"])
                if "node_stats_period_s" in msg:
                    self._stats_period = float(msg["node_stats_period_s"])
                try:
                    from ray_tpu import observability as obs

                    obs.set_identity(
                        f"agent:{self.node_id.hex()[:8]}",
                        self.node_id.hex())
                except Exception:
                    pass
            elif t == "spawn_worker":
                self._chaos_site("node_agent_spawn")
                self._spawn_worker(msg)
            elif t == "kill_worker":
                self._kill_worker(msg["worker_id"])
            elif t == "oom_kill":
                # The head's answer to our worker_oom.  The child stays in
                # _children: the reap loop reports its exit as any other.
                with self._children_lock:
                    proc = self._children.get(msg["worker_id"])
                if proc is not None:
                    proc.kill()
            elif t == "store_adopt":
                self.store.adopt(ObjectID(msg["oid"]), msg["size"],
                                 msg["meta"], segment=msg.get("segment"))
            elif t == "store_delete":
                self.store.delete(ObjectID(msg["oid"]))
            elif t == "store_pull":
                # Durability replica: pull the object from the named
                # holder into OUR store (off the reader thread — a pull
                # can move gigabytes) and ack with the replica's segment.
                threading.Thread(target=self._store_pull, args=(msg,),
                                 name="rtpu-agent-pull",
                                 daemon=True).start()
            elif t == "store_backup":
                oid = ObjectID(msg["oid"])
                self.store.backup(oid)  # spill_callback reports the record
            elif t == "shutdown":
                self._shutdown.set()
        except Exception:
            traceback.print_exc()

    def _store_pull(self, msg: dict):
        """Pull with holder failover and a short retry ladder: the named
        source may not serve the object YET (its seal raced the async
        store_adopt on that host) or may have died — try every holder
        the head named, backing off between rounds.  Used by both the
        durability plane and the scheduler's arg prefetch; a permanent
        failure is silent (the reader's demand pull is the correctness
        path)."""
        oid = ObjectID(msg["oid"])
        addrs = [tuple(a) for a in (msg.get("addrs") or [msg["addr"]])]
        try:
            if self._xfer_client is None:
                from ray_tpu._private.transfer import TransferClient

                self._xfer_client = TransferClient(self.authkey)
            meta = data = None
            striped = self._store_pull_striped(oid, msg)
            if striped is not None:
                meta, data = striped
            if data is None:
                for attempt in range(5):
                    for addr in addrs:
                        try:
                            meta, data = self._xfer_client.pull(addr, oid)
                            break
                        except Exception:
                            meta = data = None
                    if data is not None or self._shutdown.is_set():
                        break
                    time.sleep(0.05 * (2 ** attempt))
            if data is None:
                return
            seg = self.store.put_replica(oid, meta, data)
            self.send({"type": "object_replicated", "oid": oid.binary(),
                       "size": len(data), "meta": meta, "segment": seg})
        except Exception:
            traceback.print_exc()

    def _store_pull_striped(self, oid: ObjectID, msg: dict):
        """Multi-source leg of the replica/prefetch pull: stripe chunk
        ranges across every holder the head named (full holders + any
        cooperative partial holders in ``sources``), advertising our own
        landed ranges so concurrent pullers of the same object feed off
        this agent instead of the origin.  Returns (meta, bytes) or None
        (any failure falls back to the single-stream retry ladder)."""
        from ray_tpu._private.config import CONFIG

        size = int(msg.get("size") or 0)
        if size < int(CONFIG.transfer_stripe_min_bytes):
            return None
        addrs = [tuple(a) for a in (msg.get("addrs") or [msg["addr"]])]
        from ray_tpu._private import transfer as transfer_mod

        chunkb = int(msg.get("chunk") or CONFIG.transfer_chunk_bytes) \
            or transfer_mod.CHUNK
        nchunks = max(1, (size + chunkb - 1) // chunkb)
        own_addr = tuple(self.xfer.address)
        src_list = [(tuple(a), set(c) if c is not None else None)
                    for a, c in (msg.get("sources") or [])] \
            or [(a, None) for a in addrs]
        src_list = [s for s in src_list if s[0] != own_addr]
        if not src_list:
            return None
        buf = bytearray(size)
        key = None
        if self.node_id is not None:
            key = b"na:" + self.node_id.binary()
            self.xfer.register_partial(oid, buf, size, chunkb)

        def progress(off, ln):
            if key is None:
                return
            fresh = self.xfer.mark_range(oid, off, ln)
            if fresh:
                try:
                    self.send({"type": "object_partial",
                               "oid": oid.binary(), "key": key,
                               "addr": list(own_addr), "chunk": chunkb,
                               "total": nchunks, "chunks": fresh,
                               "size": size})
                except Exception:
                    pass

        try:
            meta, _stats = transfer_mod.pull_striped(
                self._xfer_client, oid, size, src_list,
                memoryview(buf), meta_hint=msg.get("meta"),
                chunk=chunkb, progress=progress)
            if meta is None:
                return None
            if key is not None:
                self.xfer.complete_partial(oid, meta)
            return meta, buf  # bytes-like: put_replica copies it once
        except Exception:
            return None
        finally:
            if key is not None:
                # put_replica lands the bytes in OUR store, which the
                # object_replicated ack registers as a full holder — the
                # in-progress partial advertisement is obsolete either way.
                self.xfer.drop_partial(oid)
                try:
                    self.send({"type": "object_partial_drop",
                               "oid": oid.binary(), "key": key})
                except Exception:
                    pass

    def _heartbeat_loop(self):
        """Liveness lease renewal: the head declares this node dead when
        heartbeats go silent past node_lease_timeout_s (any other agent
        message also renews — this just bounds the idle silence)."""
        from ray_tpu._private.config import CONFIG

        period = max(0.1, CONFIG.node_heartbeat_period_s)
        while not self._shutdown.is_set():
            time.sleep(period)
            self._chaos_site("node_agent_tick")
            try:
                self.send({"type": "heartbeat"})
            except Exception:
                pass  # head restarting: reconnect loop handles it

    def _spawn_worker(self, msg: dict):
        env = dict(os.environ)
        env.update(msg.get("env") or {})
        from ray_tpu._private import inject_pkg_pythonpath
        from ray_tpu._private.jax_env import pin_platform

        pin_platform(env)
        inject_pkg_pythonpath(env)
        env["RAY_TPU_HEAD_ADDR"] = f"{self.head_addr[0]}:{self.head_addr[1]}"
        env.pop("RAY_TPU_HEAD_SOCKET", None)
        env["RAY_TPU_AUTHKEY"] = self.authkey.hex()
        proc = subprocess.Popen(
            [sys.executable, "-m", "ray_tpu._private.default_worker"],
            env=env)
        proc._rtpu_spawned = time.monotonic()
        chips = (msg.get("env") or {}).get("TPU_VISIBLE_CHIPS")
        proc._rtpu_chips = ([int(c) for c in chips.split(",")]
                            if chips else [])
        with self._children_lock:
            self._children[msg["worker_id"]] = proc

    def _kill_worker(self, worker_id: bytes):
        with self._children_lock:
            proc = self._children.pop(worker_id, None)
        if proc is not None:
            try:
                proc.kill()
            except Exception:
                pass

    def _reap_loop(self):
        """Report child exits so the head can run its death handling even
        when the worker died before opening its control connection."""
        while not self._shutdown.is_set():
            time.sleep(0.5)
            with self._children_lock:
                items = list(self._children.items())
            for wid, proc in items:
                code = proc.poll()
                if code is not None:
                    with self._children_lock:
                        self._children.pop(wid, None)
                    try:
                        self.send({"type": "worker_exit", "worker_id": wid,
                                   "code": code})
                    except Exception:
                        pass  # head restarting: reconnect loop handles it

    def _memory_loop(self):
        """Host memory-pressure relief for THIS node (the head's monitor
        only reads the head host's memory; remote workers would otherwise
        be at the mercy of the kernel OOM-killer, which can take the
        agent/store down with them).  Kills the newest child under
        pressure — one per period, like the head-side pacing; the head's
        death handling retries/fails the victim's work.  Policy-blind by
        design: the agent has no task/actor visibility (that state lives
        in the head), so it cannot apply the ranked head-side policies —
        newest-child is the LIFO approximation."""
        from ray_tpu._private.config import CONFIG
        from ray_tpu._private.memory_monitor import host_memory_usage_fraction

        period = CONFIG.memory_monitor_refresh_ms / 1000.0
        threshold = CONFIG.memory_usage_threshold
        test_file = CONFIG.memory_monitor_test_file
        if period <= 0:
            return
        while not self._shutdown.is_set():
            time.sleep(period)
            usage = 0.0
            if test_file:
                try:
                    with open(test_file) as f:
                        usage = float(f.read().strip() or 0.0)
                except (OSError, ValueError):
                    usage = 0.0
            else:
                usage = host_memory_usage_fraction()
            if usage < threshold:
                continue
            with self._children_lock:
                items = list(self._children.items())
            now = time.monotonic()
            victim = None
            for wid, proc in items:
                # Spawn grace: a worker needs ~2s to boot; killing it
                # before it can run anything just spawn-loops the retry.
                if proc.poll() is None and \
                        now - getattr(proc, "_rtpu_spawned", 0.0) > 3.0:
                    victim = (wid, proc)  # dict order: newest spawn last
            if victim is None:
                continue
            wid, proc = victim
            asked = getattr(proc, "_rtpu_oom_asked", None)
            if asked is None:
                # The head marks the victim's task and sends oom_kill
                # back, so the mark is there whichever connection tells
                # it of the death first (the worker's own socket closes
                # on another thread than this conn's reader) and it types
                # the death as an OOM (OutOfMemoryError w/ usage,
                # retryable) instead of a generic worker crash.
                proc._rtpu_oom_asked = now
                try:
                    self.send({"type": "worker_oom",
                               "worker_id": wid, "usage": usage})
                    continue
                except Exception:
                    pass  # no head to ask: relieve the host ourselves
            elif now - asked < 5.0:
                continue  # the head's oom_kill is on its way
            try:
                proc.kill()
            except Exception:
                pass

    def _stats_loop(self):
        """Per-node usage snapshots → head (reference: the dashboard
        reporter agent per node).  The period is re-read each tick: the
        head ships its resolved value in the registration reply (the
        agent's own CONFIG never sees head-side _system_config
        overrides), which may land after this thread starts."""
        from ray_tpu._private.config import CONFIG
        from ray_tpu._private.node_stats import collect_node_stats

        while not self._shutdown.is_set():
            period = (self._stats_period if self._stats_period is not None
                      else CONFIG.node_stats_period_s)
            if period <= 0:
                time.sleep(1.0)  # disabled (possibly until the handshake)
                continue
            time.sleep(period)
            with self._children_lock:
                n_workers = len(self._children)
            try:
                frame = {"type": "node_stats",
                         "stats": collect_node_stats(
                             store=self.store, num_workers=n_workers)}
                try:
                    from ray_tpu import observability as obs

                    # Agent-side spans (transfer serving, pulls) ride
                    # the stats cadence instead of their own frames.
                    spans = obs.drain_spans()
                    if spans:
                        frame["spans"] = spans
                except Exception:
                    pass
                self.send(frame)
            except Exception:
                pass  # head restarting: reconnect loop handles it

    def shutdown(self):
        self._shutdown.set()
        with self._children_lock:
            procs = list(self._children.values())
            self._children.clear()
        for p in procs:
            try:
                p.kill()
            except Exception:
                pass
        self.xfer.shutdown()
        self.store.shutdown()
        import shutil

        shutil.rmtree(self._spill_dir, ignore_errors=True)
        try:
            self.conn.close()
        except Exception:
            pass


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--address", required=True, help="head HOST:PORT")
    p.add_argument("--authkey", default=None,
                   help="hex authkey (default: RAY_TPU_AUTHKEY env)")
    p.add_argument("--num-cpus", type=float, default=None)
    p.add_argument("--num-tpus", type=float, default=0.0)
    p.add_argument("--resources", default=None,
                   help='extra resources as JSON, e.g. \'{"nodeA": 1}\'')
    p.add_argument("--store-capacity", type=int, default=2 * 1024**3)
    p.add_argument("--max-workers", type=int, default=64)
    args = p.parse_args(argv)
    host, port = args.address.rsplit(":", 1)
    authkey = bytes.fromhex(args.authkey or os.environ["RAY_TPU_AUTHKEY"])
    ncpu = args.num_cpus if args.num_cpus is not None else os.cpu_count() or 1
    resources = {"CPU": float(ncpu)}
    if args.num_tpus:
        resources["TPU"] = float(args.num_tpus)
    if args.resources:
        import json

        resources.update(json.loads(args.resources))
    agent = NodeAgent((host, int(port)), authkey, resources,
                      store_capacity=args.store_capacity,
                      max_workers=args.max_workers)
    agent.run()


if __name__ == "__main__":
    main()
