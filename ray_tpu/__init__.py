"""ray_tpu: a TPU-native distributed ML framework.

Public core API mirrors the reference's `ray` package
(python/ray/__init__.py): init/shutdown, remote, get/put/wait, actors,
placement groups, state queries — implemented on a single-host (or virtual
multi-node) head with subprocess workers and a shared-memory object store.
The ML stack (train/tune/data/rllib/serve) and the TPU mesh layer
(parallel/, ops/, models/) build on this core.
"""
from __future__ import annotations

import os
import threading
from typing import Any, Dict, List, Optional, Sequence

from ray_tpu import exceptions  # noqa: F401
from ray_tpu._private.ids import JobID, NodeID, ObjectID, WorkerID
from ray_tpu.object_ref import ObjectRef  # noqa: F401
from ray_tpu.actor import ActorClass, ActorHandle  # noqa: F401
from ray_tpu.remote_function import RemoteFunction
from ray_tpu.runtime_context import get_runtime_context  # noqa: F401

__version__ = "0.1.0"

_head = None
_remote_driver = None
_head_lock = threading.RLock()


def _global_head():
    return _head


def _default_num_cpus() -> float:
    env = os.environ.get("RAY_TPU_NUM_CPUS")
    if env:
        return float(env)
    # On tiny dev machines a detected count of 1 starves multi-actor
    # workloads; logical CPUs are a scheduling token here, not a cgroup.
    return float(max(os.cpu_count() or 1, 8))


def _detect_num_tpus() -> float:
    """Chips on this host, counted from their device files: ``/dev/accel<N>``
    (through v4) or one numbered vfio group per chip (v5e and later).

    Initialising a JAX backend here would count them too, and would take
    the chips away from the workers: libtpu hands a chip to one process.
    A count that is wrong (a vfio group that is no TPU) is caught where
    the chip is first used — ``mesh_group.rendezvous`` raises when a
    worker granted chips comes up on the CPU."""
    env = os.environ.get("RAY_TPU_NUM_TPUS")
    if env:
        return float(env)
    import glob

    return float(len(glob.glob("/dev/accel[0-9]*"))
                 + len(glob.glob("/dev/vfio/[0-9]*")))


def _boot_head(resources: Dict[str, float], labels=None,
               store_capacity: int = 2 * 1024**3) -> NodeID:
    """Start the in-process head with one node; driver connects separately."""
    global _head
    from ray_tpu._private.head import Head

    with _head_lock:
        if _head is not None:
            raise RuntimeError("already initialized")
        _head = Head()
        return _head.add_node(resources, labels, store_capacity=store_capacity)


def _apply_job_config(worker, job_config: Optional[dict]) -> None:
    """Job-level defaults → driver worker state (reference: JobConfig's
    ray_namespace/runtime_env semantics): per-call options still win.
    Local py_modules paths are packaged + uploaded here (once, at
    connect) so every spec carrying the default ships pkg:// URIs that
    resolve on any node; job_config is updated in place so head
    registration records the normalized form."""
    if not job_config:
        return
    if job_config.get("namespace"):
        worker.namespace = job_config["namespace"]
    if job_config.get("runtime_env"):
        from ray_tpu._private.runtime_env_pkg import normalize_py_modules

        job_config["runtime_env"] = normalize_py_modules(
            job_config["runtime_env"], worker.transport)
        worker.default_runtime_env = job_config["runtime_env"]


def _connect_driver(job_config: Optional[dict] = None):
    from ray_tpu._private.worker import CoreWorker, DirectTransport, set_global_worker

    with _head_lock:
        job_id = JobID.from_random()
        worker_id = WorkerID.from_random()
        node_id = next(iter(_head.raylets))
        transport = DirectTransport(_head, worker_id)
        worker = CoreWorker(worker_id, node_id, job_id, transport, mode="driver")
        from ray_tpu._private.config import CONFIG

        if CONFIG.direct_transport:
            # The driver owns its tasks' results: start its direct listener
            # (serving fetch/pin for borrowed refs) + lease-caching submitter.
            from ray_tpu._private.direct import DirectServer

            server = DirectServer(worker._owned, _head.authkey,
                                  _head.host_key,
                                  session_dir=_head.session_dir,
                                  on_exec=None, tcp_bind=CONFIG.tcp_host)
            worker.enable_direct(server, _head.host_key)
        _apply_job_config(worker, job_config)
        set_global_worker(worker)
        _head.gcs.add_job(job_id, job_config or {})
    return worker


def init(num_cpus: Optional[float] = None, num_tpus: Optional[float] = None,
         resources: Optional[Dict[str, float]] = None,
         object_store_memory: int = 2 * 1024**3,
         labels: Optional[dict] = None,
         ignore_reinit_error: bool = False,
         address: Optional[str] = None,
         _authkey: Optional[bytes] = None, **kwargs):
    """Start a local cluster head + connect this process as the driver, or —
    with ``address="host:port"`` — join an existing remote head over TCP.

    Reference: ray.init (python/ray/_private/worker.py:1043)."""
    from ray_tpu import observability as _obs

    mode = ("local_mode" if kwargs.get("local_mode")
            else "head" if address is None else "remote")
    with _obs.span("runtime.init", _lifecycle=True, mode=mode) as sp:
        worker = _init(num_cpus, num_tpus, resources, object_store_memory,
                       labels, ignore_reinit_error, address, _authkey, kwargs)
        if worker is None:  # already up, ignore_reinit_error: no start
            sp.cancel()
        return worker


def _init(num_cpus, num_tpus, resources, object_store_memory, labels,
          ignore_reinit_error, address, _authkey, kwargs):
    global _head, _remote_driver
    with _head_lock:
        if is_initialized():
            if ignore_reinit_error:
                return
            raise RuntimeError("ray_tpu.init() called twice "
                               "(pass ignore_reinit_error=True to allow)")
        if kwargs.get("_system_config"):
            from ray_tpu._private.config import CONFIG

            CONFIG.apply_system_config(kwargs["_system_config"])
        if kwargs.get("local_mode"):
            # Inline debugging execution (reference:
            # ray.init(local_mode=True)) — no head, no subprocesses.
            from ray_tpu._private.local_mode import LocalModeWorker
            from ray_tpu._private.worker import set_global_worker

            w = LocalModeWorker()
            set_global_worker(w)
            return w
        if address == "auto":
            # Reference: ray.init(address="auto") — resolve from the env
            # the job manager / CLI sets for entrypoint subprocesses.
            address = os.environ.get("RAY_TPU_ADDRESS")
            if not address:
                raise RuntimeError(
                    'init(address="auto") needs RAY_TPU_ADDRESS in the env '
                    "(set by the job manager / ray_tpu CLI)")
        if address is not None:
            from ray_tpu.util.client import normalize_address

            return _connect_remote_driver(normalize_address(address),
                                          _authkey,
                                          kwargs.get("job_config"))
        res = dict(resources or {})
        res["CPU"] = float(num_cpus) if num_cpus is not None else _default_num_cpus()
        ntpu = float(num_tpus) if num_tpus is not None else _detect_num_tpus()
        if ntpu:
            res["TPU"] = ntpu
        res.setdefault("memory", float(object_store_memory))
        _boot_head(res, labels, store_capacity=object_store_memory)
        worker = _connect_driver(kwargs.get("job_config"))
        if kwargs.get("log_to_driver", True):
            from ray_tpu._private.log_monitor import attach_driver_echo

            attach_driver_echo(_head.gcs)
        return worker


def _connect_remote_driver(address: str, authkey: Optional[bytes],
                           job_config: Optional[dict]):
    global _remote_driver
    import os as _os

    from ray_tpu._private.driver_client import RemoteDriverRuntime
    from ray_tpu._private.worker import CoreWorker, set_global_worker

    if authkey is None:
        hexkey = _os.environ.get("RAY_TPU_AUTHKEY")
        if not hexkey:
            raise ValueError(
                "joining a remote head needs its authkey: pass _authkey= "
                "or set RAY_TPU_AUTHKEY")
        authkey = bytes.fromhex(hexkey)
    rt = RemoteDriverRuntime(address, authkey, job_config=job_config)
    worker = CoreWorker(rt.worker_id, rt.node_id, rt.job_id, rt.transport,
                        mode="driver")
    _apply_job_config(worker, job_config)
    set_global_worker(worker)
    _remote_driver = rt
    return worker


def client(address: str):
    """Ray-Client-style builder: ``ray_tpu.client("ray://host:port")
    .connect()`` (reference: ray.client, python/ray/client_builder.py)."""
    from ray_tpu.util.client import client as _client

    return _client(address)


def is_initialized() -> bool:
    from ray_tpu._private.worker import global_worker

    return _head is not None or _remote_driver is not None or \
        getattr(global_worker, "mode", None) == "local"


def shutdown():
    global _head, _remote_driver
    from ray_tpu._private.worker import global_worker, set_global_worker

    with _head_lock:
        if global_worker is not None:
            if getattr(global_worker, "mode", None) == "local":
                global_worker.shutdown()
            else:
                try:
                    global_worker.shutdown()
                except Exception:
                    pass
            try:
                global_worker._closed = True
            except Exception:
                pass
            set_global_worker(None)
        if _remote_driver is not None:
            _remote_driver.shutdown()
            _remote_driver = None
        if _head is not None:
            _head.shutdown()
            _head = None
    # Session boundary: an implicit trace context minted for this
    # session's API calls must not bleed into the next init().
    from ray_tpu import observability as _obs

    _obs.clear_context()


def remote(*args, **kwargs):
    """@remote decorator for functions and classes (reference:
    python/ray/_private/worker.py remote())."""
    if len(args) == 1 and not kwargs and callable(args[0]):
        target = args[0]
        if isinstance(target, type):
            return ActorClass(target)
        return RemoteFunction(target)

    def decorator(target):
        if isinstance(target, type):
            return ActorClass(target, dict(kwargs))
        return RemoteFunction(target, dict(kwargs))

    return decorator


def _worker():
    from ray_tpu._private.worker import global_worker

    if global_worker is None:
        raise RuntimeError("ray_tpu.init() has not been called")
    return global_worker


def put(value: Any) -> ObjectRef:
    return _worker().put(value)


def put_many(values: Sequence[Any]) -> List[ObjectRef]:
    """Put a burst of objects with coalesced control-plane traffic: the
    per-object seal/inline notifications ride one batched message (O(1)
    head messages per burst instead of O(K)).  Bytes move exactly as in
    put()."""
    w = _worker()
    if hasattr(w, "put_many"):
        return w.put_many(list(values))
    return [w.put(v) for v in values]


def get(refs, timeout: Optional[float] = None):
    return _worker().get(refs, timeout)


def get_many(refs: Sequence[ObjectRef], timeout: Optional[float] = None):
    """Batch get for a burst of refs: one resolve round trip covers every
    already-available object (same semantics as get(list))."""
    w = _worker()
    if hasattr(w, "get_many"):
        return w.get_many(list(refs), timeout)
    return w.get(list(refs), timeout)


def wait(refs: Sequence[ObjectRef], num_returns: int = 1,
         timeout: Optional[float] = None, fetch_local: bool = True):
    return _worker().wait(refs, num_returns, timeout, fetch_local)


def kill(actor: ActorHandle, no_restart: bool = True):
    _worker().transport.request(
        "kill_actor", {"actor_id": actor._actor_id, "no_restart": no_restart})


def cancel(ref: ObjectRef, force: bool = False):
    w = _worker()
    if hasattr(w, "cancel_task"):
        w.cancel_task(ref.id.task_id())
    else:
        w.transport.request("cancel", {"task_id": ref.id.task_id()})


def get_actor(name: str, namespace: Optional[str] = None) -> ActorHandle:
    w = _worker()
    if namespace is None:  # fall back to the job's namespace (JobConfig)
        namespace = getattr(w, "namespace", None) or "default"
    info = w.transport.request(
        "get_actor", {"name": name, "namespace": namespace})
    spec = info["creation_spec"]
    return ActorHandle(info["actor_id"], spec.actor_method_names,
                       spec.name.replace(".__init__", ""))


def cluster_resources() -> Dict[str, float]:
    return _worker().transport.request("cluster_resources", {})


def available_resources() -> Dict[str, float]:
    return _worker().transport.request("cluster_resources", {"available": True})


def nodes() -> List[dict]:
    return _worker().transport.request("state", {"what": "nodes"})


def timeline(filename: Optional[str] = None,
             trace_id: Optional[str] = None) -> List[dict]:
    """Chrome-trace dump of task execution (reference: ray.timeline()),
    merged with the tracing plane's cluster spans: per-node pid lanes,
    per-process tid lanes, and cross-process flow arrows.  Pass a
    ``trace_id`` to assemble one distributed trace's timeline."""
    from ray_tpu._private.profiling import chrome_tracing_dump

    try:
        raw = _worker().transport.request(
            "trace_timeline", {"trace_id": trace_id})
        tasks, spans = raw["tasks"], raw["spans"]
    except Exception:
        # Older head without the tracing plane: tasks only.
        tasks, spans = _worker().transport.request(
            "state", {"what": "tasks"}), []
    return chrome_tracing_dump(tasks, filename, spans=spans)


# Submodules re-exported lazily to keep `import ray_tpu` light (jax-free).
def __getattr__(name):
    import importlib

    if name in ("util", "air", "train", "tune", "data", "serve", "rllib",
                "parallel", "ops", "models", "workflow", "dag",
                "cluster_utils", "state", "internal_kv", "checkpoint",
                "observability"):
        return importlib.import_module(f"ray_tpu.{name}")
    raise AttributeError(f"module 'ray_tpu' has no attribute {name!r}")
