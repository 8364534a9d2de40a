"""Run/scaling/failure/checkpoint configs (reference: python/ray/air/config.py
ScalingConfig :79, FailureConfig :483, CheckpointConfig :542, RunConfig :670).

TPU-specific: ScalingConfig speaks in *hosts* and *chips* and carries a
MeshSpec — a "worker" is one process per TPU host and the real parallelism
layout lives in the mesh axes, not in worker count.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple, Union

from ray_tpu.parallel.mesh import MeshSpec


@dataclasses.dataclass
class ScalingConfig:
    """num_workers = processes (1 per TPU host). use_tpu selects the chip
    resource; chips_per_worker reserves them; mesh describes the logical
    parallelism over ALL chips of the group.

    ``num_workers`` may be an ``(min, max)`` tuple for an *elastic* gang:
    BackendExecutor starts as many workers as the cluster can place right
    now (probing max→min) and never below min."""

    num_workers: Union[int, Tuple[int, int]] = 1
    use_tpu: bool = False
    chips_per_worker: int = 0
    resources_per_worker: Optional[Dict[str, float]] = None
    mesh: Optional[MeshSpec] = None
    placement_strategy: str = "PACK"

    def __post_init__(self):
        if self.use_tpu and self.chips_per_worker < 1:
            # A worker that reserves no chip is started on the CPU (the
            # raylet keeps chips for the workers that asked): use_tpu with
            # no chips would train there without saying so.
            raise ValueError(
                "ScalingConfig(use_tpu=True) needs chips_per_worker >= 1: "
                "a worker only sees the chips it reserves")

    def worker_range(self) -> Tuple[int, int]:
        """(min, max) worker count — a fixed ``num_workers=n`` is the
        degenerate range (n, n)."""
        nw = self.num_workers
        if isinstance(nw, int):
            if nw < 1:
                raise ValueError(f"num_workers must be >= 1, got {nw}")
            return (nw, nw)
        lo, hi = int(nw[0]), int(nw[1])
        if not 1 <= lo <= hi:
            raise ValueError(f"bad elastic num_workers range {nw!r}")
        return (lo, hi)

    @property
    def min_workers(self) -> int:
        return self.worker_range()[0]

    @property
    def max_workers(self) -> int:
        return self.worker_range()[1]

    # Reference-compat alias (trainer_resources etc. intentionally dropped).
    @property
    def num_tpus_per_worker(self) -> int:
        return self.chips_per_worker

    def worker_resources(self) -> Dict[str, float]:
        res = dict(self.resources_per_worker or {})
        res.setdefault("CPU", 1.0)
        if self.use_tpu:
            res["TPU"] = float(self.chips_per_worker)
        return res


@dataclasses.dataclass
class FailureConfig:
    max_failures: int = 0  # -1 = unlimited trial retries


@dataclasses.dataclass
class CheckpointConfig:
    num_to_keep: Optional[int] = None
    checkpoint_score_attribute: Optional[str] = None
    checkpoint_score_order: str = "max"
    checkpoint_frequency: int = 0


@dataclasses.dataclass
class RunConfig:
    name: Optional[str] = None
    storage_path: Optional[str] = None  # local dir (cloud URI round-2)
    failure_config: Optional[FailureConfig] = None
    checkpoint_config: Optional[CheckpointConfig] = None
    stop: Optional[Dict[str, Any]] = None
    verbose: int = 1
    # Mirror the experiment dir to durable storage after each state save
    # (reference: SyncConfig/Syncer, python/ray/tune/syncer.py).
    sync_config: Optional["SyncConfig"] = None


@dataclasses.dataclass
class SyncConfig:
    upload_dir: Optional[str] = None
    sync_period_s: float = 0.0  # 0 = sync on every experiment-state save
