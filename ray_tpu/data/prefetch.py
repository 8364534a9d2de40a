"""Background host→device prefetch: the Data→Train ingest hot path.

``iter_device_batches`` used to run ``jax.device_put`` inline on the
consuming thread, so the object-store fetch + numpy assembly + H2D enqueue
all serialized with the training step.  :class:`DevicePrefetcher` moves
the whole producer side — block fetch, batch slicing, ``device_put`` —
onto a background thread feeding a bounded queue of device-resident
(optionally sharded) batches, double-buffered by default so the transfer
of batch N+1..N+prefetch overlaps the consumer's compute on batch N
(reference analogue: iter_torch_batches' pin_memory+prefetch worker,
python/ray/data/dataset_iterator.py; the Podracer "keep the device fed"
rule, arXiv:2104.06272).

Since the flow substrate landed this is a thin wrapper over one
:class:`ray_tpu.parallel.flow.Stage` — the bounded queue, producer
thread, error propagation and close/drain semantics all come from flow;
only the ``device_put`` placement policy lives here.

Contract (unchanged from the hand-rolled version):

- ``prefetch=0`` degrades to the old inline behavior — no thread, the
  consumer pays the device_put (useful for debugging and as the
  comparison baseline in tools/perf_smoke.py).
- Producer-thread exceptions propagate to the consumer at the point of
  ``next()`` (original traceback preserved), never silently truncate the
  stream.
- ``close()`` (also called by ``__del__`` and generator-style GC) stops
  and joins the producer thread deterministically — no leaked threads,
  even when the producer is blocked on a full queue.
- Queue occupancy and batch counts export through ray_tpu.util.metrics
  (both the legacy ``data_prefetch_*`` names and the substrate's tagged
  ``flow_*`` series; best-effort, skipped where no driver is connected).
- Spans (ray_tpu.observability; PERF.md lists their readers): on the
  producer thread one ``ingest.produce`` per batch, around the block
  fetch, the slicing and its ``ingest.h2d`` (the ``place`` call); on the
  consumer ``ingest.wait`` around ``__next__``, with the queue ``depth``
  it found.
"""
from __future__ import annotations

from typing import Any, Callable, Iterable, Iterator, Optional

from ray_tpu import observability as obs


def _make_place_fn(sharding, place_fn):
    if place_fn is not None:
        return place_fn

    def place(batch):
        import jax

        if sharding is not None:
            return jax.device_put(batch, sharding)
        return jax.device_put(batch)

    return place


class _Producer(Iterator[Any]):
    """Source and transform of the prefetcher's stage in one object,
    because one ``ingest.produce`` span covers both: it opens before a
    host batch is fetched and closes once that batch is placed.  The
    stage calls ``place`` right after each ``__next__``, on one thread."""

    def __init__(self, host_batches: Iterable[Any], place):
        self._it, self._place = iter(host_batches), place
        self._span = obs.NO_SPAN

    def __next__(self):
        self._span = obs.span("ingest.produce").__enter__()
        try:
            return next(self._it)
        except BaseException as e:
            if isinstance(e, StopIteration):
                self._span.cancel()  # the stream's end is no batch
            self._span.__exit__(type(e), e, e.__traceback__)
            raise

    def place(self, batch):
        import jax

        leaves = jax.tree_util.tree_leaves(batch)
        try:
            with obs.span("ingest.h2d", bytes=sum(
                    getattr(x, "nbytes", 0) for x in leaves)):
                return self._place(batch)
        finally:
            self._span.set(rows=next(
                (x.shape[0] for x in leaves if getattr(x, "shape", ())), 0))
            self._span.__exit__(None, None, None)

    def close(self):
        close = getattr(self._it, "close", None)
        if close is not None:
            close()


class DevicePrefetcher(Iterator[Any]):
    """Iterator of device-resident batches with background H2D transfer.

    ``host_batches``: any iterable of host batches (dict-of-numpy or
    pytree).  ``sharding``: placement for ``jax.device_put`` (None =
    default device).  ``place_fn``: overrides placement entirely (takes a
    host batch, returns the device batch).  ``prefetch``: bounded queue
    size (device batches materialized ahead of the consumer); 0 = inline.
    """

    def __init__(self, host_batches: Iterable[Any], sharding=None,
                 prefetch: int = 2,
                 place_fn: Optional[Callable[[Any], Any]] = None,
                 name: str = "device-prefetch"):
        from ray_tpu.parallel.flow import Stage  # lazy: parallel pulls jax

        self.prefetch = int(prefetch)
        producer = _Producer(host_batches,
                             _make_place_fn(sharding, place_fn))
        self._stage = Stage(
            producer, producer.place,
            depth=max(1, self.prefetch),
            workers=1 if self.prefetch > 0 else 0,
            name=name, span="",
            # flow's throttled export is kept; the legacy gauge names are
            # exported once at end-of-stream/close below.
            export_metrics=True)
        self._exported = False

    # ---- consumer side ----
    def __iter__(self) -> "DevicePrefetcher":
        return self

    def __next__(self):
        try:
            with obs.span("ingest.wait", depth=self._stage.queue_depth):
                return next(self._stage)
        except BaseException:
            self._export_metrics()
            raise

    # ---- lifecycle ----
    def close(self):
        """Stop the producer and join its thread.  Idempotent; safe to
        call mid-stream (pending device batches are dropped)."""
        self._stage.close()
        self._export_metrics()

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass

    def __enter__(self) -> "DevicePrefetcher":
        return self

    def __exit__(self, exc_type, exc_val, tb):
        self.close()

    # ---- observability ----
    @property
    def _thread(self):
        """The producer thread (None once joined / in inline mode) —
        part of the de-facto API: tests assert its lifecycle."""
        threads = self._stage.worker_threads
        return threads[0] if threads else None

    @property
    def peak_occupancy(self) -> int:
        return self._stage.peak_occupancy

    @property
    def batches_delivered(self) -> int:
        return self._stage.items_delivered

    def _export_metrics(self):
        if self._exported:
            return
        self._exported = True
        try:
            from ray_tpu.util.metrics import Counter, Gauge

            Counter("data_prefetch_batches_total",
                    "device batches delivered by the prefetch queue"
                    ).inc(self.batches_delivered)
            Gauge("data_prefetch_queue_peak",
                  "peak occupancy of the device prefetch queue"
                  ).set(float(self.peak_occupancy))
        except Exception:
            pass  # no connected driver (e.g. bare worker process)


def iter_device_batches(host_batches: Iterable[Any], sharding=None,
                        prefetch: int = 2,
                        place_fn: Optional[Callable[[Any], Any]] = None
                        ) -> DevicePrefetcher:
    """Functional form: wrap any host-batch iterable in a background
    device prefetcher (see :class:`DevicePrefetcher`)."""
    return DevicePrefetcher(host_batches, sharding=sharding,
                            prefetch=prefetch, place_fn=place_fn)
