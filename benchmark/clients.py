"""The serve cells' clients: front-end processes that hold the streams.

One process cannot take in what the engine makes: every ``next_chunk`` reply
costs it about a millisecond of Python (PERF.md section 4), so one process
holding every stream made itself the knee of both serve cells.  A traffic file
says how many client processes share the requests (``"clients"``); request i
goes to process ``i mod clients``, which sends it when it is due and reads its
stream on a thread of its own, one blocking ``next_chunk`` call after another
through the serve handle, as a front end's connection handler would.  Every
process plays its share of the one schedule against one clock: times are
seconds since ``t0``, a wall-clock instant the parent fixes for all of them,
taken on each process's ``perf_counter``.
"""
from __future__ import annotations

import threading
import time

from benchmark import loadgen


class Streams:
    """One process's share of the schedule: sends request i at its due time
    (``loadgen.OpenLoop``) and stamps each token of its answer as the reply
    that carries it arrives."""

    def __init__(self, handle, share):
        self._submit = handle.method("submit_stream")
        self._next = handle.method("next_chunk")
        self.index = [i for i, _ in share]
        self.requests = [r for _, r in share]
        n = len(share)
        self.stamps = [[] for _ in range(n)]
        self.done_at = [None] * n
        self.errors = {}
        self._gen = loadgen.OpenLoop(self.requests, self._send)

    def ready(self) -> int:
        return len(self.requests)

    def start(self, t0_wall: float):
        """``t0_wall`` on this process's ``perf_counter``: the two clocks are
        read once, together, so a stamp is off by microseconds."""
        self._t0 = time.perf_counter() + (t0_wall - time.time())
        self._gen.start(self._t0)

    def _send(self, k, req):
        threading.Thread(target=self._stream, args=(k, req),
                         name=f"stream-{k}", daemon=True).start()

    def _stream(self, k, req):
        import ray_tpu

        try:
            rid = ray_tpu.get(self._submit.remote(req["prompt"],
                                                  req["max_new_tokens"]))
            while True:
                chunk = ray_tpu.get(self._next.remote(rid, 300.0))
                t = time.perf_counter() - self._t0
                if chunk is None:
                    self.done_at[k] = t
                    return
                self.stamps[k].extend([t] * len(chunk))
        except Exception as e:  # noqa: BLE001 — counted as failed
            self.errors[k] = repr(e)

    def stop(self) -> dict:
        """Stops sending and hands over what has arrived so far, by the
        request's place in the whole schedule; streams still open are left
        to the runtime's shutdown."""
        self._gen.stop()
        sent = [None if s is None else s - self._t0 for s in self._gen.sent_at]
        return {i: {"sent_at": sent[k], "stamps": list(self.stamps[k]),
                    "done_at": self.done_at[k], "error": self.errors.get(k)}
                for k, i in enumerate(self.index)}


class Fleet:
    """``clients`` processes, each an actor of the runtime holding a
    ``Streams`` over its share; the parent only starts and stops them."""

    def __init__(self, handle, schedule, clients: int):
        import ray_tpu

        actor = ray_tpu.remote(Streams)
        shares = [list(enumerate(schedule))[c::clients]
                  for c in range(clients)]
        self._actors = [actor.remote(handle, share) for share in shares]
        ray_tpu.get([a.ready.remote() for a in self._actors], timeout=120.0)

    def start(self, t0_wall: float):
        import ray_tpu

        ray_tpu.get([a.start.remote(t0_wall) for a in self._actors],
                    timeout=60.0)

    def stop(self) -> list:
        """Every request's ``sent_at``, ``stamps``, ``done_at`` and ``error``
        in the schedule's order, in seconds since ``t0``; the processes end
        here, with whatever streams they still held."""
        import ray_tpu

        merged = {}
        for part in ray_tpu.get([a.stop.remote() for a in self._actors],
                                timeout=120.0):
            merged.update(part)
        for a in self._actors:
            ray_tpu.kill(a)
        return [merged[i] for i in range(len(merged))]
