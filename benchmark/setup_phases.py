"""``setup_s`` split by what the program was doing, for the per-layer readers
of the *set-up* layer.

The program records a lifecycle span at each stage of its set-up whatever the
tracing flag says (``ray_tpu.observability``, ``_lifecycle=True``), on the
clock of ``time.time()``, which is also the clock of ``run.py``'s
``T_PROCESS`` and of the drivers' ``window_start``.  Every instant of
``[T_PROCESS, window_start]`` is given to the most specific lifecycle span
open at that instant in any process of the session, and to ``unseen`` where
none is open: the five phases add up to ``setup_s``.

A program without lifecycle spans (an older commit) gives None, as
``program_spans``'s readers do, and so does a session that lost spans before
they could be read: a partition with holes is worse than none.
"""
from benchmark import program_spans

# most specific first: a span's rank is its row, its phase the row's
ORDER = (
    ("compile", ("jax.compile",)),
    ("compile", ("engine.compile", "train.compile")),
    ("model", ("model.build", "engine.init")),
    ("placement", ("train.rendezvous", "serve.replica_init")),
    ("placement", ("serve.deploy", "train.worker_group_start")),
    ("runtime", ("runtime.worker_start",)),
    ("runtime", ("runtime.init",)),
)
RANK = {name: row for row, (_, names) in enumerate(ORDER) for name in names}
PHASES = ("runtime", "placement", "model", "compile", "unseen")


def lifecycle_spans():
    """The session's lifecycle spans, or None where there is none or the
    session says it lost spans."""
    from ray_tpu import observability

    found = [s for s in program_spans.spans() if s["name"] in RANK]
    dropped = getattr(observability, "session_spans_dropped", None)
    if not found or (dropped is not None and dropped() > 0):
        return None
    return found


def partition(spans, t0: float, t1: float) -> dict:
    """Seconds of ``[t0, t1]`` a phase.  Spans are cut at both ends; where
    several are open the lowest rank owns the instant, so a deeper span takes
    its seconds from the one around it and spans of one rank that overlap
    (compiles side by side) count once."""
    edges = []  # (time, rank, +1 opens / -1 closes)
    for s in spans:
        start, end = max(s["start"], t0), min(s["end"], t1)
        if end > start:
            rank = RANK[s["name"]]
            edges += [(start, rank, 1), (end, rank, -1)]
    edges.sort()
    out = dict.fromkeys(PHASES, 0.0)
    open_at = [0] * len(ORDER)
    at = t0
    for t, rank, step in edges:
        if t > at:
            owner = next((ORDER[r][0] for r, n in enumerate(open_at) if n),
                         "unseen")
            out[owner] += t - at
            at = t
        open_at[rank] += step
    out["unseen"] += max(0.0, t1 - at)
    return out


def phase_s(record, phase: str):
    """Seconds of this run's set-up that ``phase`` owns."""
    spans = lifecycle_spans()
    if spans is None:
        return None
    t1 = record["window_start"]
    return partition(spans, t1 - record["end_to_end"]["setup_s"], t1)[phase]


def cache_hit_share(record):
    """Of the compile requests JAX answered before the window opened, the
    share (%) the persistent cache answered."""
    spans = lifecycle_spans()
    if spans is None:
        return None
    events = [(s.get("args") or {}).get("event") for s in spans
              if s["name"] == "jax.compile"
              and s["end"] <= record["window_start"]]
    if not events:
        return None
    return 100.0 * events.count("cache_hit") / len(events)
