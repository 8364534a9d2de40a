"""The program's own spans, for the per-layer readers of ``program_span``
metrics: ``ray_tpu.observability.session_spans()`` gives what the head of the
finished session holds plus this process's ring.  With the tracing flag off
the program records spans only while a profile runs, so what is there is the
traced window.  A program without that recorder (an older commit) has no
spans, and the readers then return None.
"""
import statistics


def spans(name=None) -> list:
    from ray_tpu import observability

    read = getattr(observability, "session_spans", None)
    return read(name) if read else []


def arg_values(name: str, key: str) -> list:
    """The argument ``key`` of every span of that name that carries it."""
    return [a[key] for a in ((s.get("args") or {}) for s in spans(name))
            if a.get(key) is not None]


def ms(span: dict) -> float:
    return (span["end"] - span["start"]) * 1e3


def median_ms(name: str, keep=None):
    """Median length of the spans of that name (that ``keep`` accepts)."""
    took = [ms(s) for s in spans(name) if keep is None or keep(s)]
    return statistics.median(took) if took else None


def admitted(span: dict) -> bool:
    """An ``engine.admit`` span in which a request got its slot."""
    return (span.get("args") or {}).get("admitted", 0) > 0
