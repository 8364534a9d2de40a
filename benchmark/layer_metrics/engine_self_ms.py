"""Host time of an engine iteration that none of its child spans covers,
median over the iterations that admitted nothing: what the loop thread does
between two device programs that has no name yet."""
import statistics

from benchmark import program_spans
from benchmark.trace_reduce import clip, total, union


def read(record, ctx):
    children = {}
    for s in program_spans.spans(None):
        children.setdefault(s.get("parent_id"), []).append(s)
    left = []
    for it in program_spans.spans("engine.iteration"):
        mine = children.get(it["span_id"], [])
        if any(c["name"] == "engine.admit" and program_spans.admitted(c)
               for c in mine):
            continue
        covered = total(union(clip([(c["start"], c["end"]) for c in mine],
                                   it["start"], it["end"])))
        left.append((it["end"] - it["start"] - covered) * 1e3)
    return statistics.median(left) if left else None
