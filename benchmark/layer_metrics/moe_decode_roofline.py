"""A routed model's decode program against the memory roofline: what the
traced decode steps had to read (``costs_moe.moe_decode_bytes``: the weights
outside the experts once, the three matrices of the experts that got a row
of an active slot, the keys and values of the cached rows) over the HBM
bandwidth, divided by the device time of the decode program in those steps.

The engine says both counts itself, per step: ``experts_hit`` on its
``engine.decode.fetch`` span and ``kv_tokens`` on ``engine.decode.dispatch``
(never ``counters.live_tokens_at_trace``, one instant's reading: PERF.md
section 7).  Means over the steps on both sides, so that a step cut by an
edge of the profile weighs on neither.  The embedding table is a look-up of
16 rows and is not counted; experts no live row reached are not counted,
and since PR 42 the decode step does not read them (``moe_hit`` streams the
step's own list of hit experts; the masked form, which read every expert, is
gone).  A program that routes nothing (or the parent of the PR that brought
this) has no ``experts_hit``: nothing to read."""
import statistics

from benchmark import costs_moe, program_spans


def read(record, ctx):
    programs = (record.get("trace") or {}).get("program_s") or {}
    runs = [s for name, v in programs.items()
            if name.endswith("llm_decode") for s in v]
    hit = program_spans.arg_values("engine.decode.fetch", "experts_hit")
    kv = program_spans.arg_values("engine.decode.dispatch", "kv_tokens")
    params = record.get("counters", {}).get("param_count")
    if not (runs and hit and kv and params) or "peak" not in ctx:
        return None
    cfg = ctx["config"]
    size = 2 if cfg["serve"]["dtype"] == "bfloat16" else 4
    counts = costs_moe.moe_param_counts(cfg)
    outside = (params - counts["experts"] - counts["embedding"]) * size
    need = costs_moe.moe_decode_bytes(cfg, outside, statistics.mean(hit),
                                      statistics.mean(kv), size)
    return 100.0 * need / ctx["peak"]["hbm_bytes_per_s"] \
        / statistics.mean(runs)
