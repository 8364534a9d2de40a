"""The whole decode program of a decoder of latent attention under a learned
selection that holds a share of its experts, against the memory roofline:
what the traced decode steps had to move (``costs_sparse_moe.decode_bytes``:
every weight outside the routed experts once, the three matrices of the held
experts that a live row hit, the index keys of the cached rows the indexers
scored, the latent rows the attention selected, as stored) over the HBM
bandwidth, divided by the device time of the program named ``llm_decode`` in
those steps.  This is the cell's share of the whole step.

The engine says the three counts itself, per step: ``index_rows`` on its
``engine.decode.dispatch`` span, ``kv_rows_read`` (the program's own count)
and ``experts_hit`` on ``engine.decode.fetch``.  Means over the steps on both
sides.  The embedding table is a look-up and is not counted; cached rows the
selection left out are not counted, and are not read.  A configuration of
another family, or a program whose spans carry no ``kv_rows_read``, has
nothing to read."""
import statistics

from benchmark import costs_sparse_moe, program_spans


def read(record, ctx):
    programs = (record.get("trace") or {}).get("program_s") or {}
    runs = [s for name, v in programs.items()
            if name.endswith("llm_decode") for s in v]
    values = program_spans.arg_values
    scored = values("engine.decode.dispatch", "index_rows")
    read_rows = values("engine.decode.fetch", "kv_rows_read")
    hit = values("engine.decode.fetch", "experts_hit")
    cfg = ctx["config"]
    if not (runs and scored and read_rows and hit) or "peak" not in ctx \
            or "index_topk" not in cfg:
        return None
    size = 2 if cfg["serve"]["dtype"] == "bfloat16" else 4
    need = costs_sparse_moe.decode_bytes(
        cfg, statistics.mean(scored), statistics.mean(read_rows),
        statistics.mean(hit), size)
    return 100.0 * need / ctx["peak"]["hbm_bytes_per_s"] \
        / statistics.mean(runs)
