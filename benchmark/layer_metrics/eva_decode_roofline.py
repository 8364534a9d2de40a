"""The whole decode program of a decoder of EVA layers against the memory
roofline: what the traced decode steps had to move (``costs_eva.
decode_bytes``: every layer's weights, the final norm and the next byte's
head once; the summary and window rows of the live slots, every layer's, K
and V each once; a page a layer for every chunk a token closed) over the HBM
bandwidth, divided by the device time of the program named ``llm_decode`` in
those steps.  This is the cell's share of the whole step.

The engine says the counts itself, per step, on its
``engine.decode.dispatch`` span: ``kv_tokens`` (the rows read) and
``chunks_closed``.  Means over the steps on both sides.  The embedding is a
look-up and is not counted.  A configuration of another family, or a run
with no profile, has nothing to read."""
import statistics

from benchmark import costs_eva, program_spans


def read(record, ctx):
    programs = (record.get("trace") or {}).get("program_s") or {}
    runs = [s for name, v in programs.items()
            if name.endswith("llm_decode") for s in v]
    values = program_spans.arg_values
    rows = values("engine.decode.dispatch", "kv_tokens")
    closed = values("engine.decode.dispatch", "chunks_closed")
    cfg = ctx["config"]
    if not (runs and rows and closed) or "peak" not in ctx \
            or cfg.get("serve", {}).get("model_kind") != "eva_decoder":
        return None
    size = 2 if cfg["serve"]["dtype"] == "bfloat16" else 4
    need = costs_eva.decode_bytes(cfg, statistics.mean(rows),
                                  statistics.mean(closed), size)
    return 100.0 * need / ctx["peak"]["hbm_bytes_per_s"] \
        / statistics.mean(runs)
