"""The whole decode program of a decoder of delta-rule and latent-attention
layers that holds a share of its experts, against the memory roofline: what
the traced decode steps had to move (``costs_linear_moe.decode_bytes``: every
weight outside the routed experts once, the three matrices of the held
experts that a live row hit, the recurrent state and convolution rows of the
live slots read and written in the KDA layers, the latent rows of the cached
tokens in the MLA layer) over the HBM bandwidth, divided by the device time
of the program named ``llm_decode`` in those steps.  This is the cell's
share of the whole step.

The engine says the three counts itself, per step: ``state_slots`` and
``kv_tokens`` on its ``engine.decode.dispatch`` span, ``experts_hit`` on
``engine.decode.fetch``.  Means over the steps on both sides.  The embedding
table is a look-up and is not counted; held experts no live row chose are
not counted, and are not read.  A configuration of another family, or a
program whose spans carry no ``experts_held``, has nothing to read."""
import statistics

from benchmark import costs_linear_moe, program_spans


def read(record, ctx):
    programs = (record.get("trace") or {}).get("program_s") or {}
    runs = [s for name, v in programs.items()
            if name.endswith("llm_decode") for s in v]
    values = program_spans.arg_values
    live = values("engine.decode.dispatch", "state_slots")
    kv = values("engine.decode.dispatch", "kv_tokens")
    hit = values("engine.decode.fetch", "experts_hit")
    cfg = ctx["config"]
    if not (runs and live and kv and hit) or "peak" not in ctx \
            or "kda_lower_bound" not in cfg \
            or not values("engine.decode.fetch", "experts_held"):
        return None
    size = 2 if cfg["serve"]["dtype"] == "bfloat16" else 4
    need = costs_linear_moe.decode_bytes(
        cfg, statistics.mean(live), statistics.mean(kv),
        statistics.mean(hit), size)
    return 100.0 * need / ctx["peak"]["hbm_bytes_per_s"] \
        / statistics.mean(runs)
