"""The whole decode program of a decoder of latent attention over the whole
cache that holds a share of its experts, against the memory roofline: what
the traced decode steps had to move (``costs_latent_moe.decode_bytes``:
every weight outside the routed experts once, the three matrices of the held
experts that a live row hit, the latent rows of the live slots' cached
tokens, every layer's, each ONCE at the columns that mean something) over
the HBM bandwidth, divided by the device time of the program named
``llm_decode`` in those steps.  This is the cell's share of the whole step.

The engine says the two counts itself, per step: ``kv_tokens`` on its
``engine.decode.dispatch`` span and ``experts_hit`` on
``engine.decode.fetch``.  Means over the steps on both sides.  The embedding
table is a look-up and is not counted.  A configuration of another family,
or a run with no profile, has nothing to read."""
import statistics

from benchmark import costs_latent_moe, program_spans


def read(record, ctx):
    programs = (record.get("trace") or {}).get("program_s") or {}
    runs = [s for name, v in programs.items()
            if name.endswith("llm_decode") for s in v]
    values = program_spans.arg_values
    rows = values("engine.decode.dispatch", "kv_tokens")
    hit = values("engine.decode.fetch", "experts_hit")
    cfg = ctx["config"]
    if not (runs and rows and hit) or "peak" not in ctx \
            or cfg.get("serve", {}).get("model_kind") != "latent_moe":
        return None
    size = 2 if cfg["serve"]["dtype"] == "bfloat16" else 4
    need = costs_latent_moe.decode_bytes(cfg, statistics.mean(rows),
                                         statistics.mean(hit), size)
    return 100.0 * need / ctx["peak"]["hbm_bytes_per_s"] \
        / statistics.mean(runs)
