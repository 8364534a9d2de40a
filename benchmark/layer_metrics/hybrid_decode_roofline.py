"""A hybrid decoder's whole decode program against the memory roofline: what
the traced decode steps had to move (``costs_hybrid.hybrid_decode_bytes``:
every block, the final norm and the head once, the recurrent state and
convolution rows of the live slots read and written, the keys and values of
the cached rows) over the HBM bandwidth, divided by the device time of the
program named ``llm_decode`` in those steps.  This is the cell's share of
the whole step.

The engine says both counts itself, per step, on its
``engine.decode.dispatch`` span: ``state_slots`` (the slots whose state the
step advances) and ``kv_tokens``.  Means over the steps on both sides, so
that a step cut by an edge of the profile weighs on neither.  The embedding
table is a look-up of a row a slot and is not counted; a free slot's lane is
not counted though the program reads and rewrites its state.  A program
whose spans carry no ``state_slots`` (it holds no recurrent state, or it is
the parent of the PR that brought this) has nothing to read."""
import statistics

from benchmark import costs_hybrid, program_spans


def read(record, ctx):
    programs = (record.get("trace") or {}).get("program_s") or {}
    runs = [s for name, v in programs.items()
            if name.endswith("llm_decode") for s in v]
    live = program_spans.arg_values("engine.decode.dispatch", "state_slots")
    kv = program_spans.arg_values("engine.decode.dispatch", "kv_tokens")
    cfg = ctx["config"]
    if not (runs and live and kv) or "peak" not in ctx \
            or "mamba_d_state" not in cfg:
        return None
    size = 2 if cfg["serve"]["dtype"] == "bfloat16" else 4
    need = costs_hybrid.hybrid_decode_bytes(
        cfg, statistics.mean(live), statistics.mean(kv), size)
    return 100.0 * need / ctx["peak"]["hbm_bytes_per_s"] \
        / statistics.mean(runs)
