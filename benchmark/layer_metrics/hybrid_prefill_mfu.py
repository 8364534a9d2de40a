"""A hybrid decoder's prefill programs against the chip's bf16 peak: the
operations the traced prefills needed (``costs_hybrid.hybrid_prefill_flops``
of each ``engine.prefill`` span's real rows: projections, feed-forward,
causal attention pairs, the recurrence, the head for one row; not the
bucket's padding, not the chunked scan's extra products) over the peak,
divided by the summed device time of the ``*prefill*`` programs.  The spans
of an engine that holds recurrent state say ``scanned_rows``; where none
does there is nothing to read."""
from benchmark import costs_hybrid, program_spans


def read(record, ctx):
    programs = (record.get("trace") or {}).get("program_s") or {}
    spent = sum(s for name, v in programs.items() if "prefill" in name
                for s in v)
    rows = program_spans.arg_values("engine.prefill", "scanned_rows")
    if spent <= 0 or not rows or "peak" not in ctx \
            or "mamba_d_state" not in ctx["config"]:
        return None
    need = sum(costs_hybrid.hybrid_prefill_flops(ctx["config"], n)
               for n in rows)
    return 100.0 * need / ctx["peak"]["bf16_flops"] / spent
