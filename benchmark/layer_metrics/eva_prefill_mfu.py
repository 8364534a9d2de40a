"""The prefill programs of a decoder of EVA layers against the chip's bf16
peak: the operations the traced prefills needed (``costs_eva.prefill_flops``
of each ``engine.prefill`` span's real rows: projections, the SwiGLU, the
windows' causal half, the summaries' part, the pooling, the head for one
row; not the bucket's padding, not a pair a mask drops) over the peak,
divided by the summed device time of the ``*prefill*`` programs: the whole
prefill's share.  A configuration of another family, no traced prefill:
nothing to read."""
from benchmark import costs_eva, program_spans


def read(record, ctx):
    programs = (record.get("trace") or {}).get("program_s") or {}
    spent = sum(s for name, v in programs.items() if "prefill" in name
                for s in v)
    rows = program_spans.arg_values("engine.prefill", "prompt_tokens")
    cfg = ctx["config"]
    if spent <= 0 or not rows or "peak" not in ctx \
            or cfg.get("serve", {}).get("model_kind") != "eva_decoder":
        return None
    need = sum(costs_eva.prefill_flops(cfg, n) for n in rows)
    return 100.0 * need / ctx["peak"]["bf16_flops"] / spent
