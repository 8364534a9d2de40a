"""The prefill programs of a decoder of latent attention over the whole
context against the chip's bf16 peak: the operations the traced prefills
needed (``costs_latent_moe.prefill_flops`` of each ``engine.prefill`` span's
real rows: projections, the expanded attention over the causal pairs, the
dense layer, the routers, the shared experts and the routed experts of the
choices that landed here, the head for one row; not the bucket's padding,
not the pairs above the diagonal, not a held expert's products for rows that
did not choose it) over the peak, divided by the summed device time of the
``*prefill*`` programs: the whole prefill's share.

A prefill does not count where its choices landed, so the share of a token's
``num_experts_per_tok`` choices counted is the one the traced decode steps
read (``local_choices`` over ``choices`` on ``engine.decode.fetch``: the
same router, the same traffic's tokens).  A configuration of another family,
no traced prefill: nothing to read."""
from benchmark import costs_latent_moe, program_spans


def read(record, ctx):
    programs = (record.get("trace") or {}).get("program_s") or {}
    spent = sum(s for name, v in programs.items() if "prefill" in name
                for s in v)
    values = program_spans.arg_values
    rows = values("engine.prefill", "prompt_tokens")
    landed = sum(values("engine.decode.fetch", "local_choices"))
    choices = sum(values("engine.decode.fetch", "choices"))
    cfg = ctx["config"]
    if spent <= 0 or not rows or not choices or "peak" not in ctx \
            or cfg.get("serve", {}).get("model_kind") != "latent_moe":
        return None
    need = sum(costs_latent_moe.prefill_flops(cfg, n, landed / choices)
               for n in rows)
    return 100.0 * need / ctx["peak"]["bf16_flops"] / spent
