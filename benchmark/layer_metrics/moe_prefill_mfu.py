"""A routed model's prefill programs against the chip's bf16 peak: the
operations the traced prefills needed (``costs_moe.moe_prefill_flops`` of
each ``engine.prefill`` span's real ``prompt_tokens`` and ``cached_tokens``:
eight experts a token, not the bucket's padding, not the experts a token
did not choose) over the peak, divided by the summed device time of the
``*prefill*`` programs.  A program whose spans carry no such arguments has
nothing to read."""
from benchmark import costs_moe, program_spans


def read(record, ctx):
    programs = (record.get("trace") or {}).get("program_s") or {}
    spent = sum(s for name, v in programs.items() if "prefill" in name
                for s in v)
    sizes = [(a["prompt_tokens"], a.get("cached_tokens", 0))
             for a in ((s.get("args") or {})
                       for s in program_spans.spans("engine.prefill"))
             if "prompt_tokens" in a]
    if spent <= 0 or not sizes or "peak" not in ctx \
            or "num_experts" not in ctx["config"]:
        return None
    need = sum(costs_moe.moe_prefill_flops(ctx["config"], p, c)
               for p, c in sizes)
    return 100.0 * need / ctx["peak"]["bf16_flops"] / spent
