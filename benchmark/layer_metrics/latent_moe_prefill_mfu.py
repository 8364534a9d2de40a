"""The routed experts' products in prefill against the chip's bf16 peak: the
operations of the choices that landed on a held expert
(``costs_hybrid_moe.routed_flops``: two matrices a choice, in every expert
layer) over the peak, divided by the device time of the kernels that ran
them: up to ``ops/moe.py``'s ``DENSE_MAX_ROWS`` rows a bucket the kernel
``moe_hit_relu2``, ``tpu_custom_call f32[<bucket>,<latent width>]``; above,
the compiler's grouped matmuls for ``lax.ragged_dot`` over the bucket's
``rows x num_experts_per_tok`` choices, ``tpu_custom_call f32[<choices>,
<expert width>]`` (up) and ``f32[<choices>,<latent width>]`` (down).

A prefill does not count where its choices landed, so the count is each
traced prefill's real rows (``engine.prefill`` spans' ``prompt_tokens``)
times ``num_experts_per_tok`` times the share of choices that landed here
in the traced decode steps (``local_choices`` over ``choices`` on
``engine.decode.fetch``: the same router, the same traffic's tokens).  Low
by nature: the kernel multiplies every row of a bucket by every expert that
any row hit and keeps a row's own few, and the grouped form sorts every
choice, landed here or not.  No such kernel call or no such span: nothing
to read."""
from benchmark import costs_hybrid_moe, program_spans


def read(record, ctx):
    t = record.get("trace") or {}
    cfg = ctx["config"]
    if "router_experts" not in cfg or "peak" not in ctx:
        return None
    try:
        from ray_tpu.ops.moe import DENSE_MAX_ROWS
    except ImportError:
        return None
    values = program_spans.arg_values
    landed = sum(values("engine.decode.fetch", "local_choices"))
    choices = sum(values("engine.decode.fetch", "choices"))
    sizes = [(a["bucket"], a["prompt_tokens"])
             for a in ((s.get("args") or {})
                       for s in program_spans.spans("engine.prefill"))
             if "bucket" in a and "prompt_tokens" in a]
    k, lat = cfg["num_experts_per_tok"], cfg["moe_latent_size"]
    ops = t.get("op_s") or {}
    spent = 0.0
    for bucket in {b for b, _ in sizes}:
        if bucket <= DENSE_MAX_ROWS:
            shapes = [(bucket, lat)]
        else:
            shapes = [(bucket * k, cfg["moe_intermediate_size"]),
                      (bucket * k, lat)]
        spent += sum(ops.get(f"tpu_custom_call f32[{rows},{cols}]", 0.0)
                     for rows, cols in shapes)
    if spent <= 0 or not choices:
        return None
    layers = costs_hybrid_moe.layers(cfg)["E"]
    need = sum(costs_hybrid_moe.routed_flops(
        cfg, layers * rows * k * landed / choices) for _, rows in sizes)
    return 100.0 * need / ctx["peak"]["bf16_flops"] / spent
