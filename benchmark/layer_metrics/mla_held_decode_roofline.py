"""The routed-expert kernel (``moe_hit`` over the held experts) in a decode
step of the latent-attention decoder against the memory roofline: the three
matrices of every held expert that a live row hit
(``costs_latent_moe.routed_decode_bytes`` of the step's ``experts_hit``,
summed over the expert layers) over the HBM bandwidth, divided by the device
time a step spends in the kernel: the ``tpu_custom_call`` rows whose first
result is ``f32[<slots>,<hidden>]`` (``swiglu_held_decode_roofline``'s rule,
which reads Ling's configuration alone; the latent paged kernel's result has
three dimensions, and a prefill's call of this kernel has its bucket's rows,
never the slots').

``experts_hit`` is what the engine says on its ``engine.decode.fetch``
spans; means over the steps on both sides.  A configuration of another
family, a program without the kernel, or a run with no profile, has nothing
to read."""
import statistics

from benchmark import costs_latent_moe, program_spans


def read(record, ctx):
    t = record.get("trace") or {}
    cfg = ctx["config"]
    if cfg.get("serve", {}).get("model_kind") != "latent_moe" \
            or "peak" not in ctx:
        return None
    slots = -(-cfg["serve"]["max_slots"] // 16) * 16  # the kernel's padding
    kernel = f"tpu_custom_call f32[{slots},{cfg['hidden_size']}]"
    spent = (t.get("op_s") or {}).get(kernel, 0.0)
    steps = sum(len(v) for name, v in (t.get("program_s") or {}).items()
                if name.endswith("llm_decode"))
    hit = program_spans.arg_values("engine.decode.fetch", "experts_hit")
    if spent <= 0 or not steps or not hit:
        return None
    size = 2 if cfg["serve"]["dtype"] == "bfloat16" else 4
    need = costs_latent_moe.routed_decode_bytes(cfg, statistics.mean(hit),
                                                size)
    return 100.0 * need / ctx["peak"]["hbm_bytes_per_s"] / (spent / steps)
