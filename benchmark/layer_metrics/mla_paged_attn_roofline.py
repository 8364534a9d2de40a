"""The latent form of the paged decode-attention kernel
(``ops/paged_attention.py::latent_paged_attention``: every query head
against ONE cached row a token, a page copied once) against its roofline:
the larger of the bytes of the latent rows a decode step attends to over the
HBM bandwidth (``costs_latent_moe.rows_bytes``: ``kv_lora_rank +
qk_rope_head_dim`` columns a row, each row counted ONCE whatever the program
stores or reads) and the absorbed form's operations over them over the bf16
peak (``costs_latent_moe.attend_flops``: heads x (the row as key + its first
``kv_lora_rank`` columns as value)), divided by the device time a step
spends in the kernel: the ``tpu_custom_call`` rows whose first result is
``f32[<slots>,<query rows>,<kv_lora_rank>]`` (the latent form's accumulator
is ``kv_lora_rank`` wide, the two-pool form's as wide as the pool: the shape
tells the two apart).  At the published widths the two bounds lie a factor
of two apart (121 FLOP/B against a ridge of ~240), so the share says how
near the memory roofline a kernel is that is not far from compute-bound.

``kv_tokens`` is what the engine says on its ``engine.decode.dispatch``
spans (live rows, not whole pages).  Means over the steps on both sides.  A
configuration of another family, a program without the kernel, or a run with
no profile, has nothing to read."""
import statistics

from benchmark import costs_latent_moe, program_spans


def read(record, ctx):
    t = record.get("trace") or {}
    cfg = ctx["config"]
    if cfg.get("serve", {}).get("model_kind") != "latent_moe" \
            or "peak" not in ctx:
        return None
    head = f"tpu_custom_call f32[{cfg['serve']['max_slots']},"
    tail = f",{cfg['kv_lora_rank']}]"
    spent = sum(s for name, s in (t.get("op_s") or {}).items()
                if name.startswith(head) and name.endswith(tail)
                and name.count(",") == 2)
    steps = sum(len(v) for name, v in (t.get("program_s") or {}).items()
                if name.endswith("llm_decode"))
    rows = program_spans.arg_values("engine.decode.dispatch", "kv_tokens")
    if spent <= 0 or not steps or not rows:
        return None
    size = 2 if cfg["serve"]["dtype"] == "bfloat16" else 4
    pairs = statistics.mean(rows) * costs_latent_moe.layers(cfg)["attn"]
    need = max(costs_latent_moe.rows_bytes(cfg, pairs, size)
               / ctx["peak"]["hbm_bytes_per_s"],
               costs_latent_moe.attend_flops(cfg, pairs)
               / ctx["peak"]["bf16_flops"])
    return 100.0 * need / (spent / steps)
