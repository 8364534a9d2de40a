"""The paged decode-attention kernel in a cell of EVA layers against its
roofline: the larger of the bytes of the rows a decode step's live slots
must read over the HBM bandwidth (``costs_eva.rows_bytes``: the summary rows
of the windows before and the open window's exact rows, every layer's, K and
V each ONCE) and the products over them over the bf16 peak
(``costs_eva.attend_flops``: every head against its own columns), divided by
the device time a step spends in the kernel: the ``tpu_custom_call`` rows
whose first result is ``f32[<slots>,<query rows>,<pool width>]`` (the paged
kernel's accumulator; this family's other kernel, the page gather, has a
result of four dimensions).

The rows are counted from the step's counters, whatever implements the read:
``kv_tokens`` on the engine's ``engine.decode.dispatch`` spans is ``128 * (n
// 2048) + n % 2048`` summed over the live slots (live rows, not whole
pages, so the count cannot carry the share past 100%).  Means over the steps
on both sides.  A configuration of another family, a program without the
kernel, or a run with no profile, has nothing to read."""
import statistics

from benchmark import costs_eva, program_spans


def read(record, ctx):
    t = record.get("trace") or {}
    cfg = ctx["config"]
    if cfg.get("serve", {}).get("model_kind") != "eva_decoder" \
            or "peak" not in ctx:
        return None
    head = f"tpu_custom_call f32[{cfg['serve']['max_slots']},"
    tail = f",{costs_eva.row_width(cfg)}]"
    spent = sum(s for name, s in (t.get("op_s") or {}).items()
                if name.startswith(head) and name.endswith(tail)
                and name.count(",") == 2)
    steps = sum(len(v) for name, v in (t.get("program_s") or {}).items()
                if name.endswith("llm_decode"))
    rows = program_spans.arg_values("engine.decode.dispatch", "kv_tokens")
    if spent <= 0 or not steps or not rows:
        return None
    size = 2 if cfg["serve"]["dtype"] == "bfloat16" else 4
    pairs = statistics.mean(rows) * cfg["num_hidden_layers"]
    need = max(costs_eva.rows_bytes(cfg, pairs, size)
               / ctx["peak"]["hbm_bytes_per_s"],
               costs_eva.attend_flops(cfg, pairs) / ctx["peak"]["bf16_flops"])
    return 100.0 * need / (spent / steps)
