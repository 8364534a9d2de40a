"""The kernel that hands a decode step the pages of the chunks its tokens
close (``ops/eva.py::gather_pages``, ``eva_page_gather``) against the memory
roofline: a page a layer, K and V, for every chunk closed
(``costs_eva.page_bytes``, counted from the ``chunks_closed`` of the engine's
``engine.decode.dispatch`` spans) over the HBM bandwidth, divided by the
device time a step spends in the kernel: the ``tpu_custom_call`` rows whose
first result is ``<dtype>[<layers>,<slots>,<page>,<pool width>]``.  The
kernel writes a block for EVERY slot whether its token closes a chunk or not
(a slot closes one every ``chunk_size`` steps), so the share says how much
of the kernel's time the closed chunks needed: low by construction, and
the kernel's time a step beside it is what to watch.  A configuration of
another family, a program without the kernel, a run with no profile:
nothing to read."""
import statistics

from benchmark import costs_eva, program_spans


def read(record, ctx):
    t = record.get("trace") or {}
    cfg = ctx["config"]
    s = cfg.get("serve", {})
    if s.get("model_kind") != "eva_decoder" or "peak" not in ctx:
        return None
    shape = (f"[{cfg['num_hidden_layers']},{s['max_slots']},"
             f"{s['page_size']},{costs_eva.row_width(cfg)}]")
    spent = sum(sec for name, sec in (t.get("op_s") or {}).items()
                if name.startswith("tpu_custom_call ")
                and name.endswith(shape))
    steps = sum(len(v) for name, v in (t.get("program_s") or {}).items()
                if name.endswith("llm_decode"))
    closed = program_spans.arg_values("engine.decode.dispatch",
                                      "chunks_closed")
    if spent <= 0 or not steps or not closed:
        return None
    size = 2 if s["dtype"] == "bfloat16" else 4
    need = (statistics.mean(closed) * cfg["num_hidden_layers"]
            * costs_eva.page_bytes(cfg, size))
    return 100.0 * need / ctx["peak"]["hbm_bytes_per_s"] / (spent / steps)
