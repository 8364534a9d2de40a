"""The decode step's pass over the recurrent state against the memory
roofline: the live slots' ``[heads, head, state]`` float32 state read once
and written once in every layer (``costs_hybrid.state_step_bytes``) over
the HBM bandwidth, divided by the device time a step spends in the
operations whose first result carries the state's shape,
``f32[<slots>,<heads>,<head>,<state>]`` (as ``moe_paged_attn_roofline``
tells its kernel by shape): in the decode program one fusion a layer, which
decays the state, adds the new token's outer product and reads the output
out in the same pass.  A prefill's write of one slot's state carries the
same shape and is summed too: it can only lower the share.

``state_slots`` is what the engine says on its ``engine.decode.dispatch``
spans; means over the steps on both sides.  The program moves the state of
every slot, live or not (a free lane is rewritten as it was): with few
slots live the share is low by that, and says so.  No such operation or no
such span: nothing to read."""
import statistics

from benchmark import costs_hybrid, program_spans


def read(record, ctx):
    t = record.get("trace") or {}
    cfg = ctx["config"]
    if "mamba_d_state" not in cfg or "peak" not in ctx:
        return None
    shape = (f" f32[{cfg['serve']['max_slots']},{cfg['mamba_n_heads']},"
             f"{cfg['mamba_d_head']},{cfg['mamba_d_state']}]")
    spent = sum(s for name, s in (t.get("op_s") or {}).items()
                if name.endswith(shape))
    steps = sum(len(v) for name, v in (t.get("program_s") or {}).items()
                if name.endswith("llm_decode"))
    live = program_spans.arg_values("engine.decode.dispatch", "state_slots")
    if spent <= 0 or not steps or not live:
        return None
    need = costs_hybrid.state_step_bytes(cfg, statistics.mean(live))
    return 100.0 * need / ctx["peak"]["hbm_bytes_per_s"] / (spent / steps)
