"""``ssm_state_roofline``'s sum under this family's keys: the live slots'
``[heads, head, state]`` float32 state read once and written once in every
mixer layer (``costs_hybrid_moe.state_step_bytes``; the pattern's ``M``
layers, not every layer) over the HBM bandwidth, divided by the device time
a decode step spends in the operations whose first result carries the
state's shape, ``f32[<slots>,<heads>,<head>,<state>]``.  A prefill's write
of one slot's state carries the same shape and is summed too: it can only
lower the share.  The program moves the state of every slot, live or not.
No such operation or no ``state_slots`` on the ``engine.decode.dispatch``
spans: nothing to read."""
import statistics

from benchmark import costs_hybrid_moe, program_spans


def read(record, ctx):
    t = record.get("trace") or {}
    cfg = ctx["config"]
    if "ssm_state_size" not in cfg or "peak" not in ctx:
        return None
    shape = (f" f32[{cfg['serve']['max_slots']},{cfg['mamba_num_heads']},"
             f"{cfg['mamba_head_dim']},{cfg['ssm_state_size']}]")
    spent = sum(s for name, s in (t.get("op_s") or {}).items()
                if name.endswith(shape))
    steps = sum(len(v) for name, v in (t.get("program_s") or {}).items()
                if name.endswith("llm_decode"))
    live = program_spans.arg_values("engine.decode.dispatch", "state_slots")
    if spent <= 0 or not steps or not live:
        return None
    need = costs_hybrid_moe.state_step_bytes(cfg, statistics.mean(live))
    return 100.0 * need / ctx["peak"]["hbm_bytes_per_s"] / (spent / steps)
