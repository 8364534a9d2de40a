"""The decode step's pass over the delta-rule state against the memory
roofline: the live slots' ``[heads, K, V]`` float32 state read once and
written once in every KDA layer (``costs_linear_moe.state_step_bytes``) over
the HBM bandwidth, divided by the device time a step spends in the
operations whose first result carries the state's shape,
``f32[<slots>,<heads>,<K>,<V>]``, whatever implements the pass (as
``ssm_state_roofline`` tells its operations by shape).  A prefill's write of
one slot's state carries the same shape and is summed too: it can only lower
the share.  The ``jax.numpy`` pass moves the state of every slot, live or
not, and may take several fusions a layer: the share says so.  No such
operation or no ``state_slots`` on the ``engine.decode.dispatch`` spans:
nothing to read."""
import statistics

from benchmark import costs_linear_moe, program_spans


def read(record, ctx):
    t = record.get("trace") or {}
    cfg = ctx["config"]
    if "kda_lower_bound" not in cfg or "peak" not in ctx:
        return None
    shape = (f" f32[{cfg['serve']['max_slots']},{cfg['num_attention_heads']},"
             f"{cfg['head_dim']},{cfg['head_dim']}]")
    spent = sum(s for name, s in (t.get("op_s") or {}).items()
                if name.endswith(shape))
    steps = sum(len(v) for name, v in (t.get("program_s") or {}).items()
                if name.endswith("llm_decode"))
    live = program_spans.arg_values("engine.decode.dispatch", "state_slots")
    if spent <= 0 or not steps or not live:
        return None
    need = costs_linear_moe.state_step_bytes(cfg, statistics.mean(live))
    return 100.0 * need / ctx["peak"]["hbm_bytes_per_s"] / (spent / steps)
