"""Operations and bytes that a decoder requires whose every layer is one
thing by a pattern (a state-space mixer ``M``, an attention ``*`` or a latent
expert layer ``E``) and which holds a share of each layer's experts,
computed from shapes alone, with ``costs.py``'s rules: a multiply-add is two
operations; nothing recomputed is counted; causal attention at the half of
the score matrix it needs; the recurrence in its one-token form; only live
slots, only real prompt tokens, only the held experts that a live row hit
and only the choices that landed here.  A share built on these counts cannot
pass 100% by the count's doing.

What sets such a model apart in a decode step: the expert weights' bytes
scale with the held experts *hit*, the recurrent state's with the *slots*
live (in ``M`` layers only), the K/V bytes with the *tokens* cached (in
``*`` layers only).

``cfg`` is the configuration file's keys: the published ones
(``hybrid_override_pattern``, ``hidden_size``, ``mamba_*``, ``n_groups``,
``ssm_state_size``, ``conv_kernel``, ``num_attention_heads``,
``num_key_value_heads``, ``head_dim``, ``moe_*``, ``num_experts_per_tok``,
``vocab_size``), ``n_routed_experts`` as the experts HELD and
``router_experts`` as the router's width.
"""
from __future__ import annotations

ROUTER_ITEMSIZE = 4  # the router and its bias are float32 leaves


def layers(cfg: dict) -> dict:
    """How many layers of each letter."""
    return {kind: cfg["hybrid_override_pattern"].count(kind)
            for kind in "ME*"}


def kv_width(cfg: dict) -> int:
    """Columns of a page-pool row: every KV head's keys (or values)."""
    return cfg["head_dim"] * cfg["num_key_value_heads"]


def d_ssm(cfg: dict) -> int:
    return cfg["mamba_num_heads"] * cfg["mamba_head_dim"]


def conv_dim(cfg: dict) -> int:
    """Channels of the mixer's causal convolution: [x | B | C]."""
    return d_ssm(cfg) + 2 * cfg["n_groups"] * cfg["ssm_state_size"]


def in_proj_dim(cfg: dict) -> int:
    """[z | x | B | C | dt]."""
    return d_ssm(cfg) + conv_dim(cfg) + cfg["mamba_num_heads"]


def expert_params(cfg: dict) -> int:
    """One routed expert: up and down in the latent width, no gate."""
    return 2 * cfg["moe_latent_size"] * cfg["moe_intermediate_size"]


def layer_params(cfg: dict) -> dict:
    """One layer's parameters by letter, its norm with it: ``M`` (in_proj,
    out_proj, the convolution's weight and bias, dt_bias, A_log, D, the
    gated norm's scale), ``*`` (q, o: d x heads x head_dim; k, v: d x kv
    width), ``E`` OUTSIDE its routed experts (``router``: d x router width
    and the bias, float32; the two latent projections; the shared
    expert's two matrices)."""
    d = cfg["hidden_size"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    heads = cfg["mamba_num_heads"]
    return {
        "M": (d * in_proj_dim(cfg) + d_ssm(cfg) * d
              + (cfg["conv_kernel"] + 1) * conv_dim(cfg) + 3 * heads
              + d_ssm(cfg) + d),
        "*": 2 * d * q + 2 * d * kv_width(cfg) + d,
        "E": {"router": (d + 1) * cfg["router_experts"],
              "rest": (2 * d * cfg["moe_latent_size"]
                       + 2 * d * cfg["moe_shared_expert_intermediate_size"]
                       + d)}}


def param_counts(cfg: dict) -> dict:
    """Parameters by what a decode step does with them: ``experts`` (the
    held ones; read only where hit), ``embedding`` (one row a token: a
    look-up, not a stream), ``router`` (float32, read whole) and
    ``streamed`` (read whole every step, in the weights' own dtype: every
    layer outside its routed experts and router, the final norm, the
    untied head)."""
    n, per = layers(cfg), layer_params(cfg)
    d = cfg["hidden_size"]
    return {"experts": n["E"] * cfg["n_routed_experts"] * expert_params(cfg),
            "embedding": cfg["vocab_size"] * d,
            "router": n["E"] * per["E"]["router"],
            "streamed": (n["M"] * per["M"] + n["*"] * per["*"]
                         + n["E"] * per["E"]["rest"] + d
                         + cfg["vocab_size"] * d)}


def streamed_bytes(cfg: dict, itemsize: int = 2) -> float:
    """The weights a decode step reads whatever was routed where."""
    counts = param_counts(cfg)
    return counts["streamed"] * itemsize + counts["router"] * ROUTER_ITEMSIZE


def state_bytes(cfg: dict, conv_itemsize: int = 2) -> dict:
    """What one slot holds in one ``M`` layer besides K/V pages: the float32
    ``[heads, head, state]`` recurrent state and the convolution's last
    ``conv_kernel - 1`` input rows."""
    return {"ssm": 4 * d_ssm(cfg) * cfg["ssm_state_size"],
            "conv": conv_itemsize * (cfg["conv_kernel"] - 1) * conv_dim(cfg)}


def kv_read_bytes(cfg: dict, kv_tokens: float, itemsize: int = 2) -> float:
    """The keys and values of ``kv_tokens`` cached rows, in every ``*``
    layer."""
    return kv_tokens * layers(cfg)["*"] * 2 * kv_width(cfg) * itemsize


def state_step_bytes(cfg: dict, live_slots: float) -> float:
    """The recurrent state of ``live_slots`` slots read once and written
    once, in every ``M`` layer (the convolution's rows not counted)."""
    return live_slots * layers(cfg)["M"] * 2 * state_bytes(cfg)["ssm"]


def routed_decode_bytes(cfg: dict, experts_hit: float,
                        itemsize: int = 2) -> float:
    """What the routed kernel of a decode step has to read: the two
    matrices of every held expert that got a row (``experts_hit``: summed
    over the ``E`` layers).  The rows' latent inputs and outputs are
    kilobytes and are not counted."""
    return experts_hit * expert_params(cfg) * itemsize


def decode_bytes(cfg: dict, live_slots: float, kv_tokens: float,
                 experts_hit: float, itemsize: int = 2) -> float:
    """What one decode step has to move: every weight outside the routed
    experts once, the held experts that were hit, the state and
    convolution rows of every live slot read and written in every ``M``
    layer, and the keys and values of the cached rows the step attends
    to."""
    per_slot = sum(state_bytes(cfg, itemsize).values())
    return (streamed_bytes(cfg, itemsize)
            + routed_decode_bytes(cfg, experts_hit, itemsize)
            + live_slots * layers(cfg)["M"] * 2 * per_slot
            + kv_read_bytes(cfg, kv_tokens, itemsize))


def routed_flops(cfg: dict, local_choices: float) -> float:
    """The routed experts' products for ``local_choices`` (row, held
    expert) pairs: two matrices a pair."""
    return 2.0 * local_choices * expert_params(cfg)


def prefill_flops(cfg: dict, prompt_tokens: int,
                  local_choice_share: float) -> float:
    """One full prefill of ``prompt_tokens`` real tokens: for each token
    every ``M`` layer's two projections, the convolution's taps and the
    recurrence in its one-token form (three multiply-adds a state element);
    every ``*`` layer's projections and QK^T and PV over the pairs a causal
    mask keeps; every ``E`` layer's router, latent projections, shared
    expert and the routed experts of the choices that landed here
    (``local_choice_share`` of ``num_experts_per_tok``); the head for the
    one row that is sampled."""
    d, n, count = cfg["hidden_size"], prompt_tokens, layers(cfg)
    per = layer_params(cfg)
    mixer = 2 * (d * in_proj_dim(cfg) + d_ssm(cfg) * d
                 + cfg["conv_kernel"] * conv_dim(cfg)
                 + 3 * d_ssm(cfg) * cfg["ssm_state_size"])
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    attn = 2 * (per["*"] - d)
    experts = 2 * (d * cfg["router_experts"] + per["E"]["rest"] - d) \
        + routed_flops(cfg, cfg["num_experts_per_tok"] * local_choice_share)
    pairs = n * (n + 1) / 2
    return (n * (count["M"] * mixer + count["*"] * attn
                 + count["E"] * experts)
            + count["*"] * 4 * q * pairs + 2 * d * cfg["vocab_size"])


def memory_sum(cfg: dict, itemsize: int = 2) -> dict:
    """Bytes the serving configuration holds on the device before the
    programs' scratch: every parameter, the full page pool (slots x pages a
    slot + the scratch page, ``*`` layers only) and every slot's state
    (``M`` layers only)."""
    s = cfg["serve"]
    counts = param_counts(cfg)
    pages = s["max_slots"] * -(-s["max_ctx"] // s["page_size"]) + 1
    n = layers(cfg)
    return {"weights": ((counts["embedding"] + counts["streamed"]
                         + counts["experts"]) * itemsize
                        + counts["router"] * ROUTER_ITEMSIZE),
            "page_pool": (pages * n["*"] * 2 * s["page_size"]
                          * kv_width(cfg) * itemsize),
            "state": (s["max_slots"] * n["M"]
                      * sum(state_bytes(cfg, itemsize).values()))}
