"""Operations and bytes that a decoder requires whose mixers are gated
delta-rule layers (KDA) with one latent-attention layer (MLA) a group, whose
first layers are dense and whose other layers hold a share of group-routed
SwiGLU experts, computed from shapes alone, with ``costs.py``'s rules: a
multiply-add is two operations; nothing recomputed is counted; causal
attention at the half of the score matrix it needs; the recurrence in its
one-token form; only live slots, only real prompt tokens, only the held
experts that a live row hit and only the choices that landed here.  A share
built on these counts cannot pass 100% by the count's doing.

What sets such a model apart in a decode step: the expert weights' bytes
scale with the held experts *hit*, the recurrent state's with the *slots*
live (in KDA layers only), the latent rows' with the *tokens* cached (in
MLA layers only, one row of ``kv_lora_rank + qk_rope_head_dim`` a token
whatever the number of heads).

``cfg`` is the configuration file's keys: the published ones, with
``num_experts`` as the experts HELD and ``router_experts`` as the router's
width; ``head_dim`` is the KDA head's.
"""
from __future__ import annotations

ROUTER_ITEMSIZE = 4  # the router and its bias are float32 leaves


def layers(cfg: dict) -> dict:
    """How many layers have each part: ``mla`` (the last of each group of
    ``layer_group_size``), ``kda`` (the others), ``dense`` (the first
    ``first_k_dense_replace``), ``moe`` (the others)."""
    n = cfg["num_hidden_layers"]
    mla = n // cfg["layer_group_size"]
    dense = min(cfg["first_k_dense_replace"], n)
    return {"kda": n - mla, "mla": mla, "dense": dense, "moe": n - dense}


def kda_dim(cfg: dict) -> int:
    return cfg["num_attention_heads"] * cfg["head_dim"]


def latent_width(cfg: dict) -> int:
    """Columns of a cached row that mean something: ``[c | rope(k_r)]``."""
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def expert_params(cfg: dict) -> int:
    """One routed expert: gate, up and down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def part_params(cfg: dict) -> dict:
    """Parameters of one layer's parts.  ``kda``: q | k | v, the gate's
    full projection, beta and the output gate (a column a head), out, the
    convolution, ``A_log``, ``dt_bias`` and the head norm's scale.  ``mla``:
    q (heads x (nope + rope)), the latent and rope key, k_nope | v from the
    latent, out, the latent's and the query's norm.  ``dense``: three
    matrices.  ``moe``: OUTSIDE its routed experts, ``router`` (d x router
    width and the bias, float32) and ``shared`` (three matrices).
    ``norms``: the block's two."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    kd, hd = kda_dim(cfg), cfg["head_dim"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return {
        "kda": (d * 3 * kd + d * kd + 2 * d * h + kd * d
                + cfg["short_conv_kernel_size"] * 3 * kd + h + h * hd + hd),
        "mla": (d * h * qk + d * latent_width(cfg) + cfg["kv_lora_rank"] * h
                * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"])
                + h * cfg["v_head_dim"] * d + cfg["kv_lora_rank"] + qk),
        "dense": 3 * d * cfg["intermediate_size"],
        "router": (d + 1) * cfg["router_experts"],
        "shared": 3 * d * cfg["moe_shared_expert_intermediate_size"],
        "norms": 2 * d}


def param_counts(cfg: dict) -> dict:
    """Parameters by what a decode step does with them: ``experts`` (the
    held ones; read only where hit), ``embedding`` (one row a token: a
    look-up, not a stream), ``router`` (float32, read whole) and
    ``streamed`` (read whole every step: every layer outside its routed
    experts and router, the final norm, the untied head)."""
    n, per, d = layers(cfg), part_params(cfg), cfg["hidden_size"]
    return {"experts": n["moe"] * cfg["num_experts"] * expert_params(cfg),
            "embedding": cfg["vocab_size"] * d,
            "router": n["moe"] * per["router"],
            "streamed": (n["kda"] * per["kda"] + n["mla"] * per["mla"]
                         + n["dense"] * per["dense"]
                         + n["moe"] * per["shared"]
                         + cfg["num_hidden_layers"] * per["norms"] + d
                         + cfg["vocab_size"] * d)}


def streamed_bytes(cfg: dict, itemsize: int = 2) -> float:
    """The weights a decode step reads whatever was routed where."""
    counts = param_counts(cfg)
    return counts["streamed"] * itemsize + counts["router"] * ROUTER_ITEMSIZE


def state_bytes(cfg: dict, conv_itemsize: int = 2) -> dict:
    """What one slot holds in one KDA layer: the float32 ``[heads, K, V]``
    state and the convolution's last ``kernel - 1`` input rows."""
    return {"S": 4 * kda_dim(cfg) * cfg["head_dim"],
            "conv": conv_itemsize * (cfg["short_conv_kernel_size"] - 1)
            * 3 * kda_dim(cfg)}


def kv_read_bytes(cfg: dict, kv_tokens: float, itemsize: int = 2) -> float:
    """The latent rows of ``kv_tokens`` cached tokens as the absorbed
    attention has to read them today, once as K and once as V, in every MLA
    layer; the padding to whole lane registers is not counted."""
    return kv_tokens * layers(cfg)["mla"] * 2 * latent_width(cfg) * itemsize


def state_step_bytes(cfg: dict, live_slots: float) -> float:
    """The recurrent state of ``live_slots`` slots read once and written
    once, in every KDA layer (the convolution's rows not counted)."""
    return live_slots * layers(cfg)["kda"] * 2 * state_bytes(cfg)["S"]


def routed_decode_bytes(cfg: dict, experts_hit: float,
                        itemsize: int = 2) -> float:
    """What the routed kernel of a decode step has to read: the three
    matrices of every held expert that got a row (``experts_hit``: summed
    over the expert layers)."""
    return experts_hit * expert_params(cfg) * itemsize


def decode_bytes(cfg: dict, live_slots: float, kv_tokens: float,
                 experts_hit: float, itemsize: int = 2) -> float:
    """What one decode step has to move: every weight outside the routed
    experts once, the held experts that were hit, the state and convolution
    rows of every live slot read and written in every KDA layer, and the
    latent rows of the cached tokens the step attends to."""
    per_slot = sum(state_bytes(cfg, itemsize).values())
    return (streamed_bytes(cfg, itemsize)
            + routed_decode_bytes(cfg, experts_hit, itemsize)
            + live_slots * layers(cfg)["kda"] * 2 * per_slot
            + kv_read_bytes(cfg, kv_tokens, itemsize))


def routed_flops(cfg: dict, local_choices: float) -> float:
    """The routed experts' products for ``local_choices`` (row, held
    expert) pairs: three matrices a pair."""
    return 2.0 * local_choices * expert_params(cfg)


def prefill_flops(cfg: dict, prompt_tokens: int,
                  local_choice_share: float) -> float:
    """One full prefill of ``prompt_tokens`` real tokens: for each token
    every KDA layer's projections, the convolution's taps and the
    recurrence in its one-token form (the decay, S^T k, the rank-one
    update and S^T q: four multiply-adds a state element); the MLA
    layer's projections, the keys and values expanded from the latent, and
    QK^T and PV over the pairs a causal mask keeps; the dense layer; every
    expert layer's router, shared expert and the routed experts of the
    choices that landed here (``local_choice_share`` of
    ``num_experts_per_tok``); the head for the one row that is sampled."""
    d, n, count = cfg["hidden_size"], prompt_tokens, layers(cfg)
    per, h = part_params(cfg), cfg["num_attention_heads"]
    kd = kda_dim(cfg)
    kda = 2 * (d * 3 * kd + d * kd + 2 * d * h + kd * d
               + cfg["short_conv_kernel_size"] * 3 * kd
               + 4 * kd * cfg["head_dim"])
    mla = 2 * (per["mla"] - cfg["kv_lora_rank"]
               - cfg["qk_nope_head_dim"] - cfg["qk_rope_head_dim"])
    moe = 2 * (d * cfg["router_experts"] + per["shared"]) + routed_flops(
        cfg, cfg["num_experts_per_tok"] * local_choice_share)
    pairs = n * (n + 1) / 2
    attend = 2 * h * (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
                      + cfg["v_head_dim"])
    return (n * (count["kda"] * kda + count["mla"] * mla
                 + count["dense"] * 2 * per["dense"] + count["moe"] * moe)
            + count["mla"] * attend * pairs + 2 * d * cfg["vocab_size"])


def memory_sum(cfg: dict, itemsize: int = 2) -> dict:
    """Bytes the serving configuration holds on the device before the
    programs' scratch: every parameter, the full page pool (slots x pages a
    slot + the scratch page, MLA layers only, the row padded to whole
    128-lane registers and stored in both pools) and every slot's state
    (KDA layers only)."""
    s = cfg["serve"]
    counts = param_counts(cfg)
    pages = s["max_slots"] * -(-s["max_ctx"] // s["page_size"]) + 1
    n = layers(cfg)
    kda_f32 = n["kda"] * (cfg["num_attention_heads"]
                          * (1 + cfg["head_dim"]))
    row = -(-latent_width(cfg) // 128) * 128
    return {"weights": ((counts["embedding"] + counts["streamed"]
                         + counts["experts"]) * itemsize
                        + kda_f32 * (4 - itemsize)
                        + counts["router"] * ROUTER_ITEMSIZE),
            "page_pool": (pages * n["mla"] * 2 * s["page_size"] * row
                          * itemsize),
            "state": (s["max_slots"] * n["kda"]
                      * sum(state_bytes(cfg, itemsize).values()))}
