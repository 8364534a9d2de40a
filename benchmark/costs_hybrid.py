"""Operations and bytes that a hybrid decoder requires (a state-space mixer
beside grouped-query attention in every block), computed from shapes alone,
with ``costs.py``'s rules: a multiply-add is two operations; nothing
recomputed is counted; causal attention at the half of the score matrix it
needs; the recurrence in its one-token form, not the chunked scan's extra
products; only live slots, only real prompt tokens and not the bucket's
padding.  A share built on these counts cannot pass 100% by the count's
doing.

What sets such a model apart in a decode step: the recurrent state's bytes
scale with the *slots* that are live (each reads and writes its whole state,
whatever its context), the K/V bytes with the *tokens* cached.

``cfg`` is the configuration file's published keys (``hidden_size``,
``head_dim``, ``num_attention_heads``, ``num_key_value_heads``,
``intermediate_size``, ``mamba_*``, ``num_hidden_layers``, ``vocab_size``).
"""
from __future__ import annotations


def kv_width(cfg: dict) -> int:
    """Columns of a page-pool row: every KV head's keys (or values).
    ``head_dim`` is a published key here, not ``hidden / heads``."""
    return cfg["head_dim"] * cfg["num_key_value_heads"]


def conv_dim(cfg: dict) -> int:
    """Channels of the mixer's causal convolution: [x | B | C]."""
    return (cfg["mamba_d_ssm"]
            + 2 * cfg["mamba_n_groups"] * cfg["mamba_d_state"])


def layer_params(cfg: dict) -> dict:
    """One block's parameters by part: ``attention`` (q, o: d x heads x
    head_dim; k, v: d x kv width), ``ffn`` (gate, up, down), ``mixer``
    (in_proj d x [z | x | B | C | dt], out_proj, the convolution's weight
    and bias, dt_bias, A_log, D, the gated norm's scale), ``norms``."""
    d = cfg["hidden_size"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    heads, d_ssm = cfg["mamba_n_heads"], cfg["mamba_d_ssm"]
    in_proj = d_ssm + conv_dim(cfg) + heads
    return {"attention": 2 * d * q + 2 * d * kv_width(cfg),
            "ffn": 3 * d * cfg["intermediate_size"],
            "mixer": (d * in_proj + d_ssm * d
                      + (cfg["mamba_d_conv"] + 1) * conv_dim(cfg)
                      + 3 * heads + d_ssm),
            "norms": 2 * d}


def param_counts(cfg: dict) -> dict:
    """Parameters by what a decode step does with them: ``embedding`` (one
    row a token: a look-up, not a stream) and ``streamed`` (read whole every
    step: every block, the final norm, the untied head)."""
    d = cfg["hidden_size"]
    return {"embedding": cfg["vocab_size"] * d,
            "streamed": (cfg["num_hidden_layers"]
                         * sum(layer_params(cfg).values())
                         + d + cfg["vocab_size"] * d)}


def state_bytes(cfg: dict, conv_itemsize: int = 2) -> dict:
    """What one slot holds in one layer besides K/V pages: the float32
    ``[heads, head, state]`` recurrent state and the convolution's last
    ``d_conv - 1`` input rows."""
    return {"ssm": 4 * cfg["mamba_n_heads"] * cfg["mamba_d_head"]
            * cfg["mamba_d_state"],
            "conv": conv_itemsize * (cfg["mamba_d_conv"] - 1) * conv_dim(cfg)}


def kv_read_bytes(cfg: dict, kv_tokens: float, itemsize: int = 2) -> float:
    """The keys and values of ``kv_tokens`` cached rows, in every layer."""
    return (kv_tokens * cfg["num_hidden_layers"] * 2 * kv_width(cfg)
            * itemsize)


def state_step_bytes(cfg: dict, live_slots: float) -> float:
    """The recurrent state of ``live_slots`` slots read once and written
    once, in every layer (the convolution's rows not counted: 0.7%)."""
    return (live_slots * cfg["num_hidden_layers"] * 2
            * state_bytes(cfg)["ssm"])


def hybrid_decode_bytes(cfg: dict, live_slots: float, kv_tokens: float,
                        itemsize: int = 2) -> float:
    """What one decode step has to move: every streamed weight once, the
    state and convolution rows of every live slot read and written in
    every layer, and the keys and values of the cached rows the step
    attends to."""
    per_slot = sum(state_bytes(cfg, itemsize).values())
    return (param_counts(cfg)["streamed"] * itemsize
            + live_slots * cfg["num_hidden_layers"] * 2 * per_slot
            + kv_read_bytes(cfg, kv_tokens, itemsize))


def hybrid_prefill_flops(cfg: dict, prompt_tokens: int) -> float:
    """One full prefill of ``prompt_tokens`` real tokens: for each token and
    layer every projection (attention, the mixer's two, the feed-forward's
    three), the convolution's taps, and the recurrence in its one-token form
    (per state element a decay, an injection and a read-out: three
    multiply-adds); QK^T and PV over the pairs a causal mask keeps (4 x
    heads x head_dim a pair, n (n + 1) / 2 pairs); the head for the one row
    that is sampled."""
    d, n = cfg["hidden_size"], prompt_tokens
    parts = layer_params(cfg)
    d_ssm = cfg["mamba_d_ssm"]
    in_proj = d_ssm + conv_dim(cfg) + cfg["mamba_n_heads"]
    matmul = parts["attention"] + parts["ffn"] + d * in_proj + d_ssm * d
    per_token = 2 * (matmul + cfg["mamba_d_conv"] * conv_dim(cfg)
                     + 3 * d_ssm * cfg["mamba_d_state"])
    pairs = n * (n + 1) / 2
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    return cfg["num_hidden_layers"] * (n * per_token + 4 * q * pairs) \
        + 2 * d * cfg["vocab_size"]


def memory_sum(cfg: dict, itemsize: int = 2) -> dict:
    """Bytes the serving configuration holds on the device before the
    programs' scratch: every parameter, the full page pool (slots x pages a
    slot + the scratch page) and every slot's state."""
    s = cfg["serve"]
    counts = param_counts(cfg)
    pages = s["max_slots"] * -(-s["max_ctx"] // s["page_size"]) + 1
    return {"weights": (counts["embedding"] + counts["streamed"]) * itemsize,
            "page_pool": (pages * cfg["num_hidden_layers"] * 2
                          * s["page_size"] * kv_width(cfg) * itemsize),
            "state": (s["max_slots"] * cfg["num_hidden_layers"]
                      * sum(state_bytes(cfg, itemsize).values()))}
