"""Operations and bytes that a decoder requires whose every layer is latent
attention (MLA, no query compression) over the WHOLE cache, whose first
layers are dense and whose other layers hold a share of a sigmoid router's
SwiGLU experts, computed from shapes alone, with ``costs.py``'s rules: a
multiply-add is two operations; nothing recomputed is counted; attention at
the pairs a causal mask keeps; only live slots, only real prompt tokens,
only the held experts that a live row hit and only the choices that landed
here.  A share built on these counts cannot pass 100% by the count's doing.

What sets such a model apart in a decode step: the attention presents
``num_attention_heads`` query heads to ONE shared row a cached token, so its
bytes scale with the tokens cached (``kv_lora_rank + qk_rope_head_dim``
columns a token a layer, counted ONCE whatever the program stores or
reads: a row's padding to whole lane registers is the implementation's, a
second copy of the row as V would be too) and its operations with heads x
tokens x (the row as key + its first ``kv_lora_rank`` columns as value): at
the published widths 2 x 64 x (576 + 512) = 139 kFLOP for 1,152 B, 121
FLOP/B, half the v5e's ridge.  The expert weights' bytes scale with the held
experts *hit*.

``cfg`` is the configuration file's keys: the published ones, with
``num_experts`` as the experts HELD and ``router_experts`` as the router's
width.
"""
from __future__ import annotations

ROUTER_ITEMSIZE = 4  # the router and its bias are float32 leaves
LANES = 128


def layers(cfg: dict) -> dict:
    """How many layers have each part: ``attn`` (all), ``dense`` (the first
    ``first_k_dense_replace``), ``moe`` (the others)."""
    n = cfg["num_hidden_layers"]
    dense = min(cfg["first_k_dense_replace"], n)
    return {"attn": n, "dense": dense, "moe": n - dense}


def latent_width(cfg: dict) -> int:
    """Columns of a cached row that mean something: ``[c | rope(k_r)]``."""
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def stored_width(cfg: dict) -> int:
    """Columns of a pool row as stored: the latent row's rounded up to
    whole lane registers."""
    return -(-latent_width(cfg) // LANES) * LANES


def expert_params(cfg: dict) -> int:
    """One routed expert: gate, up and down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def part_params(cfg: dict) -> dict:
    """Parameters of one layer's parts.  ``attn``: the query's projection,
    the latent and rope key, k_nope | v from the latent, out, the query's
    and the latent's norms.  ``dense``: three matrices.  ``moe``: OUTSIDE
    its routed experts, ``router`` (d x router width and the bias, float32)
    and ``shared`` (three matrices).  ``norms``: the block's two."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    r = cfg["kv_lora_rank"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return {
        "attn": (d * h * qk + d * latent_width(cfg)
                 + r * h * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"])
                 + h * cfg["v_head_dim"] * d + qk + r),
        "dense": 3 * d * cfg["intermediate_size"],
        "router": (d + 1) * cfg["router_experts"],
        "shared": 3 * d * cfg["num_shared_experts"]
        * cfg["moe_intermediate_size"],
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
            "streamed": (n["attn"] * (per["attn"] + per["norms"])
                         + n["dense"] * per["dense"]
                         + n["moe"] * per["shared"] + d
                         + cfg["vocab_size"] * d)}


def streamed_bytes(cfg: dict, itemsize: int = 2) -> float:
    """The weights a decode step reads whatever was routed where."""
    counts = param_counts(cfg)
    return counts["streamed"] * itemsize + counts["router"] * ROUTER_ITEMSIZE


def rows_bytes(cfg: dict, kv_rows: float, itemsize: int = 2) -> float:
    """``kv_rows`` (cached token, layer) pairs' latent rows, each counted
    ONCE at the columns that mean something: the work, not what the program
    stores (640 columns) or how often it reads them."""
    return kv_rows * latent_width(cfg) * itemsize


def attend_flops(cfg: dict, kv_rows: float) -> float:
    """The absorbed form's products over ``kv_rows`` (cached token, layer)
    pairs: every head's query against the row (QK^T over ``kv_lora_rank +
    qk_rope_head_dim`` columns) and the probabilities against its first
    ``kv_lora_rank`` columns."""
    return 2.0 * kv_rows * cfg["num_attention_heads"] * (
        latent_width(cfg) + cfg["kv_lora_rank"])


def routed_decode_bytes(cfg: dict, experts_hit: float,
                        itemsize: int = 2) -> float:
    """The three matrices of every held expert that got a row
    (``experts_hit``: summed over the expert layers)."""
    return experts_hit * expert_params(cfg) * itemsize


def decode_bytes(cfg: dict, kv_tokens: float, experts_hit: float,
                 itemsize: int = 2) -> float:
    """What one decode step has to move: every weight outside the routed
    experts once, the held experts that were hit, and the latent rows of
    the ``kv_tokens`` cached tokens of the live slots, every layer's, each
    once."""
    return (streamed_bytes(cfg, itemsize)
            + routed_decode_bytes(cfg, experts_hit, itemsize)
            + rows_bytes(cfg, kv_tokens * layers(cfg)["attn"], itemsize))


def routed_flops(cfg: dict, local_choices: float) -> float:
    """The routed experts' products for ``local_choices`` (row, held
    expert) pairs: three matrices a pair."""
    return 2.0 * local_choices * expert_params(cfg)


def prefill_flops(cfg: dict, prompt_tokens: int,
                  local_choice_share: float) -> float:
    """One full prefill of ``prompt_tokens`` real tokens: for each token
    the attention's projections (the keys and values expanded from the
    latent among them), the dense layer, every expert layer's router,
    shared expert and the routed experts of the choices that landed here
    (``local_choice_share`` of ``num_experts_per_tok``); QK^T and PV in the
    expanded form over the pairs a causal mask keeps; the head for the one
    row that is sampled.  Not the bucket's padding, not the pairs above the
    diagonal, no sorting."""
    d, n, count = cfg["hidden_size"], prompt_tokens, layers(cfg)
    per, h = part_params(cfg), cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    attn = 2 * (per["attn"] - qk - cfg["kv_lora_rank"])
    moe = 2 * (d * cfg["router_experts"] + per["shared"]) + routed_flops(
        cfg, cfg["num_experts_per_tok"] * local_choice_share)
    attend = 2 * h * (qk + cfg["v_head_dim"])
    return (n * (count["attn"] * attn + count["dense"] * 2 * per["dense"]
                 + count["moe"] * moe)
            + count["attn"] * attend * n * (n + 1) / 2
            + 2 * d * cfg["vocab_size"])


def memory_sum(cfg: dict, itemsize: int = 2) -> dict:
    """Bytes the serving configuration holds on the device before the
    programs' scratch: every parameter and the full page pool (slots x
    pages a slot + the scratch page, every layer, a row as stored, in ONE
    pool)."""
    s = cfg["serve"]
    counts = param_counts(cfg)
    pages = s["max_slots"] * -(-s["max_ctx"] // s["page_size"]) + 1
    return {"weights": ((counts["embedding"] + counts["streamed"]
                         + counts["experts"]) * itemsize
                        + counts["router"] * ROUTER_ITEMSIZE),
            "page_pool": (pages * layers(cfg)["attn"] * s["page_size"]
                          * stored_width(cfg) * itemsize)}
