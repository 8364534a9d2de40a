"""Operations and bytes that a decoder requires whose every layer is latent
attention (MLA, compressed query) under a learned selection of the rows it
attends to (an indexer's top-k), whose first layers are dense and whose
other layers hold a share of a sigmoid router's SwiGLU experts, computed
from shapes alone, with ``costs.py``'s rules: a multiply-add is two
operations; nothing recomputed is counted; attention and the indexer at the
pairs a causal mask (and the selection) keeps; only live slots, only real
prompt tokens, only the held experts that a live row hit and only the
choices that landed here.  A share built on these counts cannot pass 100%
by the count's doing.

What sets such a model apart in a decode step: the index keys' bytes scale
with the tokens *cached* (``index_head_dim`` columns a token a layer), the
latent rows' with the rows *selected* (at most ``index_topk`` a slot a
layer, whatever is cached), the expert weights' with the held experts
*hit*.

``cfg`` is the configuration file's keys: the published ones, with
``n_routed_experts`` as the experts HELD and ``router_experts`` as the
router's width.
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
    """Columns of a cached K row that mean something: ``[c | rope(k_r)]``."""
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def stored_width(cfg: dict) -> int:
    """Columns of a pool row as stored: the latent row's rounded up to
    whole lane registers (the V row ``[c | k_idx]`` fills them)."""
    return -(-latent_width(cfg) // LANES) * LANES


def expert_params(cfg: dict) -> int:
    """One routed expert: gate, up and down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def part_params(cfg: dict) -> dict:
    """Parameters of one layer's parts.  ``attn``: the query's two
    projections, the latent and rope key, k_nope | v from the latent, out,
    the two latents' norms.  ``indexer``: its queries from the query latent,
    its key with the LayerNorm's scale and bias, its weights.  ``dense``:
    three matrices.  ``moe``: OUTSIDE its routed experts, ``router`` (d x
    router width and the bias, float32) and ``shared`` (three matrices).
    ``norms``: the block's two."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    q, r = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    j, di = cfg["index_n_heads"], cfg["index_head_dim"]
    return {
        "attn": (d * q + q * h * qk + d * latent_width(cfg)
                 + r * h * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"])
                 + h * cfg["v_head_dim"] * d + q + r),
        "indexer": q * j * di + d * di + 2 * di + d * j,
        "dense": 3 * d * cfg["intermediate_size"],
        "router": (d + 1) * cfg["router_experts"],
        "shared": 3 * d * cfg["n_shared_experts"]
        * cfg["moe_intermediate_size"],
        "norms": 2 * d}


def param_counts(cfg: dict) -> dict:
    """Parameters by what a decode step does with them: ``experts`` (the
    held ones; read only where hit), ``embedding`` (one row a token: a
    look-up, not a stream), ``router`` (float32, read whole) and
    ``streamed`` (read whole every step: every layer outside its routed
    experts and router, the final norm, the untied head)."""
    n, per, d = layers(cfg), part_params(cfg), cfg["hidden_size"]
    return {"experts": n["moe"] * cfg["n_routed_experts"]
            * expert_params(cfg),
            "embedding": cfg["vocab_size"] * d,
            "router": n["moe"] * per["router"],
            "streamed": (n["attn"] * (per["attn"] + per["indexer"]
                                      + per["norms"])
                         + n["dense"] * per["dense"]
                         + n["moe"] * per["shared"] + d
                         + cfg["vocab_size"] * d)}


def streamed_bytes(cfg: dict, itemsize: int = 2) -> float:
    """The weights a decode step reads whatever was routed where."""
    counts = param_counts(cfg)
    return counts["streamed"] * itemsize + counts["router"] * ROUTER_ITEMSIZE


def index_key_bytes(cfg: dict, index_rows: float, itemsize: int = 2) -> float:
    """The index keys of ``index_rows`` (cached row, layer) pairs: what the
    decode step's indexers have to read to score them."""
    return index_rows * cfg["index_head_dim"] * itemsize


def rows_read_bytes(cfg: dict, kv_rows_read: float,
                    itemsize: int = 2) -> float:
    """``kv_rows_read`` selected latent rows as stored (a pool row, its
    padding to whole lane registers with it: a row is read whole)."""
    return kv_rows_read * stored_width(cfg) * itemsize


def routed_decode_bytes(cfg: dict, experts_hit: float,
                        itemsize: int = 2) -> float:
    """The three matrices of every held expert that got a row
    (``experts_hit``: summed over the expert layers)."""
    return experts_hit * expert_params(cfg) * itemsize


def decode_bytes(cfg: dict, index_rows: float, kv_rows_read: float,
                 experts_hit: float, itemsize: int = 2) -> float:
    """What one decode step has to move: every weight outside the routed
    experts once, the held experts that were hit, the index keys of the
    cached rows its indexers score and the latent rows its attention
    selected, as stored."""
    return (streamed_bytes(cfg, itemsize)
            + routed_decode_bytes(cfg, experts_hit, itemsize)
            + index_key_bytes(cfg, index_rows, itemsize)
            + rows_read_bytes(cfg, kv_rows_read, itemsize))


def selected_pairs(cfg: dict, n: int) -> float:
    """(query, key) pairs the attention of one layer runs over in a context
    of n rows: ``sum_t min(t + 1, index_topk)``."""
    k = min(cfg["index_topk"], n)
    return k * (k + 1) / 2 + (n - k) * k


def routed_flops(cfg: dict, local_choices: float) -> float:
    """The routed experts' products for ``local_choices`` (row, held
    expert) pairs: three matrices a pair."""
    return 2.0 * local_choices * expert_params(cfg)


def prefill_flops(cfg: dict, prompt_tokens: int,
                  local_choice_share: float) -> float:
    """One full prefill of ``prompt_tokens`` real tokens: for each token
    the attention's and the indexer's projections (the keys and values
    expanded from the latent among them), the dense layer, every expert
    layer's router, shared expert and the routed experts of the choices
    that landed here (``local_choice_share`` of ``num_experts_per_tok``);
    the indexer's scores over the pairs a causal mask keeps; QK^T and PV
    over the pairs the selection keeps (``selected_pairs``); the head for
    the one row that is sampled.  Not the bucket's padding, not the scores
    and the attention of pairs a mask then drops, no sorting."""
    d, n, count = cfg["hidden_size"], prompt_tokens, layers(cfg)
    per, h = part_params(cfg), cfg["num_attention_heads"]
    attn = 2 * (per["attn"] - cfg["q_lora_rank"] - cfg["kv_lora_rank"])
    indexer = 2 * (per["indexer"] - 2 * cfg["index_head_dim"])
    moe = 2 * (d * cfg["router_experts"] + per["shared"]) + routed_flops(
        cfg, cfg["num_experts_per_tok"] * local_choice_share)
    score = 2 * cfg["index_n_heads"] * cfg["index_head_dim"]
    attend = 2 * h * (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
                      + cfg["v_head_dim"])
    return (n * (count["attn"] * (attn + indexer)
                 + count["dense"] * 2 * per["dense"] + count["moe"] * moe)
            + count["attn"] * (score * n * (n + 1) / 2
                               + attend * selected_pairs(cfg, n))
            + 2 * d * cfg["vocab_size"])


def memory_sum(cfg: dict, itemsize: int = 2) -> dict:
    """Bytes the serving configuration holds on the device before the
    programs' scratch: every parameter and the full page pool (slots x
    pages a slot + the scratch page, every layer, a row as stored, in both
    pools: the index key rides the V row)."""
    s = cfg["serve"]
    counts = param_counts(cfg)
    pages = s["max_slots"] * -(-s["max_ctx"] // s["page_size"]) + 1
    return {"weights": ((counts["embedding"] + counts["streamed"]
                         + counts["experts"]) * itemsize
                        + counts["router"] * ROUTER_ITEMSIZE),
            "page_pool": (pages * layers(cfg)["attn"] * 2 * s["page_size"]
                          * stored_width(cfg) * itemsize)}
