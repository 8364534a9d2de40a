"""Operations and bytes that a routed (mixture-of-experts) decoder requires,
computed from shapes alone, with ``costs.py``'s rules: a multiply-add is two
operations; nothing recomputed is counted; causal attention at the half of
the score matrix it needs; only the experts that really got a row, only real
prompt tokens and not the bucket's padding.  A share built on these counts
cannot pass 100% by the count's doing.

``cfg`` is the configuration file's published keys (``hidden_size``,
``num_attention_heads``, ``num_key_value_heads``, ``num_hidden_layers``,
``num_experts``, ``num_experts_per_tok``, ``intermediate_size`` as one
expert's width, ``vocab_size``).
"""
from __future__ import annotations


def kv_width(cfg: dict) -> int:
    """Columns of a page-pool row: every KV head's keys (or values)."""
    return (cfg["hidden_size"] // cfg["num_attention_heads"]
            * cfg["num_key_value_heads"])


def expert_params(cfg: dict) -> int:
    """One expert: gate, up and down, d x f each."""
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def moe_param_counts(cfg: dict) -> dict:
    """Parameters by what a decode step does with them: ``experts`` (read
    only where hit), ``embedding`` (one row a token: a look-up, not a
    stream) and ``streamed`` (read whole every step: attention, router,
    norms, final norm, head)."""
    d, layers = cfg["hidden_size"], cfg["num_hidden_layers"]
    qk_norm = d + kv_width(cfg)
    per_layer = (2 * d * d + 2 * d * kv_width(cfg)  # q, o; k, v
                 + d * cfg["num_experts"] + 2 * d + qk_norm)
    return {"experts": layers * cfg["num_experts"] * expert_params(cfg),
            "embedding": cfg["vocab_size"] * d,
            "streamed": layers * per_layer + d + cfg["vocab_size"] * d}


def kv_read_bytes(cfg: dict, kv_tokens: float, itemsize: int = 2) -> float:
    """The keys and values of ``kv_tokens`` cached rows, in every layer."""
    return (kv_tokens * cfg["num_hidden_layers"] * 2 * kv_width(cfg)
            * itemsize)


def moe_decode_bytes(cfg: dict, param_bytes_outside_experts: float,
                     experts_hit: float, kv_tokens: float,
                     itemsize: int = 2) -> float:
    """What one decode step has to read: the weights outside the experts
    once, the three matrices of every expert that got a row
    (``experts_hit``: summed over the layers), and the keys and values of
    the ``kv_tokens`` cached rows the step attends to, in every layer."""
    return (param_bytes_outside_experts
            + experts_hit * expert_params(cfg) * itemsize
            + kv_read_bytes(cfg, kv_tokens, itemsize))


def moe_prefill_flops(cfg: dict, prompt_tokens: int,
                      cached_tokens: int = 0) -> float:
    """One prefill of ``prompt_tokens`` of which ``cached_tokens`` were
    already in the cache: for each new token and layer the projections
    (q, o: d^2 each; k, v: d x kv width each), the router (d x E) and its
    ``num_experts_per_tok`` experts (3 d f each); QK^T and PV over the
    positions each new token may see (4 d a pair; n new tokens after c
    cached see n c + n (n + 1) / 2 pairs); the head for the one row that
    is sampled."""
    d, layers = cfg["hidden_size"], cfg["num_hidden_layers"]
    n, c = prompt_tokens - cached_tokens, cached_tokens
    per_token = 2 * (2 * d * d + 2 * d * kv_width(cfg)
                     + d * cfg["num_experts"]
                     + cfg["num_experts_per_tok"] * expert_params(cfg))
    pairs = n * c + n * (n + 1) / 2
    return layers * (n * per_token + 4 * d * pairs) \
        + 2 * d * cfg["vocab_size"]
