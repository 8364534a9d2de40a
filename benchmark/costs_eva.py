"""Operations and bytes that a decoder of EVA attention requires (an exact
window of rows beside one pooled row for every chunk before it, in every
layer; a dense SwiGLU; a head of ``num_pred_heads`` x ``vocab_size`` columns
of which a serve program computes the first ``vocab_size``), computed from
shapes alone, with ``costs.py``'s rules: a multiply-add is two operations;
nothing recomputed is counted; attention at the pairs its masks keep; only
live slots, only real prompt rows.  A share built on these counts cannot
pass 100% by the count's doing.

What sets such a model apart: a cached row is a K row and a V row of ALL the
heads (``num_key_value_heads`` = ``num_attention_heads``: 2 x 4,096 columns,
16 KB in bfloat16 a layer), and a slot at position n reads ``window_size /
chunk_size * (n // window_size)`` summary rows and ``n % window_size`` exact
ones, not n.  At the published widths a row's products are 2 x 2 x 4,096 =
16 kFLOP for 16 KB read: 1 FLOP/B, far under the v5e's ridge (~240), so the
decode attention is bound by the bytes of the rows, which the engine's
counters give (``kv_tokens``: the rows read; ``ctx_tokens``: the positions
they stand for).

``cfg`` is the configuration file's keys (the published ones).
"""
from __future__ import annotations


def head_dim(cfg: dict) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def row_width(cfg: dict) -> int:
    """Columns of a cached K row (and of a V row): every KV head's."""
    return cfg["num_key_value_heads"] * head_dim(cfg)


def layer_params(cfg: dict) -> int:
    """One layer: q, k, v and o (no bias), the two pooling vectors a head,
    three SwiGLU matrices, two norms."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    return (2 * d * h * head_dim(cfg) + 2 * d * row_width(cfg)
            + 2 * h * head_dim(cfg) + 3 * d * cfg["intermediate_size"]
            + 2 * d)


def param_counts(cfg: dict) -> dict:
    """Parameters by what a decode step does with them: ``embedding`` (one
    row a token: a look-up, not a stream), ``head`` (all ``num_pred_heads``
    heads; a serve program reads ``head_read``, the next byte's) and
    ``layers`` (read whole every step, the final norm with them)."""
    d = cfg["hidden_size"]
    return {"embedding": cfg["vocab_size"] * d,
            "head": d * cfg["num_pred_heads"] * cfg["vocab_size"],
            "head_read": d * cfg["vocab_size"],
            "layers": cfg["num_hidden_layers"] * layer_params(cfg) + d}


def total_params(cfg: dict) -> int:
    counts = param_counts(cfg)
    return counts["embedding"] + counts["head"] + counts["layers"]


def streamed_bytes(cfg: dict, itemsize: int = 2) -> float:
    """The weights a decode step has to read: every layer's, the final norm
    and the next byte's head."""
    counts = param_counts(cfg)
    return (counts["layers"] + counts["head_read"]) * itemsize


def rows_bytes(cfg: dict, kv_rows: float, itemsize: int = 2) -> float:
    """``kv_rows`` (cached row, layer) pairs, K and V each once."""
    return kv_rows * 2 * row_width(cfg) * itemsize


def attend_flops(cfg: dict, kv_rows: float) -> float:
    """Every head's query against its own columns of ``kv_rows`` (cached
    row, layer) pairs, and the probabilities against the values."""
    return 2.0 * kv_rows * 2 * cfg["num_attention_heads"] * head_dim(cfg)


def page_bytes(cfg: dict, itemsize: int = 2) -> float:
    """One chunk's rows of one layer, K and V: what pooling a closed chunk
    reads (a page is a chunk)."""
    return cfg["chunk_size"] * 2 * row_width(cfg) * itemsize


def decode_bytes(cfg: dict, kv_tokens: float, chunks_closed: float,
                 itemsize: int = 2) -> float:
    """What one decode step has to move: the weights once, the rows the
    live slots read (``kv_tokens``: summary and window rows, every layer's,
    K and V each once) and a page a layer for every chunk a token closed."""
    n = cfg["num_hidden_layers"]
    return (streamed_bytes(cfg, itemsize)
            + rows_bytes(cfg, kv_tokens * n, itemsize)
            + chunks_closed * n * page_bytes(cfg, itemsize))


def attention_pairs(cfg: dict, rows: int) -> float:
    """(query, key) pairs of a context of ``rows`` real rows: every row with
    the rows of its own window up to itself, and with one summary for every
    chunk of the windows before."""
    w, per = cfg["window_size"], cfg["window_size"] // cfg["chunk_size"]
    full, rest = divmod(rows, w)
    own = full * w * (w + 1) / 2 + rest * (rest + 1) / 2
    summaries = per * (w * full * (full - 1) / 2 + rest * full)
    return own + summaries


def prefill_flops(cfg: dict, prompt_tokens: int) -> float:
    """One full prefill of ``prompt_tokens`` real rows: each row's
    projections and SwiGLU, QK^T and PV over ``attention_pairs``, the
    pooling of every whole chunk (a score a vector a row and the weighted
    sums of K and of V), the next byte's head for the one row that is
    sampled.  Not the bucket's padding, not the pairs a mask drops."""
    d, n = cfg["hidden_size"], prompt_tokens
    h, hd = cfg["num_attention_heads"], head_dim(cfg)
    matmuls = 2 * (2 * d * h * hd + 2 * d * row_width(cfg)
                   + 3 * d * cfg["intermediate_size"])
    pooled = n // cfg["chunk_size"] * cfg["chunk_size"]
    return cfg["num_hidden_layers"] * (
        n * matmuls + 4 * h * hd * attention_pairs(cfg, n)
        + pooled * 8 * h * hd) + 2 * d * cfg["vocab_size"]


def memory_sum(cfg: dict, itemsize: int = 2) -> dict:
    """Bytes the serving configuration holds on the device before the
    programs' scratch: every parameter and the full page pool (slots x the
    pages a slot owns at ``max_ctx`` + the scratch page; K and V; every
    layer).  ``pages_per_slot``: a summary page for every ``chunk_size *
    page_size`` positions and a ring of ``window_size / page_size``."""
    s = cfg["serve"]
    ps = s["page_size"]
    ceil = lambda a, b: -(-a // b)  # noqa: E731
    per_slot = (ceil(ceil(s["max_ctx"], cfg["chunk_size"]), ps)
                + ceil(min(cfg["window_size"], s["max_ctx"]), ps))
    pages = s["max_slots"] * per_slot + 1
    return {"weights": total_params(cfg) * itemsize,
            "pages_per_slot": per_slot,
            "page_pool": (pages * cfg["num_hidden_layers"] * ps * 2
                          * row_width(cfg) * itemsize),
            "dense_page_pool": ((s["max_slots"] * ceil(s["max_ctx"], ps) + 1)
                                * cfg["num_hidden_layers"] * ps * 2
                                * row_width(cfg) * itemsize)}
