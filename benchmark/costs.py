"""Operations and bytes that the work requires, computed from shapes alone.

Kept with the benchmark so that no later PR changes the yardstick.  A
multiply-add is two operations.  Recomputed operations are not counted, and
causal attention is counted at the half of the score matrix it needs, so a
share built on these counts cannot pass 100% by the count's doing.
"""
from __future__ import annotations


def gpt2_matmul_params(cfg: dict) -> int:
    """Parameters that take part in a matrix multiplication for every token:
    per layer qkv (3d^2), the attention projection (d^2) and the MLP (8d^2),
    and the tied head (V*d).  Embedding look-ups, biases and layer norms do
    no multiply-adds worth counting."""
    d, layers, vocab = cfg["n_embd"], cfg["n_layer"], cfg["vocab_size"]
    return layers * 12 * d * d + vocab * d


def gpt2_attention_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward, per token, over all layers: QK^T and PV are 2*S*d each per
    token without a mask; the causal mask needs (S+1)/(2S) of that."""
    d, layers = cfg["n_embd"], cfg["n_layer"]
    return layers * 4 * d * (seq + 1) / 2


def gpt2_train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward plus backward (twice the forward) of one training step."""
    return 3 * (2 * gpt2_matmul_params(cfg)
                + gpt2_attention_flops_per_token(cfg, seq))


def flash_train_cost(batch: int, seq: int, heads: int, head_dim: int,
                     itemsize: int = 2) -> dict:
    """What causal attention needs in one training step of one layer, for
    the three flash kernels together (forward, dq, dk/dv).

    Operations: forward QK^T and PV (4*S^2*D per head), backward dV, dP, dQ
    and dK (8*S^2*D); half of each under the causal mask.  The backward
    kernels' second computation of QK^T is recomputation and not counted.
    Bytes: forward reads Q, K, V and writes O; backward reads Q, K, V, O, dO
    and writes dQ, dK, dV: twelve [B,S,H,D] arrays in all, each once."""
    rows = batch * heads
    flops = rows * 12 * seq * seq * head_dim / 2
    bytes_ = 12 * batch * seq * heads * head_dim * itemsize
    return {"flops": flops, "bytes": bytes_}


def roofline_seconds(flops: float, bytes_: float, peak: dict) -> dict:
    """The least time the chip could take, and which bound gives it."""
    t_flops = flops / peak["bf16_flops"]
    t_bytes = bytes_ / peak["hbm_bytes_per_s"]
    return {"seconds": max(t_flops, t_bytes),
            "bound": "compute" if t_flops >= t_bytes else "memory"}


def gpt2_decode_bytes(param_bytes: int, live_tokens: float, cfg: dict,
                      itemsize: int = 2) -> float:
    """What one decode step has to read: every weight once, and the keys and
    values of every live token of the active slots, in every layer."""
    return param_bytes + live_tokens * cfg["n_layer"] * 2 * cfg["n_embd"] \
        * itemsize
