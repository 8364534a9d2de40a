"""Rope frequencies, plain and YaRN-scaled.

Plain rope rotates channel pair ``i`` of a ``dim``-wide vector by
``position * base^(-2i / dim)``.  YaRN (arXiv:2309.00071) as DeepSeek-V2/V3
publish it (``rope_scaling.type`` ``deepseek_yarn``; ``modeling_deepseek.py``:
``yarn_find_correction_range``, ``yarn_linear_ramp_mask``, ``yarn_get_mscale``)
stretches a model trained on ``original_max_position_embeddings`` positions
over ``factor`` times as many, a channel at a time: a pair that turns more
than ``beta_fast`` times over the original context keeps its frequency
(extrapolated), one that turns less than ``beta_slow`` times has it divided
by ``factor`` (interpolated), and the pairs between are blended linearly by
their index.  Beside the frequencies the softmax scale is multiplied by
``mscale(factor, mscale_all_dim)^2`` and cos and sin by ``mscale(factor,
mscale) / mscale(factor, mscale_all_dim)``.

A model's config carries the published ``rope_scaling`` block as a
``YarnScaling``; ``inv_freq`` and ``softmax_mscale`` take None for a rope
that is not scaled, and are then what every unscaled rope of
``ray_tpu/models/`` computes.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class YarnScaling:
    """The keys of a published ``rope_scaling`` block of type
    ``deepseek_yarn``, under their names."""
    factor: float
    original_max_position_embeddings: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0

    @classmethod
    def from_config(cls, block) -> Optional["YarnScaling"]:
        """None, a ``YarnScaling`` or a published block (a dict with its
        ``type``) → None or a ``YarnScaling``."""
        if block is None or isinstance(block, cls):
            return block
        block = dict(block)
        kind = block.pop("type", block.pop("rope_type", "deepseek_yarn"))
        if kind not in ("deepseek_yarn", "yarn"):
            raise ValueError(f"rope_scaling type {kind!r}: only "
                             "deepseek_yarn is built")
        return cls(**block)


def yarn_correction_range(scaling: YarnScaling, dim: int,
                          base: float) -> Tuple[int, int]:
    """(low, high): pairs under ``low`` keep their frequency, pairs from
    ``high`` on are interpolated.  ``corr(n)`` is the (real-valued) pair
    that turns ``n`` times over the original context."""
    def corr(turns: float) -> float:
        return dim * math.log(scaling.original_max_position_embeddings
                              / (turns * 2 * math.pi)) / (2 * math.log(base))

    return (max(math.floor(corr(scaling.beta_fast)), 0),
            min(math.ceil(corr(scaling.beta_slow)), dim - 1))


def yarn_mscale(factor: float, mscale: float) -> float:
    """``0.1 * mscale * ln(factor) + 1`` (1 for a factor of 1 or less)."""
    if factor <= 1:
        return 1.0
    return 0.1 * mscale * math.log(factor) + 1.0


def inv_freq(dim: int, base: float,
             scaling: Optional[YarnScaling] = None):
    """float32 [dim / 2]: the angle a position advances pair ``i`` by."""
    plain = 1.0 / (base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    if scaling is None:
        return plain
    low, high = yarn_correction_range(scaling, dim, base)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / max(high - low, 0.001), 0.0, 1.0)
    return plain * (1.0 - ramp) + plain / scaling.factor * ramp


def softmax_mscale(scaling: Optional[YarnScaling]) -> float:
    """What multiplies the softmax scale: ``mscale(factor,
    mscale_all_dim)^2`` (the published code applies it only where
    ``mscale_all_dim`` is set)."""
    if scaling is None or not scaling.mscale_all_dim:
        return 1.0
    return yarn_mscale(scaling.factor, scaling.mscale_all_dim) ** 2


def cos_sin_mscale(scaling: Optional[YarnScaling]) -> float:
    """What multiplies cos and sin: ``mscale(factor, mscale) /
    mscale(factor, mscale_all_dim)``."""
    if scaling is None:
        return 1.0
    return yarn_mscale(scaling.factor, scaling.mscale) \
        / yarn_mscale(scaling.factor, scaling.mscale_all_dim)
