"""ResNet family in flax (ResNet-18/50 + CIFAR stem variant).

For the Train north-star "ResNet-50/CIFAR-10 DataParallel" config
(BASELINE.json; reference benchmark: doc/source/ray-air/benchmarks.rst
TorchTrainer ResNet).  NHWC layout (TPU-native), bfloat16 compute, fp32
batch-norm statistics.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ray_tpu.ops.losses import softmax_cross_entropy


@dataclasses.dataclass(frozen=True)
class ResNetConfig:
    stage_sizes: Tuple[int, ...] = (3, 4, 6, 3)  # resnet50
    num_classes: int = 10
    num_filters: int = 64
    bottleneck: bool = True
    cifar_stem: bool = False  # 3x3 stem, no maxpool (32x32 inputs)
    dtype: Any = jnp.bfloat16

    @classmethod
    def resnet18(cls, **kw):
        return cls(stage_sizes=(2, 2, 2, 2), bottleneck=False, **kw)

    @classmethod
    def resnet50(cls, **kw):
        return cls(**kw)

    @classmethod
    def resnet50_cifar(cls, **kw):
        return cls(cifar_stem=True, **kw)

    @classmethod
    def tiny(cls, **kw):
        return cls(stage_sizes=(1, 1), bottleneck=False, num_filters=8,
                   cifar_stem=True, **kw)


class ResNetBlock(nn.Module):
    filters: int
    strides: int
    bottleneck: bool
    dtype: Any

    @nn.compact
    def __call__(self, x, train: bool):
        conv = partial(nn.Conv, use_bias=False, dtype=self.dtype)
        norm = partial(nn.BatchNorm, use_running_average=not train,
                       momentum=0.9, dtype=jnp.float32)
        residual = x
        if self.bottleneck:
            y = conv(self.filters, (1, 1))(x)
            y = nn.relu(norm()(y))
            y = conv(self.filters, (3, 3), strides=(self.strides,) * 2)(y)
            y = nn.relu(norm()(y))
            y = conv(4 * self.filters, (1, 1))(y)
            y = norm(scale_init=nn.initializers.zeros)(y)
            out_filters = 4 * self.filters
        else:
            y = conv(self.filters, (3, 3), strides=(self.strides,) * 2)(x)
            y = nn.relu(norm()(y))
            y = conv(self.filters, (3, 3))(y)
            y = norm(scale_init=nn.initializers.zeros)(y)
            out_filters = self.filters
        if residual.shape != y.shape:
            residual = conv(out_filters, (1, 1),
                            strides=(self.strides,) * 2)(residual)
            residual = norm()(residual)
        return nn.relu(residual + y)


class ResNet(nn.Module):
    config: ResNetConfig

    @nn.compact
    def __call__(self, x: jax.Array, train: bool = True) -> jax.Array:
        c = self.config
        x = x.astype(c.dtype)
        if c.cifar_stem:
            x = nn.Conv(c.num_filters, (3, 3), use_bias=False,
                        dtype=c.dtype, name="stem")(x)
        else:
            x = nn.Conv(c.num_filters, (7, 7), strides=(2, 2),
                        use_bias=False, dtype=c.dtype, name="stem")(x)
        x = nn.relu(nn.BatchNorm(use_running_average=not train, momentum=0.9,
                                 dtype=jnp.float32, name="stem_bn")(x))
        if not c.cifar_stem:
            x = nn.max_pool(x, (3, 3), strides=(2, 2), padding="SAME")
        for i, n_blocks in enumerate(c.stage_sizes):
            for j in range(n_blocks):
                strides = 2 if i > 0 and j == 0 else 1
                x = ResNetBlock(c.num_filters * 2 ** i, strides,
                                c.bottleneck, c.dtype)(x, train)
        x = jnp.mean(x, axis=(1, 2))
        return nn.Dense(c.num_classes, dtype=jnp.float32, name="head")(x)


def resnet_loss_fn(params, batch_stats, apply_fn, batch):
    """Softmax CE with batch-norm stat updates.
    batch: {"image": [B,H,W,C], "label": [B]}."""
    logits, new_state = apply_fn(
        {"params": params, "batch_stats": batch_stats}, batch["image"],
        train=True, mutable=["batch_stats"])
    acc = jnp.mean(jnp.argmax(logits, -1) == batch["label"])
    loss = softmax_cross_entropy(logits, batch["label"])
    return loss, (new_state["batch_stats"], acc)
