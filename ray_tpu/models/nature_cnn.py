"""Nature-DQN CNN trunk for pixel RL (equivalent of RLlib's visionnet,
rllib/models/torch/visionnet.py).  NHWC, bfloat16-friendly.

The first layer reads **packed frames**.  An 8x8 convolution of stride 4,
padded ``SAME``, is exactly a 2x2 convolution of stride 1, ``VALID``, over
the padded frame folded four by four pixels into channels
(``pack_frames``: ``[H, W, C]`` -> ``[ceil(H/4) + 1, ceil(W/4) + 1, 16 C]``,
the dtype kept) with the kernel ``[8, 8, C, 32]`` reshaped to
``[2, 2, 16 C, 32]`` inside the forward pass: the same products, summed in
another order.  A caller that keeps many frames and reads them more than
once (anakin PPO's trajectory) packs them once, as uint8, and hands them in
packed: the bytes stay bytes until the convolution's operand, and a packed
frame is a row of whole 128-byte lane tiles (PERF.md, PR 55).  The chip
holds a convolution's frames with the batch in the lanes, whatever order
the program names; packed frames may therefore come with the batch LAST,
``[H', W', 16 C, B]``, which is what ``ops.gather_rows`` writes when it
picks a minibatch out of a sample-major trajectory in one pass, and the
first layer then reads them as they lie, with no gather-then-transpose in
front (PERF.md, PR 58).  ``pack_frames`` is the plain definition of the
packed form and what every caller with raw frames gets; where frames are
KEPT on a TPU (anakin PPO's rollout) they are packed by the kernel
``ops.gather_rows.fold_tiles`` instead (``pack_frames_tiled``: uint8 frames
of four channels, one pass from the environment's bytes to the
trajectory's word tiles and to the batch-last form the trunk reads, the
same bytes in the same order; PERF.md, PR 60).  The parameter tree is the
unpacked kernel's: ``Conv_0/kernel`` stays ``[8, 8, C, 32]`` with
``nn.Conv``'s initialiser.
"""
from __future__ import annotations

from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ray_tpu.ops import gather_rows as rows_op

_KERNEL, _STRIDE = 8, 4
_FOLD = _KERNEL // _STRIDE  # the packed kernel's side


def _pads(height: int, width: int):
    """What ``SAME`` puts around a frame for the first layer's window."""
    return jax.lax.padtype_to_pads(
        (height, width), (_KERNEL, _KERNEL), (_STRIDE, _STRIDE), "SAME")


def folds_tiled(frame_shape: Tuple[int, ...], dtype=jnp.uint8) -> bool:
    """Whether ``pack_frames_tiled`` takes raw frames ``[H, W, C]`` of
    ``dtype`` (``ops.gather_rows.folds_frames``: uint8, four channels, a
    row of whole words)."""
    return rows_op.folds_frames(
        jax.ShapeDtypeStruct((1, *frame_shape), dtype),
        _pads(*frame_shape[:2]))


def pack_frames_tiled(x: jax.Array, into=None, at=0):
    """``pack_frames(x)`` of raw frames ``[B, H, W, C]`` in the two forms a
    caller that keeps them holds, by ONE kernel that reads the raw bytes
    once (``ops.gather_rows.fold_tiles``; ``folds_tiled`` says which frames
    it takes): ``(tiles, packed)`` with ``tiles`` the packed frames as
    ``ops.gather_rows.row_tiles`` has them (written over items ``at ..`` of
    the buffer ``into`` where one is given, in place) and ``packed``
    ``[H', W', 16 C, B]``, the batch last, which the first layer reads as
    it lies."""
    tiles, cols = rows_op.fold_tiles(x, _pads(*x.shape[1:3]), into=into,
                                     at=at)
    return tiles, cols.reshape(*packed_shape(x.shape[1:]), -1)


def packed_shape(frame_shape: Tuple[int, ...]) -> Tuple[int, int, int]:
    """``[H, W, C]`` of a raw frame -> the shape ``pack_frames`` gives it."""
    h, w, c = frame_shape
    (top, bottom), (left, right) = _pads(h, w)
    return ((h + top + bottom) // _STRIDE, (w + left + right) // _STRIDE,
            _STRIDE * _STRIDE * c)


def pack_frames(x: jax.Array) -> jax.Array:
    """``[..., H, W, C]`` -> ``[..., H', W', 16 C]``: the frame padded as the
    first layer's ``SAME`` convolution pads it (with zeros, by
    ``lax.padtype_to_pads``, so 210x160 packs by the same rule as 84x84)
    and folded four by four pixels into channels, channel
    ``(dy * 4 + dx) * C + c``.  The dtype is kept: uint8 in, uint8 out."""
    *lead, h, w, c = x.shape
    ph, pw, pc = packed_shape((h, w, c))
    (top, bottom), (left, right) = _pads(h, w)
    # A row is W * C values from here on, so dx and c stay side by side:
    # one dimension fewer to fold (4% of the rollout on the chip).
    x = jnp.pad(x.reshape(*lead, h, w * c), [(0, 0)] * len(lead) + [
        (top, bottom), (left * c, right * c)])
    x = x.reshape(*lead, ph, _STRIDE, pw, _STRIDE * c)
    n = len(lead)
    return x.transpose(*range(n), n, n + 2, n + 1, n + 3).reshape(
        *lead, ph, pw, pc)


class PackedConv(nn.Module):
    """``nn.Conv(features, (8, 8), strides=(4, 4))`` (``SAME``, with bias)
    computed on ``pack_frames``'s form.  Parameters, their shapes and their
    initialisers are ``nn.Conv``'s."""

    features: int
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, packed: jax.Array,
                 batch_last: bool = False) -> jax.Array:
        """packed: ``[B, H', W', 16 C]``, or ``[H', W', 16 C, B]`` with
        ``batch_last``; ``[B, H' - 1, W' - 1, features]`` either way."""
        channels = packed.shape[2 if batch_last else 3]
        c = channels // (_STRIDE * _STRIDE)
        kernel = self.param("kernel", nn.linear.default_kernel_init,
                            (_KERNEL, _KERNEL, c, self.features))
        bias = self.param("bias", nn.initializers.zeros_init(),
                          (self.features,))
        # [8, 8, C, F] -> [2, 2, 16 C, F], channel (dy * 4 + dx) * C + c.
        folded = kernel.reshape(_FOLD, _STRIDE, _FOLD, _STRIDE, c,
                                self.features)
        folded = folded.transpose(0, 2, 1, 3, 4, 5).reshape(
            _FOLD, _FOLD, channels, self.features)
        y = jax.lax.conv_general_dilated(
            packed.astype(self.dtype), folded.astype(self.dtype), (1, 1),
            "VALID", dimension_numbers=(
                "HWCN" if batch_last else "NHWC", "HWIO", "NHWC"))
        return y + bias.astype(self.dtype)


class NatureCNN(nn.Module):
    out_dim: int = 256
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array, packed: bool = False,
                 batch_last: bool = False) -> jax.Array:
        """x: [B, H, W, C] uint8 or float → [B, out_dim]; with ``packed``,
        x is ``pack_frames`` of such frames (``packed_shape``), and with
        ``batch_last`` too those with the batch as their last dimension,
        ``[H', W', 16 C, B]``: the order in which the chip holds a
        convolution's frames, so what ``ops.gather_rows`` hands over is
        read as it lies.  uint8 is scaled by 1/255, float frames pass as
        they are."""
        if batch_last and not packed:
            raise ValueError("batch-last frames are packed frames")
        if not packed:
            x = pack_frames(x)
        if x.dtype == jnp.uint8:
            # The barrier keeps the bytes bytes up to the convolution's own
            # operand: without it the TPU compiler converts first, wherever
            # the frames come from, and folds, transposes and stores 2-byte
            # values (a rollout then folds twice, once to keep and once to
            # read).
            x = jax.lax.optimization_barrier(x).astype(self.dtype) / 255.0
        else:
            x = x.astype(self.dtype)
        # Named by hand: the tree keeps nn.Conv's automatic names.
        x = nn.relu(PackedConv(32, dtype=self.dtype, name="Conv_0")(
            x, batch_last=batch_last))
        x = nn.relu(nn.Conv(64, (4, 4), strides=(2, 2), dtype=self.dtype,
                            name="Conv_1")(x))
        x = nn.relu(nn.Conv(64, (3, 3), strides=(1, 1), dtype=self.dtype,
                            name="Conv_2")(x))
        x = x.reshape((x.shape[0], -1))
        return nn.relu(nn.Dense(self.out_dim, dtype=self.dtype)(x))


class MinAtarCNN(nn.Module):
    """Small-grid pixel trunk (10x10-class boards): the 84x84 Nature stack's
    8x8/4 stride degenerates below ~32px, so small boards get one 3x3
    conv + dense, the standard MinAtar-scale architecture."""

    out_dim: int = 128
    features: int = 16
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        x = x.astype(self.dtype)
        x = nn.relu(nn.Conv(self.features, (3, 3), dtype=self.dtype)(x))
        x = x.reshape((x.shape[0], -1))
        return nn.relu(nn.Dense(self.out_dim, dtype=self.dtype)(x))
