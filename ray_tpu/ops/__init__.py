"""TPU compute ops: Pallas kernels beside their pure-XLA references.

Kernel selection is by platform and shape: pallas on real TPU, jnp
reference elsewhere (CPU test meshes can't run Mosaic kernels).  A kernel
that was selected and fails is an error, never a quiet switch of path.  Everything here is shape-static and
jit/scan-friendly per XLA's compilation model.
"""
from ray_tpu.ops.attention import (  # noqa: F401
    mha_attention,
    mha_attention_qkv,
    flash_attention,
    flash_attention_qkv,
    blockwise_update,
)
from ray_tpu.ops.layers import gelu, layer_norm, rms_norm, rope  # noqa: F401
