"""What every Pallas TPU kernel here shares: the memory-space handles under
one spelling, and the MXU product.

``vmem_scratch(shape, dtype)`` allocates a VMEM scratch buffer; ``SMEM`` is
the block memory space for scalars read by the kernel body.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

__all__ = ["vmem_scratch", "SMEM", "mxu_dot"]

vmem_scratch = pltpu.VMEM
SMEM = pltpu.SMEM


def mxu_dot(a: jax.Array, b: jax.Array, out_dtype) -> jax.Array:
    """``a @ b`` accumulated at ``out_dtype``.

    The MXU multiplies f32 operands as one bf16 pass unless asked for full
    precision, which the factorizations cannot afford: a rank-B update at
    bf16 accuracy makes ``H + λI`` indefinite at the paper's smallest λ.
    16-bit operands multiply exactly at the default, and Mosaic refuses
    the full-precision request for them.
    """
    precision = (jax.lax.Precision.HIGHEST if a.dtype == jnp.float32
                 else jax.lax.Precision.DEFAULT)
    return jnp.dot(a, b, precision=precision, preferred_element_type=out_dtype)
