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


def mxu_dot(a: jax.Array, b: jax.Array, out_dtype, *,
            transpose_b: bool = False) -> jax.Array:
    """``a @ b`` (``a @ bᵀ`` with ``transpose_b``) accumulated at
    ``out_dtype``; the transposed form is the MXU's own, no copy of ``b``.

    The MXU multiplies f32 operands as one bf16 pass unless asked for full
    precision, which the factorizations cannot afford: a rank-B update at
    bf16 accuracy makes ``H + λI`` indefinite at the paper's smallest λ.
    16-bit operands multiply exactly at the default, and Mosaic refuses
    the full-precision request for them.
    """
    precision = (jax.lax.Precision.HIGHEST if a.dtype == jnp.float32
                 else jax.lax.Precision.DEFAULT)
    dims = (((1,), (1 if transpose_b else 0,)), ((), ()))
    return jax.lax.dot_general(a, b, dims, precision=precision,
                               preferred_element_type=out_dtype)
