"""Plain reference of the ``uvm_affine`` ifunc: y = relu(x @ W) per
128x128 tile, in float64 with numpy.

The control is the same product in the precision just below the one the
configuration states (float32 at ``highest``): three bf16 passes, as XLA's
``Precision.HIGH`` computes it on a TPU, written out so that it gives the
same numbers on any backend.
"""

from __future__ import annotations

import numpy as np


def reference(x: np.ndarray, w: np.ndarray) -> np.ndarray:
    """x [..., T, T], w [T, T] -> relu(x @ w) in float64."""
    return np.maximum(np.asarray(x, np.float64) @ np.asarray(w, np.float64), 0)


def _bf16(a):
    """Round to bfloat16's 8-bit mantissa, kept as float32 (a rounding XLA
    may not fold away, as it may a convert pair)."""
    import jax

    return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)


def control(x: np.ndarray, w: np.ndarray) -> np.ndarray:
    """relu(x @ w) by three bf16 passes with float32 accumulation:
    hi*hi + hi*lo + lo*hi, where hi is the bf16 rounding and lo the bf16
    rounding of what is left."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(x, w):
        xh, wh = _bf16(x), _bf16(w)
        xl, wl = _bf16(x - xh), _bf16(w - wh)

        def mm(a, b):          # exact products of bf16 values, f32 sums
            return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST,
                              preferred_element_type=jnp.float32)
        return jnp.maximum(mm(xh, wh) + mm(xh, wl) + mm(xl, wh), 0)

    return np.asarray(f(jnp.asarray(x, jnp.float32), jnp.asarray(w, jnp.float32)))
