"""Pure-jnp oracle for the fused worker kernel (the correctness ground
truth), and the worker step off a TPU.

It is deliberately written via the canonical repro.core.field spec (which
tests separately against numpy int64), NOT by sharing code with the kernel.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import field, sigmoid_poly


def coded_grad_mc_ref(x: jax.Array, w: jax.Array, cbar: jax.Array,
                      p: int = field.P) -> jax.Array:
    """Multi-head Eq. 20: x (mk, d), w (d, c, r) -> (d, c) mod p.

    Reshaping W̃ to (d, c*r) before the matmul is exact: Lagrange encoding is
    elementwise-linear across parts, so column cls*r+j of X̃ @ W̃ is precisely
    the head-cls degree-j product the polynomial needs.
    """
    d, c, r = w.shape
    xw = field.matmul(x, w.reshape(d, c * r), p)     # (mk, c*r)
    xw = xw.reshape(x.shape[0], c, r)
    s = sigmoid_poly.gbar_field(xw, cbar.astype(jnp.int32), p)  # (mk, c)
    return field.matmul(x.T, s, p)                   # (d, c)
