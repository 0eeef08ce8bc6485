"""Pallas bodies for the communication-compression hot paths.

Elementwise exprs (compiled through the shared flat launcher,
``repro.kernels.api._flat_launch``):

  * ``qsgd_quantize``   — stochastic uniform quantization of a pre-normalized
                          buffer: ``sign(x) * min(floor(|x| * L + u), L)``.
  * ``qsgd_dequantize`` — ``q * scale / L`` (scale broadcast per node).

The top-k pack/unpack ops have no kernel (see ``ops.py``): they run their
XLA gather/scatter oracle on every platform.
"""
from __future__ import annotations

import jax.numpy as jnp

__all__ = ["qsgd_quantize_expr", "qsgd_dequantize_expr"]


# ------------------------------------------------------------- elementwise
def qsgd_quantize_expr(s, x, u):
    """Stochastic rounding of the normalized buffer; scalars s = (levels,).
    2 reads + 1 write per element; u ~ Uniform[0, 1) makes it unbiased."""
    q = jnp.floor(jnp.abs(x) * s[0] + u)
    return jnp.sign(x) * jnp.minimum(q, s[0])


def qsgd_dequantize_expr(s, q, scale):
    """q * scale / levels; scalars s = (1/levels,).  scale is the per-node
    max-|x| broadcast to the buffer shape."""
    return q * scale * s[0]
