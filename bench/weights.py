"""The benchmark's weights: one jitted call from the seed, in float32, in the
parameter layout the trainer takes (``{"embed", "blocks": {"b0": ...},
"final_norm", ...}``, layers stacked on a leading axis), as the cell's
architecture module writes it out (its ``param_shapes``).

The reference builds the same tree from the same seed with this function, so
it takes no weights that the program made.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp

__all__ = ["make_params", "nest"]


def nest(flat: dict) -> dict:
    out: dict = {}
    for path, v in flat.items():
        *head, last = path.split("/")
        node = out
        for p in head:
            node = node.setdefault(p, {})
        node[last] = v
    return out


def make_params(shapes: dict, key) -> dict:
    """Float32 weights of layout ``shapes`` (an architecture module's
    ``param_shapes(m)``) from ``key`` (traceable: call it inside one
    ``jax.jit``).  Each leaf draws from its own key, folded in from a hash
    of its path, so a leaf does not depend on the others."""
    flat = {}
    for path, (shape, init, scale) in shapes.items():
        if init in ("ones", "zeros"):
            flat[path] = jnp.full(shape, float(init == "ones"), jnp.float32)
        else:
            sub = jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)
            flat[path] = scale * jax.random.normal(sub, shape, jnp.float32)
    return nest(flat)
