"""The benchmark's weights: one jitted call from the seed, in float32, in the
parameter layout the trainer takes (``{"embed", "blocks": {"b0": ...},
"final_norm", ...}``, layers stacked on a leading axis).

The reference builds the same tree from the same seed with this function, so
it takes no weights that the program made.
"""
from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp

__all__ = ["param_shapes", "make_params", "nest"]


def param_shapes(m: dict) -> dict:
    """``{path: (shape, init, scale)}`` for configuration ``m`` (its ``model``
    block).  ``init`` is ``normal`` (scale = standard deviation), ``ones``
    or ``zeros``.  Only a text decoder with an untied head and no biases is
    written out (the benchmark's configurations); another is refused."""
    other = {k: m[k] for k in ("use_bias", "tie_embeddings", "n_vision_tokens", "mrope_sections")
             if m.get(k)}
    if other:
        raise ValueError(f"the benchmark's weights and reference do not cover {other}")
    d, L, V = m["d_model"], m["n_layers"], m["vocab_size"]
    h, k, hd, f = m["n_heads"], m["n_kv_heads"], m["head_dim"], m["d_ff"]
    s = {
        "embed": ((V, d), "normal", 0.02),
        "final_norm": ((d,), "ones", None),
        "blocks/b0/norm1": ((L, d), "ones", None),
        "blocks/b0/norm2": ((L, d), "ones", None),
        "blocks/b0/attn/wq": ((L, d, h, hd), "normal", 1 / math.sqrt(d)),
        "blocks/b0/attn/wk": ((L, d, k, hd), "normal", 1 / math.sqrt(d)),
        "blocks/b0/attn/wv": ((L, d, k, hd), "normal", 1 / math.sqrt(d)),
        "blocks/b0/attn/wo": ((L, h, hd, d), "normal", 1 / math.sqrt(h * hd)),
        "blocks/b0/ffn/w_gate": ((L, d, f), "normal", 1 / math.sqrt(d)),
        "blocks/b0/ffn/w_up": ((L, d, f), "normal", 1 / math.sqrt(d)),
        "blocks/b0/ffn/w_down": ((L, f, d), "normal", 1 / math.sqrt(f)),
        "lm_head": ((d, V), "normal", 1 / math.sqrt(d)),
    }
    return s


def nest(flat: dict) -> dict:
    out: dict = {}
    for path, v in flat.items():
        *head, last = path.split("/")
        node = out
        for p in head:
            node = node.setdefault(p, {})
        node[last] = v
    return out


def make_params(m: dict, key) -> dict:
    """Float32 weights of configuration ``m`` from ``key`` (traceable: call
    it inside one ``jax.jit``).  Each leaf draws from its own key, folded in
    from a hash of its path, so a leaf does not depend on the others."""
    flat = {}
    for path, (shape, init, scale) in param_shapes(m).items():
        if init in ("ones", "zeros"):
            flat[path] = jnp.full(shape, float(init == "ones"), jnp.float32)
        else:
            sub = jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)
            flat[path] = scale * jax.random.normal(sub, shape, jnp.float32)
    return nest(flat)
