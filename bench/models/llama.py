"""The Llama-architecture decoder (Yi): grouped-query attention with
rotate-half RoPE, RMSNorm, a SwiGLU MLP, no biases and an untied head.

One of the benchmark's architecture modules.  A configuration names its
module by path (``"arch"`` in ``bench/configs/<name>.json``), and the
harness asks it for what depends on the architecture:

* ``param_shapes(m)``: the parameter layout the trainer takes, as
  ``{path: (shape, init, scale)}``;
* ``loss_fn(m, params, batch, mm)``: the float32 mean next-token loss of
  one node's batch, every matmul through ``mm(spec, a, b)``, which the
  reference gives its precision and, for the control, its rounding;
* ``pass_flops_per_position(m, seq)``: the model FLOPs of one gradient
  pass per sequence position, by the rules of ``bench/flops.py``.

Written from the published description, importing nothing of the program.
RMSNorm's epsilon is 1e-6, where the configuration states the program's.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

__all__ = ["param_shapes", "loss_fn", "pass_flops_per_position", "matmul_params"]


def param_shapes(m: dict) -> dict:
    """``{path: (shape, init, scale)}`` for configuration ``m`` (its ``model``
    block).  ``init`` is ``normal`` (scale = standard deviation), ``ones``
    or ``zeros``.  Only a text decoder with an untied head and no biases is
    written out; another is refused."""
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


def _rms(x, w, eps=1e-6):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rotate(x, ang):
    """Rotate-half RoPE: x (b, S, H, hd), ang (b, S, hd/2)."""
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _angles(m, b, S):
    """Rotary angles (b, S, hd/2) of positions 0 .. S-1."""
    half = m["head_dim"] // 2
    inv = 1.0 / (m["rope_theta"] ** (np.arange(half, dtype=np.float64) / half))
    ang = jnp.asarray(np.arange(S, dtype=np.float64)[:, None] * inv, jnp.float32)
    return jnp.broadcast_to(ang[None], (b, S, half))


def _layer(m, mm, x, lp, ang):
    h, k, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    b, S, _ = x.shape
    a = _rms(x, lp["norm1"])
    q = mm("bsd,dhk->bshk", a, lp["attn"]["wq"])
    kk = mm("bsd,dhk->bshk", a, lp["attn"]["wk"])
    v = mm("bsd,dhk->bshk", a, lp["attn"]["wv"])
    q, kk = _rotate(q, ang), _rotate(kk, ang)
    kk = jnp.repeat(kk, h // k, axis=2)       # query head j reads kv head j // (h/k)
    v = jnp.repeat(v, h // k, axis=2)
    s = mm("bshk,bthk->bhst", q, kk) / np.sqrt(hd)
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    o = mm("bhst,bthk->bshk", jax.nn.softmax(s, axis=-1), v)
    x = x + mm("bshk,hkd->bsd", o, lp["attn"]["wo"])
    a = _rms(x, lp["norm2"])
    g = mm("bsd,df->bsf", a, lp["ffn"]["w_gate"])
    u = mm("bsd,df->bsf", a, lp["ffn"]["w_up"])
    return x + mm("bsf,fd->bsd", jax.nn.silu(g) * u, lp["ffn"]["w_down"])


def loss_fn(m: dict, params, batch, mm):
    """Mean next-token cross-entropy of one node's batch; each layer is
    rematerialised."""
    x = params["embed"][batch["tokens"]]
    b, S, _ = x.shape
    ang = _angles(m, b, S)
    layer = jax.checkpoint(lambda x, lp: _layer(m, mm, x, lp, ang))
    blocks = params["blocks"]["b0"]
    for i in range(m["n_layers"]):
        x = layer(x, jax.tree.map(lambda p: p[i], blocks))
    x = _rms(x, params["final_norm"])
    logits = mm("bsd,dv->bsv", x, params["lm_head"])
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, batch["targets"][..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)


def matmul_params(m: dict) -> int:
    """Weights that enter a matmul at every position (blocks and head)."""
    d, h, k, hd, f = m["d_model"], m["n_heads"], m["n_kv_heads"], m["head_dim"], m["d_ff"]
    if m["activation"] not in ("silu", "gelu"):
        raise ValueError(f"ungated MLP ({m['activation']}) not counted")
    attn = d * hd * (2 * h + 2 * k)
    mlp = 3 * d * f
    return m["n_layers"] * (attn + mlp) + d * m["vocab_size"]


def pass_flops_per_position(m: dict, seq: int) -> float:
    """Forward + backward FLOPs of one gradient pass, averaged over the
    ``seq`` positions of a sequence: 6 x the matmul weights, and causal
    attention, 6 x layers x heads x head_dim x seq."""
    attn = 6 * m["n_layers"] * m["n_heads"] * m["head_dim"] * seq
    return 6 * matmul_params(m) + attn
