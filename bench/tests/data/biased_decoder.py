"""An architecture module that no configuration of the benchmark uses: the
llama decoder with biases on the q, k and v projections and on the MLP's up
and down projections (the program's ``use_bias`` layout).  It stands for a
new family, brought as a file of its own (``test_bench_new_family.py``)."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


def param_shapes(m: dict) -> dict:
    if not m.get("use_bias") or m.get("tie_embeddings"):
        raise ValueError("this module writes out an untied decoder with biases only")
    d, L, V = m["d_model"], m["n_layers"], m["vocab_size"]
    h, k, hd, f = m["n_heads"], m["n_kv_heads"], m["head_dim"], m["d_ff"]
    return {
        "embed": ((V, d), "normal", 0.02),
        "final_norm": ((d,), "ones", None),
        "blocks/b0/norm1": ((L, d), "ones", None),
        "blocks/b0/norm2": ((L, d), "ones", None),
        "blocks/b0/attn/wq": ((L, d, h, hd), "normal", 1 / math.sqrt(d)),
        "blocks/b0/attn/wk": ((L, d, k, hd), "normal", 1 / math.sqrt(d)),
        "blocks/b0/attn/wv": ((L, d, k, hd), "normal", 1 / math.sqrt(d)),
        "blocks/b0/attn/wo": ((L, h, hd, d), "normal", 1 / math.sqrt(h * hd)),
        "blocks/b0/attn/bq": ((L, h, hd), "zeros", None),
        "blocks/b0/attn/bk": ((L, k, hd), "zeros", None),
        "blocks/b0/attn/bv": ((L, k, hd), "zeros", None),
        "blocks/b0/ffn/w_gate": ((L, d, f), "normal", 1 / math.sqrt(d)),
        "blocks/b0/ffn/w_up": ((L, d, f), "normal", 1 / math.sqrt(d)),
        "blocks/b0/ffn/w_down": ((L, f, d), "normal", 1 / math.sqrt(f)),
        "blocks/b0/ffn/b_up": ((L, f), "zeros", None),
        "blocks/b0/ffn/b_down": ((L, d), "zeros", None),
        "lm_head": ((d, V), "normal", 1 / math.sqrt(d)),
    }


def _rms(x, w, eps=1e-6):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rotate(x, ang):
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _block(m, mm, x, lp, ang):
    h, k, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    S = x.shape[1]
    at, ff = lp["attn"], lp["ffn"]
    a = _rms(x, lp["norm1"])
    q = _rotate(mm("bsd,dhk->bshk", a, at["wq"]) + at["bq"], ang)
    kk = _rotate(mm("bsd,dhk->bshk", a, at["wk"]) + at["bk"], ang)
    v = mm("bsd,dhk->bshk", a, at["wv"]) + at["bv"]
    kk, v = jnp.repeat(kk, h // k, axis=2), jnp.repeat(v, h // k, axis=2)
    s = mm("bshk,bthk->bhst", q, kk) / np.sqrt(hd)
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    x = x + mm("bshk,hkd->bsd", mm("bhst,bthk->bshk", jax.nn.softmax(s, axis=-1), v), at["wo"])
    a = _rms(x, lp["norm2"])
    u = mm("bsd,df->bsf", a, ff["w_up"]) + ff["b_up"]
    g = mm("bsd,df->bsf", a, ff["w_gate"])
    return x + mm("bsf,fd->bsd", jax.nn.silu(g) * u, ff["w_down"]) + ff["b_down"]


def loss_fn(m: dict, params, batch, mm):
    x = params["embed"][batch["tokens"]]
    b, S, _ = x.shape
    half = m["head_dim"] // 2
    inv = 1.0 / (m["rope_theta"] ** (np.arange(half, dtype=np.float64) / half))
    ang = jnp.asarray(np.arange(S, dtype=np.float64)[:, None] * inv, jnp.float32)
    ang = jnp.broadcast_to(ang[None], (b, S, half))
    block = jax.checkpoint(lambda x, lp: _block(m, mm, x, lp, ang))
    for i in range(m["n_layers"]):
        x = block(x, jax.tree.map(lambda p: p[i], params["blocks"]["b0"]))
    logits = mm("bsd,dv->bsv", _rms(x, params["final_norm"]), params["lm_head"])
    gold = jnp.take_along_axis(logits, batch["targets"][..., None], axis=-1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - gold)


def pass_flops_per_position(m: dict, seq: int) -> float:
    d, h, k, hd, f = m["d_model"], m["n_heads"], m["n_kv_heads"], m["head_dim"], m["d_ff"]
    weights = m["n_layers"] * (d * hd * (2 * h + 2 * k) + 3 * d * f) + d * m["vocab_size"]
    return 6 * weights + 6 * m["n_layers"] * h * hd * seq
