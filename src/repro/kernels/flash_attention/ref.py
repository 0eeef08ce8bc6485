"""Pure-jnp oracle for the flash-attention kernel (GQA + causal +
sliding-window + score softcap).  Logits are ``q·kᵀ``: q arrives scaled."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -2.0e38


def flash_attention_ref(
    q: jnp.ndarray,          # (B, Sq, H, D)
    k: jnp.ndarray,          # (B, Skv, K, D)
    v: jnp.ndarray,          # (B, Skv, K, D)
    causal: bool = True,
    sliding_window: Optional[int] = None,
    softcap: Optional[float] = None,
) -> jnp.ndarray:
    b, sq, h, d = q.shape
    skv, kh = k.shape[1], k.shape[2]
    assert h % kh == 0
    qg = q.reshape(b, sq, kh, h // kh, d)
    scores = jnp.einsum("bskgd,btkd->bkgst", qg.astype(jnp.float32), k.astype(jnp.float32))
    if softcap is not None:
        scores = softcap * jnp.tanh(scores / softcap)
    qpos = jnp.arange(sq)[:, None]
    kpos = jnp.arange(skv)[None, :]
    mask = jnp.ones((sq, skv), bool)
    if causal:
        mask &= qpos >= kpos
    if sliding_window is not None:
        mask &= (qpos - kpos) < sliding_window
    scores = jnp.where(mask[None, None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgst,btkd->bskgd", probs, v.astype(jnp.float32))
    return out.reshape(b, sq, h, d).astype(q.dtype)
