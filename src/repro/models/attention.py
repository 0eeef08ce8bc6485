"""GQA attention: training/prefill path, decode path with KV cache.

Supports: grouped-query attention, causal or bidirectional masks, sliding
windows (gemma2 local layers; windowed ring-buffer cache at decode),
attention-score soft-capping, standard RoPE and M-RoPE.

Training/prefill dispatch (``attention_forward``): on the TPU a causal
self-attention with arange positions (no M-RoPE), whole kernel tiles along
the sequence and lane-dense heads runs the flash-attention kernel, forward
and backward; everything else, and every run off the TPU, runs ``_sdpa``.
``attn_impl='pallas'`` forces the kernel where it applies (interpret mode off
the TPU); ``'blockwise'`` is the pure-XLA online-softmax scan.  Decode always
runs ``_sdpa``.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..kernels import api as kernel_api
from ..kernels.flash_attention import kernel as flash_kernel
from .common import (
    Initializer, activation_spec, apply_mrope, apply_rope, logical_constraint, shard_local,
)

__all__ = ["AttentionConfig", "init_attention", "attention_forward", "init_kv_cache", "attention_decode"]

NEG_INF = -2.0e38


@dataclasses.dataclass(frozen=True)
class AttentionConfig:
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    causal: bool = True
    sliding_window: Optional[int] = None       # None = full attention
    attn_softcap: Optional[float] = None
    rope_theta: float = 10000.0
    mrope_sections: Optional[Tuple[int, int, int]] = None  # M-RoPE if set
    use_bias: bool = False
    qk_norm: bool = False
    attn_impl: str = "xla"                      # 'xla' | 'pallas' | 'blockwise'

    @property
    def q_per_kv(self) -> int:
        return self.n_heads // self.n_kv_heads


def init_attention(cfg: AttentionConfig, ini: Initializer):
    d, h, k, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    p = {
        "wq": ini.param((d, h, hd), ("embed", "heads", "head_dim")),
        "wk": ini.param((d, k, hd), ("embed", "kv_heads", "head_dim")),
        "wv": ini.param((d, k, hd), ("embed", "kv_heads", "head_dim")),
        "wo": ini.param((h, hd, d), ("heads", "head_dim", "embed")),
    }
    if cfg.use_bias:
        p["bq"] = ini.param((h, hd), ("heads", "head_dim"), init="zeros")
        p["bk"] = ini.param((k, hd), ("kv_heads", "head_dim"), init="zeros")
        p["bv"] = ini.param((k, hd), ("kv_heads", "head_dim"), init="zeros")
    if cfg.qk_norm:
        p["q_norm"] = ini.param((hd,), ("head_dim",), init="ones")
        p["k_norm"] = ini.param((hd,), ("head_dim",), init="ones")
    return p


def _project_qkv(cfg: AttentionConfig, params, x, positions, q_scale: float = 1.0):
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"].astype(x.dtype))
    if cfg.use_bias:
        q = q + params["bq"].astype(x.dtype)
        k = k + params["bk"].astype(x.dtype)
        v = v + params["bv"].astype(x.dtype)
    if cfg.qk_norm:
        from .common import rms_norm

        q = rms_norm(q, params["q_norm"])
        k = rms_norm(k, params["k_norm"])
    if cfg.mrope_sections is not None:
        q = apply_mrope(q, positions, cfg.rope_theta, cfg.mrope_sections)
        k = apply_mrope(k, positions, cfg.rope_theta, cfg.mrope_sections)
    else:
        q = apply_rope(q, positions, cfg.rope_theta, scale=q_scale)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _sdpa(cfg: AttentionConfig, q, k, v, q_pos, kv_pos, kv_mask=None):
    """Reference scaled-dot-product attention with GQA + window + softcap.

    q: (B, Sq, H, hd); k/v: (B, Skv, K, hd); *_pos: (B, Sq)/(B, Skv).
    """
    b, sq, h, hd = q.shape
    kgroups = cfg.n_kv_heads
    qpk = h // kgroups
    qg = q.reshape(b, sq, kgroups, qpk, hd)
    scores = jnp.einsum("bskgh,btkh->bkgst", qg.astype(jnp.float32), k.astype(jnp.float32))
    scores = scores / jnp.sqrt(hd).astype(jnp.float32)
    if cfg.attn_softcap is not None:
        scores = cfg.attn_softcap * jnp.tanh(scores / cfg.attn_softcap)
    mask = jnp.ones((b, sq, k.shape[1]), bool)
    delta = q_pos[:, :, None] - kv_pos[:, None, :]
    if cfg.causal:
        mask &= delta >= 0
    if cfg.sliding_window is not None:
        mask &= jnp.abs(delta) < cfg.sliding_window
    if kv_mask is not None:
        mask &= kv_mask[:, None, :]
    scores = jnp.where(mask[:, None, None, :, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgst,btkh->bskgh", probs, v.astype(jnp.float32))
    return out.reshape(b, sq, h, hd).astype(q.dtype)


def _blockwise_sdpa(cfg: AttentionConfig, q, k, v, q_pos, kv_pos, block: int = 512):
    """Flash-style attention in pure XLA: scan over KV blocks with an online
    softmax so the (Sq, Skv) score matrix is never materialized.

    This is the jit-level twin of the Pallas kernel (same math, XLA fusions
    instead of explicit VMEM tiles) and the memory-roofline fix for training:
    HBM traffic per layer drops from O(S^2) score tensors to O(S * block).
    """
    b, sq, h, hd = q.shape
    kgroups = cfg.n_kv_heads
    qpk = h // kgroups
    skv = k.shape[1]
    block = min(block, skv)
    assert skv % block == 0, (skv, block)
    nblk = skv // block
    qg = q.reshape(b, sq, kgroups, qpk, hd).astype(jnp.float32)
    scale = 1.0 / (hd ** 0.5)

    kb = k.reshape(b, nblk, block, kgroups, hd).swapaxes(0, 1)
    vb = v.reshape(b, nblk, block, kgroups, hd).swapaxes(0, 1)
    pb = kv_pos.reshape(b, nblk, block).swapaxes(0, 1)

    def body(carry, inp):
        acc, m_prev, l_prev = carry
        k_t, v_t, p_t = inp
        s = jnp.einsum("bskgh,btkh->bkgst", qg, k_t.astype(jnp.float32)) * scale
        if cfg.attn_softcap is not None:
            s = cfg.attn_softcap * jnp.tanh(s / cfg.attn_softcap)
        delta = q_pos[:, :, None] - p_t[:, None, :]
        mask = jnp.ones((b, sq, block), bool)
        if cfg.causal:
            mask &= delta >= 0
        if cfg.sliding_window is not None:
            mask &= jnp.abs(delta) < cfg.sliding_window
        s = jnp.where(mask[:, None, None], s, NEG_INF)
        m_cur = jnp.maximum(m_prev, s.max(axis=-1))
        corr = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur[..., None])
        l_cur = l_prev * corr + p.sum(axis=-1)
        acc = acc * corr[..., None] + jnp.einsum("bkgst,btkh->bkgsh", p, v_t.astype(jnp.float32))
        return (acc, m_cur, l_cur), ()

    acc0 = jnp.zeros((b, kgroups, qpk, sq, hd), jnp.float32)
    m0 = jnp.full((b, kgroups, qpk, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, kgroups, qpk, sq), jnp.float32)
    (acc, m, l), _ = jax.lax.scan(body, (acc0, m0, l0), (kb, vb, pb))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    out = out.transpose(0, 3, 1, 2, 4).reshape(b, sq, h, hd)
    return out.astype(q.dtype)


def _takes_flash(cfg: AttentionConfig, seq: int) -> bool:
    """Whether a (training / prefill) self-attention of ``seq`` positions
    runs the flash kernel: see the module docstring."""
    if not (cfg.causal and cfg.mrope_sections is None and flash_kernel.tiles(seq)):
        return False
    if cfg.attn_impl == "pallas":
        return True
    return (cfg.attn_impl == "xla" and kernel_api.resolve_mode() == "kernel"
            and flash_kernel.supported(seq, cfg.head_dim))


def _flash(cfg: AttentionConfig, q, k, v):
    """The flash kernel on each device's own rows and heads: GSPMD cannot
    partition a Pallas call and would gather q/k/v (a vmap over nodes with
    ``spmd_axis_name`` keeps each node on its own devices)."""
    mode = "kernel" if kernel_api.resolve_mode() == "kernel" else "interpret"

    def call(q, k, v):
        with kernel_api.dispatch_mode(mode):
            return kernel_api.call(
                "flash_attention", q, k, v,
                causal=True, sliding_window=cfg.sliding_window, softcap=cfg.attn_softcap,
            )

    # q's heads split with k's, so each device keeps whole GQA groups
    kv_spec = activation_spec(k.shape, "batch", None, "kv_heads", None)
    q_spec = P(kv_spec[0], None, kv_spec[2], None)
    return shard_local(call, q, k, v, specs=(q_spec, kv_spec, kv_spec), out_spec=q_spec)


def attention_forward(
    cfg: AttentionConfig,
    params,
    x: jnp.ndarray,
    positions: jnp.ndarray,
    return_cache: bool = False,
):
    """Full-sequence (training / prefill) attention.  x: (B, S, d)."""
    flash = _takes_flash(cfg, x.shape[1])
    # the kernel's logits are q·kᵀ: the 1/√D scale rides RoPE's float32 math
    q, k, v = _project_qkv(cfg, params, x, positions,
                           q_scale=cfg.head_dim ** -0.5 if flash else 1.0)
    q = logical_constraint(q, "batch", "seq", "heads", None)
    k = logical_constraint(k, "batch", "seq", "kv_heads", None)
    v = logical_constraint(v, "batch", "seq", "kv_heads", None)
    pos1 = positions[0] if cfg.mrope_sections is not None else positions
    if flash:
        kernel_api.count_call("attn.flash")
        out = _flash(cfg, q, k, v)
    elif cfg.attn_impl == "blockwise":
        out = _blockwise_sdpa(cfg, q, k, v, pos1, pos1)
    else:
        kernel_api.count_call("attn.sdpa")
        out = _sdpa(cfg, q, k, v, pos1, pos1)
    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(out.dtype))
    y = logical_constraint(y, "batch", "seq", "embed")
    if return_cache:
        return y, {"k": k, "v": v, "pos": pos1}
    return y


# --------------------------------------------------------------------------
# decode path
# --------------------------------------------------------------------------
def init_kv_cache(cfg: AttentionConfig, batch: int, max_len: int, dtype=jnp.bfloat16):
    """Ring-buffer KV cache.  For sliding-window layers the buffer is only
    ``window`` long — the sub-quadratic-memory decode path for gemma2 local
    layers at 500k context."""
    size = max_len if cfg.sliding_window is None else min(max_len, cfg.sliding_window)
    return {
        "k": jnp.zeros((batch, size, cfg.n_kv_heads, cfg.head_dim), dtype),
        "v": jnp.zeros((batch, size, cfg.n_kv_heads, cfg.head_dim), dtype),
        "pos": jnp.full((batch, size), -1, jnp.int32),  # -1 = empty slot
    }


def attention_decode(
    cfg: AttentionConfig,
    params,
    x: jnp.ndarray,           # (B, 1, d)
    position: jnp.ndarray,    # (B,) current token position
    cache,
):
    """Single-token decode against the ring-buffer cache."""
    if cfg.mrope_sections is not None:
        pos3 = jnp.broadcast_to(position[None, :, None], (3, x.shape[0], 1))
        q, k_new, v_new = _project_qkv(cfg, params, x, pos3)
    else:
        q, k_new, v_new = _project_qkv(cfg, params, x, position[:, None])
    size = cache["k"].shape[1]
    slot = position % size
    bidx = jnp.arange(x.shape[0])
    k = cache["k"].at[bidx, slot].set(k_new[:, 0].astype(cache["k"].dtype))
    v = cache["v"].at[bidx, slot].set(v_new[:, 0].astype(cache["v"].dtype))
    pos = cache["pos"].at[bidx, slot].set(position)
    kv_mask = pos >= 0
    out = _sdpa(cfg, q, k, v, position[:, None], pos, kv_mask=kv_mask)
    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(out.dtype))
    return y, {"k": k, "v": v, "pos": pos}
