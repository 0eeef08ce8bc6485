"""Flash attention on the TPU: JAX's splash-attention Pallas kernels.

The kernel bodies (forward, dq, dkv) and the block-sparse mask tables come
with JAX (``jax.experimental.pallas.ops.tpu.splash_attention``); this module
launches them for the repo's (B, S, H, D) layout:

  * GQA runs the kernels' MQA form, one launch per (batch row, kv head) over
    that head's group of q heads; the dkv kernel sums the group's gradients
    in VMEM.
  * the masks are block-sparse: key/value tiles wholly above the causal
    diagonal or outside the window are skipped (neither fetched nor
    computed), not masked; partial tiles mask by position in the kernel.
  * scores, running max, sum and logsumexp are float32; q·kᵀ and the
    backward's products take the inputs' dtype as MXU operands with float32
    accumulation.  No (S, S) tensor is written to HBM: the custom VJP keeps
    the output and the logsumexp, and dq, dk, dv come from the kernels.

The launchers are splash's, cut to that one case (MQA, head-minor layouts,
no segment ids, no sinks, masks computed from positions), and pass no
``metadata``: Mosaic prints it inside the custom call's HLO instruction over
several lines, which hides the call's ``op_name`` (its named scope) from a
line-by-line reader of the compiled module.

Logits are ``q·kᵀ``: the caller folds the 1/√D scale into q (the model does
it in float32 inside RoPE, so q is rounded once, as before).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.experimental.pallas.ops.tpu.splash_attention import splash_attention_kernel as sk
from jax.experimental.pallas.ops.tpu.splash_attention import splash_attention_mask as mask_lib
from jax.experimental.pallas.ops.tpu.splash_attention import splash_attention_mask_info as info_lib

#: q and kv tile of every kernel.  On a v5e at S = 2048, head_dim 128 a
#: layer's forward+backward took 6.92 / 3.72 / 3.58 ms at 256 / 512 / 1024
#: (PERF.md §6); 512 keeps the faster forward and skips more causal tiles.
#: Shorter sequences take one tile.
BLOCK = 512
LANE = 128
_SUBLANE = 8
_HEAD_MINOR = dict(q_layout=sk.QKVLayout.HEAD_DIM_MINOR, k_layout=sk.QKVLayout.HEAD_DIM_MINOR,
                   v_layout=sk.QKVLayout.HEAD_DIM_MINOR)


def block_size(seq: int) -> int:
    return min(BLOCK, seq)


def tiles(seq: int) -> bool:
    """Whether a sequence splits into whole lane-aligned tiles."""
    block = block_size(seq)
    return seq % block == 0 and block % LANE == 0


def supported(seq: int, head_dim: int) -> bool:
    """Whether the chip runs a (seq, head_dim) self-attention on the kernel:
    whole tiles, and heads that fill the MXU's lanes."""
    return tiles(seq) and head_dim % LANE == 0


def _next(h, i, j, data_next, block_mask, mask_next, next_i=False):
    return sk._next_nonzero(h, i, j, data_next, block_mask, mask_next, next_i=next_i)


def _forward(info, mask_fn, q, k, v, *, block, softcap, interpret):
    """Splash's forward kernel: out (G, S, D) and logsumexp (G, S)."""
    g, s, d = q.shape
    width = info.data_next.shape[-1]

    def kv_map(h, i, j, *refs):
        return _next(h, i, j, *refs)[0], 0

    rows = lambda h, i, j, *_: (h, i, 0)  # noqa: E731
    fixed = lambda *_: (0, 0)              # noqa: E731
    q_seq = None
    if info.q_sequence is not None:
        q_seq = jax.lax.broadcast_in_dim(jnp.asarray(info.q_sequence), (s, LANE), (0,))
    in_specs = [
        pl.BlockSpec((None, block, d), rows),
        pl.BlockSpec((block, d), kv_map),
        pl.BlockSpec((block, d), kv_map),
        None, None, None, None,              # segment ids, sinks, explicit mask blocks
        None if q_seq is None else pl.BlockSpec((block, LANE), lambda h, i, j, *_: (i, 0)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((block, LANE), jnp.float32),   # running max
        jax.ShapeDtypeStruct((block, LANE), jnp.float32),   # running sum
        jax.ShapeDtypeStruct((block, d), jnp.float32),      # accumulator
        jax.ShapeDtypeStruct((g, s, d), q.dtype),
        jax.ShapeDtypeStruct((g, s, LANE), jnp.float32),    # logsumexp
    ]
    out_specs = [pl.BlockSpec((block, LANE), fixed), pl.BlockSpec((block, LANE), fixed),
                 pl.BlockSpec((block, d), fixed), pl.BlockSpec((None, block, d), rows),
                 pl.BlockSpec((None, block, LANE), rows)]
    kernel = functools.partial(
        sk.flash_attention_kernel, mask_value=sk.DEFAULT_MASK_VALUE, grid_width=width,
        bq=block, bkv=block, bkv_compute=block, head_dim_v=d,
        attn_logits_soft_cap=softcap, mask_function=mask_fn, **_HEAD_MINOR,
    )
    *_, out, lse = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, in_specs=in_specs, out_specs=out_specs,
            grid=(g, s // block, width)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        out_shape=out_shape, name="flash_attention_fwd", interpret=interpret,
    )(info.data_next, info.block_mask, info.mask_next, q, k, v, None, None, None, None, q_seq)
    return out, lse[..., 0]


def _backward_dq(info, mask_fn, q, k, v, lse, do, di, *, block, softcap, interpret):
    """Splash's dq kernel: dq (G, S, D)."""
    g, s, d = q.shape
    width = info.data_next.shape[-1]

    def kv_map(h, i, j, *refs):
        return _next(h, i, j, *refs)[0], 0

    rows = lambda h, i, *_: (h, i, 0)    # noqa: E731
    stat = lambda h, i, *_: (h, 0, i)    # noqa: E731
    q_seq = None
    if info.q_sequence is not None:
        q_seq = jax.lax.broadcast_in_dim(jnp.asarray(info.q_sequence), (s, LANE), (0,))
    in_specs = [
        pl.BlockSpec((None, block, d), rows),
        pl.BlockSpec((block, d), kv_map),
        pl.BlockSpec((block, d), kv_map),
        None, None, None,                              # segment ids, sinks
        pl.BlockSpec((None, 1, block), stat),          # logsumexp
        pl.BlockSpec((None, block, d), rows),          # do
        pl.BlockSpec((None, 1, block), stat),          # di
        None,                                          # explicit mask blocks
        None if q_seq is None else pl.BlockSpec((block, LANE), lambda h, i, j, *_: (i, 0)),
    ]
    kernel = functools.partial(
        sk._flash_attention_dq_kernel, mask_value=sk.DEFAULT_MASK_VALUE, grid_width=width,
        bq=block, bkv=block, attn_logits_soft_cap=softcap, mask_function=mask_fn, **_HEAD_MINOR,
    )
    _, dq = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, in_specs=in_specs,
            out_specs=[pl.BlockSpec((block, d), lambda *_: (0, 0)),
                       pl.BlockSpec((None, block, d), rows)],
            grid=(g, s // block, width)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
        out_shape=[jax.ShapeDtypeStruct((block, d), jnp.float32),
                   jax.ShapeDtypeStruct(q.shape, q.dtype)],
        name="flash_attention_dq", interpret=interpret,
    )(info.data_next, info.block_mask, info.mask_next, q, k, v, None, None, None,
      lse[:, None, :], do, di[:, None, :], None, q_seq)
    return dq


def _backward_dkv(info, mask_fn, q, k, v, lse, do, di, *, block, softcap, interpret):
    """Splash's dkv kernel: dk and dv (S, D), summed over the G q heads."""
    g, s, d = q.shape
    width = info.data_next.shape[-2]

    def q_rows(kv, h, i, *refs):
        return h, _next(h, i, kv, *refs, next_i=True)[0], 0

    def q_stat(kv, h, i, *refs):
        return h, 0, _next(h, i, kv, *refs, next_i=True)[0]

    def q_cols(kv, h, i, *refs):
        return 0, _next(h, i, kv, *refs, next_i=True)[0]

    kv_rows = lambda kv, *_: (kv, 0)   # noqa: E731
    fixed = lambda *_: (0, 0)          # noqa: E731
    q_seq = None
    if info.q_sequence is not None:
        q_seq = jax.lax.broadcast_in_dim(jnp.asarray(info.q_sequence), (_SUBLANE, s), (1,))
    # logsumexp and di ride 8 sublanes, as the kernel reads them
    stats = lambda x: jnp.broadcast_to(x[:, None, :], (g, _SUBLANE, s))  # noqa: E731
    in_specs = [
        pl.BlockSpec((None, block, d), q_rows),
        pl.BlockSpec((block, d), kv_rows),
        pl.BlockSpec((block, d), kv_rows),
        None, None, None,                              # segment ids, sinks
        pl.BlockSpec((None, _SUBLANE, block), q_stat),  # logsumexp
        pl.BlockSpec((None, block, d), q_rows),         # do
        pl.BlockSpec((None, _SUBLANE, block), q_stat),  # di
        None,                                           # explicit mask blocks
        None if q_seq is None else pl.BlockSpec((_SUBLANE, block), q_cols),
    ]
    out_shape = [
        None,                                                # dq scratch (fused kernel only)
        jax.ShapeDtypeStruct((block, d), jnp.float32),       # dk accumulator
        jax.ShapeDtypeStruct((block, d), jnp.float32),       # dv accumulator
        None,                                                # dq (fused kernel only)
        jax.ShapeDtypeStruct(k.shape, k.dtype),
        jax.ShapeDtypeStruct(v.shape, v.dtype),
    ]
    out_specs = [None, pl.BlockSpec((block, d), fixed), pl.BlockSpec((block, d), fixed), None,
                 pl.BlockSpec((block, d), kv_rows), pl.BlockSpec((block, d), kv_rows)]
    kernel = functools.partial(
        sk._flash_attention_dkv_kernel, mask_value=sk.DEFAULT_MASK_VALUE, num_q_heads=g,
        num_kv_heads=1, grid_width=width, bq=block, bkv_compute=block, is_mqa=True,
        attn_logits_soft_cap=softcap, bkv=block, mask_function=mask_fn, **_HEAD_MINOR,
    )
    *_, dk, dv = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, in_specs=in_specs, out_specs=out_specs,
            grid=(s // block, g, width)),
        # the kv tiles accumulate over heads and q tiles: no axis is parallel
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
        out_shape=out_shape, name="flash_attention_dkv", interpret=interpret,
    )(info.data_next, info.block_mask, info.mask_next, q, k, v, None, None, None,
      stats(lse), do, stats(di), None, q_seq)
    return dk, dv


@functools.lru_cache(maxsize=None)
def _attend(seq: int, group: int, causal: bool, window: Optional[int],
            softcap: Optional[float], interpret: bool):
    """The differentiable (G, S, D) × (S, D) × (S, D) attention of one kv
    head, with its mask tables built once (numpy constants)."""
    if window is not None:
        mask = mask_lib.LocalMask((seq, seq), (window - 1, 0 if causal else None), 0)
    elif causal:
        mask = mask_lib.CausalMask((seq, seq))
    else:
        mask = mask_lib.FullMask((seq, seq))
    heads = mask_lib.MultiHeadMask([mask] * group)
    block = block_size(seq)
    fwd_info, mask_fn = info_lib.process_mask(heads, (block, block))
    dkv_info, _ = info_lib.process_mask_dkv(heads, (block, block))
    # these masks come out as block tables (and, causal or windowed, a mask
    # function of positions): never as explicit mask blocks
    assert fwd_info.partial_mask_blocks is None and dkv_info.partial_mask_blocks is None
    static = dict(block=block, softcap=softcap, interpret=interpret)

    @jax.custom_vjp
    def attend(q, k, v):
        return _forward(fwd_info, mask_fn, q, k, v, **static)[0]

    def fwd(q, k, v):
        out, lse = _forward(fwd_info, mask_fn, q, k, v, **static)
        return out, (q, k, v, out, lse)

    def bwd(res, do):
        q, k, v, out, lse = res
        di = jnp.einsum("hsd,hsd->hs", out.astype(jnp.float32), do.astype(jnp.float32))
        dq = _backward_dq(fwd_info, mask_fn, q, k, v, lse, do, di, **static)
        dk, dv = _backward_dkv(dkv_info, mask_fn, q, k, v, lse, do, di, **static)
        return dq, dk, dv

    attend.defvjp(fwd, bwd)
    return attend


def flash_attention(
    q: jax.Array,            # (B, S, H, D), pre-scaled
    k: jax.Array,            # (B, S, K, D)
    v: jax.Array,            # (B, S, K, D)
    causal: bool = True,
    sliding_window: Optional[int] = None,
    softcap: Optional[float] = None,
    interpret: bool = False,
) -> jax.Array:
    b, s, h, d = q.shape
    kh = k.shape[2]
    if h % kh or k.shape[1] != s or not tiles(s):
        raise ValueError(f"flash attention cannot take q {q.shape}, k {k.shape}")
    g = h // kh
    attend = _attend(s, g, causal, sliding_window, softcap, interpret)
    qt = q.reshape(b, s, kh, g, d).transpose(0, 2, 3, 1, 4)     # (B, K, G, S, D)
    out = jax.vmap(jax.vmap(attend))(qt, k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3))
    return out.transpose(0, 3, 1, 2, 4).reshape(b, s, h, d)
