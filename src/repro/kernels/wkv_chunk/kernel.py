"""Chunked RWKV-6 wkv Pallas TPU kernel.

The VMEM-resident form of the chunked linear-attention recurrence
(EXPERIMENTS.md §Perf A1): the XLA-level chunked path still streams the
(P, P) state and the fp32 r̃/k̃ temporaries through HBM between scan
iterations — here the state lives in VMEM scratch across the chunk grid
dimension and the decay-weighted temporaries exist only in registers.

Grid = (batch, heads, S / CHUNK); TPU executes the last grid dim
sequentially, so the per-(b, h) state scratch persists across chunks (the
same carry idiom as the flash-attention kernel).  Per chunk:

    cum_t  = cumsum(logw)                      (fp32, tril-ones matmul)
    r~     = r * exp(cum_{t-1}),  k~ = k * exp(-cum_t)     [clamped ±25]
    y      = tril(r~ k~^T, -1) v  +  r~ S                  (MXU)
    S     <- exp(cum_L) ⊙ S + (k * exp(cum_L - cum_t))^T v (MXU)

Chunk length defaults to 16: the fp32 clamp on exp(±cum) bounds the safe
within-chunk decay range (measured in EXPERIMENTS A1 — the same reason GLA
kernels sub-chunk); P=64 keeps the (P, P) state one MXU tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CLAMP = 25.0
DEFAULT_CHUNK = 16


def _wkv_kernel(r_ref, k_ref, v_ref, w_ref, y_ref, sfin_ref, s_ref, *, num_chunks: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    r = r_ref[0, 0].astype(jnp.float32)        # (L, P)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    w = w_ref[0, 0].astype(jnp.float32)        # log decay, < 0

    lc = r.shape[0]
    li = jax.lax.broadcasted_iota(jnp.int32, (lc, lc), 0)
    lj = jax.lax.broadcasted_iota(jnp.int32, (lc, lc), 1)
    # inclusive cumsum as a lower-triangular matmul (Mosaic has no cumsum)
    cum = jax.lax.dot_general(
        (li >= lj).astype(jnp.float32), w, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST, preferred_element_type=jnp.float32,
    )                                           # (L, P)
    cex = cum - w                               # exclusive
    total = jnp.sum(w, axis=0, keepdims=True)   # (1, P)
    # the same sum as a column, for the row scaling of S (no in-kernel
    # transpose of a vector)
    total_col = jax.lax.dot_general(
        w, jnp.ones((lc, 1), jnp.float32), (((0,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST, preferred_element_type=jnp.float32,
    )                                           # (P, 1)

    r_t = r * jnp.exp(jnp.maximum(cex, -CLAMP))
    k_t = k * jnp.exp(jnp.minimum(-cum, CLAMP))
    scores = jax.lax.dot_general(
        r_t, k_t, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (L, L)
    scores = jnp.where(li > lj, scores, 0.0)    # strict lower: y_t uses S_{t-1}
    y = jax.lax.dot_general(
        scores, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    s = s_ref[...]
    y = y + jax.lax.dot_general(
        r_t, s, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    k_s = k * jnp.exp(jnp.maximum(total - cum, -CLAMP))
    ds = jax.lax.dot_general(
        k_s, v, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )  # (P, P)
    s_ref[...] = jnp.exp(total_col) * s + ds
    y_ref[0, 0] = y.astype(y_ref.dtype)

    @pl.when(ci == num_chunks - 1)
    def _final():
        sfin_ref[0, 0] = s_ref[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv_chunk_fwd(
    r: jnp.ndarray,       # (B, S, H, P)
    k: jnp.ndarray,
    v: jnp.ndarray,
    logw: jnp.ndarray,
    chunk: int = DEFAULT_CHUNK,
    interpret: bool = False,
):
    b, s, h, p = r.shape
    assert s % chunk == 0, (s, chunk)
    num_chunks = s // chunk
    kernel = functools.partial(_wkv_kernel, num_chunks=num_chunks)
    grid = (b, h, num_chunks)
    # heads-major (B, H, S, P) so a block's last two dims are (chunk, P): the
    # TPU tiling needs them divisible by (8, 128) or equal to the array's,
    # which a (1, P) head slice of the (B, S, H, P) layout is not
    r, k, v, logw = (t.swapaxes(1, 2) for t in (r, k, v, logw))
    tile = pl.BlockSpec((1, 1, chunk, p), lambda bi, hi, ci: (bi, hi, ci, 0))
    y, s_final = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[tile, tile, tile, tile],
        out_specs=[
            tile,
            pl.BlockSpec((1, 1, p, p), lambda bi, hi, ci: (bi, hi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, s, p), jnp.float32),
            jax.ShapeDtypeStruct((b, h, p, p), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((p, p), jnp.float32)],
        interpret=interpret,
    )(r, k, v, logw)
    return y.swapaxes(1, 2), s_final
