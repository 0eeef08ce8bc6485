"""Registry entries for the communication-compression fused ops.

Consumed by ``repro.compression.compressors`` — the QSGD quantize/dequantize
and top-k pack/unpack hot paths of every compressed gossip message dispatch
through ``api.call`` here (bucketed flat Pallas launch on TPU, fused jnp
oracle elsewhere, interpret force-able for CI parity).

top-k pack/unpack are registered WITHOUT a kernel.  Their one-hot MXU
kernels built a (k, 512) one-hot per d-block: at one Gemma-2 MLP leaf
(2304 x 9216) and ``top_k:0.1`` that is k ~ 2.1 M, a ~4.3 GB one-hot and
work growing as d * k, which the TPU compiler could not finish.  The XLA
gather/scatter oracles are the path on every platform until a kernel that
scales with k alone exists."""
from __future__ import annotations

from .. import api
from .kernel import qsgd_dequantize_expr, qsgd_quantize_expr
from .ref import (
    qsgd_dequantize_ref,
    qsgd_quantize_ref,
    top_k_pack_ref,
    top_k_unpack_ref,
)

__all__ = []

api.register(
    api.FusedOp(
        name="qsgd_quantize",
        expr=qsgd_quantize_expr,
        ref_fn=qsgd_quantize_ref,
        n_inputs=2,            # normalized x, uniform noise
        n_outputs=1,
        n_scalars=1,           # levels
        out_dtype_from=(0,),
        doc="stochastic uint8-grid quantization of a normalized buffer",
    )
)

api.register(
    api.FusedOp(
        name="qsgd_dequantize",
        expr=qsgd_dequantize_expr,
        ref_fn=qsgd_dequantize_ref,
        n_inputs=2,            # q (int8 payload, upcast in-kernel), scale bcast
        n_outputs=1,
        n_scalars=1,           # 1/levels
        out_dtype_from=(1,),   # the fp32 scale's dtype, NOT the int8 payload's
        doc="dequantize q * scale / levels",
    )
)


api.register(
    api.FusedOp(
        name="top_k_pack",
        ref_fn=top_k_pack_ref,
        n_inputs=2,            # x (N, d), idx (N, k)
        doc="gather the packed top-k payload vals[i,j] = x[i, idx[i,j]]",
    )
)

api.register(
    api.FusedOp(
        name="top_k_unpack",
        ref_fn=top_k_unpack_ref,
        n_inputs=2,            # idx (N, k), vals (N, k); static d
        doc="scatter the packed payload back to a dense (N, d) buffer",
    )
)
