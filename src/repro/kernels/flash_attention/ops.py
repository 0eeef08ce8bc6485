"""Registry entry + legacy wrapper for the flash-attention kernel.

Canonical entry:
``api.call("flash_attention", q, k, v, causal=..., sliding_window=..., softcap=...)``
with q already scaled by 1/√D.  The kernel carries its own VJP (splash's dq
and dkv kernels), so a differentiated call runs kernels both ways; the jnp
oracle is the parity target and the path off the TPU.
"""
from __future__ import annotations

from .. import api
from .kernel import flash_attention as _flash_kernel_call
from .ref import flash_attention_ref

__all__ = ["flash_attention"]


api.register(
    api.FusedOp(
        name="flash_attention",
        kernel_fn=_flash_kernel_call,
        ref_fn=flash_attention_ref,
        n_inputs=3,
        kernel_vjp=True,
        doc="online-softmax attention, (B, S, H, D) layout, GQA/window/softcap",
    )
)


def flash_attention(q, k, v, causal=True, sliding_window=None, softcap=None):
    """DEPRECATED: use ``api.call('flash_attention', q, k, v, ...)``."""
    api.deprecated_entry(
        "kernels.flash_attention.flash_attention", "api.call('flash_attention', ...)"
    )
    return api.call(
        "flash_attention", q, k, v,
        causal=causal, sliding_window=sliding_window, softcap=softcap,
    )
