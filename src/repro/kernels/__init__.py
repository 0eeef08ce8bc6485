"""Pallas kernels behind ONE fused-op backend (``repro.kernels.api``).

Each kernel package keeps kernel.py (the Pallas body: an elementwise ``expr``
for the shared flat launcher, or a shaped ``pl.pallas_call``) and ref.py (the
pure-jnp oracle used for parity sweeps and as every backward pass); ops.py is
now just the :class:`~repro.kernels.api.FusedOp` registration plus thin
deprecated legacy wrappers.  Platform dispatch (TPU kernel / interpret /
ref), tile policy, custom VJPs and the bucketed whole-pytree executor
``tree_apply`` all live once in ``api``.

Importing this package populates the registry:

    elementwise (tree_apply-able): mvr_update, axpby, add_sub,
                                   dse_combine, dse_combine_yh,
                                   qsgd_quantize, qsgd_dequantize
    shaped:                        flash_attention, rms_norm, wkv_chunk
    no kernel (XLA oracle):        top_k_pack, top_k_unpack
"""
from . import api
from . import (
    comm_compress,
    dse_combine,
    flash_attention,
    mvr_update,
    rms_norm,
    tree_math,
    wkv_chunk,
)
from .api import (
    REGISTRY,
    FusedOp,
    TilePolicy,
    call,
    call_counts,
    dispatch_mode,
    launch_counts,
    register,
    reset_counters,
    tree_add_sub,
    tree_apply,
    tree_axpby,
    tree_dse_combine,
    tree_dse_combine_yh,
    tree_mvr_update,
)

__all__ = [
    "api",
    "flash_attention", "rms_norm", "mvr_update", "wkv_chunk",
    "tree_math", "dse_combine", "comm_compress",
    "FusedOp", "TilePolicy", "REGISTRY", "register",
    "call", "tree_apply", "dispatch_mode",
    "tree_mvr_update", "tree_axpby", "tree_add_sub",
    "tree_dse_combine", "tree_dse_combine_yh",
    "launch_counts", "call_counts", "reset_counters",
]
