"""Fused-op backend: ONE declarative kernel API for the whole compute layer.

Before this module, each kernel package (``flash_attention``, ``rms_norm``,
``mvr_update``, ``wkv_chunk``) re-implemented its own ``_on_tpu()`` check,
interpret fallback, block-size selection and ref-backed custom VJP, and the
algorithm hot loop (the paper's MVR inner update and dual-slow combines)
never reached the hand-written kernels at all — it ran as per-leaf
``jax.tree.map`` jnp ops.  This module replaces all of that with:

  * :class:`FusedOp` — a declarative registration: ``ref_fn`` (pure-jnp
    oracle, also the backward pass), either an elementwise ``expr`` (compiled
    through the shared flat Pallas launcher) or a shaped ``kernel_fn``
    (wrapping the package's ``pl.pallas_call``), a :class:`TilePolicy`, and
    output-dtype rules.  ``register()`` wires the dispatch + custom VJP once.
  * platform dispatch — one mode resolver (``kernel`` on TPU, ``ref``
    elsewhere; ``interpret`` force-able via :func:`dispatch_mode`, or off the
    TPU via the ``REPRO_FUSED_MODE`` env var, which a TPU refuses) instead
    of four copy-pasted ``_on_tpu()`` helpers.  Every dispatch is
    differentiable: backward always runs the jnp oracle through
    ``jax.vjp``.
  * :func:`tree_apply` — the bucketed executor.  A whole parameter pytree is
    flattened into contiguous, lane-padded 1-D buffers (grouped by dtype
    signature) so ONE kernel launch covers a tree of up to 64M elements
    (larger trees: one per 64M-element group) instead of one launch (or one
    XLA fusion) per leaf.  Padding to a lane multiple replaces
    the old ``while n % blk: blk //= 2`` halving loop that degraded
    odd-length buffers to tiny blocks or the ref fallback.

Launch accounting (``launch_counts`` / ``call_counts``) happens at dispatch
(i.e. trace) time, which is what the one-launch-per-op-per-step tests
assert on.  NOTE: the mode is resolved when a computation is *traced*;
closures already jitted keep the mode they were traced under.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import os
import warnings
from collections import Counter
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

PyTree = Any

__all__ = [
    "FusedOp", "TilePolicy", "REGISTRY", "register", "get", "ceil_to",
    "dispatch_mode", "resolve_mode", "on_tpu", "MODES",
    "call", "tree_apply",
    "tree_mvr_update", "tree_axpby", "tree_add_sub",
    "tree_dse_combine", "tree_dse_combine_yh",
    "launch_counts", "call_counts", "count_call", "reset_counters",
]

LANE = 128           # TPU lane width: flat buffers are padded to multiples
MODES = ("kernel", "interpret", "ref")


def on_tpu() -> bool:
    """True when JAX's default device is a TPU.  A backend that fails to
    start raises here: the program must not fall back to the CPU unseen."""
    return jax.devices()[0].platform == "tpu"


# CPU-side CI knob (e.g. ``REPRO_FUSED_MODE=interpret``); refused on a TPU
_env_mode: Optional[str] = (
    os.environ.get("REPRO_FUSED_MODE", "").strip().lower() or None
)
if _env_mode is not None and _env_mode not in MODES:
    raise ValueError(f"REPRO_FUSED_MODE={_env_mode!r} not in {MODES}")

_mode_override: Optional[str] = None   # set by dispatch_mode()


def resolve_mode() -> str:
    """Current dispatch mode: a :func:`dispatch_mode` block if one is open,
    else kernel on TPU / ``REPRO_FUSED_MODE`` or ref elsewhere."""
    if _mode_override is not None:
        return _mode_override
    if on_tpu():
        if _env_mode is not None:
            raise RuntimeError(
                f"REPRO_FUSED_MODE={_env_mode!r} is set but the device is a "
                "TPU, which always runs the kernels; unset it (tests force a "
                "mode in code with api.dispatch_mode)"
            )
        return "kernel"
    return _env_mode or "ref"


@contextlib.contextmanager
def dispatch_mode(mode: str):
    """Force a dispatch mode ("kernel" | "interpret" | "ref") for the block.

    Trace-time: applies to computations traced inside the block; functions
    jitted *before* entering keep whatever mode they were traced under.
    """
    if mode not in MODES:
        raise ValueError(f"mode {mode!r} not in {MODES}")
    global _mode_override
    prev = _mode_override
    _mode_override = mode
    try:
        yield
    finally:
        _mode_override = prev


# ---------------------------------------------------------------- accounting
_launches: Counter = Counter()   # pallas_call dispatches (kernel/interpret)
_calls: Counter = Counter()      # registry dispatches, any mode (incl. ref)


def launch_counts() -> Dict[str, int]:
    """Kernel launches per op since the last reset (trace-time count)."""
    return dict(_launches)


def call_counts() -> Dict[str, int]:
    """Registry dispatches per op since the last reset (any mode)."""
    return dict(_calls)


def reset_counters() -> None:
    _launches.clear()
    _calls.clear()


def count_call(name: str) -> None:
    """Count one trace-time event under ``name`` in :func:`call_counts`
    (callers that choose between a kernel and a plain path record which)."""
    _calls[name] += 1


def _count(name: str, mode: str) -> None:
    count_call(name)
    if mode != "ref":
        _launches[name] += 1


# ---------------------------------------------------------------- tile policy
def ceil_to(n: int, m: int) -> int:
    """Smallest multiple of ``m`` that is >= ``n`` (tile-rounding helper,
    part of the TilePolicy contract — shaped launchers use it too)."""
    return -(-n // m) * m


@dataclasses.dataclass(frozen=True)
class TilePolicy:
    """How a flat buffer is tiled into kernel blocks.

    Buffers are PADDED up to a lane multiple (and, above ``max_block``, to a
    block multiple) — never shrunk to whatever power of two happens to divide
    ``n``.  The old halving loop turned an odd-length buffer into 1-element
    blocks and fell back to the oracle; padding wastes at most
    ``max_block - 1`` trailing elements and keeps every size on the kernel
    path with full-width tiles.  The kernel sees a buffer as ``(n / lane,
    lane)`` rows, tiled ``(block / lane, lane)``.
    """

    lane: int = LANE
    max_block: int = 1 << 16     # 64k elements/tile = 256 KB fp32
    #: elements per launch (256 MB fp32).  Each launch copies its leaves into
    #: contiguous buffers; unbounded, the fused DSE-MVR step at Gemma-2 2B
    #: widths (4 layers) needed 15.9 GB of a v5e's 15.75, bounded 11.8 GB.
    max_bucket: int = 1 << 26

    def plan(self, n: int) -> Tuple[int, int]:
        """(block, padded_n) for an ``n``-element flat buffer."""
        if n <= 0:
            raise ValueError(f"cannot tile a {n}-element buffer")
        block = self.max_block if n >= self.max_block else ceil_to(n, self.lane)
        return block, ceil_to(n, block)

    def groups(self, sizes: Sequence[int]) -> List[List[int]]:
        """Positions of ``sizes`` split into consecutive launch groups of at
        most ``max_bucket`` elements (a larger leaf is a group alone)."""
        out: List[List[int]] = [[]]
        total = 0
        for i, n in enumerate(sizes):
            if out[-1] and total + n > self.max_bucket:
                out.append([])
                total = 0
            out[-1].append(i)
            total += n
        return out


# ---------------------------------------------------------------- the op
@dataclasses.dataclass(frozen=True, eq=False)
class FusedOp:
    """Declarative fused-op registration.

    At most one of ``expr`` / ``kernel_fn`` is set; with neither, the op
    has no kernel and every platform and mode runs ``ref_fn`` through XLA:

    expr:       elementwise body ``expr(s, *ins) -> out | tuple`` where ``s``
                indexes the packed fp32 scalar operands (``s[0]``, ...) and
                ``ins`` are fp32 blocks.  Compiled through the shared flat
                Pallas launcher; eligible for :func:`tree_apply` bucketing.
    kernel_fn:  shaped launcher ``kernel_fn(*tensors, interpret=..., **static)``
                wrapping the package's ``pl.pallas_call`` (flash attention,
                rms norm, wkv — ops with intra-op structure).
    ref_fn:     pure-jnp oracle with the same calling convention as the
                public entry (elementwise: ``ref_fn(*tensors, *scalars)``;
                shaped: ``ref_fn(*tensors, **static)``).  It is the parity
                target AND the backward pass of every dispatch, unless
                ``kernel_vjp`` is set.
    kernel_vjp: the shaped ``kernel_fn`` is differentiable itself (its
                backward runs kernels too); ``call`` then uses it as it is
                and the oracle's VJP only in ref mode.
    out_dtype_from: per output, the index of the input whose dtype the output
                inherits (elementwise ops; kernel computes fp32, casts out).
    """

    name: str
    ref_fn: Callable
    expr: Optional[Callable] = None
    kernel_fn: Optional[Callable] = None
    n_inputs: int = 0
    n_outputs: int = 1
    n_scalars: int = 0
    out_dtype_from: Tuple[int, ...] = (0,)
    kernel_vjp: bool = False
    tile: TilePolicy = TilePolicy()
    doc: str = ""
    _cache: Dict = dataclasses.field(
        default_factory=dict, repr=False, compare=False
    )

    def __post_init__(self):
        if self.expr is not None and self.kernel_fn is not None:
            raise ValueError(f"{self.name}: at most one of expr/kernel_fn")
        if self.kernel_vjp and self.kernel_fn is None:
            raise ValueError(f"{self.name}: kernel_vjp needs a kernel_fn")
        if self.expr is not None:
            if self.n_inputs <= 0:
                raise ValueError(f"{self.name}: elementwise ops need n_inputs")
            if len(self.out_dtype_from) != self.n_outputs:
                raise ValueError(f"{self.name}: out_dtype_from vs n_outputs")

    @property
    def elementwise(self) -> bool:
        return self.expr is not None


REGISTRY: Dict[str, FusedOp] = {}


def register(op: FusedOp) -> FusedOp:
    """Add an op to the registry.  Re-registering the same name is an error
    unless it is the same (expr/kernel, ref) pair re-imported — a silent
    overwrite would leave the parity sweeps exercising the wrong kernel."""
    prev = REGISTRY.get(op.name)
    if prev is not None and (prev.expr, prev.kernel_fn, prev.ref_fn) != (
        op.expr, op.kernel_fn, op.ref_fn
    ):
        raise ValueError(f"fused op {op.name!r} is already registered")
    REGISTRY[op.name] = op
    return op


def get(name: str) -> FusedOp:
    try:
        return REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown fused op {name!r}; registered: {sorted(REGISTRY)}"
        ) from None


# ------------------------------------------------------- elementwise backend
class _ScalarList:
    """Adapter so ``expr`` indexes scalars identically in kernel (SMEM ref)
    and ref (plain list) execution: ``s[i]`` -> fp32 scalar."""

    def __init__(self, values):
        self._values = values

    def __getitem__(self, i):
        return self._values[i]


def _elementwise_kernel(expr: Callable, n_in: int, n_out: int) -> Callable:
    def kernel(scal_ref, *refs):
        ins = [r[...].astype(jnp.float32) for r in refs[:n_in]]
        outs = expr(scal_ref, *ins)
        if not isinstance(outs, tuple):
            outs = (outs,)
        for o_ref, o in zip(refs[n_in:], outs):
            o_ref[...] = o.astype(o_ref.dtype)

    return kernel


@functools.partial(
    jax.jit, static_argnames=("name", "out_dtypes", "block", "interpret")
)
def _flat_launch(name, scalars, bufs, out_dtypes, block, interpret):
    """One Pallas launch over lane-padded flat buffers (shared by every
    elementwise op — this is the single copy of the grid/BlockSpec plumbing
    that used to be duplicated per package)."""
    op = REGISTRY[name]
    (n,) = bufs[0].shape
    assert n % block == 0 and block % LANE == 0, (name, n, block)
    # (rows, lane) view: the TPU's native 2-D tiling; for a lane multiple it
    # is the same bytes as the flat buffer
    bufs = [b.reshape(n // LANE, LANE) for b in bufs]
    spec = lambda: pl.BlockSpec((block // LANE, LANE), lambda i, *_: (i, 0))  # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n // block,),
        in_specs=[spec() for _ in range(op.n_inputs)],
        out_specs=[spec() for _ in range(op.n_outputs)],
    )
    scal = (
        jnp.stack([jnp.asarray(s, jnp.float32) for s in scalars])
        if scalars
        else jnp.zeros((1,), jnp.float32)
    )
    outs = pl.pallas_call(
        _elementwise_kernel(op.expr, op.n_inputs, op.n_outputs),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((n // LANE, LANE), jnp.dtype(d)) for d in out_dtypes
        ],
        interpret=interpret,
    )(scal, *bufs)
    return tuple(o.reshape(n) for o in outs)


def _flat_ref(op: FusedOp, scalars, bufs, out_dtypes):
    """The expr evaluated as plain jnp on the flat buffers (still ONE fused
    XLA computation per bucket) — the off-TPU fast path and the VJP target."""
    s = _ScalarList([jnp.asarray(x, jnp.float32) for x in scalars])
    outs = op.expr(s, *[b.astype(jnp.float32) for b in bufs])
    if not isinstance(outs, tuple):
        outs = (outs,)
    return tuple(o.astype(jnp.dtype(d)) for o, d in zip(outs, out_dtypes))


def _flat_fn(op: FusedOp, out_dtypes, block: int, mode: str) -> Callable:
    """custom_vjp'd flat dispatch, cached per (out_dtypes, block, mode)."""
    key = ("flat", out_dtypes, block, mode)
    fn = op._cache.get(key)
    if fn is not None:
        return fn

    def primal(scalars, bufs):
        if mode == "ref":
            return _flat_ref(op, scalars, bufs, out_dtypes)
        return _flat_launch(
            op.name, tuple(scalars), tuple(bufs), out_dtypes, block,
            mode == "interpret",
        )

    f = jax.custom_vjp(primal)

    def fwd(scalars, bufs):
        return primal(scalars, bufs), (tuple(scalars), tuple(bufs))

    def bwd(res, cts):
        scalars, bufs = res
        _, vjp = jax.vjp(
            lambda s, b: _flat_ref(op, s, b, out_dtypes), scalars, bufs
        )
        return vjp(tuple(cts))

    f.defvjp(fwd, bwd)
    op._cache[key] = f
    return f


# ---------------------------------------------------------------- tree_apply
def tree_apply(name: str, *trees: PyTree, scalars: Sequence = (), like=None):
    """Bucketed whole-tree executor for an elementwise fused op.

    Flattens every input pytree into contiguous 1-D buffers — leaves grouped
    into buckets by their (input dtypes, output dtypes) signature, raveled,
    concatenated and padded to the op's tile policy — and dispatches the
    fused kernel ONCE per bucket, then splits the result back into the
    original tree.  A homogeneous-dtype parameter tree therefore costs
    one kernel launch per op per step, independent of leaf count, up to
    ``TilePolicy.max_bucket`` elements; beyond that, one launch per group
    of consecutive leaves of at most that size.

    scalars: traced/python scalar operands, delivered to the kernel via SMEM
    scalar-prefetch (one compiled kernel serves every schedule step).
    like:    optional pytree whose leaf dtypes override the op's output-dtype
             rule (single-output ops only).
    """
    op = get(name)
    if not op.elementwise:
        raise ValueError(f"{name} is a shaped op; use api.call()")
    if len(trees) != op.n_inputs:
        raise ValueError(f"{name}: expected {op.n_inputs} trees, got {len(trees)}")
    if len(scalars) != op.n_scalars:
        raise ValueError(
            f"{name}: expected {op.n_scalars} scalars, got {len(scalars)}"
        )
    treedef = jax.tree.structure(trees[0])
    leaves = [jax.tree.leaves(t) for t in trees]
    n_leaves = len(leaves[0])
    for t in trees[1:]:
        if jax.tree.structure(t) != treedef:
            raise ValueError(
                f"{name}: input tree structures differ "
                f"({jax.tree.structure(t)} vs {treedef})"
            )
    for i in range(n_leaves):
        shapes = {tuple(leaves[t][i].shape) for t in range(op.n_inputs)}
        if len(shapes) > 1:
            # raveling would silently combine mismatched leaves; the per-leaf
            # jnp path raises a broadcast error here, so must we
            raise ValueError(f"{name}: leaf {i} shapes differ: {sorted(shapes)}")
    like_leaves = None
    if like is not None:
        if op.n_outputs != 1:
            raise ValueError(f"{name}: like= only supported for 1-output ops")
        if jax.tree.structure(like) != treedef:
            raise ValueError(f"{name}: like= tree structure differs from inputs")
        like_leaves = jax.tree.leaves(like)
    mode = resolve_mode()
    scalars = tuple(jnp.asarray(s, jnp.float32) for s in scalars)

    def out_dtypes_of(i):
        if like_leaves is not None:
            return (jnp.dtype(like_leaves[i].dtype).name,)
        return tuple(
            jnp.dtype(leaves[j][i].dtype).name for j in op.out_dtype_from
        )

    buckets: Dict[Tuple, list] = {}
    for i in range(n_leaves):
        key = (
            tuple(jnp.dtype(leaves[t][i].dtype).name for t in range(op.n_inputs)),
            out_dtypes_of(i),
        )
        buckets.setdefault(key, []).append(i)

    out_leaves = [[None] * n_leaves for _ in range(op.n_outputs)]
    for (_, out_dts), bucket in buckets.items():
        for group in op.tile.groups([leaves[0][i].size for i in bucket]):
            idxs = [bucket[g] for g in group]
            sizes = [leaves[0][i].size for i in idxs]
            n = sum(sizes)
            if n == 0:   # group of empty leaves: nothing to launch
                for i in idxs:
                    for j, d in enumerate(out_dts):
                        out_leaves[j][i] = jnp.zeros(leaves[0][i].shape, jnp.dtype(d))
                continue
            block, n_pad = op.tile.plan(n)

            def cat(t):
                parts = [leaves[t][i].ravel() for i in idxs]
                buf = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
                return jnp.pad(buf, (0, n_pad - n)) if n_pad != n else buf

            bufs = tuple(cat(t) for t in range(op.n_inputs))
            _count(name, mode)
            # named scope: one profiler-visible "repro/fused/<op>" region per
            # launch (HLO metadata only; numerics untouched)
            with jax.named_scope(f"repro/fused/{name}"):
                outs = _flat_fn(op, out_dts, block, mode)(scalars, bufs)
            off = 0
            for i, sz in zip(idxs, sizes):
                for j in range(op.n_outputs):
                    out_leaves[j][i] = outs[j][off : off + sz].reshape(
                        leaves[0][i].shape
                    )
                off += sz

    res = tuple(
        jax.tree.unflatten(treedef, out_leaves[j]) for j in range(op.n_outputs)
    )
    return res[0] if op.n_outputs == 1 else res


# ---------------------------------------------------------------- shaped call
def call(name: str, *tensors, **static):
    """Dispatch a registered op.

    Shaped ops: ``call("flash_attention", q, k, v, causal=True, ...)`` —
    keyword arguments are the op's static config (hashable).  Elementwise
    ops delegate to :func:`tree_apply` (``scalars=`` keyword carries the
    scalar operands), so single arrays work too.  Ops registered without a
    kernel always run their oracle.

    Always differentiable: the backward pass is ``jax.vjp`` of ``ref_fn``,
    or the kernel's own for ops registered with ``kernel_vjp``.
    """
    op = get(name)
    if op.elementwise:
        return tree_apply(
            name, *tensors, scalars=static.pop("scalars", ()), **static
        )
    mode = resolve_mode() if op.kernel_fn is not None else "ref"
    key = ("shaped", tuple(sorted(static.items())), mode)
    fn = op._cache.get(key)
    if fn is None and op.kernel_vjp and mode != "ref":
        fn = functools.partial(op.kernel_fn, interpret=(mode == "interpret"), **static)
        op._cache[key] = fn
    if fn is None:

        def primal(*ts):
            if mode == "ref":
                return op.ref_fn(*ts, **static)
            return op.kernel_fn(*ts, interpret=(mode == "interpret"), **static)

        f = jax.custom_vjp(primal)

        def fwd(*ts):
            return primal(*ts), ts

        def bwd(res, cts):
            _, vjp = jax.vjp(lambda *ts: op.ref_fn(*ts, **static), *res)
            return vjp(cts)

        f.defvjp(fwd, bwd)
        op._cache[key] = f
        fn = f
    _count(name, mode)
    with jax.named_scope(f"repro/fused/{name}"):
        return fn(*tensors)


# --------------------------------------------------- algorithm-layer helpers
def tree_mvr_update(g_new: PyTree, v: PyTree, g_old: PyTree, alpha) -> PyTree:
    """Whole-tree MVR direction update: v <- g_new + (1 - alpha)(v - g_old)."""
    return tree_apply("mvr_update", g_new, v, g_old, scalars=(alpha,))


def tree_axpby(a, x: PyTree, b, y: PyTree, like: Optional[PyTree] = None) -> PyTree:
    """Whole-tree a*x + b*y (out dtype: y's, or ``like``'s)."""
    return tree_apply("axpby", x, y, scalars=(a, b), like=like)


def tree_add_sub(a: PyTree, b: PyTree, c: PyTree) -> PyTree:
    """Whole-tree a + b - c (the gradient-tracking correction shape)."""
    return tree_apply("add_sub", a, b, c)


def tree_dse_combine(params: PyTree, v: PyTree, x_ref: PyTree, z: PyTree, gamma):
    """Fused dual-slow combine, fused-z form: one pass computing
    ``h = x_ref - (params - gamma*v)`` and the SGT pre-mix message
    ``u = z + h``.  Returns ``(u, h)``."""
    return tree_apply("dse_combine", params, v, x_ref, z, scalars=(gamma,))


def tree_dse_combine_yh(
    params: PyTree, v: PyTree, x_ref: PyTree, y: PyTree, h_prev: PyTree, gamma
):
    """Fused dual-slow combine, (y, h_prev) form: one pass computing
    ``h = x_ref - (params - gamma*v)`` and ``u = y + h - h_prev``.
    Returns ``(u, h)``."""
    return tree_apply(
        "dse_combine_yh", params, v, x_ref, y, h_prev, scalars=(gamma,)
    )


def deprecated_entry(old: str, new: str) -> None:
    """One-liner used by the legacy per-package wrappers."""
    warnings.warn(
        f"{old} is deprecated; use {new} (repro.kernels.api)",
        DeprecationWarning,
        stacklevel=3,
    )
