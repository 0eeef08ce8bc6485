"""Common model building blocks: param builder with logical sharding axes,
norms, rotary embeddings (incl. M-RoPE), losses.

Sharding follows the MaxText pattern: every parameter and key activation is
tagged with *logical* axis names; a rules table (set per launch context) maps
logical names to mesh axes, and ``with_sharding_constraint`` is a no-op when
no rules are active (CPU tests) or when the dim is not divisible by the mesh
axis (e.g. gemma2's 8 heads on a 16-way model axis stay replicated).
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
import threading
from typing import Any, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

PyTree = Any

__all__ = [
    "LogicalAxes", "Initializer", "axis_rules", "logical_constraint",
    "activation_spec", "shard_local", "resolve_specs", "rms_norm", "layer_norm", "softcap",
    "rope_frequencies", "apply_rope", "apply_mrope", "make_mrope_positions",
    "cross_entropy_loss", "Param",
]


# --------------------------------------------------------------------------
# logical axis rules
# --------------------------------------------------------------------------
class _Rules(threading.local):
    def __init__(self):
        self.acts: dict[str, Any] = {}
        self.params: dict[str, Any] = {}
        self.mesh = None

    @property
    def rules(self):  # activation rules (logical_constraint path)
        return self.acts


_RULES = _Rules()


@contextlib.contextmanager
def axis_rules(rules: dict[str, Any], mesh=None, param_rules: dict[str, Any] = None):
    """Activate logical->mesh axis rules for the enclosed region.

    ``rules`` applies to activations (``logical_constraint``); ``param_rules``
    (defaults to ``rules``) applies to parameter/state specs
    (``resolve_specs``).  Separating the two enables FSDP-style layouts where
    e.g. 'embed' shards parameters but not activations.  ``mesh`` enables the
    divisibility check (non-divisible dims replicate).
    """
    old = (_RULES.acts, _RULES.params, _RULES.mesh)
    _RULES.acts = dict(rules)
    _RULES.params = dict(param_rules if param_rules is not None else rules)
    _RULES.mesh = mesh
    try:
        yield
    finally:
        _RULES.acts, _RULES.params, _RULES.mesh = old


def _axis_size(mesh_axes) -> int:
    mesh = _RULES.mesh
    if mesh is None:
        return 1
    if isinstance(mesh_axes, str):
        mesh_axes = (mesh_axes,)
    size = 1
    for a in mesh_axes:
        size *= dict(zip(mesh.axis_names, mesh.devices.shape))[a]
    return size


def _resolve_axes(
    names: Sequence[Optional[str]],
    shape: Optional[Sequence[int]] = None,
    table: Optional[dict] = None,
) -> P:
    """Resolve logical names to mesh axes; each mesh axis is used at most once
    per spec (first divisible dim wins — e.g. qwen2-moe's 60 experts are not
    divisible by the 16-way model axis, so the expert-hidden dim shards
    instead)."""
    table = _RULES.acts if table is None else table
    out = []
    used: set = set()
    for i, name in enumerate(names):
        mesh_axes = table.get(name) if name else None
        if mesh_axes is not None:
            key = tuple(mesh_axes) if isinstance(mesh_axes, (tuple, list)) else (mesh_axes,)
            if any(a in used for a in key):
                mesh_axes = None
            elif shape is not None and shape[i] % max(1, _axis_size(mesh_axes)) != 0:
                mesh_axes = None  # not divisible -> replicate
            else:
                used.update(key)
        out.append(mesh_axes)
    return P(*out)


def logical_constraint(x: jnp.ndarray, *names: Optional[str]) -> jnp.ndarray:
    """Apply a sharding constraint by logical axis names (no-op without rules)."""
    if not _RULES.acts:
        return x
    if len(names) != x.ndim:
        raise ValueError(f"{len(names)} names for rank-{x.ndim} array")
    spec = _resolve_axes(names, x.shape, _RULES.acts)
    if all(s is None for s in spec):
        return x
    mesh = _RULES.mesh
    if mesh is not None:
        from jax.sharding import NamedSharding

        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
    return jax.lax.with_sharding_constraint(x, spec)


def shard_local(fn, *args: jnp.ndarray, specs: Sequence[P], out_spec: P):
    """Run ``fn`` on each device's block of ``args``, laid out by ``specs``
    (mesh axes of the active rules, e.g. from :func:`activation_spec`),
    under ``shard_map``; a vmap's ``spmd_axis_name`` adds its axes.  For
    calls GSPMD cannot partition (Pallas kernels), which it would otherwise
    run on gathered operands.  Without rules or on one device: ``fn(*args)``."""
    mesh = _RULES.mesh
    if mesh is None or not _RULES.acts or mesh.size == 1:
        return fn(*args)
    return jax.shard_map(fn, mesh=mesh, in_specs=tuple(specs), out_specs=out_spec,
                         check_vma=False)(*args)


def activation_spec(shape: Sequence[int], *names: Optional[str]) -> P:
    """The mesh axes the active rules give an activation of this shape and
    these logical names (what :func:`logical_constraint` applies)."""
    return _resolve_axes(names, shape, _RULES.acts)


def force_replicated(x: jnp.ndarray) -> jnp.ndarray:
    """Explicitly replicate a tensor across the whole mesh (one up-front
    all-gather instead of partitioner-chosen per-op resharding)."""
    mesh = _RULES.mesh
    if mesh is None or not _RULES.acts:
        return x
    from jax.sharding import NamedSharding

    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*([None] * x.ndim)))
    )


@dataclasses.dataclass(frozen=True)
class LogicalAxes:
    """Pytree *leaf* carrying per-dim logical names for one parameter."""

    names: Tuple[Optional[str], ...]
    shape: Tuple[int, ...] = ()

    def spec(self) -> P:
        return _resolve_axes(self.names, self.shape if self.shape else None, _RULES.params)


def resolve_specs(spec_tree: PyTree, prefix: Tuple = ()) -> PyTree:
    """LogicalAxes tree -> PartitionSpec tree under the active *param* rules.

    ``prefix`` prepends mesh axes (e.g. the decentralized node axis for the
    leading node dim of stacked state arrays)."""
    def one(l: LogicalAxes) -> P:
        spec = l.spec()
        return P(*prefix, *spec) if prefix else spec

    return jax.tree.map(
        lambda l: one(l) if isinstance(l, LogicalAxes) else P(*prefix),
        spec_tree,
        is_leaf=lambda l: isinstance(l, LogicalAxes),
    )


# --------------------------------------------------------------------------
# parameter builder (single source of truth for params AND their specs)
# --------------------------------------------------------------------------
Param = jnp.ndarray


class Initializer:
    """Builds either parameter arrays or their LogicalAxes spec tree from the
    same model-definition code path (mode='params' | 'specs' | 'shapes')."""

    def __init__(self, mode: str, key: Optional[jax.Array] = None, dtype=jnp.float32):
        assert mode in ("params", "specs", "shapes")
        self.mode = mode
        self._key = key
        self.dtype = dtype

    def _next_key(self) -> jax.Array:
        self._key, sub = jax.random.split(self._key)
        return sub

    def param(
        self,
        shape: Sequence[int],
        axes: Sequence[Optional[str]],
        init: str = "normal",
        scale: Optional[float] = None,
        dtype=None,
    ):
        shape = tuple(int(s) for s in shape)
        axes = tuple(axes)
        assert len(shape) == len(axes), (shape, axes)
        if self.mode == "specs":
            return LogicalAxes(axes, shape)
        if self.mode == "shapes":
            return jax.ShapeDtypeStruct(shape, dtype or self.dtype)
        dt = dtype or self.dtype
        if init == "zeros":
            return jnp.zeros(shape, dt)
        if init == "ones":
            return jnp.ones(shape, dt)
        if init == "normal":
            fan_in = shape[0] if len(shape) > 1 else max(shape[-1], 1)
            s = scale if scale is not None else 1.0 / math.sqrt(fan_in)
            return (jax.random.normal(self._next_key(), shape) * s).astype(dt)
        if init == "embed":
            s = scale if scale is not None else 1.0
            return (jax.random.normal(self._next_key(), shape) * s).astype(dt)
        raise ValueError(init)


# --------------------------------------------------------------------------
# norms / activations
# --------------------------------------------------------------------------
def rms_norm(x: jnp.ndarray, weight: jnp.ndarray, eps: float = 1e-6, *, plus_one: bool = False) -> jnp.ndarray:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    w = weight.astype(jnp.float32)
    if plus_one:  # gemma convention: weight stored as delta from 1
        w = w + 1.0
    return (y * w).astype(dt)


def layer_norm(x: jnp.ndarray, weight: jnp.ndarray, bias: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    mu = xf.mean(axis=-1, keepdims=True)
    var = ((xf - mu) ** 2).mean(axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * weight.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dt)


def softcap(x: jnp.ndarray, cap: Optional[float]) -> jnp.ndarray:
    """Gemma-2 style logit soft-capping: cap * tanh(x / cap)."""
    if cap is None:
        return x
    return (cap * jnp.tanh(x.astype(jnp.float32) / cap)).astype(x.dtype)


# --------------------------------------------------------------------------
# rotary position embeddings
# --------------------------------------------------------------------------
def rope_frequencies(head_dim: int, theta: float = 10000.0) -> jnp.ndarray:
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))


def _rotate(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray) -> jnp.ndarray:
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


def apply_rope(
    x: jnp.ndarray, positions: jnp.ndarray, theta: float = 10000.0, scale: float = 1.0
) -> jnp.ndarray:
    """x: (..., S, n_heads, head_dim); positions: (..., S) int.  ``scale``
    multiplies the rotation in float32, before the one cast back to x's dtype."""
    freqs = rope_frequencies(x.shape[-1], theta)  # (half,)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (..., S, half)
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]  # (.., S, 1, half)
    if scale != 1.0:
        cos, sin = cos * scale, sin * scale
    return _rotate(x, cos, sin)


def make_mrope_positions(batch: int, seq: int, n_vision: int, grid: Tuple[int, int]) -> jnp.ndarray:
    """Qwen2-VL M-RoPE positions (3, B, S): (temporal, height, width).

    Vision tokens occupy the first ``n_vision`` slots with 2-D (h, w) grid
    coordinates and a constant temporal index; text tokens get equal t/h/w
    indices continuing after the vision block (the paper's scheme).
    """
    gh, gw = grid
    assert gh * gw == n_vision, (grid, n_vision)
    hh = jnp.repeat(jnp.arange(gh), gw)
    ww = jnp.tile(jnp.arange(gw), gh)
    tt = jnp.zeros(n_vision, jnp.int32)
    text = jnp.arange(seq - n_vision) + max(gh, gw)
    pos_t = jnp.concatenate([tt, text])
    pos_h = jnp.concatenate([hh, text])
    pos_w = jnp.concatenate([ww, text])
    pos = jnp.stack([pos_t, pos_h, pos_w])  # (3, S)
    return jnp.broadcast_to(pos[:, None, :], (3, batch, seq))


def apply_mrope(x: jnp.ndarray, positions3: jnp.ndarray, theta: float, sections: Tuple[int, int, int]) -> jnp.ndarray:
    """Multimodal RoPE: the rotary half-dim is split into (t, h, w) sections,
    each rotated with its own position stream.  x: (B, S, H, hd);
    positions3: (3, B, S)."""
    half = x.shape[-1] // 2
    assert sum(sections) == half, (sections, half)
    freqs = rope_frequencies(x.shape[-1], theta)  # (half,)
    # build per-frequency positions by section
    sec_id = jnp.concatenate(
        [jnp.full((s,), i, jnp.int32) for i, s in enumerate(sections)]
    )  # (half,)
    pos = positions3[sec_id]  # (half, B, S) via take along modality
    pos = jnp.moveaxis(pos, 0, -1)  # (B, S, half)
    ang = pos.astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    return _rotate(x, cos, sin)


# --------------------------------------------------------------------------
# losses
# --------------------------------------------------------------------------
def cross_entropy_loss(
    logits: jnp.ndarray, targets: jnp.ndarray, mask: Optional[jnp.ndarray] = None
) -> jnp.ndarray:
    """Token-level cross entropy, fp32 accumulation. logits (..., V), targets (...)."""
    logits = logits.astype(jnp.float32)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    nll = logz - gold
    if mask is not None:
        return (nll * mask).sum() / jnp.maximum(mask.sum(), 1)
    return nll.mean()
