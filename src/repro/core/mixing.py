"""Gossip (mixing) backends.

Three interchangeable implementations of ``x_i <- sum_j w_ij x_j``:

  * ``dense_mix``      — node-stacked pytrees (leading axis N), dense einsum
                         with W.  Used by the CPU simulation engine.
  * ``allgather_mix``  — inside ``shard_map``: the *paper-faithful mechanical
                         port*: every node all-gathers all N replicas and
                         contracts with its own row of W.  Link bytes:
                         O((N-1) * |x|) per node.
  * ``ring_mix``       — inside ``shard_map``: the TPU-native backend.  For a
                         shift-structured topology (ring/torus) only the
                         actual graph neighbors move, via ``lax.ppermute``
                         (collective-permute).  Link bytes: O(deg * |x|),
                         deg = 2 for a ring — independent of N.

plus the *scheduled* variants consumed by the scenario engine
(``make_round_step(..., scheduled=True)``), whose mix signature is
``(tree, ctx)`` with the per-round context supplying W_t / the rotation
pattern.  The static and scheduled variants share one arithmetic
implementation (``_dense_contract`` / ``Rotation.apply``), so the
degenerate-scenario bit-identity is structural, not copy-maintained.

All backends compute the same linear operator (property-tested); they differ
only in collective footprint, which is exactly what EXPERIMENTS.md §Perf
quantifies.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .topology import Topology

PyTree = Any
MixFn = Callable[[PyTree], PyTree]

AxisName = Union[str, tuple[str, ...]]

__all__ = [
    "dense_mix", "allgather_mix", "ring_mix", "make_mix_fn", "identity_mix",
    "Rotation", "scheduled_dense_mix", "scheduled_rotation_mix",
    "replicate_gather", "replicate_pin", "replicated_local",
    "node_pin",
]


def identity_mix(tree: PyTree) -> PyTree:
    """No-op mixing (single node / centralized degenerate case)."""
    return tree


def _dense_contract(w: jnp.ndarray, tree: PyTree) -> PyTree:
    """The one dense contraction: leaf (N, ...) -> W @ leaf, f32 accumulate.

    Shared by ``dense_mix`` (W closed over) and ``scheduled_dense_mix`` (W_t
    traced from the round context) so both are the same arithmetic by
    construction.  Full f32 precision: the TPU's default for an f32 matmul
    is one bf16 pass, which would round every gossiped parameter to bf16."""

    def one(x):
        xf = x.reshape(x.shape[0], -1)
        out = jnp.einsum(
            "ij,jk->ik", w.astype(jnp.float32), xf.astype(jnp.float32),
            precision=lax.Precision.HIGHEST,
        )
        return out.reshape(x.shape).astype(x.dtype)

    return jax.tree.map(one, tree)


def dense_mix(w: np.ndarray) -> MixFn:
    """Mixing for node-stacked pytrees: leaf shape (N, ...) -> (N, ...)."""
    w = jnp.asarray(w)
    return functools.partial(_dense_contract, w)


def allgather_mix(w: np.ndarray, axis_name: AxisName) -> MixFn:
    """Paper-faithful dense gossip inside shard_map: all_gather + W-row contraction."""
    w = jnp.asarray(w, jnp.float32)

    def mix(tree: PyTree) -> MixFn:
        idx = lax.axis_index(axis_name)
        row = w[idx]  # (N,)

        def one(x):
            full = lax.all_gather(x, axis_name, axis=0, tiled=False)  # (N, ...)
            out = jnp.tensordot(
                row, full.astype(jnp.float32), axes=(0, 0),
                precision=lax.Precision.HIGHEST,
            )
            return out.astype(x.dtype)

        return jax.tree.map(one, tree)

    return mix


def ring_mix(topology: Topology, axis_name: AxisName) -> MixFn:
    """Sparse gossip via collective-permute for shift-structured topologies.

    node i receives from i-s for every shift s, weighted by w[0, s]; plus the
    self-weight.  For the Metropolis-Hastings ring this is
    ``x/3 + left/3 + right/3`` with two collective-permutes.
    """
    if not topology.shifts:
        raise ValueError(
            f"topology {topology.name!r} is not shift-structured; use allgather_mix"
        )
    n = topology.n
    shifts = topology.shifts
    weights = topology.shift_weights()
    w_self = topology.self_weight()
    perms = [[(j, (j + s) % n) for j in range(n)] for s in shifts]

    def mix(tree: PyTree) -> PyTree:
        def one(x):
            acc = w_self * x.astype(jnp.float32)
            for perm, wgt in zip(perms, weights):
                acc = acc + wgt * lax.ppermute(
                    x.astype(jnp.float32), axis_name, perm=perm
                )
            return acc.astype(x.dtype)

        return jax.tree.map(one, tree)

    return mix


@dataclasses.dataclass(frozen=True)
class Rotation:
    """One gossip rotation of a shift-structured topology: the self weight
    plus cyclic (shift, weight) pairs.  ``apply`` is THE jit-level rotation
    arithmetic — ``roll_mix`` and ``scheduled_rotation_mix`` both call it, so
    static and scheduled rotation gossip are bit-identical by construction.
    """

    self_weight: float
    shifts: tuple[int, ...]
    weights: tuple[float, ...]

    @classmethod
    def from_topology(cls, topology: Topology) -> "Rotation":
        if not topology.shifts:
            raise ValueError(f"{topology.name} is not shift-structured")
        return cls(
            self_weight=topology.self_weight(),
            shifts=topology.shifts,
            weights=topology.shift_weights(),
        )

    def apply(self, tree: PyTree) -> PyTree:
        def one(x):
            # x_i <- w_self x_i + sum_s w_s x_{(i+s) mod n}: jnp.roll along a
            # node-sharded leading axis lowers to collective-permute under
            # GSPMD — only graph neighbors move, O(deg * |x|) link bytes
            acc = self.self_weight * x.astype(jnp.float32)
            for s, w in zip(self.shifts, self.weights):
                acc = acc + w * jnp.roll(x.astype(jnp.float32), -s, axis=0)
            return acc.astype(x.dtype)

        return jax.tree.map(one, tree)


def roll_mix(topology: Topology) -> MixFn:
    """Sparse gossip on *node-stacked* pytrees (leading axis N = nodes).

    The jit-level (no shard_map) TPU-native backend: one :class:`Rotation`
    built from the topology, lowering to collective-permute under GSPMD.
    Exactly equivalent to ``dense_mix`` for shift-structured topologies
    (property-tested)."""
    if topology.n == 1:
        return identity_mix
    return Rotation.from_topology(topology).apply


def scheduled_dense_mix() -> Callable[[PyTree, Any], PyTree]:
    """Dense gossip with the per-round mixing matrix taken from ``ctx.w``.

    Same contraction as :func:`dense_mix` (shared implementation, so
    bit-identical for a constant W_t), but W is a traced input — one
    compiled executor serves every round of a time-varying schedule."""

    def mix(tree: PyTree, ctx) -> PyTree:
        return _dense_contract(ctx.w, tree)

    return mix


def scheduled_rotation_mix(rotations: Sequence[Rotation]) -> Callable[[PyTree, Any], PyTree]:
    """Shift-structured scheduled gossip: ``ctx.pattern`` switches between a
    static tuple of rotations, each lowering to ``collective-permute`` — the
    sharded runtime's mapping of time-varying graphs onto neighbor-only
    traffic.

    A single rotation skips the ``lax.switch`` entirely, making the static
    schedule bit-identical to :func:`roll_mix` (same ``Rotation.apply``)."""
    rotations = tuple(rotations)
    if not rotations:
        raise ValueError("need at least one rotation")

    def mix(tree: PyTree, ctx) -> PyTree:
        if len(rotations) == 1:
            return rotations[0].apply(tree)
        return lax.switch(
            ctx.pattern, [r.apply for r in rotations], tree
        )

    return mix


def replicate_gather(mesh, node_axes=None) -> Callable[[PyTree], PyTree]:
    """The compressed-allgather transport primitive: reshard every array of
    a (packed payload) tree to fully replicated.

    Under GSPMD the node-sharded → replicated reshard lowers to an
    ``all-gather`` of exactly the arrays it is applied to — apply it to a
    codec's packed payload and ONLY payload bytes cross the links, after
    which decode-then-weight runs locally per device.  This is the wire
    backend for topologies with no shift structure (fault-rewritten ``W_t``,
    arbitrary graphs), where neighbor rolls cannot express the contraction.

    ``node_axes`` (the mesh axes the leading node dim shards over) pins the
    payload node-sharded behind an optimization barrier before the
    replicated constraint.  Without the pin, sharding propagation hoists
    the reshard INTO the encode computation — gathering the full argsort
    order and the pack's dense operands instead of the k-slice payload —
    and the "compressed" allgather moves more bytes than the dense
    fallback it replaces.
    """
    from jax.sharding import NamedSharding, PartitionSpec

    replicated = NamedSharding(mesh, PartitionSpec())
    sharded = (
        None if node_axes is None
        else NamedSharding(mesh, PartitionSpec(node_axes))
    )

    def gather(tree: PyTree) -> PyTree:
        if sharded is not None:
            tree = jax.tree.map(
                lambda a: lax.with_sharding_constraint(a, sharded)
                if a.ndim >= 1 else a,
                tree,
            )
            tree = lax.optimization_barrier(tree)
        return jax.tree.map(
            lambda a: lax.with_sharding_constraint(a, replicated), tree
        )

    return gather


def replicate_pin(mesh) -> Callable[[PyTree], PyTree]:
    """A bare replicated sharding constraint — free when the value already
    computes replicated.  Applied to trees DERIVED from gathered payloads
    (replica estimates, decoded message sets) so sharding propagation
    cannot re-shard them and then pay a dense all-gather at the W
    contraction, which would out-spend the packed gather."""
    from jax.sharding import NamedSharding, PartitionSpec

    replicated = NamedSharding(mesh, PartitionSpec())

    def pin(tree: PyTree) -> PyTree:
        return jax.tree.map(
            lambda a: lax.with_sharding_constraint(a, replicated), tree
        )

    return pin


def node_pin(mesh, node_axes) -> Callable[[PyTree], PyTree]:
    """Constrain every array of a node-stacked tree to shard its leading
    (node) dim over ``node_axes``.  Applied to the consensus OUTPUT in the
    compressed-allgather wire mode: the replicated wire's preference
    otherwise propagates backwards through ``x + γ(Wx̂⁺ − x̂⁺)`` into the
    local-update scan, and the partitioner all-gathers the dense params
    every round to compute the iterate replicated — re-spending the bytes
    the packed gather saved.  Slicing the replicated gossip terms down to
    the node shard is free; gathering the params is not."""
    from jax.sharding import NamedSharding, PartitionSpec

    sharded = NamedSharding(mesh, PartitionSpec(node_axes))

    def pin(tree: PyTree) -> PyTree:
        return jax.tree.map(
            lambda a: lax.with_sharding_constraint(a, sharded)
            if a.ndim >= 1 else a,
            tree,
        )

    return pin


def replicated_local(mesh) -> Callable[[Callable], Callable]:
    """Wrap a replicated-tree -> replicated-tree function so it runs
    DEVICE-LOCALLY on every device (``shard_map`` with unmapped in/out
    specs: each device sees the full arrays and recomputes the result
    redundantly).

    Sharding constraints alone cannot express this: the partitioner is
    free to shard the function's interior (scatter-based sparse decodes
    actively prefer a sharded batch dim) and then re-gather the DENSE
    result at the constraint — which puts the decoded messages on the
    links and erases the compressed-allgather's wire win.  Inside
    shard_map there is nothing to re-shard, so a collective-free body is
    guaranteed collective-free in the lowering; redundant decode compute
    is the (cheap, elementwise) price of wire-true link accounting."""
    from jax.sharding import PartitionSpec

    spec = PartitionSpec()

    def wrap(fn: Callable) -> Callable:
        def run(*trees: PyTree) -> PyTree:
            return jax.shard_map(
                fn, mesh=mesh, in_specs=spec, out_specs=spec,
                check_vma=False,
            )(*trees)

        return run

    return wrap


def make_mix_fn(
    topology: Topology,
    backend: str,
    axis_name: AxisName = None,
) -> MixFn:
    """Factory: backend in {'dense', 'roll', 'allgather', 'ring'}."""
    if topology.n == 1:
        return identity_mix
    if backend == "dense":
        return dense_mix(topology.w)
    if backend == "roll":
        return roll_mix(topology)
    if backend == "allgather":
        assert axis_name is not None
        return allgather_mix(topology.w, axis_name)
    if backend == "ring":
        assert axis_name is not None
        return ring_mix(topology, axis_name)
    raise ValueError(f"unknown gossip backend {backend!r}")
