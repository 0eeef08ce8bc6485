"""The comparison that decides ``correct``: the program's readings of a
cell's first rounds against the float32 reference's.

Readings (``{"loss": [per round], "grad": {leaf: (N,)}, "delta": {leaf: (N,)}}``):
``loss`` is each round's loss as the step reports it, ``grad`` the norm of
each node's leaf of ``v`` after round 1 (the gradient the optimizer got at
the mixed iterate), ``delta`` the norm of each node's change of each
parameter leaf over all check rounds.

Numbers, each against its limit in the cell's ``bench/checks/<cell>.json``
(a number without one there is not compared; the file says why):

* ``loss_gap``: the largest ``|loss - ref| / |ref|`` over rounds;
* ``grad_gap``: over nodes and leaves, the largest gap of norms
  ``|g - g_ref|`` over ``max(g_ref, the node's median leaf g_ref)``;
* ``update_gap``: the same for the parameter change, over the leaves whose
  reference gradient is at least a thousandth of the node's median leaf
  (a leaf with no gradient, such as a key bias under softmax, moves by
  round-off alone);
* ``grad_gap_median``, ``update_gap_median``: the same gaps, the median
  over a node's leaves in place of the largest, the worst node's: steady
  from seed to seed where one leaf's gap swings (PERF.md says where).
"""
from __future__ import annotations

import math

import numpy as np

__all__ = ["numbers", "verdict", "excluded_leaves", "MOVES_FLOOR"]

MOVES_FLOOR = 1e-3


def _gaps(prog: dict, ref: dict, keep=None) -> tuple:
    """``(gaps (leaves, N), leaf names)``, or ``(None, why)`` where the
    readings cannot be compared."""
    names = sorted(ref)
    if prog.keys() != ref.keys():
        return None, "layout"
    p = np.stack([np.asarray(prog[k], np.float64) for k in names])   # (leaves, N)
    r = np.stack([np.asarray(ref[k], np.float64) for k in names])
    if p.shape != r.shape or not np.all(np.isfinite(p)):
        return None, "non-finite"
    if keep is not None:
        names = [k for k, kp in zip(names, keep) if kp]
        p, r = p[keep], r[keep]
    if p.size == 0:
        return None, "none"
    floor = np.maximum(r, np.median(r, axis=0, keepdims=True))
    return np.abs(p - r) / floor, names


def _worst_gap(prog: dict, ref: dict, keep=None) -> tuple:
    """``(gap, "leaf[node]")`` of the worst node and leaf, and the worst
    node's median leaf gap."""
    gap, names = _gaps(prog, ref, keep)
    if gap is None:
        return math.inf, names, math.inf
    i, n = np.unravel_index(np.argmax(gap), gap.shape)
    return float(gap[i, n]), f"{names[i]}[{n}]", float(np.max(np.median(gap, axis=0)))


def numbers(prog: dict, ref: dict, where: dict | None = None) -> dict:
    """``{number: value}`` of program readings ``prog`` against ``ref``;
    ``where``, if given, gets the leaf that each gap was read at."""
    lp, lr = np.asarray(prog["loss"], np.float64), np.asarray(ref["loss"], np.float64)
    loss_gap = (float(np.max(np.abs(lp - lr) / np.abs(lr)))
                if lp.shape == lr.shape and np.all(np.isfinite(lp)) else math.inf)
    grad_gap, grad_at, grad_med = _worst_gap(prog["grad"], ref["grad"])
    update_gap, update_at, update_med = _worst_gap(prog["delta"], ref["delta"], keep=_moved(ref))
    if where is not None:
        where.update(grad_gap=grad_at, update_gap=update_at)
    return {"loss_gap": loss_gap, "grad_gap": grad_gap, "update_gap": update_gap,
            "grad_gap_median": grad_med, "update_gap_median": update_med}


def verdict(values: dict, limits: dict) -> tuple:
    """``(correct, {number: {"value", "limit"}})``; a missing or non-finite
    number is not correct."""
    out = {k: {"value": values.get(k, math.inf), "limit": limits[k]} for k in limits}
    ok = all(math.isfinite(v["value"]) and v["value"] <= v["limit"] for v in out.values())
    return ok, out


def _moved(ref: dict) -> np.ndarray:
    """Per leaf (sorted by name): its reference gradient is at least
    ``MOVES_FLOOR`` of the median leaf's, on every node."""
    g = np.stack([np.asarray(ref["grad"][k], np.float64) for k in sorted(ref["grad"])])
    return np.all(g >= MOVES_FLOOR * np.median(g, axis=0, keepdims=True), axis=1)


def excluded_leaves(ref: dict) -> list:
    """The leaves left out of ``update_gap``."""
    return [k for k, kept in zip(sorted(ref["grad"]), _moved(ref)) if not kept]
