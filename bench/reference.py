"""Plain float32 reference of what a training cell's round step computes.

Written from the published descriptions, importing nothing of the program:

* the model's loss, from the cell's architecture module (``loss_fn``,
  ``bench/models/``), every matmul through :func:`matmul` at
  ``precision=highest``;
* DSE-MVR (the paper's Alg. 1, with its separate ``y`` and ``h_prev``):
  tau-1 local steps ``x' = x - lr v``, ``v' = g(x') + (1-alpha)(v - g(x))``
  on one minibatch each, then the communication step
  ``h = x_ref - (x - lr v)``, ``y' = W(y + h - h_prev)``,
  ``x' = W(x_ref - y')``, ``v' = g(x')`` on the round's last minibatch;
* ``W`` of a ring with Metropolis-Hastings weights (1/3 each for self and
  both neighbours on a ring of 4; the identity for one node).

Departures, all where the configuration states the program's behaviour:
``v``, ``y`` and ``h_prev`` start at zero (the trainer's default, where the
paper starts ``v`` at a full gradient); the architecture module lists its own.

``quant="fp8"`` is the control: every matmul operand of the forward pass
rounded to float8 e4m3 with a per-tensor scale (gradients pass straight
through), the step below bfloat16 that would tempt a faster trainer.

Memory: the gradient is taken one row at a time and each layer is
rematerialised, so the reference fits beside nothing else on one chip.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

__all__ = ["Reference", "matmul", "flat", "leaf_norms"]

F8_MAX = 448.0  # largest finite float8_e4m3fn


def _fp8(x):
    scale = jnp.max(jnp.abs(x)) / F8_MAX
    scale = jnp.where(scale > 0, scale, 1.0)
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x + lax.stop_gradient(q - x)


def matmul(quant=None):
    """``mm(spec, a, b)``, which an architecture module's ``loss_fn`` takes
    every matmul through: an einsum at ``precision=highest``, its operands
    first rounded to float8 where ``quant="fp8"`` (the control)."""
    def mm(spec, a, b):
        if quant == "fp8":
            a, b = _fp8(a), _fp8(b)
        return jnp.einsum(spec, a, b, precision=lax.Precision.HIGHEST)
    return mm


def flat(tree, prefix="") -> dict:
    """``{"blocks/b0/attn/wq": leaf, ...}`` of a nested dict."""
    out = {}
    for k, v in tree.items():
        if isinstance(v, dict):
            out.update(flat(v, f"{prefix}{k}/"))
        else:
            out[f"{prefix}{k}"] = v
    return out


def leaf_norms(tree) -> dict:
    """Per-node L2 norm of each node-stacked leaf: ``{path: (N,)}``."""
    return {p: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)),
                                axis=tuple(range(1, x.ndim))))
            for p, x in flat(tree).items()}


class Reference:
    """DSE-MVR rounds over node-stacked float32 state, one node per device
    of ``devices`` (or all nodes on the one device), of the model that
    architecture module ``arch`` writes out for configuration ``m``."""

    def __init__(self, arch, m: dict, traffic: dict, nodes: int, devices, *,
                 quant=None, mix=True):
        self.m, self.nodes = m, nodes
        lr, alpha = traffic["lr"], traffic["alpha"]
        mesh = jax.sharding.Mesh(np.array(devices[: max(1, min(nodes, len(devices)))]), ("n",))
        node = NamedSharding(mesh, P("n"))
        mm = matmul(quant)

        def grad(x, batch):
            """Per-node (loss, gradient), one row of the batch at a time."""
            def one(p, bt):
                rows = bt["tokens"].shape[0]
                tot_l, tot_g = 0.0, None
                for r in range(rows):
                    row = jax.tree.map(lambda a: a[r:r + 1], bt)
                    l_, g_ = jax.value_and_grad(lambda q: arch.loss_fn(m, q, row, mm))(p)
                    tot_l = tot_l + l_
                    tot_g = g_ if tot_g is None else jax.tree.map(jnp.add, tot_g, g_)
                return tot_l / rows, jax.tree.map(lambda a: a / rows, tot_g)
            return jax.vmap(one)(x, batch)

        def w(t):
            if nodes == 1 or not mix:
                return t
            return jax.tree.map(
                lambda a: (a + jnp.roll(a, 1, axis=0) + jnp.roll(a, -1, axis=0)) / 3.0, t)

        # a local step in two programs, so one gradient at a time is live:
        # x' = x - lr v and w = (1 - alpha)(v - g(x)), then v' = g(x') + w
        def local_old(st, batch):
            x, v = st["x"], st["v"]
            _, go = grad(x, batch)
            return {**st, "x": jax.tree.map(lambda a, b: a - lr * b, x, v),
                    "v": jax.tree.map(lambda b, c: (1 - alpha) * (b - c), v, go)}

        def local_new(st, batch):
            _, gn = grad(st["x"], batch)
            return {**st, "v": jax.tree.map(jnp.add, gn, st["v"])}

        def comm(st, batch):
            x, v, xr = st["x"], st["v"], st["x_ref"]
            h = jax.tree.map(lambda r, a, b: r - (a - lr * b), xr, x, v)
            y = w(jax.tree.map(lambda a, b, c: a + b - c, st["y"], h, st["h_prev"]))
            x = w(jax.tree.map(lambda a, b: a - b, xr, y))
            loss, v = grad(x, batch)
            return {"x": x, "x_ref": x, "v": v, "y": y, "h_prev": h}, jnp.mean(loss)

        def init(x0):
            x = jax.tree.map(lambda a: jnp.broadcast_to(a, (nodes,) + a.shape), x0)
            z = jax.tree.map(jnp.zeros_like, x)
            return {"x": x, "x_ref": x, "v": z, "y": z, "h_prev": z}

        self._init = jax.jit(init, out_shardings=node)
        self._local = tuple(jax.jit(f, out_shardings=node, donate_argnums=0)
                            for f in (local_old, local_new))
        self._comm = jax.jit(comm, out_shardings=(node, None), donate_argnums=0)
        self._node = node
        self._norms = jax.jit(leaf_norms)
        self._delta = jax.jit(lambda x, x0: leaf_norms(
            jax.tree.map(lambda a, b: a - b[None], x, x0)))

    def run(self, x0, rounds: list) -> dict:
        """Follow ``rounds`` (each ``(tau, N, b, ...)`` numpy batches) from
        weights ``x0``; returns the readings that the check compares."""
        out = {"loss": []}
        with jax.default_matmul_precision("highest"):
            st = self._init(x0)
            for i, rb in enumerate(rounds):
                rb = jax.device_put(rb, self._node_batch(rb))
                for t in range(rb["tokens"].shape[0] - 1):
                    for step in self._local:
                        st = step(st, jax.tree.map(lambda a: a[t], rb))
                st, loss = self._comm(st, jax.tree.map(lambda a: a[-1], rb))
                out["loss"].append(float(loss))
                if i == 0:
                    out["grad"] = jax.tree.map(np.asarray, self._norms(st["v"]))
            out["delta"] = jax.tree.map(np.asarray, self._delta(st["x"], x0))
        jax.tree.map(lambda a: a.delete(), st)
        return out

    def _node_batch(self, rb):
        mesh = self._node.mesh
        return jax.tree.map(lambda a: NamedSharding(mesh, P(None, "n")), rb)
