"""DSE-MVR and DSE-SGD — the paper's algorithms (Alg. 1 / Alg. 2).

The algorithms are written *per node* over arbitrary parameter pytrees and are
agnostic to where the node lives:

  * in the CPU simulation engine (``repro.core.simulate``) the state carries a
    leading node axis and ``mix_fn`` is a dense ``W`` contraction;
  * in the distributed runtime (``repro.launch.distributed``) the state is the
    per-node shard inside ``shard_map`` and ``mix_fn`` is built from
    ``lax.ppermute`` / ``lax.all_gather`` over the node mesh axis.

Update rules (Alg. 1, DSE-MVR), node index dropped:

  local step t (mod(t+1, tau) != 0):
      x_{t+1}   = x_t - gamma_t * v_t
      v_{t+1}   = g(x_{t+1}; xi) + (1 - alpha) * (v_t - g(x_t; xi))   # same xi!
  communication step (mod(t+1, tau) == 0):
      x_half    = x_t - gamma_t * v_t
      h_{t+1}   = x_ref - x_half            # accumulated descent this round
      y_{t+1}   = mix(y + h_{t+1} - h_prev) # SGT: slow gradient tracking
      x_{t+1}   = mix(x_ref - y_{t+1})      # SPA: slow partial averaging
      v_{t+1}   = full_grad(x_{t+1})        # MVR reset keeps E[V_t] unbiased

DSE-SGD (Alg. 2) is the special case alpha = 1 with no reset (v_t == g_t).

``fuse_tracking_buffers=True`` stores ``z = y - h_prev`` instead of ``(y, h_prev)``
(one fewer param-sized state buffer; exact same iterates since mix is linear) —
a beyond-paper memory optimization, equivalence-tested in
``tests/test_dse_algorithms.py``.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from ..kernels import api as fused
from .algorithm import CommSpec, DecentralizedAlgorithm

PyTree = Any
GradFn = Callable[[PyTree], PyTree]          # params -> grads (batch closed over)
MixFn = Callable[[PyTree], PyTree]           # gossip: tree -> mixed tree
ScheduleOrFloat = Any

__all__ = ["DSEState", "DSEMVR", "DSESGD", "tree_axpy", "tree_sub", "tree_add"]

#: named scope of the update arithmetic (HLO metadata only), opened around
#: each piece between the gradient and gossip calls, never around them
UPDATE_SCOPE = "repro/update"


def _sched(v: ScheduleOrFloat, t) -> jnp.ndarray:
    if callable(v):
        return jnp.asarray(v(t), dtype=jnp.float32)
    return jnp.asarray(v, dtype=jnp.float32)


def tree_add(a: PyTree, b: PyTree) -> PyTree:
    return jax.tree.map(jnp.add, a, b)


def tree_sub(a: PyTree, b: PyTree) -> PyTree:
    return jax.tree.map(jnp.subtract, a, b)


def tree_axpy(alpha, x: PyTree, y: PyTree) -> PyTree:
    """alpha * x + y, preserving y's dtype."""
    return jax.tree.map(lambda xi, yi: (alpha * xi + yi).astype(yi.dtype), x, y)


def _cast_like(src: PyTree, ref: PyTree) -> PyTree:
    return jax.tree.map(lambda s, r: s.astype(r.dtype), src, ref)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class DSEState:
    """State of DSE-MVR / DSE-SGD for one node (or node-stacked in simulation).

    ``y`` and ``h_prev`` are None when tracking buffers are fused into ``z``;
    ``z`` is None otherwise.  ``v`` is None for DSE-SGD (no momentum buffer).
    ``comp`` (None unless gossip compression with error feedback is on)
    carries the per-buffer residual state — see ``repro.compression``.
    """

    params: PyTree
    x_ref: PyTree                 # x at the start of the current round  (x_{tau(t)})
    v: Optional[PyTree]           # MVR direction estimate
    y: Optional[PyTree]           # SGT tracked global accumulated direction
    h_prev: Optional[PyTree]      # h_{tau(t)} from the previous round
    z: Optional[PyTree]           # fused y - h_prev buffer
    step: jnp.ndarray             # global iteration t
    comp: Optional[Any] = None    # gossip-compression side state


def _zeros_like_f32(tree: PyTree, dtype) -> PyTree:
    return jax.tree.map(lambda p: jnp.zeros(p.shape, dtype or p.dtype), tree)


@dataclasses.dataclass(frozen=True)
class DSEMVR(DecentralizedAlgorithm):
    """Decentralized local updates with Dual-Slow Estimation + MVR (Alg. 1)."""

    lr: ScheduleOrFloat
    alpha: ScheduleOrFloat = 1.0
    tau: int = 1
    fuse_tracking_buffers: bool = False
    state_dtype: Any = None        # None => match params dtype
    #: route the update arithmetic through the fused-op backend
    #: (``repro.kernels.api``): whole-pytree bucketed kernel launches for the
    #: MVR inner update and the dual-slow combine.  False (default) keeps
    #: today's exact per-leaf jnp path bit-for-bit.
    use_fused: bool = False
    #: gossip wire codec (``repro.compression`` name or instance); None /
    #: "identity" keeps the exact uncompressed gossip path
    compression: Any = None
    #: gossip channel protocol ("sync" / "choco" / "async:2" / instance);
    #: None keeps synchronous gossip
    channel: Any = None
    #: comm/compute overlap: double-buffer the channel's sends
    overlap: bool = False

    # one comm event per round, two param-sized messages (SGT y + SPA x);
    # v resets with the full/large-batch local gradient (Alg. 1 line 11)
    comm = CommSpec(cadence="every_tau", buffers=("y", "params"), reset="full")

    # v is the gradient-direction estimate; the SGT buffer y tracks the
    # accumulated *displacement* h = x_ref - x_half (scale ~lr*tau), so it is
    # NOT comparable against ∇f(x̄)
    tracking_buffer = "v"

    # -- state ------------------------------------------------------------
    def init(self, params: PyTree, full_grad_fn: Optional[GradFn] = None) -> DSEState:
        """v_0 = full local gradient (Alg. 1 line 3); zeros if fn not given."""
        dt = self.state_dtype
        v0 = (
            _cast_like(full_grad_fn(params), _zeros_like_f32(params, dt))
            if full_grad_fn is not None
            else _zeros_like_f32(params, dt)
        )
        zeros = _zeros_like_f32(params, dt)
        if self.fuse_tracking_buffers:
            y = h_prev = None
            z = zeros
        else:
            y, h_prev = zeros, _zeros_like_f32(params, dt)
            z = None
        return DSEState(
            params=params,
            x_ref=jax.tree.map(jnp.copy, params),
            v=v0,
            y=y,
            h_prev=h_prev,
            z=z,
            step=jnp.zeros((), jnp.int32),
        )

    # -- inner (local) update ----------------------------------------------
    def local_update(self, state: DSEState, grad_fn: GradFn) -> DSEState:
        """One local MVR step.  ``grad_fn`` closes over ONE minibatch xi and is
        evaluated at both x_{t+1} and x_t (the paper's same-sample requirement).
        """
        with jax.named_scope(UPDATE_SCOPE):
            gamma = _sched(self.lr, state.step)
            alpha = _sched(self.alpha, state.step + 1)
            if self.use_fused:
                # fused path: two bucketed kernel launches for the whole tree
                # (x step + MVR direction), instead of 2 jnp passes per leaf
                x_new = fused.tree_axpby(-gamma, state.v, 1.0, state.params)
            else:
                x_new = tree_axpy(-gamma, state.v, state.params)
        g_new = grad_fn(x_new)
        g_old = grad_fn(state.params)
        with jax.named_scope(UPDATE_SCOPE):
            if self.use_fused:
                v_new = fused.tree_mvr_update(g_new, state.v, g_old, alpha)
            else:
                # v_{t+1} = g_{t+1} + (1 - alpha) (v_t - g_t)
                v_new = jax.tree.map(
                    lambda gn, v, go: (gn + (1.0 - alpha) * (v.astype(gn.dtype) - go)).astype(v.dtype),
                    g_new,
                    state.v,
                    g_old,
                )
            return dataclasses.replace(state, params=x_new, v=v_new, step=state.step + 1)

    # -- communication round -------------------------------------------------
    def comm_update(
        self,
        state: DSEState,
        mix_fn: MixFn,
        grad_fn: Optional[GradFn] = None,
        reset_grad_fn: Optional[GradFn] = None,
    ) -> DSEState:
        """The SGT + SPA + v-reset step (Alg. 1 lines 7-11).

        ``reset_grad_fn`` computes the (full or large-batch) local gradient
        for the MVR reset (falls back to ``grad_fn``); if both are None the
        v buffer is kept (used by the DSE-SGD subclass).
        """
        reset_grad_fn = reset_grad_fn if reset_grad_fn is not None else grad_fn
        # the arithmetic is scoped in pieces around the two gossips, so no
        # op of the mix (or of the reset gradient) lands in the update scope
        with jax.named_scope(UPDATE_SCOPE):
            gamma = _sched(self.lr, state.step)
            if self.use_fused:
                # fused path: ONE dse_combine pass computes x_half, h and the
                # SGT pre-mix message; the z refresh and the post-mix SPA
                # subtraction are axpby launches (they cannot fuse across the
                # gossip collective)
                if self.fuse_tracking_buffers:
                    u, h_new = fused.tree_dse_combine(
                        state.params, state.v, state.x_ref, state.z, gamma
                    )
                else:
                    u, h_new = fused.tree_dse_combine_yh(
                        state.params, state.v, state.x_ref, state.y, state.h_prev,
                        gamma,
                    )
            else:
                x_half = tree_axpy(-gamma, state.v, state.params)
                h_new = tree_sub(_cast_like(state.x_ref, x_half), x_half)  # x_ref - x_half
                h_new = _cast_like(h_new, state.v)
                if self.fuse_tracking_buffers:
                    u = tree_add(state.z, h_new)
                else:
                    u = tree_add(state.y, tree_sub(h_new, state.h_prev))
        y_new = mix_fn(u)
        with jax.named_scope(UPDATE_SCOPE):
            if not self.fuse_tracking_buffers:
                y_upd = dict(y=y_new, h_prev=h_new)
            elif self.use_fused:
                y_upd = dict(z=fused.tree_axpby(-1.0, h_new, 1.0, y_new))
            else:
                y_upd = dict(z=tree_sub(y_new, h_new))
            # SPA: x_{t+1} = mix(x_ref - y_{t+1})
            if self.use_fused:
                w = fused.tree_axpby(-1.0, y_new, 1.0, state.x_ref, like=state.params)
            else:
                w = tree_axpy(-1.0, _cast_like(y_new, state.x_ref), state.x_ref)
        x_new = mix_fn(w)
        with jax.named_scope(UPDATE_SCOPE):
            x_new = _cast_like(x_new, state.params)
        g = reset_grad_fn(x_new) if reset_grad_fn is not None else None
        with jax.named_scope(UPDATE_SCOPE):
            v_new = state.v if g is None else _cast_like(g, state.v)
            return dataclasses.replace(
                state,
                params=x_new,
                x_ref=jax.tree.map(jnp.copy, x_new),
                v=v_new,
                step=state.step + 1,
                **y_upd,
            )

    # legacy local_step / round_end shims live on the base class
    # (DecentralizedAlgorithm), where they warn once per class.


@dataclasses.dataclass(frozen=True)
class DSESGD(DSEMVR):
    """DSE-SGD (Alg. 2): plain minibatch SGD inner update + dual-slow estimation.

    Equivalent to DSE-MVR with alpha == 1 and no reset; implemented directly so
    no extra ``g_old`` evaluation is wasted.
    """

    alpha: ScheduleOrFloat = 1.0

    # like DSE-MVR but v resets with a fresh *minibatch* gradient (Alg. 2)
    comm = CommSpec(cadence="every_tau", buffers=("y", "params"), reset="minibatch")

    def init(self, params: PyTree, full_grad_fn: Optional[GradFn] = None) -> DSEState:
        # v_0 = g_0 (Alg. 2 line 2); the first local_update supplies the gradient.
        return super().init(params, full_grad_fn)

    def local_update(self, state: DSEState, grad_fn: GradFn) -> DSEState:
        with jax.named_scope(UPDATE_SCOPE):
            gamma = _sched(self.lr, state.step)
            if self.use_fused:
                x_new = fused.tree_axpby(-gamma, state.v, 1.0, state.params)
            else:
                x_new = tree_axpy(-gamma, state.v, state.params)
        g = grad_fn(x_new)
        with jax.named_scope(UPDATE_SCOPE):
            g_new = _cast_like(g, state.v)
            return dataclasses.replace(state, params=x_new, v=g_new, step=state.step + 1)

    def comm_update(
        self,
        state: DSEState,
        mix_fn: MixFn,
        grad_fn: Optional[GradFn] = None,
        reset_grad_fn: Optional[GradFn] = None,
    ) -> DSEState:
        state = DSEMVR.comm_update(self, state, mix_fn, None, None)
        rf = reset_grad_fn if reset_grad_fn is not None else grad_fn
        if rf is not None:  # v_{t+1} = g(x_{t+1}) — fresh minibatch
            g = rf(state.params)
            with jax.named_scope(UPDATE_SCOPE):
                state = dataclasses.replace(state, v=_cast_like(g, state.v))
        return state
