"""Unified algorithm interface: ``DecentralizedAlgorithm`` + ``CommSpec``.

Every decentralized method in this repo factors into two pure, jit/scan
compatible transitions (the seam identified by the gradient-tracking
literature: *local update* + *what/when to communicate*):

    init(params, full_grad_fn=None)                    -> state
    local_update(state, grad_fn)                       -> state   # no comm
    comm_update(state, mix_fn, grad_fn, reset_grad_fn) -> state   # gossip step

plus a declarative :class:`CommSpec` (class attribute ``comm``) naming which
state buffers are communicated and on what cadence.  The spec — not
``isinstance`` checks or a Python-level ``step()`` dispatch — is what the
execution engines consume:

  * ``repro.core.simulate.Simulator`` drives any algorithm through one
    generic ``lax.scan``-able round executor (:func:`make_round_step`);
  * ``repro.launch.distributed.make_train_job`` builds a sharded train step
    for any registered algorithm from the same executor.

The legacy protocol (``local_step`` / ``round_end`` / python-dispatch
``step(..., t=int)``) is kept as thin deprecation shims on the base class
(warning once per class; see ``reset_legacy_warnings``).

The communication runtime (``repro.compression``) plugs in declaratively:
the spec's ``compression`` field names a wire codec and its ``channel``
field a gossip protocol (``sync``, ``choco`` difference gossip, ``async``
stale-mix); :func:`make_round_step` routes every ``mix_fn`` call inside
``comm_update`` through a trace-time ``ChannelSession`` (encode ->
transport/combine -> per-buffer wire state — residuals, replica estimates,
staleness ages — carried in the state's ``comp`` field).
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Any, Callable, Optional, Tuple

import jax
from jax import lax

import jax.numpy as jnp

PyTree = Any
GradFn = Callable[[PyTree], PyTree]       # params -> grads (batch closed over)
MixFn = Callable[[PyTree], PyTree]        # gossip: tree -> mixed tree

__all__ = [
    "CommSpec", "DecentralizedAlgorithm", "RoundCtx", "make_round_step",
    "reset_legacy_warnings",
]

#: named scope of the gossip mix (collectives, neighbour combination and any
#: channel's codec work) around each ``mix_fn`` call the executor hands to
#: ``comm_update``
MIX_SCOPE = "repro/mix"

CADENCES = ("every_step", "every_tau")
RESETS = ("none", "minibatch", "full")


@dataclasses.dataclass(frozen=True)
class CommSpec:
    """Declarative communication schedule of a decentralized algorithm.

    cadence:  "every_step" — the method gossips at every iteration (its
              ``local_update`` is undefined; the executor calls ``comm_update``
              each step).  "every_tau" — tau-1 local updates, then one
              ``comm_update`` closes the round.
    buffers:  names of the param-sized messages gossiped per communication
              event (bandwidth accounting; e.g. DSE sends the SGT tracking
              buffer *and* the parameters => two messages per round).  The
              ORDER matters: the k-th ``mix_fn`` call inside ``comm_update``
              must gossip the k-th named buffer (compression matches its
              per-buffer residual state positionally).
    reset:    which gradient the executor should hand to ``comm_update`` as
              ``reset_grad_fn``: "full" (full/large-batch local gradient —
              the DSE-MVR v-reset), "minibatch" (a fresh minibatch gradient —
              DSE-SGD), or "none".
    compression: how gossiped messages are encoded on the wire — None, a
              ``repro.compression`` registry name ("identity", "qsgd",
              "top_k:0.1", "rand_k:0.1", "low_rank:2"; lossy codecs are
              error-feedback-wrapped by default), or a ready
              ``repro.compression.Compressor`` instance.  None and
              "identity" take the exact uncompressed gossip path.
    channel:  the gossip *protocol* — None / "sync" (synchronous gossip,
              today's semantics), "choco" (CHOCO-style compressed-difference
              gossip against shared replica estimates; ``choco:0.8`` sets
              the consensus step γ), "async" (stale-mix against bounded-
              staleness snapshots with event-triggered sends; ``async:2``
              sets the staleness bound), a ready
              ``repro.compression.GossipChannel`` instance, or a
              ``{buffer_name: spec}`` mapping for per-buffer overrides
              (e.g. ``{"params": "choco"}`` — CHOCO on the parameters, the
              exact sync path for the small tracking buffer; unmapped
              buffers default to "sync").  The channel encodes with the
              spec's ``compression`` codec (difference-gossip channels
              unwrap the error-feedback default — the replica is the
              memory).
    overlap:  comm/compute overlap — double-buffer the channel's sends
              against the τ local steps.  Requires a difference/stale-mix
              channel (choco/async) on every buffer: the channel's wire
              state grows an in-flight payload, each round applies the
              PREVIOUS round's message and encodes the next, so the wire
              hides behind the local phase at the documented cost of one
              round of delivery delay (one staleness unit — async channels
              therefore need ``max_staleness >= 2``).
    """

    cadence: str = "every_tau"
    buffers: Tuple[str, ...] = ("params",)
    reset: str = "none"
    compression: Any = None
    channel: Any = None
    overlap: bool = False

    def __post_init__(self):
        if self.cadence not in CADENCES:
            raise ValueError(f"cadence {self.cadence!r} not in {CADENCES}")
        if self.reset not in RESETS:
            raise ValueError(f"reset {self.reset!r} not in {RESETS}")
        if self.compression is not None:
            from ..compression.base import make_compressor  # lazy: no cycle

            object.__setattr__(
                self, "compression", make_compressor(self.compression)
            )
        if self.channel is not None:
            from ..compression.channels import (  # lazy: no cycle
                PerBufferChannel,
                make_channel,
            )

            chan = self.channel
            if isinstance(chan, dict):
                unknown = sorted(set(chan) - set(self.buffers))
                if unknown:
                    raise ValueError(
                        f"per-buffer channel mapping names unknown buffers "
                        f"{unknown}; declared buffers: {self.buffers}"
                    )
                chan = PerBufferChannel(channels=tuple(
                    make_channel(chan.get(b, "sync")) for b in self.buffers
                ))
            else:
                chan = make_channel(chan)
            object.__setattr__(self, "channel", chan.bind(self.compression))
        if self.overlap:
            from ..compression.channels import (  # lazy: no cycle
                ChocoChannel,
                PerBufferChannel,
            )

            chan = self.channel
            if chan is None:
                raise ValueError(
                    "overlap=True double-buffers a stateful channel's sends; "
                    "set channel='choco'/'async:k' (sync gossip has no "
                    "replica to mix against while the message is in flight)"
                )

            def _ov(c):
                if not isinstance(c, ChocoChannel):
                    raise ValueError(
                        "overlap=True requires a difference/stale-mix channel "
                        f"(choco/async) per buffer, got {c.name!r}"
                    )
                return c if c.overlap else dataclasses.replace(c, overlap=True)

            if isinstance(chan, PerBufferChannel):
                chan = dataclasses.replace(
                    chan, channels=tuple(_ov(c) for c in chan.channels)
                )
            else:
                chan = _ov(chan)
            object.__setattr__(self, "channel", chan)

    def round_len(self, tau: int) -> int:
        """Steps per communication round (1 for every-step methods)."""
        return 1 if self.cadence == "every_step" else max(int(tau), 1)

    def comm_events_per_round(self, tau: int) -> int:
        """Communication events in a window of ``tau`` iterations."""
        return tau if self.cadence == "every_step" else 1

    def active_compression(self):
        """The compressor the executors must honor (None for identity —
        identity short-circuits to the uncompressed path, which is what
        makes its bit-parity structural rather than numeric)."""
        comp = self.compression
        if comp is None or comp.is_identity:
            return None
        return comp

    def resolved_channel(self):
        """The :class:`~repro.compression.GossipChannel` the executors must
        drive, or None when the plain gossip path applies (sync channel, no
        active codec) — the ONE is-it-active rule shared by the executor,
        state attachment and the sharding derivation, so they can never
        disagree.  A bare ``compression`` spec implies the sync channel."""
        chan = self.channel
        if chan is not None:
            return None if chan.is_passthrough else chan
        comp = self.active_compression()
        if comp is None:
            return None
        from ..compression.channels import SyncChannel  # lazy: no cycle

        return SyncChannel(compression=comp)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class RoundCtx:
    """Per-round execution context scanned into the round executor.

    The scenario engine (``repro.scenarios``) materializes one of these per
    communication round; a static/no-fault scenario carries the same mixing
    matrix, an all-ones active mask and an all-ones local mask every round —
    in which case the scheduled executor is bit-identical to the static one.

    w:          (N, N) mixing matrix W_t for this round (dense backends; the
                rotation backend may ignore it for mixing but it still feeds
                the on-device spectral-gap stream).
    active:     (N,) bool — nodes that participate in this round at all.
                Inactive nodes keep their ENTIRE state frozen (dropout fault);
                W_t is renormalized upstream so the active block stays doubly
                stochastic.
    local_mask: (L, N) bool with L >= round_len - 1 — per-(local-step, node)
                participation (straggler fault / local-step jitter).  A masked
                node skips that local update (state unchanged).
    pattern:    () int32 — index into a static tuple of gossip rotations for
                shift-structured schedules (collective-permute backend).
    comp_scale: () float32 — this round's adaptive-compression knob in
                (0, 1]: the fraction of the codec's shape-static payload
                actually spent (warmup-dense -> compress-harder schedules).
                None = no schedule, codecs run at their static setting.
    trigger:    () float32 — this round's event-trigger threshold override
                for async channels (< 0 = keep the channel's static value).
    """

    w: Optional[jnp.ndarray] = None
    active: Optional[jnp.ndarray] = None
    local_mask: Optional[jnp.ndarray] = None
    pattern: Optional[jnp.ndarray] = None
    comp_scale: Optional[jnp.ndarray] = None
    trigger: Optional[jnp.ndarray] = None


def _select_nodes(mask: Optional[jnp.ndarray], new: Any, old: Any) -> Any:
    """Per-node select between two algorithm states.

    ``mask`` is (N,) bool over the leading node axis; node-stacked leaves take
    ``new`` where the node is unmasked and ``old`` otherwise.  Leaves without
    a node axis (the scalar step counter) always advance — the step indexes
    lr schedules and is global, not per-node.  With an all-True mask this is
    exactly ``new`` (bit-identical), so the no-fault path pays no numerics.

    Relies on the same state contract the runtime's sharding derivation
    assumes (see ``make_train_job``): every state leaf is either node-stacked
    (leading axis N) or a scalar.  A non-node leaf whose leading dim happens
    to equal N would be gated per-"node" — don't add such buffers to
    algorithm states.
    """
    if mask is None:
        return new
    n = mask.shape[0]

    def sel(a, b):
        if a.ndim == 0 or a.shape[0] != n:
            return a
        m = mask.reshape((n,) + (1,) * (a.ndim - 1))
        return jnp.where(m, a, b)

    return jax.tree.map(sel, new, old)


_warned: set = set()


def _warn_legacy(cls, method: str, alt: str) -> None:
    """Once-per-(class, method) DeprecationWarning for the legacy shims."""
    key = (cls, method)
    if key in _warned:
        return
    _warned.add(key)
    warnings.warn(
        f"{cls.__name__}.{method}() is deprecated; {alt}",
        DeprecationWarning,
        stacklevel=3,
    )


def reset_legacy_warnings() -> None:
    """Re-arm the once-per-class legacy-shim warnings (tests)."""
    _warned.clear()


class DecentralizedAlgorithm:
    """Base class / protocol for all decentralized optimization methods.

    Subclasses are frozen dataclasses holding hyperparameters and implement
    ``init`` / ``local_update`` / ``comm_update`` as *pure* functions of the
    state (scan-compatible: no host syncs, no data-dependent Python control
    flow).  ``comm`` declares the communication schedule.

    Every subclass carries ``compression`` and ``channel`` hyperparameter
    fields (spec names or ``Compressor`` / ``GossipChannel`` instances);
    when set, the instance's ``comm`` spec is rebuilt with that codec /
    gossip protocol so the executors — which only ever look at
    ``algorithm.comm`` — pick them up declaratively.
    """

    comm: CommSpec = CommSpec()

    #: per-instance wire codec (dataclass field on every subclass); None
    #: keeps the class spec's compression (usually None = uncompressed)
    compression: Any = None

    #: per-instance gossip channel ("sync" / "choco" / "async:2" / instance);
    #: None keeps the class spec's channel (usually None = sync)
    channel: Any = None

    #: per-instance comm/compute overlap (``CommSpec.overlap``): double-buffer
    #: the channel's sends so each round mixes against the PREVIOUS round's
    #: in-flight message.  Requires a choco-family ``channel``.
    overlap: bool = False

    def __post_init__(self):
        comp = getattr(self, "compression", None)
        chan = getattr(self, "channel", None)
        overlap = bool(getattr(self, "overlap", False))
        if comp is not None or chan is not None or overlap:
            repl = {}
            if comp is not None:
                repl["compression"] = comp
            if chan is not None:
                repl["channel"] = chan
            if overlap:
                repl["overlap"] = True
            object.__setattr__(
                self,
                "comm",
                dataclasses.replace(type(self).comm, **repl),
            )

    #: name of the state field that estimates the (global) gradient
    #: direction, consumed by the scenario metrics streams' tracking-error
    #: computation.  None for methods whose buffers are not gradient-scale
    #: (momentum sums, displacement trackers) — comparing those against
    #: ∇f(x̄) would be off by the momentum/lr factor and meaningless.
    tracking_buffer: Optional[str] = None

    # -- to implement ------------------------------------------------------
    def init(self, params: PyTree, full_grad_fn: Optional[GradFn] = None) -> Any:
        raise NotImplementedError

    def local_update(self, state: Any, grad_fn: GradFn) -> Any:
        raise NotImplementedError(
            f"{type(self).__name__} communicates every step and has no "
            "communication-free local update; drive it via comm_update()"
        )

    def comm_update(
        self,
        state: Any,
        mix_fn: MixFn,
        grad_fn: Optional[GradFn] = None,
        reset_grad_fn: Optional[GradFn] = None,
    ) -> Any:
        raise NotImplementedError

    # -- legacy protocol (deprecation shims) -------------------------------
    def step(self, state, grad_fn, mix_fn, reset_grad_fn=None, t=None):
        """DEPRECATED python-level dispatch (host-syncs on ``int(t)``).

        Kept so pre-refactor call sites keep working; new code should use
        :func:`make_round_step` (or the Simulator / make_train_job drivers),
        which never leave the device.
        """
        _warn_legacy(
            type(self), "step",
            "drive the algorithm through repro.core.make_round_step / Simulator",
        )
        rl = self.comm.round_len(getattr(self, "tau", 1))
        t_ = int(t if t is not None else state.step)
        if (t_ + 1) % rl == 0:
            return self.comm_update(state, mix_fn, grad_fn, reset_grad_fn)
        return self.local_update(state, grad_fn)

    def local_step(self, state, grad_fn):
        """DEPRECATED pre-PR-1 alias of :meth:`local_update`."""
        _warn_legacy(type(self), "local_step", "use local_update()")
        return self.local_update(state, grad_fn)

    def round_end(self, state, mix_fn, grad_fn=None, reset_grad_fn=None):
        """DEPRECATED pre-PR-1 round-closing step; :meth:`comm_update` is the
        canonical transition (same fallback: ``reset_grad_fn or grad_fn``)."""
        _warn_legacy(type(self), "round_end", "use comm_update()")
        return self.comm_update(state, mix_fn, grad_fn, reset_grad_fn)


def make_round_step(
    algorithm: DecentralizedAlgorithm,
    mix_fn: MixFn,
    grad_of_batch: Callable[[PyTree, Any], PyTree],
    full_grad_fn: Optional[GradFn] = None,
    comm_grad_of_batch: Optional[Callable[[PyTree, Any], PyTree]] = None,
    *,
    scheduled: bool = False,
    gate_local: bool = True,
    gate_active: bool = True,
    compressed_combine=None,
    transport_hooks: Optional[dict] = None,
):
    """The ONE generic round executor shared by simulator and runtime.

    Returns ``(round_step, round_len)`` where ``round_step(state, batches)``
    advances the algorithm by one communication round:  ``batches`` is a
    pytree whose leaves carry a leading ``round_len`` axis (one minibatch per
    iteration of the round); the first ``round_len - 1`` are consumed by a
    ``lax.scan`` of ``local_update`` and the last one closes the round with
    ``comm_update``.  Cadence, round length and the reset gradient are all
    taken from the algorithm's :class:`CommSpec` — no isinstance dispatch,
    no host syncs, fully jit/scan compatible.

    ``comm_grad_of_batch`` optionally substitutes a different gradient
    function for the communication step only (the distributed runtime passes
    a loss-capturing ``value_and_grad`` there; it must NOT be used inside the
    local-update scan, where captured values would be leaked tracers).

    With ``scheduled=True`` the executor consumes the scenario engine's
    per-round context: ``round_step(state, batches, ctx)`` where ``ctx`` is a
    :class:`RoundCtx`, ``mix_fn`` takes ``(tree, ctx)``, stragglers are gated
    via ``ctx.local_mask`` and dropped-out nodes via ``ctx.active``.
    ``gate_local`` / ``gate_active`` (statically known from the scenario
    spec: ``Scenario.needs_local_gate`` / ``needs_active_gate``) elide the
    per-node selects when no fault can produce a masked step, keeping
    fault-free scenarios — in particular the degenerate static/no-fault one —
    bit-identical to the static executor (a traced always-true select still
    changes XLA fusion, hence ulp-level drift, if left in).

    When the algorithm's spec resolves to an *active* gossip channel
    (``CommSpec.resolved_channel()`` — an explicit ``channel=`` protocol, or
    the sync channel implied by an active compression codec), every gossip
    inside ``comm_update`` is routed through a fresh trace-time
    ``repro.compression.ChannelSession``: the channel encodes each buffer
    (reading/writing its per-buffer wire state — residuals, replica
    estimates, staleness ages — in ``state.comp``) and delivers through a
    ``Transport`` wrapping ``mix_fn`` plus the optional engine-supplied
    ``compressed_combine`` — a ``(payload, decoded, ctx) -> mixed`` payload
    transport (the sharded runtime's payload-rolling collective-permute
    backend); without one, decoded messages mix through ``mix_fn`` (the
    dense engines).  ``transport_hooks`` optionally extends the Transport
    with engine wire backends for the difference-gossip channels —
    ``{"neighbor": NeighborExchange}`` (packed payload rolls + per-shift
    replica contraction) and/or ``{"gather_payload": fn}`` (compressed
    allgather via replicated resharding); see ``repro.compression.gossip``.
    No channel and no codec skips this machinery entirely, so the plain
    path is untouched — bit-identical by construction.
    """
    spec = algorithm.comm
    round_len = spec.round_len(getattr(algorithm, "tau", 1))
    comm_gb = comm_grad_of_batch or grad_of_batch
    channel = spec.resolved_channel()

    def _reset_fn(gf):
        if spec.reset == "full" and full_grad_fn is not None:
            return full_grad_fn
        if spec.reset in ("full", "minibatch"):
            return gf
        return None

    def _scoped(mix):
        """``mix`` under the gossip mix's named scope (HLO metadata only)."""
        def scoped(tree):
            with jax.named_scope(MIX_SCOPE):
                return mix(tree)
        return scoped

    def _comm(state, gf, ctx=None):
        """The communication step, channel-routed or plain."""
        if channel is None:
            mfn = (lambda tree: mix_fn(tree, ctx)) if scheduled else mix_fn
            return algorithm.comm_update(state, _scoped(mfn), gf, _reset_fn(gf))
        from ..compression.channels import ChannelSession, Transport  # lazy

        chan_state = getattr(state, "comp", None)
        if chan_state is None:
            raise ValueError(
                f"{type(algorithm).__name__} declares a gossip channel but "
                "the state carries no ChannelState — initialize it via "
                "repro.compression.attach_channel_state(algorithm, state)"
            )
        session = ChannelSession(
            channel, len(spec.buffers), chan_state,
            Transport(mix_fn, scheduled=scheduled,
                      payload_combine=compressed_combine,
                      **(transport_hooks or {})),
        )
        new = algorithm.comm_update(
            state, _scoped(lambda tree: session.mix(tree, ctx)), gf, _reset_fn(gf)
        )
        return dataclasses.replace(new, comp=session.final_state())

    # The round is factored into two named phases so (a) profiler traces
    # show "repro/local_update" / "repro/gossip" scopes on device (named
    # scopes attach HLO metadata only — numerics untouched), and (b) the
    # Simulator's telemetry mode can dispatch the phases separately with
    # fenced span timers (repro.telemetry) — the composed ``round_step`` is
    # the same op sequence as before, and stays the only scanned entry point.
    if not scheduled:

        def local_phase(state, micro):
            with jax.named_scope("repro/local_update"):

                def body(st, mb):
                    return algorithm.local_update(st, lambda p: grad_of_batch(p, mb)), ()

                state, _ = lax.scan(body, state, micro)
            return state

        def comm_phase(state, last):
            with jax.named_scope("repro/gossip"):
                gf = lambda p: comm_gb(p, last)
                return _comm(state, gf)

        def round_step(state, batches):
            if round_len > 1:
                micro = jax.tree.map(lambda x: x[: round_len - 1], batches)
                state = local_phase(state, micro)
            last = jax.tree.map(lambda x: x[round_len - 1], batches)
            return comm_phase(state, last)

        round_step.phases = (local_phase, comm_phase)
        return round_step, round_len

    def local_phase_sched(state, micro, masks):
        with jax.named_scope("repro/local_update"):

            def body(st, xs):
                mb, mask = xs
                new = algorithm.local_update(st, lambda p: grad_of_batch(p, mb))
                gated = _select_nodes(mask, new, st)
                if getattr(new, "comp", None) is not None:
                    # local updates never touch the channel wire: pass it
                    # through un-gated.  The where is semantically identity
                    # here, but an open-coded select over a REPLICATED wire
                    # (compressed-allgather mode) is computed node-sharded
                    # by the partitioner and re-gathered DENSE every scan
                    # iteration — link bytes for a no-op.
                    gated = dataclasses.replace(gated, comp=new.comp)
                return gated, ()

            # None is an empty pytree, so a missing mask scans transparently
            state, _ = lax.scan(body, state, (micro, masks))
        return state

    def comm_phase_sched(state, last, ctx: RoundCtx):
        with jax.named_scope("repro/gossip"):
            gf = lambda p: comm_gb(p, last)
            new = _comm(state, gf, ctx)
        mask = ctx.active if gate_active else None
        gated = _select_nodes(mask, new, state)
        run_local = (transport_hooks or {}).get("run_local")
        if (mask is not None and run_local is not None
                and getattr(new, "comp", None) is not None):
            # gate the channel wire DEVICE-LOCALLY: in the compressed-
            # allgather wire mode the wire is stored replicated, and an
            # open-coded where over it computes node-sharded (free slices)
            # then pays a dense all-gather back to replicated, per buffer.
            # run_local (mixing.replicated_local) is only installed for
            # that mode, so sharded wires never take this path.
            comp_gated = run_local(
                lambda m, n_, o_: _select_nodes(m, n_, o_)
            )(mask, new.comp, state.comp)
            gated = dataclasses.replace(gated, comp=comp_gated)
        return gated

    def round_step_scheduled(state, batches, ctx: RoundCtx):
        if round_len > 1:
            micro = jax.tree.map(lambda x: x[: round_len - 1], batches)
            masks = (
                ctx.local_mask[: round_len - 1]
                if gate_local and ctx.local_mask is not None
                else None
            )
            state = local_phase_sched(state, micro, masks)
        last = jax.tree.map(lambda x: x[round_len - 1], batches)
        return comm_phase_sched(state, last, ctx)

    round_step_scheduled.phases = (local_phase_sched, comm_phase_sched)
    return round_step_scheduled, round_len
