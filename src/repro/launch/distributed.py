"""Distributed decentralized training / serving step builders.

Training (the paper's algorithm as a first-class runtime feature):

  * decentralized nodes = mesh slices along the profile's node axes; every
    algorithm state tensor carries a leading node dim sharded over those axes.
  * per-node model compute = ``jax.vmap`` over the node dim, with logical
    sharding constraints resolving to the within-node layout (tp/fsdp/2d).
  * one jitted ``train_step`` = one communication round, built by the SAME
    generic round executor the CPU simulator uses (``core.algorithm.
    make_round_step``): ``lax.scan`` over round_len-1 local updates, then the
    algorithm's ``comm_update`` — cadence and reset gradient from its
    declarative ``CommSpec``.  Works for every entry in ``core.ALGORITHMS``.
  * gossip backends: 'dense' (paper-faithful X@W -> all-gather) and 'roll'
    (ring neighbors only -> collective-permute), selectable per job.

Serving: standard single-model layout (batch over data axes, TP over model);
``prefill`` builds caches, ``decode_step`` advances one token.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..compression.base import (
    ChannelState,
    abstract_channel_state,
    attach_channel_state,
)
from ..compression.channels import ChocoChannel, SyncChannel
from ..compression.gossip import (
    allgather_combine,
    neighbor_exchange,
    rotation_combine,
)
from ..core import make_algorithm, ring
from ..core.algorithm import DecentralizedAlgorithm, RoundCtx, make_round_step
from ..core.mixing import (
    Rotation,
    dense_mix,
    identity_mix,
    replicate_gather,
    replicate_pin,
    node_pin,
    replicated_local,
    roll_mix,
    scheduled_dense_mix,
    scheduled_rotation_mix,
)
from ..models import Model, ModelConfig, axis_rules, resolve_specs
from .sharding import ShardingProfile, cache_specs, profile_for_arch

PyTree = Any

__all__ = ["TrainJob", "ServeJob", "make_train_job", "make_serve_job"]


def _named(mesh, spec_tree):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        spec_tree,
        is_leaf=lambda s: isinstance(s, P),
    )


@dataclasses.dataclass
class TrainJob:
    """A compiled-able decentralized training round.

    With ``scenario`` set, ``step_fn`` takes a third per-round argument —
    the scenario engine's :class:`~repro.core.algorithm.RoundCtx` — and the
    metrics dict gains the on-device streams (consensus, tracking error,
    effective spectral gap, active node count).  ``schedule_for`` /
    ``round_ctx`` materialize and slice the schedule for the driver loop.
    """

    model: Model
    mesh: Any
    profile: ShardingProfile
    algorithm: Any
    tau: int                          # the algorithm's local-update interval
    round_len: int                    # batches consumed per train_step call
    n_nodes: int
    gossip: str
    step_fn: Callable                 # (state, batches[, ctx]) -> (state, metrics)
    state_shardings: PyTree
    batch_shardings: PyTree
    abstract_state: PyTree
    abstract_batch_fn: Callable       # (seq_len, global_batch) -> batch SDS tree
    scenario: Any = None

    def jit_step(self):
        """``step_fn`` jitted onto the mesh.  The state is donated: the new
        state reuses the old one's buffers, so a round holds one copy of it
        (callers must not read a state after passing it in)."""
        in_shardings = (self.state_shardings, self.batch_shardings)
        if self.scenario is not None:
            in_shardings = in_shardings + (None,)
        return jax.jit(
            self.step_fn,
            in_shardings=in_shardings,
            out_shardings=(self.state_shardings, None),
            donate_argnums=0,
        )

    def lower(self, seq_len: int, global_batch: int):
        args = (self.abstract_state, self.abstract_batch_fn(seq_len, global_batch))
        if self.scenario is not None:
            args = args + (self.abstract_ctx(),)
        return self.jit_step().lower(*args)

    # ---- scenario plumbing ------------------------------------------------
    def schedule_for(self, n_rounds: int):
        """Materialize the scenario's per-round arrays for a driver loop."""
        if self.scenario is None:
            raise ValueError("job has no scenario")
        return self.scenario.materialize(self.n_nodes, n_rounds, self.round_len)

    def round_ctx(self, schedule, r: int) -> RoundCtx:
        """The (replicated) RoundCtx of round ``r`` of a materialized schedule."""
        return RoundCtx(
            w=jnp.asarray(schedule.w[r]),
            active=jnp.asarray(schedule.active[r]),
            local_mask=jnp.asarray(schedule.local_mask[r]),
            pattern=jnp.asarray(schedule.pattern[r]),
            comp_scale=(
                None if schedule.comp_scale is None
                else jnp.asarray(schedule.comp_scale[r])
            ),
            trigger=(
                None if schedule.trigger is None
                else jnp.asarray(schedule.trigger[r])
            ),
        )

    def abstract_ctx(self) -> RoundCtx:
        n, L = self.n_nodes, max(self.round_len - 1, 1)
        def knob(name):
            if self.scenario is not None and getattr(self.scenario, name) is not None:
                return jax.ShapeDtypeStruct((), jnp.float32)
            return None

        return RoundCtx(
            w=jax.ShapeDtypeStruct((n, n), jnp.float32),
            active=jax.ShapeDtypeStruct((n,), jnp.bool_),
            local_mask=jax.ShapeDtypeStruct((L, n), jnp.bool_),
            pattern=jax.ShapeDtypeStruct((), jnp.int32),
            comp_scale=knob("comp_scale"),
            trigger=knob("trigger"),
        )

    def init_state(self, key) -> PyTree:
        """Materialized initial state, built under jit straight into
        ``state_shardings`` (each device computes only its own shard, so no
        node's state passes through one device first); attaches the
        gossip-compression side state when the algorithm's spec asks for it."""

        def build(key):
            params = self.model.init(key)
            n = self.n_nodes
            stacked = jax.tree.map(
                lambda p: jnp.broadcast_to(p[None], (n,) + p.shape), params
            )
            state = self.algorithm.init(stacked)
            return attach_channel_state(
                self.algorithm, state, jax.random.fold_in(key, 0x636F)
            )

        return jax.jit(build, out_shardings=self.state_shardings)(key)


def _node_batch_struct(model: Model, tau: int, n_nodes: int, seq_len: int, global_batch: int):
    """(tau, N, b_node, ...) ShapeDtypeStructs for one round of batches."""
    per_node = global_batch // max(n_nodes, 1)
    spec = model.input_specs(seq_len, per_node)
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((tau, n_nodes) + s.shape, s.dtype), spec
    )


def make_train_job(
    cfg: ModelConfig,
    mesh,
    *,
    algorithm="dse_mvr",
    tau: int = 4,
    lr: float = 1e-3,
    alpha: float = 0.05,
    gossip: str = "roll",
    profile: Optional[ShardingProfile] = None,
    state_dtype=jnp.float32,
    grad_accum: int = 1,
    algorithm_kwargs: Optional[Dict[str, Any]] = None,
    scenario=None,
    use_fused: bool = False,
    compression=None,
    channel=None,
    wire_mode: str = "auto",
    overlap: bool = False,
) -> TrainJob:
    """Build a sharded decentralized training round for ANY registered
    algorithm: ``algorithm`` is a name from ``repro.core.ALGORITHMS`` (or a
    ready ``DecentralizedAlgorithm`` instance); cadence, round length and the
    reset gradient are taken from its declarative ``CommSpec`` — the same
    executor the CPU simulator uses, compiled onto the mesh.

    ``use_fused=True`` routes the algorithm's update arithmetic through the
    fused-op backend (``repro.kernels.api``): whole-pytree bucketed kernel
    launches on TPU, the bucketed jnp path elsewhere; the default False keeps
    the exact per-leaf jnp arithmetic.

    ``compression`` (a ``repro.compression`` spec name like ``"qsgd"`` /
    ``"top_k:0.1"``, or a ``Compressor`` instance) encodes every gossiped
    buffer on the wire.  On the ``"roll"`` backends the *packed payload*
    arrays are what rolls through collective-permute (decoded per shift on
    arrival), so the measured HLO link bytes shrink by the codec's ratio;
    the dense backends mix the decoded messages (same iterates, no wire
    win).  ``None`` / ``"identity"`` is bit-identical to the uncompressed
    path.  Ignored when ``algorithm`` is a ready instance (set the field on
    the instance instead).

    ``channel`` selects the gossip protocol (``"sync"`` — default semantics;
    ``"choco"`` — compressed-difference gossip against replica estimates;
    ``"async:k"`` — stale-mix with staleness bound k and event-triggered
    sends).  Channel wire state (replicas, ages) is node-sharded like any
    other state buffer.  Like ``compression``, ignored when ``algorithm``
    is a ready instance.

    ``wire_mode`` picks the wire backend for difference/stale channels:

      * ``"neighbor"``  — packed neighbor-replica gossip: the channel keeps
        one replica tree per incoming shift and only the encoded difference
        payload rolls through collective-permute (bitwise identical to the
        dense rolled-replica path).  Requires a shift-structured schedule.
      * ``"allgather"`` — compressed allgather: the packed payload is
        resharded to replicated (an all-gather of exactly the packed
        arrays); replica update and W contraction run locally.  Serves
        fault-rewritten / non-shift W_t, and sync-channel codecs on dense
        contractions via ``allgather_combine``.
      * ``"dense"``     — the pre-wire-true behavior: replica trees move
        through the engine mix operator dense.
      * ``"auto"``      — neighbor on shift-structured schedules; allgather
        for choco/async + active codec when faults rewrite W (where the
        fallback used to be dense); dense otherwise.

    ``overlap=True`` double-buffers the channel's sends against the τ local
    steps (requires choco/async; the message lands one round late — one
    staleness unit, so async bounds must be ≥ 2; see ``CommSpec.overlap``).

    With a ``scenario`` (``repro.scenarios.Scenario``), the train step
    consumes a per-round :class:`RoundCtx` and gossips over the scenario's
    time-varying W_t: shift-structured schedules with W-preserving faults map
    onto a static set of collective-permute rotations selected by
    ``ctx.pattern`` (``gossip="roll"``); everything else falls back to the
    dense scheduled contraction with the scanned W_t."""
    profile = profile or profile_for_arch(cfg.name)
    node_axes = profile.node_axes(mesh)
    n_nodes = profile.n_nodes(mesh)
    topology = ring(n_nodes)
    model = Model(cfg)

    if isinstance(algorithm, DecentralizedAlgorithm):
        alg = algorithm
    else:
        alg = make_algorithm(
            algorithm, lr=lr, alpha=alpha, tau=tau,
            fuse_tracking_buffers=True, state_dtype=state_dtype,
            use_fused=use_fused, compression=compression, channel=channel,
            **(algorithm_kwargs or {}),
        )
    round_len = alg.comm.round_len(getattr(alg, "tau", 1))
    if wire_mode not in ("auto", "dense", "neighbor", "allgather"):
        raise ValueError(
            f"wire_mode must be auto/dense/neighbor/allgather, got {wire_mode!r}"
        )
    chan = alg.comm.resolved_channel()
    if overlap:
        if not isinstance(chan, ChocoChannel):
            raise ValueError(
                "overlap=True requires a choco/async channel (got "
                f"{getattr(chan, 'name', None)!r}) — sync gossip has no "
                "replica to mix against while the message is in flight"
            )
        alg = dataclasses.replace(alg, channel=dataclasses.replace(chan, overlap=True))
        chan = alg.comm.resolved_channel()

    def _rebind_channel(**updates):
        """Rewire the difference channel's wire mode and rebuild the
        algorithm so executor, state attachment and sharding derivation all
        see the same channel instance."""
        nonlocal alg, chan
        alg = dataclasses.replace(
            alg, channel=dataclasses.replace(chan, **updates)
        )
        chan = alg.comm.resolved_channel()

    # the sync channel encodes the buffers themselves — its packed payloads
    # move through the payload combine; difference/stale channels encode
    # replica diffs and deliver through the neighbor/allgather wire hooks
    comp = chan.compression if isinstance(chan, SyncChannel) else None
    diff_chan = isinstance(chan, ChocoChannel)
    diff_codec = (
        diff_chan
        and chan.compression is not None
        and not chan.compression.is_identity
    )
    compressed_combine = None   # None => mix the decoded messages densely
    transport_hooks: Dict[str, Any] = {}

    if scenario is not None:
        scenario.warn_if_vacuous(round_len, runtime_batches=True)
        rotations = (
            None
            if scenario.mutates_w or n_nodes == 1
            else scenario.topology_schedule(n_nodes).rotations()
        )
        if n_nodes == 1:
            mix_fn = lambda tree, ctx: tree
        elif gossip == "roll" and rotations and wire_mode != "allgather":
            mix_fn = scheduled_rotation_mix(rotations)
            if comp is not None:
                # compress before collective-permute: only the packed payload
                # arrays roll across links, decoded per shift on arrival
                compressed_combine = rotation_combine(
                    comp, rotations, scheduled=True
                )
            if diff_chan and wire_mode in ("auto", "neighbor"):
                ex = neighbor_exchange(rotations, scheduled=True)
                _rebind_channel(neighbor_shifts=ex.shifts)
                transport_hooks["neighbor"] = ex
        elif gossip in ("roll", "dense"):
            mix_fn = scheduled_dense_mix()
            # "auto" goes allgather only where the fallback used to be dense
            # with NO wire win at all: fault-rewritten W on the roll backend
            rewritten = gossip == "roll" and scenario.mutates_w
            want_ag = wire_mode == "allgather" or (
                wire_mode == "auto" and rewritten
            )
            if want_ag and comp is not None:
                compressed_combine = allgather_combine(
                    comp, mesh, scheduled=True, node_axes=node_axes
                )
            if want_ag and diff_codec:
                _rebind_channel(replicated_wire=True)
                transport_hooks["gather_payload"] = replicate_gather(mesh, node_axes=node_axes)
                transport_hooks["pin_replicated"] = replicate_pin(mesh)
                transport_hooks["run_local"] = replicated_local(mesh)
                transport_hooks["pin_node"] = node_pin(mesh, node_axes)
        else:
            raise ValueError(gossip)
    elif n_nodes == 1:
        mix_fn = identity_mix
    elif gossip == "dense":
        mix_fn = dense_mix(topology.w)
        if wire_mode == "allgather":
            if comp is not None:
                compressed_combine = allgather_combine(comp, mesh, w=topology.w,
                                                      node_axes=node_axes)
            if diff_codec:
                _rebind_channel(replicated_wire=True)
                transport_hooks["gather_payload"] = replicate_gather(mesh, node_axes=node_axes)
                transport_hooks["pin_replicated"] = replicate_pin(mesh)
                transport_hooks["run_local"] = replicated_local(mesh)
                transport_hooks["pin_node"] = node_pin(mesh, node_axes)
    elif gossip == "roll":
        if wire_mode == "allgather":
            mix_fn = dense_mix(topology.w)
            if comp is not None:
                compressed_combine = allgather_combine(comp, mesh, w=topology.w,
                                                      node_axes=node_axes)
            if diff_codec:
                _rebind_channel(replicated_wire=True)
                transport_hooks["gather_payload"] = replicate_gather(mesh, node_axes=node_axes)
                transport_hooks["pin_replicated"] = replicate_pin(mesh)
                transport_hooks["run_local"] = replicated_local(mesh)
                transport_hooks["pin_node"] = node_pin(mesh, node_axes)
        else:
            mix_fn = roll_mix(topology)
            if comp is not None:
                compressed_combine = rotation_combine(
                    comp, (Rotation.from_topology(topology),)
                )
            if diff_chan and wire_mode in ("auto", "neighbor"):
                ex = neighbor_exchange(
                    (Rotation.from_topology(topology),), scheduled=False
                )
                _rebind_channel(neighbor_shifts=ex.shifts)
                transport_hooks["neighbor"] = ex
    else:
        raise ValueError(gossip)

    rules = profile.train_rules(mesh)
    param_rules = profile.train_param_rules(mesh)

    # ---- per-node loss/grad, vmapped over the node axis ----
    def node_loss(params, batch):
        return model.loss(params, batch, dtype=jnp.bfloat16)

    # the node dim lies on the node axes: naming them lets a shard_map inside
    # the model (the flash kernel) keep each node on its own devices
    node_vmap = partial(jax.vmap, spmd_axis_name=tuple(node_axes) or None)
    vgrad_full = node_vmap(jax.grad(node_loss))
    vloss = node_vmap(node_loss)

    def vgrad(p, batch):
        """Per-node gradients, optionally microbatched (gradient accumulation
        inside each local step: activation memory divides by grad_accum at
        the cost of re-walking the weights per microbatch — §Perf A5)."""
        if grad_accum <= 1:
            return vgrad_full(p, batch)

        def split(x):  # (N, b, ...) -> (accum, N, b/accum, ...)
            n, b = x.shape[0], x.shape[1]
            assert b % grad_accum == 0, (b, grad_accum)
            return x.reshape(n, grad_accum, b // grad_accum, *x.shape[2:]).swapaxes(0, 1)

        mbs = jax.tree.map(split, batch)
        zero = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), p)

        def body(acc, mb):
            g = vgrad_full(p, mb)
            return jax.tree.map(lambda a, gi: a + gi.astype(jnp.float32), acc, g), ()

        total, _ = lax.scan(body, zero, mbs)
        return jax.tree.map(lambda t, pp: (t / grad_accum).astype(pp.dtype), total, p)

    def _make_comm_grad(loss_cell):
        def comm_grad(p, b):
            """Gradient for the communication step, capturing the metrics
            loss (only traced OUTSIDE the local-update scan)."""
            if grad_accum > 1:
                # metrics loss from the first microbatch (cheap); grads
                # accumulate over all microbatches
                mb0 = jax.tree.map(lambda x: x[:, : x.shape[1] // grad_accum], b)
                loss_cell.append(vloss(p, mb0).mean())
                return vgrad(p, b)
            losses, grads = node_vmap(jax.value_and_grad(node_loss))(p, b)
            loss_cell.append(losses.mean())
            return grads

        return comm_grad

    def _base_metrics(state, loss_cell):
        direction = next(
            (
                getattr(state, name)
                for name in ("v", "m", "u", "y")
                if getattr(state, name, None) is not None
            ),
            None,
        )
        return {
            "loss": loss_cell[0] if loss_cell else jnp.zeros(()),
            "v_norm": (
                sum(
                    jnp.sum(v.astype(jnp.float32) ** 2)
                    for v in jax.tree.leaves(direction)
                )
                if direction is not None
                else jnp.zeros(())
            ),
        }

    if scenario is None:

        def train_step(state, batches):
            with axis_rules(rules, mesh, param_rules=param_rules):
                loss_cell = []
                round_step, _ = make_round_step(
                    alg, mix_fn, grad_of_batch=vgrad,
                    comm_grad_of_batch=_make_comm_grad(loss_cell),
                    compressed_combine=compressed_combine,
                    transport_hooks=transport_hooks or None,
                )
                state = round_step(state, batches)
                return state, _base_metrics(state, loss_cell)

    else:
        from ..scenarios.metrics import make_stream_fn  # lazy: launch <- scenarios

        # runtime reference: the buffer mean (no full-batch closure here)
        stream_fn = make_stream_fn(
            buffer_name=getattr(alg, "tracking_buffer", None),
            comm_buffers=alg.comm.buffers,
        )

        def train_step(state, batches, ctx):
            with axis_rules(rules, mesh, param_rules=param_rules):
                loss_cell = []
                round_step, _ = make_round_step(
                    alg, mix_fn, grad_of_batch=vgrad,
                    comm_grad_of_batch=_make_comm_grad(loss_cell),
                    scheduled=True,
                    gate_local=scenario.needs_local_gate,
                    gate_active=scenario.needs_active_gate,
                    compressed_combine=compressed_combine,
                    transport_hooks=transport_hooks or None,
                )
                state = round_step(state, batches, ctx)
                metrics = _base_metrics(state, loss_cell)
                metrics.update(stream_fn(state, ctx))
                return state, metrics

    # ---- abstract state (dry-run, no allocation) + shardings ----
    # The state layout is derived generically: every algorithm state is a
    # registered dataclass whose fields are param-shaped pytrees (node-stacked)
    # or the scalar step counter, so eval_shape(init) + field-wise spec
    # assignment covers all of ALGORITHMS without per-class code.
    shapes = model.param_shapes(dtype=jnp.float32)
    stacked_struct = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((n_nodes,) + s.shape, s.dtype), shapes
    )
    abstract_state = abstract_channel_state(
        alg, jax.eval_shape(lambda p: alg.init(p), stacked_struct)
    )

    with axis_rules(rules, mesh, param_rules=param_rules):
        node_prefix = (node_axes if node_axes else None,)
        param_spec = resolve_specs(model.param_specs(), prefix=node_prefix)

    state_spec_fields = {}
    for f in dataclasses.fields(type(abstract_state)):
        v = getattr(abstract_state, f.name)
        if v is None:
            state_spec_fields[f.name] = None
        elif isinstance(v, ChannelState):
            # the channel describes its own wire layout: params-shaped
            # subtrees (residuals / replicas) get the param sharding, (N,)
            # per-node vectors (ages, send masks) shard over the node axes,
            # and the codec PRNG key is a replicated scalar
            node_vec_spec = P(node_axes if node_axes else None)
            state_spec_fields[f.name] = ChannelState(
                wire=tuple(
                    chan.for_buffer(i).wire_spec(
                        param_spec, node_vec_spec, stacked_struct
                    )
                    for i in range(len(v.wire))
                ),
                key=P(),
            )
        elif isinstance(v, jax.ShapeDtypeStruct) and v.ndim == 0:
            state_spec_fields[f.name] = P()
        else:
            state_spec_fields[f.name] = param_spec
    state_spec = type(abstract_state)(**state_spec_fields)
    state_shardings = _named(mesh, state_spec)

    batch_rule = rules.get("batch")
    def batch_spec(s):
        # (tau, N, b, ...) -> P(None, node_axes, batch_rule, None...)
        # batch_rule drops out when the per-node batch is not divisible by the
        # within-node axis (e.g. fsdp on the multi-pod mesh: 256/32 nodes = 8
        # rows < 16-way model axis)
        rule = batch_rule
        seq_rule = None
        if rule is not None and s.shape[2] % max(1, _axsize(mesh, rule)):
            # batch not divisible: shard the sequence dim instead when the
            # profile provides a 'seq' rule (fsdp multi-pod, EXPERIMENTS A6)
            sr = rules.get("seq")
            if sr is not None and len(s.shape) >= 4 and s.shape[3] % max(1, _axsize(mesh, sr)) == 0:
                seq_rule = sr
            rule = None
        extra = (None,) * (len(s.shape) - 4) if len(s.shape) >= 4 else ()
        dims = [None, node_axes if node_axes else None, rule]
        if len(s.shape) >= 4:
            dims.append(seq_rule)
        return NamedSharding(mesh, P(*dims, *extra))

    def abstract_batch_fn(seq_len, global_batch):
        return _node_batch_struct(model, round_len, n_nodes, seq_len, global_batch)

    probe_seq = max(512, cfg.n_vision_tokens + 64)
    probe = abstract_batch_fn(probe_seq, max(n_nodes, 1))
    batch_shardings = jax.tree.map(batch_spec, probe)

    return TrainJob(
        model=model,
        mesh=mesh,
        profile=profile,
        algorithm=alg,
        tau=int(getattr(alg, "tau", 1)),
        round_len=round_len,
        n_nodes=n_nodes,
        gossip=gossip,
        step_fn=train_step,
        state_shardings=state_shardings,
        batch_shardings=batch_shardings,
        abstract_state=abstract_state,
        abstract_batch_fn=abstract_batch_fn,
        scenario=scenario,
    )


# ==========================================================================
# serving
# ==========================================================================
@dataclasses.dataclass
class ServeJob:
    model: Model
    mesh: Any
    profile: ShardingProfile
    prefill_fn: Callable
    decode_fn: Callable
    param_shardings: PyTree
    abstract_params: PyTree

    def lower_prefill(self, seq_len: int, batch: int):
        spec = self.model.input_specs(seq_len, batch, for_loss=False)
        batch_axes = self.profile.data_axes(self.mesh)
        shardings = jax.tree.map(
            lambda s: NamedSharding(
                self.mesh,
                P(batch_axes if s.shape[0] % max(1, _axsize(self.mesh, batch_axes)) == 0 else None,
                  *([None] * (len(s.shape) - 1))),
            ),
            spec,
        )
        return jax.jit(
            self.prefill_fn, in_shardings=(self.param_shardings, shardings)
        ).lower(self.abstract_params, spec)

    def lower_decode(self, cache_len: int, batch: int, seq_shard_cache: bool = False):
        cache = jax.eval_shape(lambda: self.model.init_cache(batch, cache_len, jnp.bfloat16))
        batch_axes = self.profile.data_axes(self.mesh)
        if batch % max(1, _axsize(self.mesh, batch_axes)):
            batch_axes = None
        c_specs = cache_specs(
            cache, batch_axes, mesh=self.mesh,
            seq_shard_axes=self.profile.data_axes(self.mesh) if seq_shard_cache else None,
        )
        c_shard = _named(self.mesh, c_specs)
        tok = jax.ShapeDtypeStruct((batch, 1), jnp.int32)
        pos = jax.ShapeDtypeStruct((batch,), jnp.int32)
        tok_shard = NamedSharding(self.mesh, P(batch_axes, None))
        pos_shard = NamedSharding(self.mesh, P(batch_axes))
        return jax.jit(
            self.decode_fn,
            in_shardings=(self.param_shardings, c_shard, tok_shard, pos_shard),
            out_shardings=(None, c_shard),
        ).lower(self.abstract_params, cache, tok, pos)


def _axsize(mesh, axes):
    if not axes or axes is None:
        return 1
    shape = dict(zip(mesh.axis_names, mesh.devices.shape))
    axes = (axes,) if isinstance(axes, str) else axes
    n = 1
    for a in axes:
        n *= shape[a]
    return n


def make_serve_job(
    cfg: ModelConfig,
    mesh,
    *,
    profile: Optional[ShardingProfile] = None,
    param_dtype=jnp.bfloat16,
) -> ServeJob:
    profile = profile or profile_for_arch(cfg.name)
    model = Model(cfg)
    rules = profile.serve_rules(mesh)
    param_rules = profile.serve_param_rules(mesh)

    def prefill_fn(params, batch):
        with axis_rules(rules, mesh, param_rules=param_rules):
            return model.prefill(params, batch, dtype=jnp.bfloat16)

    def decode_fn(params, caches, tokens, position):
        with axis_rules(rules, mesh, param_rules=param_rules):
            return model.decode_step(params, caches, tokens, position, dtype=jnp.bfloat16)

    with axis_rules(rules, mesh, param_rules=param_rules):
        param_spec = resolve_specs(model.param_specs())
    param_shardings = _named(mesh, param_spec)
    abstract_params = model.param_shapes(dtype=param_dtype)

    return ServeJob(
        model=model,
        mesh=mesh,
        profile=profile,
        prefill_fn=prefill_fn,
        decode_fn=decode_fn,
        param_shardings=param_shardings,
        abstract_params=abstract_params,
    )
