"""Communication-compression subsystem acceptance tests.

Covers the new_subsystem criteria:

  * registry + validation (``make_compressor`` shorthands, CommSpec's
    ``compression`` field, ValueError on junk specs / hyperparameters);
  * per-codec roundtrip properties (identity exact, qsgd error bound +
    unbiasedness, top-k/rand-k sparsity, low-rank reconstruction) and the
    analytic ``payload_bytes`` model (>= 4x for qsgd / top_k:0.1);
  * error feedback: residual = input - decode(encode(input)), matched
    per-buffer through the round executor's ChannelSession;
  * ``compression="identity"`` is BIT-identical to the uncompressed gossip
    path for all 8 algorithms on the simulator (the sharded-engine half of
    that guarantee lives in the subprocess test below);
  * compressed DSE-MVR still converges (loss decreases, finite iterates)
    and streams a finite per-round ``compression_err``;
  * sharded engine: identity bit-parity for all 8 algorithms, and the
    compressed roll backend's measured HLO collective-permute bytes shrink
    >= 4x (packed payloads actually cross the links, not dense buffers).
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.compression import (
    COMPRESSORS,
    ChannelSession,
    ChannelState,
    ErrorFeedback,
    Identity,
    LowRank,
    QSGD,
    RandK,
    SyncChannel,
    TopK,
    Transport,
    attach_compression,
    compression_error,
    make_compressor,
)
from repro.core import ALGORITHMS, CommSpec, Simulator, make_algorithm, ring
from repro.data import iid_partition, make_classification, partition_to_node_data

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

N_NODES = 4
DIM, CLASSES = 8, 3


def make_data(seed=0):
    x, y = make_classification(400, DIM, CLASSES, seed=seed, class_sep=2.0)
    parts = iid_partition(len(x), N_NODES, seed=seed)
    return partition_to_node_data(x, y, parts)


def loss_fn(params, batch):
    xb, yb = batch
    logits = xb @ params["w"] + params["b"]
    logp = jax.nn.log_softmax(logits)
    return -jnp.take_along_axis(logp, yb[..., None], axis=-1).mean()


def init_params():
    return {"w": jnp.zeros((DIM, CLASSES), jnp.float32), "b": jnp.zeros(CLASSES)}


# ---------------------------------------------------------------- registry
def test_make_compressor_registry_and_shorthands():
    assert set(COMPRESSORS) >= {"identity", "qsgd", "top_k", "rand_k", "low_rank"}
    assert isinstance(make_compressor("identity"), Identity)
    # lossy codecs are error-feedback-wrapped by default
    c = make_compressor("top_k:0.05")
    assert isinstance(c, ErrorFeedback) and isinstance(c.inner, TopK)
    assert c.inner.ratio == 0.05 and c.uses_residual
    assert isinstance(make_compressor("qsgd", error_feedback=False), QSGD)
    assert isinstance(make_compressor("rand_k:0.5").inner, RandK)
    assert isinstance(make_compressor("low_rank:3").inner, LowRank)
    # instance passthrough
    inst = TopK(ratio=0.2)
    assert make_compressor(inst) is inst


@pytest.mark.parametrize(
    "bad",
    ["nope", 123, "top_k:zzz", "qsgd:9000", "top_k:0.0", "top_k:1.5", "low_rank:0"],
)
def test_make_compressor_rejects_junk(bad):
    with pytest.raises(ValueError):
        make_compressor(bad)


def test_error_feedback_wrapping_rules():
    with pytest.raises(ValueError):
        ErrorFeedback(inner=None)
    with pytest.raises(ValueError):
        ErrorFeedback(inner=ErrorFeedback(inner=TopK()))
    # wrapping identity stays identity (and the executor short-circuits it)
    assert ErrorFeedback(inner=Identity()).is_identity


# ---------------------------------------------------------------- CommSpec
def test_commspec_validation_edge_cases():
    # comm_events_per_round at tau=1: one event per window on both cadences
    assert CommSpec(cadence="every_tau").comm_events_per_round(1) == 1
    assert CommSpec(cadence="every_step").comm_events_per_round(1) == 1
    assert CommSpec(cadence="every_step").comm_events_per_round(4) == 4
    assert CommSpec(cadence="every_tau").round_len(1) == 1
    with pytest.raises(ValueError):
        CommSpec(cadence="sometimes")
    with pytest.raises(ValueError):
        CommSpec(reset="hard")
    with pytest.raises(ValueError):
        CommSpec(compression="nope")
    with pytest.raises(ValueError):
        CommSpec(compression=3.14)
    # names resolve to instances; identity is not "active"
    spec = CommSpec(compression="qsgd")
    assert isinstance(spec.compression, ErrorFeedback)
    assert spec.active_compression() is spec.compression
    assert CommSpec(compression="identity").active_compression() is None
    assert CommSpec().active_compression() is None


def test_algorithm_compression_field_rebuilds_spec():
    alg = make_algorithm("dse_mvr", lr=0.1, tau=2, compression="top_k:0.25")
    assert alg.comm.active_compression() is not None
    assert alg.comm.buffers == type(alg).comm.buffers
    # the class-level spec is untouched
    assert type(alg).comm.compression is None
    plain = make_algorithm("dse_mvr", lr=0.1, tau=2)
    assert plain.comm.active_compression() is None


# ---------------------------------------------------------------- codecs
def _leaf(key, shape=(N_NODES, 33, 7)):
    return jax.random.normal(key, shape)


def test_identity_roundtrip_exact():
    x = _leaf(jax.random.key(0))
    c = Identity()
    np.testing.assert_array_equal(np.asarray(c.decode(c.encode(x, None))), np.asarray(x))


def test_qsgd_roundtrip_error_bound_and_unbiasedness():
    c = QSGD()
    x = _leaf(jax.random.key(1))
    dec = c.decode(c.encode(x, jax.random.key(0)))
    # per-element error <= one quantization step of that node's scale
    scale = jnp.max(jnp.abs(x.reshape(N_NODES, -1)), axis=1)
    step = scale / c.levels
    err = jnp.max(jnp.abs((dec - x).reshape(N_NODES, -1)), axis=1)
    assert np.all(np.asarray(err) <= np.asarray(step) * (1 + 1e-5))
    # stochastic rounding is unbiased: averaging decodes converges to x
    one = float(jnp.mean(jnp.abs(dec - x)))
    avg = jnp.mean(
        jnp.stack([
            c.decode(c.encode(x, jax.random.key(i))) for i in range(32)
        ]),
        axis=0,
    )
    assert float(jnp.mean(jnp.abs(avg - x))) < one / 3


@pytest.mark.parametrize("cls", [TopK, RandK])
def test_sparsifiers_keep_exactly_k(cls):
    c = cls(ratio=0.25)
    x = _leaf(jax.random.key(2))
    d = 33 * 7
    k = c.k_for(d)
    p = c.encode(x, jax.random.key(3))
    assert p.data["vals"].shape == (N_NODES, k)
    dense = c.decode(p)
    nz = np.count_nonzero(np.asarray(dense).reshape(N_NODES, -1), axis=1)
    assert np.all(nz <= k)
    # kept entries match x exactly
    mask = np.asarray(dense) != 0
    np.testing.assert_allclose(
        np.asarray(dense)[mask], np.asarray(x)[mask], rtol=1e-6
    )
    # top-k specifically keeps the largest magnitudes
    if cls is TopK:
        xa = np.abs(np.asarray(x).reshape(N_NODES, -1))
        kept = np.asarray(dense).reshape(N_NODES, -1) != 0
        for i in range(N_NODES):
            thr = np.sort(xa[i])[-k]
            assert xa[i][kept[i]].min() >= thr - 1e-6


def test_low_rank_reconstructs_low_rank_matrices():
    c = LowRank(rank=2)
    key = jax.random.key(4)
    u = jax.random.normal(key, (N_NODES, 24, 2))
    v = jax.random.normal(jax.random.fold_in(key, 1), (N_NODES, 2, 18))
    x = u @ v  # exactly rank 2
    dec = c.decode(c.encode(x, jax.random.key(5)))
    np.testing.assert_allclose(np.asarray(dec), np.asarray(x), rtol=1e-3, atol=1e-3)
    # 1-D leaves fall back to raw (exact)
    b = jax.random.normal(key, (N_NODES, 13))
    np.testing.assert_array_equal(
        np.asarray(c.decode(c.encode(b, jax.random.key(6)))), np.asarray(b)
    )


def test_payload_bytes_model():
    d = 100_000
    raw = d * 4
    assert Identity().payload_bytes((d,), jnp.float32) == raw
    q = QSGD().payload_bytes((d,), jnp.float32)
    assert raw / q > 3.99
    t = TopK(ratio=0.1).payload_bytes((d,), jnp.float32)
    assert raw / t == pytest.approx(5.0, rel=1e-3)
    lr_ = LowRank(rank=2).payload_bytes((500, 200), jnp.float32)
    assert lr_ == (500 + 200) * 2 * 4
    # the EF wrapper never changes wire bytes
    assert make_compressor("qsgd").payload_bytes((d,), jnp.float32) == q


def test_error_feedback_residual_semantics():
    c = make_compressor("top_k:0.25")
    x = {"w": _leaf(jax.random.key(7))}
    zero = jax.tree.map(jnp.zeros_like, x)
    payload, dec, res = c.roundtrip(x, zero, jax.random.key(8))
    np.testing.assert_allclose(
        np.asarray(res["w"]), np.asarray(x["w"] - dec["w"]), rtol=1e-5, atol=1e-6
    )
    # second round transmits x + e; residual now tracks the new message
    payload2, dec2, res2 = c.roundtrip(x, res, jax.random.key(9))
    inp = x["w"] + res["w"]
    np.testing.assert_allclose(
        np.asarray(res2["w"]), np.asarray(inp - dec2["w"]), rtol=1e-5, atol=1e-6
    )


def test_channel_session_enforces_buffer_count():
    channel = SyncChannel(compression=make_compressor("top_k:0.5"))
    tree = {"w": _leaf(jax.random.key(10))}
    wire = channel.init_wire(tree)
    transport = Transport(lambda t: t)
    state = ChannelState(wire=(wire, wire), key=jax.random.key(0))
    sess = ChannelSession(channel, 2, state, transport)
    sess.mix(tree)
    with pytest.raises(ValueError):
        sess.final_state()          # only 1 of 2 declared buffers gossiped
    sess.mix(tree)
    out = sess.final_state()
    assert len(out.wire) == 2
    sess2 = ChannelSession(
        channel, 1, ChannelState((wire,), jax.random.key(0)), transport
    )
    sess2.mix(tree)
    with pytest.raises(ValueError):
        sess2.mix(tree)             # more gossip calls than declared buffers


# ------------------------------------------------------- simulator engine
def _run_sim(name, comp, steps=8, key=42):
    alg = make_algorithm(name, lr=0.15, tau=2, alpha=0.2, compression=comp)
    sim = Simulator(alg, ring(N_NODES), loss_fn, make_data(), batch_size=8)
    return sim.run(init_params(), jax.random.key(key), num_steps=steps)["state"]


@pytest.mark.parametrize("name", sorted(ALGORITHMS))
def test_identity_bit_parity_simulator(name):
    """compression='identity' must be BIT-identical to the uncompressed
    gossip path (acceptance criterion; the sharded half is below)."""
    a = _run_sim(name, None)
    b = _run_sim(name, "identity")
    for la, lb in zip(jax.tree.leaves(a.params), jax.tree.leaves(b.params)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


@pytest.mark.parametrize("comp", ["qsgd", "top_k:0.25"])
def test_all_algorithms_run_compressed_simulator(comp):
    for name in sorted(ALGORITHMS):
        state = _run_sim(name, comp, steps=6)
        assert state.comp is not None, name
        for leaf in jax.tree.leaves(state.params):
            assert np.all(np.isfinite(np.asarray(leaf))), (name, comp)


def test_dse_mvr_compressed_converges():
    data = make_data()
    results = {}
    for comp in (None, "qsgd"):
        alg = make_algorithm("dse_mvr", lr=0.2, tau=4, alpha=0.1, compression=comp)
        sim = Simulator(alg, ring(N_NODES), loss_fn, data, batch_size=16)
        out = sim.run(init_params(), jax.random.key(0), num_steps=32, eval_every=16)
        results[comp] = out["history"]
    first, last = results["qsgd"][0], results["qsgd"][-1]
    assert last["train_loss"] < first["train_loss"]
    # compressed loss lands in the same regime as uncompressed
    assert results["qsgd"][-1]["train_loss"] < 2 * results[None][-1]["train_loss"] + 0.1


def test_compression_error_stream():
    from repro.scenarios import make_scenario
    from repro.scenarios.metrics import STREAM_FIELDS

    assert "compression_err" in STREAM_FIELDS
    data = make_data()
    for comp, finite in ((None, False), ("qsgd", True)):
        alg = make_algorithm("dse_mvr", lr=0.15, tau=2, alpha=0.2, compression=comp)
        sim = Simulator(alg, None, loss_fn, data, batch_size=8,
                        scenario=make_scenario("baseline"))
        out = sim.run(init_params(), jax.random.key(0), num_steps=6)
        ce = np.asarray(out["streams"]["compression_err"])
        assert ce.shape == (3,)
        assert np.all(np.isfinite(ce)) == finite


def test_attach_compression_noop_without_codec():
    alg = make_algorithm("dse_mvr", lr=0.1, tau=2)
    stacked = jax.tree.map(
        lambda p: jnp.broadcast_to(p[None], (N_NODES,) + p.shape), init_params()
    )
    state = alg.init(stacked)
    assert attach_compression(alg, state) is state
    assert not np.isfinite(float(compression_error(state)))
    alg_c = make_algorithm("dse_mvr", lr=0.1, tau=2, compression="top_k:0.5")
    state_c = attach_compression(alg_c, alg_c.init(stacked), jax.random.key(0))
    assert isinstance(state_c.comp, ChannelState)
    assert len(state_c.comp.wire) == len(alg_c.comm.buffers)
    assert all("res" in w for w in state_c.comp.wire)
    assert float(compression_error(state_c)) == 0.0


def test_compressed_state_checkpoints(tmp_path):
    """The CompressionState (typed PRNG key included) must survive the
    checkpoint round trip like any other state buffer."""
    from repro.checkpoint.checkpoint import load_checkpoint, save_checkpoint

    alg = make_algorithm("dse_mvr", lr=0.1, tau=2, compression="top_k:0.5")
    stacked = jax.tree.map(
        lambda p: jnp.broadcast_to(p[None], (N_NODES,) + p.shape), init_params()
    )
    state = attach_compression(alg, alg.init(stacked), jax.random.key(7))
    save_checkpoint(str(tmp_path), 0, state)
    loaded, _ = load_checkpoint(str(tmp_path), like=state)
    np.testing.assert_array_equal(
        np.asarray(jax.random.key_data(loaded.comp.key)),
        np.asarray(jax.random.key_data(state.comp.key)),
    )
    for a, b in zip(jax.tree.leaves(loaded.params), jax.tree.leaves(state.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------- fused hot paths
def test_compression_fused_ops_registered():
    from repro.kernels import api

    names = {"qsgd_quantize", "qsgd_dequantize", "top_k_pack", "top_k_unpack"}
    assert names <= set(api.REGISTRY)
    # top-k has no kernel: its one-hot design could not compile at real k
    assert api.REGISTRY["top_k_pack"].kernel_fn is None
    assert api.REGISTRY["top_k_unpack"].kernel_fn is None
    assert api.REGISTRY["qsgd_quantize"].expr is not None


def test_top_k_pack_unpack_interpret_parity():
    from repro.kernels import api
    from repro.kernels.comm_compress import top_k_pack_ref, top_k_unpack_ref

    key = jax.random.key(11)
    x = jax.random.normal(key, (3, 777))          # odd d: exercises padding
    idx = jax.random.randint(jax.random.fold_in(key, 1), (3, 13), 0, 777).astype(jnp.int32)
    with api.dispatch_mode("interpret"):
        vals = api.call("top_k_pack", x, idx)
        dense = api.call("top_k_unpack", idx, vals, d=777)
    np.testing.assert_allclose(
        np.asarray(vals), np.asarray(top_k_pack_ref(x, idx)), rtol=1e-6
    )
    np.testing.assert_allclose(
        np.asarray(dense), np.asarray(top_k_unpack_ref(idx, vals, 777)),
        rtol=1e-6, atol=1e-6,
    )


# ------------------------------------------------------------ sharded engine
def run_sub(code: str, timeout=1200):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, timeout=timeout, env=env,
    )
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr[-4000:]}"
    return out.stdout


def test_identity_bit_parity_and_link_bytes_sharded():
    """Sharded-engine acceptance: identity is bit-identical to the plain
    train step for ALL 8 algorithms, and top_k compression shrinks the
    measured collective-permute link bytes >= 4x while the step stays
    finite."""
    run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import ALGORITHMS
        from repro.launch.distributed import make_train_job
        from repro.launch.hlo_analysis import analyze_module
        from repro.launch.mesh import make_test_mesh
        from repro.models import ModelConfig

        mesh = make_test_mesh((4, 2), ("data", "model"))
        cfg = ModelConfig(name="lm-tiny", arch_type="dense", n_layers=1,
                          d_model=32, n_heads=4, n_kv_heads=2, d_ff=64,
                          vocab_size=256, block_unit=("attn",), tie_embeddings=True)
        seq, gb = 16, 8
        def bat(rl, key):
            return {"tokens": jax.random.randint(key, (rl, 4, gb // 4, seq), 0, cfg.vocab_size),
                    "targets": jax.random.randint(jax.random.fold_in(key, 1), (rl, 4, gb // 4, seq), 0, cfg.vocab_size)}

        for name in sorted(ALGORITHMS):
            j0 = make_train_job(cfg, mesh, algorithm=name, tau=3, lr=1e-2)
            j1 = make_train_job(cfg, mesh, algorithm=name, tau=3, lr=1e-2,
                                compression="identity")
            b = bat(j0.round_len, jax.random.key(1))
            s0, _ = jax.jit(j0.step_fn)(j0.init_state(jax.random.key(0)), b)
            s1, _ = jax.jit(j1.step_fn)(j1.init_state(jax.random.key(0)), b)
            for a, c in zip(jax.tree.leaves(s0.params), jax.tree.leaves(s1.params)):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(c))
            print(name, "IDENTITY PARITY OK")

        # compressed roll: packed payloads on the wire, >= 4x fewer bytes
        jc = make_train_job(cfg, mesh, algorithm="dse_mvr", tau=3, lr=1e-2,
                            compression="top_k:0.03125")
        j0 = make_train_job(cfg, mesh, algorithm="dse_mvr", tau=3, lr=1e-2)
        b = bat(3, jax.random.key(1))
        sc, mc = jax.jit(jc.step_fn)(jc.init_state(jax.random.key(0)), b)
        assert np.isfinite(float(mc["loss"])), mc
        assert all(np.all(np.isfinite(np.asarray(l))) for l in jax.tree.leaves(sc.params))
        p0 = analyze_module(j0.lower(seq, gb).compile().as_text()).collective_link_bytes.get("collective-permute", 0)
        pc = analyze_module(jc.lower(seq, gb).compile().as_text()).collective_link_bytes.get("collective-permute", 0)
        ratio = p0 / max(pc, 1)
        assert ratio >= 4.0, (p0, pc, ratio)
        print(f"LINK BYTES OK {p0:.0f} -> {pc:.0f} ({ratio:.1f}x)")
    """)
