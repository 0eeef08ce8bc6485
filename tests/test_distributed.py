"""Distributed runtime tests (run in a subprocess with 8 fake CPU devices,
since the main pytest process must keep the default 1-device config)."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_sub(code: str, timeout=900):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, timeout=timeout, env=env,
    )
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr[-4000:]}"
    return out.stdout


def test_train_job_runs_and_matches_simulator():
    """THE integration test: the distributed train round (4 nodes x 2-way
    model mesh, roll gossip) must produce numerically identical iterates to
    the single-process simulation engine running the same algorithm."""
    run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_reduced
        from repro.launch.distributed import make_train_job
        from repro.launch.mesh import make_test_mesh
        from repro.core import DSEMVR, ring
        from repro.core.mixing import dense_mix

        mesh = make_test_mesh((4, 2), ("data", "model"))
        cfg = get_reduced("yi_9b")
        tau, lr, alpha = 3, 1e-2, 0.1
        job = make_train_job(cfg, mesh, tau=tau, lr=lr, alpha=alpha, gossip="roll")
        assert job.n_nodes == 4

        key = jax.random.key(0)
        state = job.init_state(key)
        seq, gb = 32, 8
        bkey = jax.random.key(1)
        toks = jax.random.randint(bkey, (tau, 4, gb // 4, seq), 0, cfg.vocab_size)
        tgts = jax.random.randint(jax.random.fold_in(bkey, 1), (tau, 4, gb // 4, seq), 0, cfg.vocab_size)
        batches = {"tokens": toks, "targets": tgts}

        step = jax.jit(job.step_fn,
                       in_shardings=(job.state_shardings, job.batch_shardings),
                       out_shardings=(job.state_shardings, None))
        new_state, metrics = step(state, batches)
        assert np.isfinite(float(metrics["loss"])), metrics

        # ---- reference: same algorithm via the simulation path (dense W) ----
        from repro.models import Model
        model = Model(cfg)
        alg = DSEMVR(lr=lr, alpha=alpha, tau=tau, fuse_tracking_buffers=True)
        mix = dense_mix(ring(4).w)
        vgrad = jax.vmap(jax.grad(lambda p, b: model.loss(p, b, dtype=jnp.bfloat16)))
        ref = alg.init(jax.tree.map(lambda p: jnp.broadcast_to(p[None], (4,) + p.shape),
                                    model.init(jax.random.key(0))))
        for t in range(tau - 1):
            mb = {"tokens": toks[t], "targets": tgts[t]}
            ref = alg.local_step(ref, lambda p: vgrad(p, mb))
        rb = {"tokens": toks[-1], "targets": tgts[-1]}
        ref = alg.round_end(ref, mix, reset_grad_fn=lambda p: vgrad(p, rb))

        got = jax.tree.leaves(new_state.params)
        want = jax.tree.leaves(ref.params)
        for g, w in zip(got, want):
            # sharded vs single-device execution reorders bf16 reductions
            np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=5e-3, atol=1e-4)
        print("EQUIVALENCE OK")
    """)


def test_train_job_builds_for_every_algorithm():
    """Unified-API + fused-op acceptance: EVERY entry in repro.core.ALGORITHMS
    builds a sharded train step via make_train_job and runs one round on the
    test mesh WITH use_fused=True (the fused-op backend's update arithmetic
    must survive sharding propagation on the runtime engine; the Simulator
    counterpart, plus fused-vs-jnp equivalence, lives in test_fused_api.py)."""
    run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import ALGORITHMS
        from repro.launch.distributed import make_train_job
        from repro.launch.mesh import make_test_mesh
        from repro.models import ModelConfig

        mesh = make_test_mesh((4, 2), ("data", "model"))
        cfg = ModelConfig(
            name="lm-tiny", arch_type="dense", n_layers=1, d_model=32,
            n_heads=4, n_kv_heads=2, d_ff=64, vocab_size=256,
            block_unit=("attn",), tie_embeddings=True,
        )
        seq, gb = 16, 8
        for name in sorted(ALGORITHMS):
            job = make_train_job(cfg, mesh, algorithm=name, tau=3, lr=1e-2,
                                 use_fused=True)
            assert job.n_nodes == 4, name
            rl = job.round_len
            assert rl == (1 if ALGORITHMS[name].comm.cadence == "every_step" else 3), name
            state = job.init_state(jax.random.key(0))
            bkey = jax.random.key(1)
            batches = {
                "tokens": jax.random.randint(bkey, (rl, 4, gb // 4, seq), 0, cfg.vocab_size),
                "targets": jax.random.randint(jax.random.fold_in(bkey, 1), (rl, 4, gb // 4, seq), 0, cfg.vocab_size),
            }
            step = jax.jit(job.step_fn,
                           in_shardings=(job.state_shardings, job.batch_shardings),
                           out_shardings=(job.state_shardings, None))
            new_state, metrics = step(state, batches)
            assert np.isfinite(float(metrics["loss"])), (name, metrics)
            assert all(np.all(np.isfinite(np.asarray(l)))
                       for l in jax.tree.leaves(new_state.params)), name
            print(name, "OK", float(metrics["loss"]))
        print("ALL ALGORITHMS OK")
    """)


def test_scenario_runtime_degenerate_and_faults():
    """Scenario-engine acceptance on the sharded runtime: the degenerate
    (static ring, no-fault) scenario reproduces the plain train step BIT FOR
    BIT through the default roll gossip; a shift-structured schedule lowers
    to collective-permute rotations; a dropout scenario runs end-to-end with
    the on-device streams in the metrics."""
    run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.distributed import make_train_job
        from repro.launch.mesh import make_test_mesh
        from repro.models import ModelConfig
        from repro.scenarios import make_scenario

        mesh = make_test_mesh((4, 2), ("data", "model"))
        cfg = ModelConfig(name="lm-tiny", arch_type="dense", n_layers=1,
                          d_model=32, n_heads=4, n_kv_heads=2, d_ff=64,
                          vocab_size=256, block_unit=("attn",), tie_embeddings=True)
        seq, gb = 16, 8
        def bat(rl, key):
            return {"tokens": jax.random.randint(key, (rl, 4, gb // 4, seq), 0, cfg.vocab_size),
                    "targets": jax.random.randint(jax.random.fold_in(key, 1), (rl, 4, gb // 4, seq), 0, cfg.vocab_size)}

        # 1) degenerate bit-identity (roll gossip -> single-rotation backend)
        job0 = make_train_job(cfg, mesh, algorithm="dse_mvr", tau=3, lr=1e-2)
        job1 = make_train_job(cfg, mesh, algorithm="dse_mvr", tau=3, lr=1e-2,
                              scenario=make_scenario("baseline"))
        b = bat(3, jax.random.key(1))
        s0, _ = jax.jit(job0.step_fn)(job0.init_state(jax.random.key(0)), b)
        s1, m1 = jax.jit(job1.step_fn)(
            job1.init_state(jax.random.key(0)), b,
            job1.round_ctx(job1.schedule_for(1), 0))
        for a, c in zip(jax.tree.leaves(s0.params), jax.tree.leaves(s1.params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(c))
        assert {"consensus", "tracking_err", "spectral_gap", "active_nodes"} <= set(m1)
        print("DEGENERATE RUNTIME OK")

        # 2) time-varying shift-structured schedule -> collective-permute
        job2 = make_train_job(cfg, mesh, algorithm="dlsgd", tau=2, lr=1e-2,
                              scenario=make_scenario("exponential"))
        txt = job2.lower(seq, gb).compile().as_text()
        assert "collective-permute" in txt, "rotation gossip must permute, not gather"
        print("ROTATION LOWERING OK")

        # 3) dropout scenario end-to-end (dense fallback, renormalized W_t)
        job3 = make_train_job(cfg, mesh, algorithm="dse_mvr", tau=3, lr=1e-2,
                              scenario=make_scenario("dropout_ring"))
        sch = job3.schedule_for(3)
        st = job3.init_state(jax.random.key(0))
        step = jax.jit(job3.step_fn)
        for r in range(3):
            st, m = step(st, bat(job3.round_len, jax.random.fold_in(jax.random.key(2), r)),
                         job3.round_ctx(sch, r))
            assert np.isfinite(float(m["loss"])), (r, m)
            assert np.isfinite(float(m["consensus"]))
        assert sch.active.min() == False  # the fault fired in this schedule
        print("DROPOUT RUNTIME OK")
    """)


def test_gossip_backends_agree_distributed():
    """dense (all-gather) and roll (collective-permute) backends must give the
    same mixed values on a sharded node axis."""
    run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.core import ring
        from repro.core.mixing import dense_mix, roll_mix
        from repro.launch.mesh import make_test_mesh

        mesh = make_test_mesh((8,), ("data",))
        top = ring(8)
        x = {"w": jax.random.normal(jax.random.key(0), (8, 64))}
        sh = NamedSharding(mesh, P("data", None))
        xs = jax.device_put(x, {"w": sh})
        d = jax.jit(dense_mix(top.w), in_shardings=({"w": sh},), out_shardings={"w": sh})(xs)
        r = jax.jit(roll_mix(top), in_shardings=({"w": sh},), out_shardings={"w": sh})(xs)
        np.testing.assert_allclose(np.asarray(d["w"]), np.asarray(r["w"]), rtol=1e-5, atol=1e-6)
        # and roll really lowers to collective-permute, dense to all-gather
        rt = jax.jit(roll_mix(top), in_shardings=({"w": sh},)).lower(x).compile().as_text()
        dt = jax.jit(dense_mix(top.w), in_shardings=({"w": sh},)).lower(x).compile().as_text()
        assert "collective-permute" in rt
        # dense W contraction over the sharded node axis lowers to a global
        # collective (all-gather / all-reduce / reduce-scatter depending on
        # the partitioner's choice) — never the neighbor-only permute
        assert any(c in dt for c in ("all-gather", "all-reduce", "reduce-scatter")), dt
        print("GOSSIP BACKENDS OK")
    """)


def test_serve_decode_runs_sharded():
    run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_reduced
        from repro.launch.distributed import make_serve_job
        from repro.launch.mesh import make_test_mesh

        mesh = make_test_mesh((4, 2), ("data", "model"))
        cfg = get_reduced("gemma2_2b")
        job = make_serve_job(cfg, mesh)
        lowered = job.lower_decode(cache_len=64, batch=8)
        compiled = lowered.compile()
        print("DECODE LOWERED OK")
    """)


def test_dryrun_hlo_analysis_sane():
    """Per-device flops from the HLO analyzer must exceed XLA's loop-blind
    cost_analysis and be within sane bounds of the analytic model cost."""
    run_sub("""
        import jax, jax.numpy as jnp
        from repro.configs import get_reduced
        from repro.launch.distributed import make_train_job
        from repro.launch.mesh import make_test_mesh
        from repro.launch.hlo_analysis import analyze_module

        mesh = make_test_mesh((4, 2), ("data", "model"))
        cfg = get_reduced("minitron_8b")
        job = make_train_job(cfg, mesh, tau=3)
        compiled = job.lower(seq_len=128, global_batch=8).compile()
        ours = analyze_module(compiled.as_text())
        xla = compiled.cost_analysis()["flops"]
        assert ours.flops >= xla, (ours.flops, xla)
        print("ANALYSIS OK", ours.flops, xla)
    """)
