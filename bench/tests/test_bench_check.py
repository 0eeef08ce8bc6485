"""``correct`` comes out false when the timed path is broken underneath, and
when the control (fp8 forward matmuls) stands in the program's place.

A whole run (``run.measure``) at a small size on the CPU, past the chip
check, with each fault a training cell can have planted under it: a step
that returns its state unchanged and half of the batch left out (the
exchange between nodes left out: ``test_bench_ring_faults.py``).  The unbroken run comes out correct under the same limits, the
cell's own: at d_model 512 the program's bfloat16 gaps on the CPU read
under them (at d_model 256 they do not; the cells, 8x wider, read lower).
"""
from __future__ import annotations

import math

import jax
import numpy as np
import pytest

import check
import harness
import run

SMALL = dict(n_layers=2, d_model=512, n_heads=8, n_kv_heads=2, head_dim=64, d_ff=1024,
             vocab_size=512)


def small_cell(name: str) -> harness.Cell:
    cell = harness.load_cell(name)
    cell.config = dict(cell.config, model=dict(cell.model, **SMALL))
    cell.traffic = dict(cell.traffic, seq_len=64, pool_rounds=4)
    return cell


def measure(cell, monkeypatch, seed=2**31 + 17):
    monkeypatch.setattr(run, "use_cache", lambda root: {"hits": 0, "misses": 0, "compiles": 0})
    devices = jax.devices()[: cell.chips]
    return run.measure(cell, seed, 0.5, False, devices, {"bf16_flops": 1e12})


def test_verdict():
    limits = {"a": 1.0, "b": 2.0}
    assert check.verdict({"a": 0.5, "b": 2.0}, limits)[0]
    assert not check.verdict({"a": 1.5, "b": 0.0}, limits)[0]
    assert not check.verdict({"a": math.nan, "b": 0.0}, limits)[0]
    ok, out = check.verdict({"a": 0.1}, limits)
    assert not ok and out["b"]["value"] == math.inf


def test_leaves_without_gradient_leave_the_update_gap():
    ref = {"loss": [1.0], "grad": {"a": np.array([1.0]), "b": np.array([1.0]), "bk": np.array([1e-9])},
           "delta": {"a": np.array([2.0]), "b": np.array([2.0]), "bk": np.array([1e-12])}}
    prog = dict(ref, delta=dict(ref["delta"], bk=np.array([5e-7])))
    assert check.numbers(prog, ref)["update_gap"] == 0.0
    assert check.excluded_leaves(ref) == ["bk"]


def test_median_leaf_gap_passes_over_one_swinging_leaf():
    """One leaf 10 % off moves the worst gap, not the median; every leaf
    off moves both, on the worst node."""
    ones = {k: np.array([1.0, 1.0]) for k in ("a", "b", "c", "d", "e")}
    ref = {"loss": [1.0], "grad": ones, "delta": ones}
    one = dict(ones, a=np.array([1.1, 1.0]))
    got = check.numbers({"loss": [1.0], "grad": one, "delta": one}, ref)
    assert got["grad_gap"] == pytest.approx(0.1) and got["update_gap"] == pytest.approx(0.1)
    assert got["grad_gap_median"] == 0.0 and got["update_gap_median"] == 0.0
    every = {k: np.array([1.0, 1.05]) for k in ones}
    got = check.numbers({"loss": [1.0], "grad": every, "delta": every}, ref)
    assert got["grad_gap_median"] == pytest.approx(0.05)
    assert got["update_gap_median"] == pytest.approx(0.05)


def test_unbroken_run_is_correct(monkeypatch):
    res = measure(small_cell("yi9b.s2k.tau4"), monkeypatch)
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks" and res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {"tokens_per_s", "setup_s"}


def test_state_left_unchanged_is_not_correct(monkeypatch):
    compile_ = harness.Trainer.compile

    def frozen(self):
        compile_(self)
        step = jax.jit(self.job.step_fn)
        self.step = lambda state, batch: (state, step(state, batch)[1])

    monkeypatch.setattr(harness.Trainer, "compile", frozen)
    res = measure(small_cell("yi9b.s2k.tau4"), monkeypatch)
    assert not res["correct"]
    assert res["checks"]["update_gap"]["value"] == pytest.approx(1.0)


def test_half_batch_is_not_correct(monkeypatch):
    from control import halve

    class Halved:
        def __init__(self, feed):
            self.feed = feed

        def round(self, r):
            return halve(self.feed.round(r))

    init = harness.Trainer.__init__

    def halved(self, m, job, feed):
        init(self, m, job, Halved(feed))

    monkeypatch.setattr(harness.Trainer, "__init__", halved)
    res = measure(small_cell("yi9b.s2k.tau4"), monkeypatch)
    assert not res["correct"], res["checks"]


def test_control_is_not_correct():
    """The reference with fp8 forward matmuls, in the program's place."""
    import control
    from reference import Reference
    from traffic import Traffic

    cell = small_cell("yi9b.s2k.tau4")
    m, tr = cell.model, cell.traffic
    feed = Traffic.make(tr, m, 1, 2**31 + 3)
    x0 = jax.jit(lambda k: control_weights(m, k))(jax.random.key(2**31 + 3))
    rounds = [feed.round(r) for r in range(tr["check_rounds"])]
    ref = Reference(m, tr, 1, jax.devices()[:1]).run(x0, rounds)
    got = Reference(m, tr, 1, jax.devices()[:1], quant="fp8").run(x0, rounds)
    ok, out = check.verdict(check.numbers(got, ref), cell.checks["limits"])
    assert not ok, out
    assert control.halve(rounds[0])["tokens"].shape == rounds[0]["tokens"].shape


def control_weights(m, key):
    from weights import make_params

    return make_params(m, key)
