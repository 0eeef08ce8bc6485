"""Model FLOPs by hand, the peak table, and the refusal to measure off the chip."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import types

import pytest

import flops
import harness


def _yi():
    return harness.load_json(harness.ROOT / "bench/configs/yi-9b.json")["model"]


def test_yi_9b_cut_by_hand():
    m = _yi()
    attn = 4096 * 32 * 128 * 2 + 4096 * 4 * 128 * 2          # wq, wo; wk, wv
    mlp = 3 * 4096 * 11008                                   # gate, up, down
    head = 4096 * 8000                                       # untied head
    assert flops.matmul_params(m) == 2 * (attn + mlp) + head == 378_798_080
    causal = 6 * 2 * 32 * 128 * 2048
    assert flops.pass_flops_per_position(m, 2048) == 6 * 378_798_080 + causal
    s2k = harness.load_json(harness.ROOT / "bench/traffic/s2k.tau4.json")
    # tau 4: 3 local steps of two passes and one pass in the communication step
    assert flops.passes_per_round(s2k) == 7
    assert flops.positions_per_round(s2k, 1) == 4 * 2 * 2048
    assert flops.round_flops(m, s2k, 1) == 7 * 2 * 2048 * (6 * 378_798_080 + causal)
    ring = harness.load_json(harness.ROOT / "bench/traffic/ring4.s2k.tau4.json")
    assert flops.round_flops(m, ring, 4) == 4 * 7 * 1 * 2048 * (6 * 378_798_080 + causal)


def test_peaks_table():
    v5e = flops.peaks("TPU v5 lite")
    assert v5e["bf16_flops"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["ici_bytes_per_s"] * 8 == 1600e9
    with pytest.raises(KeyError):
        flops.peaks("TPU v9 imaginary")


def test_unknown_device_kind_is_refused(monkeypatch):
    import jax

    import run

    fake = [types.SimpleNamespace(platform="tpu", device_kind="TPU v9 imaginary")]
    monkeypatch.setattr(jax, "devices", lambda *a: fake)
    with pytest.raises(run.Fail):
        run.check_devices(1)
    fake[0].device_kind = "TPU v5 lite"
    with pytest.raises(run.Fail):                 # one chip where four are asked
        run.check_devices(4)


def test_cpu_run_exits_without_metrics(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu", TMPDIR=str(tmp_path))
    p = subprocess.run(
        [sys.executable, str(harness.ROOT / "bench/run.py"), "--workload", "yi9b.s512.tau4",
         "--seed", str(2**31 + 5), "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=240)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    for line in p.stdout.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)
