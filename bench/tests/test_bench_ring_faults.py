"""The exchange between chips left out of the ring cell's timed path:
``correct`` comes out false, where the unbroken ring comes out true.  A
whole run (``test_bench_check.measure``) at a small size, in a process of
its own with four CPU devices."""
from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

import harness


@pytest.mark.parametrize("fault", ["none", "no_exchange"])
def test_ring_without_exchange_is_not_correct(fault):
    code = f"""
import sys, json
sys.path[:0] = {[str(harness.BENCH), str(harness.ROOT / 'src')]!r}
import test_bench_check as t
import repro.launch.distributed as d
from repro.core.mixing import identity_mix
if {fault!r} == "no_exchange":
    d.roll_mix = lambda topology: identity_mix
class MP:
    def setattr(self, obj, name, value):
        setattr(obj, name, value)
res = t.measure(t.small_cell("yi9b.ring4.s2k.tau4"), MP())
print(json.dumps({{"correct": res["correct"], "checks": res["checks"]}}))
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", code], cwd=harness.BENCH / "tests", env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["correct"] is (fault == "none"), res


