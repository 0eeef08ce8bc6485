"""A new architecture family is new files only: a configuration naming its
own module (``bench/tests/data/biased_decoder.py``, q/k/v and MLP biases),
a traffic mix, a check, a metric and the manifest's entries, dropped into a
copy of the benchmark, are loaded, weighted, referenced and counted with no
existing file of ``bench/`` touched.  On the CPU at a small size."""
from __future__ import annotations

import filecmp
import json
import math
import shutil

import jax
import jax.numpy as jnp
import numpy as np

import flops
import harness
import reference
import run
import weights

ROOT = harness.ROOT
MODULE = "bench/tests/data/biased_decoder.py"
MODEL = dict(name="biased-decoder", arch_type="dense", n_layers=2, d_model=128, n_heads=4,
             n_kv_heads=2, head_dim=32, d_ff=256, vocab_size=512, activation="silu",
             rope_theta=10000.0, tie_embeddings=False, use_bias=True)
CELL = "biased.s64.tau4"


def _drop_in(root):
    """The new family's files and manifest entries in a copy of the benchmark
    at ``root``."""
    shutil.copytree(ROOT / "bench", root / "bench")
    conf = {"name": "biased-decoder", "source": "test", "arch": MODULE, "model": MODEL}
    (root / "bench/configs/biased-decoder.json").write_text(json.dumps(conf))
    traffic = dict(harness.load_json(ROOT / "bench/traffic/s512.tau4.json"), seq_len=64,
                   pool_rounds=2, check_rounds=1)
    (root / "bench/traffic/s64.tau4.json").write_text(json.dumps(traffic))
    (root / f"bench/checks/{CELL}.json").write_text(
        json.dumps({"limits": {"loss_gap": 0.02, "grad_gap": 0.05, "update_gap": 0.1}}))
    (root / "bench/metrics/model_layers.py").write_text(
        'def read(run):\n    return run["model"]["n_layers"]\n')
    manifest = harness.load_json(ROOT / "BENCHMARK.json")
    manifest["configs"].append({"name": "biased-decoder", "source": "test",
                                "file": "bench/configs/biased-decoder.json", "reduced": [],
                                "why": "test"})
    manifest["workloads"].append({"name": CELL, "config": "biased-decoder",
                                  "traffic": "s64.tau4", "chips": 1, "why": "test"})
    manifest["end_to_end"].append({"name": "model_layers", "unit": "layers", "better": "higher",
                                   "bound": 0.01, "source": "host_clock", "workloads": [CELL]})
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))


def test_a_new_family_is_new_files_only(tmp_path, monkeypatch):
    _drop_in(tmp_path)
    cell = harness.load_cell(CELL, root=tmp_path)
    assert cell.arch.__file__ == str(tmp_path / MODULE)
    monkeypatch.setattr(run, "use_cache", lambda root: {"hits": 0, "misses": 0, "compiles": 0})
    # the trainer's weights check against the program's Model, its rounds,
    # the reference over the check round, and the metric readers
    res = run.measure(cell, 2**31 + 29, 0.5, False, jax.devices()[:1], {"bf16_flops": 1e12},
                      root=tmp_path)
    assert res["failed"] == 0 and res["metrics"]["model_layers"]["value"] == 2
    assert all(math.isfinite(c["value"]) for c in res["checks"].values()), res["checks"]
    assert res["correct"], res["checks"]

    d, h, k, hd, f = 128, 4, 2, 32, 256
    per_position = 6 * (2 * (d * hd * (2 * h + 2 * k) + 3 * d * f) + d * 512) + 6 * 2 * h * hd * 64
    # 7 passes a round of one 64-position row (s512's node batch)
    assert flops.round_flops(cell.arch, cell.model, cell.traffic, 1) == 7 * 1 * 64 * per_position

    cmp = filecmp.dircmp(ROOT / "bench", tmp_path / "bench", ignore=["__pycache__"])
    changed = []

    def walk(c, at):
        changed.extend(f"{at}{n}" for n in c.diff_files + c.left_only)
        for name, sub in c.subdirs.items():
            walk(sub, f"{at}{name}/")
    walk(cmp, "bench/")
    assert not changed, changed


def test_the_new_family_matches_the_program_in_float32():
    """The module's loss and gradient are the program's with ``use_bias``,
    so the run above compares like with like."""
    from repro.models import Model

    arch = harness.load_module(ROOT / MODULE, "bench_arch_")
    key = jax.random.key(5)
    p = weights.make_params(arch.param_shapes(MODEL), key)
    p = jax.tree.map(lambda a: a + 0.02 * jax.random.normal(jax.random.key(6), a.shape), p)
    tokens = jax.random.randint(jax.random.key(7), (2, 33), 0, MODEL["vocab_size"])
    b = {"tokens": tokens[:, :-1], "targets": tokens[:, 1:]}
    model = Model(harness.model_config(MODEL))
    with jax.default_matmul_precision("highest"):
        lp, gp = jax.value_and_grad(lambda q: model.loss(q, b, dtype=jnp.float32))(p)
        lr, gr = jax.value_and_grad(lambda q: arch.loss_fn(MODEL, q, b, reference.matmul()))(p)
    assert abs(float(lp) - float(lr)) <= 1e-5 * abs(float(lr))
    for path, g in reference.flat(gr).items():
        scale = float(jnp.max(jnp.abs(g)))
        np.testing.assert_allclose(reference.flat(gp)[path], g, rtol=2e-4, atol=1e-5 * scale,
                                   err_msg=path)


def test_model_config_takes_every_list_as_a_tuple():
    """Any tuple-typed field of ``ModelConfig`` may come as a JSON list."""
    cfg = harness.model_config(dict(MODEL, block_unit=["attn", "attn"], vision_grid=[8, 8],
                                    mrope_sections=[4, 6, 6]))
    assert (cfg.block_unit, cfg.vision_grid, cfg.mrope_sections) == (("attn", "attn"), (8, 8), (4, 6, 6))
    assert hash(cfg) == hash(harness.model_config(dict(MODEL, block_unit=("attn", "attn"),
                                                       vision_grid=(8, 8), mrope_sections=(4, 6, 6))))
