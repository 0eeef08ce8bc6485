"""``BENCHMARK.json`` against the benchmark's contract, and the files the
harness finds by name."""
from __future__ import annotations

import json
import re
import shutil

import pytest

import check
import harness

ROOT = harness.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
TEXT_KEYS = ("why", "layer", "source")
KEYS = {
    "top": {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"},
    "config": {"name", "source", "file", "reduced", "why"},
    "workload": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}
WIDTH = re.compile(r"(_dim|_rank)$|^(d_model|d_ff|head_dim|hidden|intermediate|n_heads|n_kv_heads|top_k)")


@pytest.fixture(scope="module")
def manifest():
    with open(ROOT / "BENCHMARK.json") as f:
        return json.load(f)


def test_keys_and_sizes(manifest):
    assert set(manifest) == KEYS["top"]
    assert (ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024
    for kind, key in (("configs", "config"), ("workloads", "workload")):
        assert 1 <= len(manifest[kind]) <= 24
        for x in manifest[kind]:
            assert set(x) == KEYS[key], x
    for kind in ("end_to_end", "per_layer"):
        for x in manifest[kind]:
            assert set(x) - {"workloads"} == KEYS[kind], x
    assert 1 <= len(manifest["end_to_end"]) <= 16
    assert 1 <= len(manifest["per_layer"]) <= 128
    assert 1 <= manifest["run_seconds"] <= 51 and isinstance(manifest["run_seconds"], int)


def test_command_and_paths(manifest):
    assert 1 <= len(manifest["paths"]) <= 16
    for p in manifest["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p.split("/")
        assert (ROOT / p).is_dir()
    cmd = manifest["command"]
    assert 1 <= len(cmd) <= 32
    for word in cmd:
        assert 1 <= len(word) <= 200 and "\n" not in word and "\t" not in word
        assert not word.startswith("/") and ".." not in word.split("/")
        if "/" in word:
            assert any(word.startswith(p + "/") for p in manifest["paths"]), word


def test_names_and_units(manifest):
    everything = manifest["configs"] + manifest["workloads"] + manifest["end_to_end"] + manifest["per_layer"]
    for x in everything:
        assert NAME.match(x["name"]), x["name"]
        for k in TEXT_KEYS:
            if k in x:
                assert 1 <= len(x[k]) <= 200 and "\n" not in x[k] and "\t" not in x[k], (x["name"], k)
    for x in manifest["workloads"]:
        assert NAME.match(x["config"]) and NAME.match(x["traffic"])
    for x in manifest["end_to_end"] + manifest["per_layer"]:
        assert UNIT.match(x["unit"]), x["unit"]
        assert x["better"] in ("lower", "higher")
    for kind in ("configs", "workloads"):
        names = [x["name"] for x in manifest[kind]]
        assert len(names) == len(set(names))
    metrics = [x["name"] for x in manifest["end_to_end"] + manifest["per_layer"]]
    assert len(metrics) == len(set(metrics))


def test_configs(manifest):
    used = {w["config"] for w in manifest["workloads"]}
    files = set()
    for c in manifest["configs"]:
        assert c["name"] in used, f"configuration {c['name']} has no cell"
        assert any(c["file"].startswith(p + "/") for p in manifest["paths"])
        assert c["file"] not in files
        files.add(c["file"])
        assert len(c["reduced"]) <= 16
        conf = harness.load_json(ROOT / c["file"])
        assert conf["name"] == c["name"]
        assert sorted(c["reduced"]) == sorted(conf["reduced"]), "reduced differs from the file"
        for key in c["reduced"]:
            assert NAME.match(key) and not WIDTH.search(key), key
            published, run = conf["reduced"][key]
            assert conf["model"][key] == run and conf["published"][key] == published


def test_every_configuration_names_its_architecture_module(manifest):
    """``"arch"`` in each configuration file is a module under ``paths``
    with the three functions the harness asks it for."""
    for c in manifest["configs"]:
        path = harness.load_json(ROOT / c["file"])["arch"]
        assert PATH.match(path) and any(path.startswith(p + "/") for p in manifest["paths"])
        assert (ROOT / path).is_file(), path
        arch = harness.load_module(ROOT / path, "bench_arch_")
        for fn in ("param_shapes", "loss_fn", "pass_flops_per_position"):
            assert callable(getattr(arch, fn, None)), (path, fn)


# the numbers that bench/check.py reads, each of which a cell may compare
_one = {"loss": [1.0], "grad": {"a": [1.0]}, "delta": {"a": [1.0]}}
NUMBERS = set(check.numbers(_one, _one))


def test_workloads(manifest):
    configs = {c["name"] for c in manifest["configs"]}
    pairs = set()
    for w in manifest["workloads"]:
        assert w["config"] in configs
        assert w["chips"] in (1, 4)
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        cell = harness.load_cell(w["name"])
        assert cell.traffic["chips"] == w["chips"]
        limits = cell.checks["limits"]
        assert limits and set(limits) <= NUMBERS
        for k, v in limits.items():
            r = cell.checks["readings"][k]
            assert r["lower"] < v < r["upper"], (w["name"], k)
    four = sum(w["chips"] == 4 for w in manifest["workloads"])
    assert four <= max(1, len(manifest["workloads"]) // 2)


def test_metrics(manifest):
    cells = {w["name"] for w in manifest["workloads"]}
    e2e = {x["name"] for x in manifest["end_to_end"]}
    assert "setup_s" in e2e
    for x in manifest["end_to_end"]:
        assert x["source"] in ("host_clock", "device_trace")
        assert 0 < x["bound"] <= 0.25 and x["bound"] >= 0.01
    for x in manifest["end_to_end"] + manifest["per_layer"]:
        assert x["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
        assert set(x.get("workloads", cells)) <= cells, x["name"]
        assert harness.metric_reader(x["name"]), x["name"]
    perf = (ROOT / "PERF.md").read_text()
    for x in manifest["per_layer"]:
        assert x["moves"] in e2e
        assert f"| {x['layer']} |" in perf, f"layer {x['layer']!r} is not in PERF.md's table"
    for cell in cells:
        c = harness.load_cell(cell)
        assert {m["name"] for m in c.metrics("end_to_end")} - {"setup_s"}, cell
        assert c.metrics("per_layer"), cell


def test_a_cell_is_found_by_name_with_no_code_edit(tmp_path):
    """A configuration, a traffic mix, a check and a metric dropped in as
    files, with their manifest entries, are found by the harness."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench")
    manifest = harness.load_json(ROOT / "BENCHMARK.json")
    conf = harness.load_json(ROOT / manifest["configs"][0]["file"])
    conf["name"] = "new-model"
    (tmp_path / "bench/configs/new-model.json").write_text(json.dumps(conf))
    traffic = harness.load_json(ROOT / "bench/traffic/s512.tau4.json")
    traffic["seq_len"] = 1024
    (tmp_path / "bench/traffic/s1k.tau4.json").write_text(json.dumps(traffic))
    (tmp_path / "bench/checks/new.s1k.tau4.json").write_text(
        json.dumps({"limits": {"loss_gap": 1, "grad_gap": 1, "update_gap": 1}}))
    (tmp_path / "bench/metrics/rounds_done.py").write_text("def read(run):\n    return run['rounds']\n")
    manifest["configs"].append(dict(manifest["configs"][0], name="new-model",
                                    file="bench/configs/new-model.json"))
    manifest["workloads"].append({"name": "new.s1k.tau4", "config": "new-model",
                                  "traffic": "s1k.tau4", "chips": 1, "why": "test"})
    manifest["per_layer"].append({"name": "rounds_done", "unit": "rounds", "better": "higher",
                                  "source": "host_clock", "layer": "device", "moves": "tokens_per_s",
                                  "workloads": ["new.s1k.tau4"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))

    cell = harness.load_cell("new.s1k.tau4", root=tmp_path)
    assert cell.config["name"] == "new-model" and cell.traffic["seq_len"] == 1024
    assert [m["name"] for m in cell.metrics("per_layer")][-1] == "rounds_done"
    assert harness.metric_reader("rounds_done", root=tmp_path)({"rounds": 7}) == 7
    with pytest.raises(SystemExit):
        harness.load_cell("missing", root=tmp_path)
