"""What a run of a cell does, apart from its command line: find the cell's
files by name, build the trainer's job on the chip's devices, make the state
from the benchmark's weights, feed rounds, and take the readings that the
correctness check compares.

The round loop is the benchmark's copy of the host work of
``repro.launch.train.train``: numpy batches, ``jax.device_put`` to the job's
batch shardings, one call of the donated, jitted round step, and a sync on
the round's loss.  ``train()`` itself runs a fixed number of rounds, so the
benchmark cannot time a window through it.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import pathlib

import jax
import numpy as np

ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"

__all__ = ["Cell", "load_cell", "metric_reader", "build_job", "Trainer"]


@dataclasses.dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with the files it names."""

    name: str
    chips: int
    config: dict          # bench/configs/<config>.json
    traffic: dict         # bench/traffic/<traffic>.json
    checks: dict          # bench/checks/<workload>.json
    manifest: dict

    @property
    def model(self) -> dict:
        return self.config["model"]

    def metrics(self, kind: str) -> list:
        """The manifest's ``end_to_end`` or ``per_layer`` metrics this cell reports."""
        return [x for x in self.manifest[kind]
                if self.name in x.get("workloads", [self.name])]


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    manifest = load_json(root / "BENCHMARK.json")
    by_name = {w["name"]: w for w in manifest["workloads"]}
    if name not in by_name:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json "
                         f"(have {sorted(by_name)})")
    w = by_name[name]
    conf = {c["name"]: c for c in manifest["configs"]}[w["config"]]
    bench = root / "bench"
    return Cell(
        name=name,
        chips=w["chips"],
        config=load_json(root / conf["file"]),
        traffic=load_json(bench / "traffic" / f"{w['traffic']}.json"),
        checks=load_json(bench / "checks" / f"{name}.json"),
        manifest=manifest,
    )


def metric_reader(name: str, root: pathlib.Path = ROOT):
    """``read(run) -> float | None`` of ``bench/metrics/<name>.py``."""
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def model_config(m: dict):
    """The trainer's ``ModelConfig`` for a configuration's ``model`` block."""
    from repro.models import ModelConfig

    kw = dict(m)
    for k in ("block_unit", "mrope_sections", "vision_grid"):
        if kw.get(k) is not None:
            kw[k] = tuple(kw[k])
    return ModelConfig(**kw)


def build_job(m: dict, traffic: dict, devices):
    """``make_train_job`` on a ``traffic["mesh"]`` mesh of ``devices``: mesh
    (N, 1) puts one decentralized node on each device."""
    from repro.launch.distributed import make_train_job

    shape = tuple(traffic["mesh"])
    n = int(np.prod(shape))
    mesh = jax.sharding.Mesh(np.array(devices[:n]).reshape(shape), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
    return make_train_job(
        model_config(m), mesh, algorithm=traffic["algorithm"], tau=traffic["tau"],
        lr=traffic["lr"], alpha=traffic["alpha"], gossip=traffic["gossip"],
    )


class _SeededModel:
    """The job's model with ``init`` taken from the benchmark's weights, so
    ``TrainJob.init_state`` builds the state from them."""

    def __init__(self, model, init):
        self._model, self.init = model, init

    def __getattr__(self, name):
        return getattr(self._model, name)


class Trainer:
    """The job, its compiled round step and its state, driven round by round."""

    def __init__(self, cell_model: dict, job, feed):
        from reference import leaf_norms
        from weights import make_params

        self.job, self.feed = job, feed
        self.nodes = job.n_nodes
        self._params = jax.jit(lambda key: make_params(cell_model, key))
        want = jax.tree.map(lambda s: s.shape, job.model.param_shapes())
        got = jax.tree.map(lambda s: s.shape, jax.eval_shape(self._params, jax.random.key(0)))
        if want != got:
            raise SystemExit(f"benchmark weights do not match the trainer's layout:\n{got}\n{want}")
        self._init_model = _SeededModel(job.model, lambda key: make_params(cell_model, key))
        self._norms = jax.jit(leaf_norms)
        self._delta = jax.jit(lambda x, x0: leaf_norms(
            jax.tree.map(lambda a, b: a - b[None], x, x0)))
        self.round_idx = 0
        self.state = None
        self.step = self.compiled = None

    def x0(self, key):
        """The benchmark's weights of ``key`` (one node's, unstacked)."""
        return self._params(key)

    def init(self, key):
        """``TrainJob.init_state`` on the benchmark's weights of ``key``."""
        self.state = dataclasses.replace(self.job, model=self._init_model).init_state(key)
        jax.block_until_ready(self.state)

    def compile(self):
        batch = jax.device_put(self.feed.round(0), self.job.batch_shardings)
        self.compiled = self.job.jit_step().lower(self.state, batch).compile()
        self.step = self.compiled

    def round(self, annotate=None) -> float:
        """One round as ``train()`` runs it; ``annotate(name)`` (such as
        ``jax.profiler.TraceAnnotation``) wraps each part.  Returns the
        round's loss."""
        ann = annotate or (lambda name: contextlib.nullcontext())
        with ann("bench/batch"):
            batch = self.feed.round(self.round_idx)
        with ann("bench/put"):
            batch = jax.device_put(batch, self.job.batch_shardings)
        with ann("bench/step"):
            self.state, metrics = self.step(self.state, batch)
        with ann("bench/sync"):
            loss = float(metrics["loss"])
        self.round_idx += 1
        return loss

    def check_rounds(self, key, n: int) -> dict:
        """The first ``n`` rounds, with the readings the check compares:
        each round's loss, each node's leaf norms of ``v`` after round 1,
        and of the parameters' change after round ``n``."""
        out = {"loss": []}
        for i in range(n):
            out["loss"].append(self.round())
            if i == 0:
                out["grad"] = jax.tree.map(np.asarray, self._norms(self.state.v))
        x0 = self.x0(key)
        out["delta"] = jax.tree.map(np.asarray, self._delta(self.state.params, x0))
        jax.tree.map(lambda a: a.delete(), x0)
        return out

    def free(self):
        """Delete the state, so the reference has the chip to itself."""
        for leaf in jax.tree.leaves(self.state):
            leaf.delete()
        self.state = None
