#!/usr/bin/env python3
"""Readings that a cell's limits are set from, at the cell's own size, on
the chip, in one process (run by hand; the benchmark's runs never run it):

    python3 bench/control.py --workload NAME --seeds 1,2,...,12 --control-seeds 3

For every seed, the program's check rounds (the same set-up path as
``bench/run.py``) against the float32 reference: the lower readings.  For
the first ``--control-seeds`` seeds also, against the same reference:

* ``control``: the reference in the program's place with fp8 forward
  matmuls (``reference.py``, ``quant="fp8"``), the step below the
  configuration's bfloat16 compute;
* ``half_batch``: the reference taking the mean over half of each batch
  (:func:`halve`);
* ``no_exchange``: the reference with the gossip left out (cells of more
  than one node);
* ``unchanged``: a step that returns its state unchanged, read without a
  run (its gradient and change are zero, so both gaps read 1).

Each line of stdout is one JSON object; the last sums them up.
"""
from __future__ import annotations

import argparse
import functools
import json
import sys
import time
import pathlib

HERE = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parent / "src"), str(HERE)]

import harness  # noqa: E402


def halve(batch: dict) -> dict:
    """A round's batches ``(tau, N, b, ...)`` with the second half of the
    rows replaced by the first, so every mean is taken over half of them:
    half of each node's rows, or with one row per node, half of the nodes'.
    A cell of one node and one row has no half batch (returns None)."""
    import numpy as np

    n, b = batch["tokens"].shape[1:3]
    axis = 2 if b >= 2 else 1 if n >= 2 else None
    if axis is None:
        return None
    size = batch["tokens"].shape[axis]
    idx = np.arange(size) % (size // 2)
    return {k: np.take(v, idx, axis=axis) for k, v in batch.items()}


class Readings:
    """The cell's job, compiled step and references, built once and run
    for one seed after another."""

    def __init__(self, cell, devices):
        from reference import Reference

        self.cell = cell
        m, tr = cell.model, cell.traffic
        self.job = harness.build_job(m, tr, devices)
        self.nodes = self.job.n_nodes
        self.trainer = harness.Trainer(cell, self.job, None)
        ref = functools.partial(Reference, cell.arch, m, tr, self.nodes, devices)
        self.refs = {"reference": ref(), "control": ref(quant="fp8")}
        if self.nodes > 1:
            self.refs["no_exchange"] = ref(mix=False)

    def seed(self, seed: int, *, controls: bool) -> dict:
        import jax
        import numpy as np

        import check
        from traffic import Traffic

        m, tr, t = self.cell.model, self.cell.traffic, self.trainer
        t.feed = Traffic.make(tr, m, self.nodes, seed)
        t.round_idx = 0
        key = jax.random.key(seed)
        t.init(key)
        if t.step is None:
            t.compile()
        start = time.perf_counter()
        prog = t.check_rounds(key, tr["check_rounds"])
        t_prog = time.perf_counter() - start
        t.free()
        rounds = [t.feed.round(r) for r in range(tr["check_rounds"])]
        start = time.perf_counter()
        ref = self.refs["reference"].run(t.x0(key), rounds)
        where: dict = {}
        out = {"seed": seed, "program": check.numbers(prog, ref, where), "where": where,
               "seconds": {"program": t_prog, "reference": time.perf_counter() - start},
               "loss": {"program": prog["loss"], "reference": ref["loss"]},
               "excluded": check.excluded_leaves(ref)}
        if controls:
            runs = {"control": ("control", rounds)}
            if halve(rounds[0]) is not None:
                runs["half_batch"] = ("reference", [halve(r) for r in rounds])
            if self.nodes > 1:
                runs["no_exchange"] = ("no_exchange", rounds)
            for name, (ref_name, rs) in runs.items():
                got = self.refs[ref_name].run(t.x0(key), rs)
                out[name] = check.numbers(got, ref)
            zero = {k: np.zeros_like(v) for k, v in ref["grad"].items()}
            still = {"loss": ref["loss"], "grad": zero,
                     "delta": {k: np.zeros_like(v) for k, v in ref["delta"].items()}}
            out["unchanged"] = check.numbers(still, ref)
        return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True, help="comma-separated")
    p.add_argument("--control-seeds", type=int, default=3)
    args = p.parse_args(argv)
    import run

    cell = harness.load_cell(args.workload)
    devices, _ = run.check_devices(cell.chips)
    run.use_cache(harness.ROOT)
    seeds = [int(s) for s in args.seeds.split(",")]
    rows = []
    readings = Readings(cell, devices)
    for i, s in enumerate(seeds):
        row = readings.seed(s, controls=i < args.control_seeds)
        rows.append(row)
        print(json.dumps(row), flush=True)
    summary = {}
    for k in rows[0]["program"]:
        summary[k] = {"program_max": max(r["program"][k] for r in rows)}
        for name in ("control", "half_batch", "no_exchange", "unchanged"):
            vals = [r[name][k] for r in rows if name in r]
            if vals:
                summary[k][f"{name}_min"] = min(vals)
    print(json.dumps({"workload": cell.name, "seeds": seeds, "summary": summary}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
