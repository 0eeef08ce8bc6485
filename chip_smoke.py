#!/usr/bin/env python3
"""Chip smoke test: the decentralized trainer's main path on a TPU.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # four chips: gossip between chips

Runs from the repository root, in ONE process (a chip belongs to one process
at a time), through ``repro.launch.train.train`` — the function behind
``python -m repro.launch.train`` — with arguments parsed by the train CLI's
own parser.  The model is Gemma-2 2B at its published widths with two fields
cut (``smoke_config``): 4 layers (two local/global periods) and a 32,000-token
vocabulary (one chip's share of 256,000 split 8 ways).  Weights are random
from ``--seed``; data is ``make_lm_tokens`` from ``--seed``.

One chip, two phases, DSE-MVR with tau=4, lr 1e-4, seq 2048, global batch 2,
3 rounds:
  1. ``main``:  the CLI's default path (one node, per-leaf jnp update math);
  2. ``fused``: the same rounds with ``--use-fused``: the Pallas kernels must
     have been launched, and losses and the per-leaf checksums of the
     iterates must match phase 1 within the fused tolerance (rtol 5e-4,
     atol 1e-5).
Four chips (``--four-chips``, this phase only): mesh (4, 1), one node per
chip on a ring of 4, 2 rounds with ``--gossip roll`` (collective-permute)
against the same rounds with ``--gossip dense``, at the same tolerance.

Two XLA programs of the same math do not round alike in the bf16 model
compute (on a v5e, donating the state alone moved a round's loss by 7e-5
of its value),
and training amplifies that every round: at lr 1e-3 fused and main drifted
2e-3 apart by round 3 although the kernels matched their XLA oracle bit for
bit.  lr 1e-4 keeps that drift in the losses inside the tolerance.  The
per-leaf checksums asserted are those of the iterates (params, x_ref); those
of v and z, which are bf16-computed gradients and their sums, are printed:
between roll and dense gossip on the CPU they differed by up to 4e-3 and
6e-3 while the iterates agreed within 2e-7.

Findings go to earlier lines of stdout; the seconds are smoke figures, not
benchmark numbers.  The last line is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
Any failed check or phase exits non-zero without that line, as does a run
where JAX finds no TPU.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, compiled programs are cached
there; otherwise in ``<repo>/.jax_cache``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(REPO, "src"))

# the fused-vs-jnp tolerance (README), for every pair of phases that run the
# same math as two different XLA programs (fused vs main, roll vs dense)
RTOL, ATOL = 5e-4, 1e-5
# state fields whose per-leaf checksums are held to it: the iterates.  v (the
# MVR direction, a fresh bf16-computed gradient after every round) and z (its
# accumulation) move with the bf16 rounding of the model compute; they are
# printed, not asserted (see the module docstring)
ASSERTED_FIELDS = (".params", ".x_ref")


def smoke_config():
    """gemma2-2b at published widths with depth and vocabulary cut."""
    from repro.configs import get_config

    return dataclasses.replace(get_config("gemma2-2b"), n_layers=4, vocab_size=32_000)


def reduced_fields(cfg) -> dict:
    from repro.configs import get_config

    full = get_config(cfg.name)
    return {
        f.name: [getattr(full, f.name), getattr(cfg, f.name)]
        for f in dataclasses.fields(cfg)
        if getattr(full, f.name) != getattr(cfg, f.name)
    }


def n_params(cfg) -> int:
    import jax

    from repro.models import Model

    return sum(int(np.prod(s.shape)) for s in jax.tree.leaves(Model(cfg).param_shapes()))


def train_args(*extra: str, seed: int, steps: int, global_batch: int, seq_len: int):
    from repro.launch import train

    return train.build_parser().parse_args([
        "--algorithm", "dse_mvr", "--tau", "4", "--lr", "1e-4",
        "--seq-len", str(seq_len), "--global-batch", str(global_batch),
        "--steps", str(steps), "--seed", str(seed), *extra,
    ])


def state_checks(state) -> dict:
    """Fails on any non-finite float leaf; returns per-leaf mean |x|."""
    import jax
    import jax.numpy as jnp

    sums = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(state):
        if not jnp.issubdtype(leaf.dtype, jnp.floating):
            continue
        name = jax.tree_util.keystr(path)
        if not bool(jnp.all(jnp.isfinite(leaf))):
            raise SystemExit(f"chip_smoke: non-finite values in state leaf {name}")
        sums[name] = float(jnp.mean(jnp.abs(leaf.astype(jnp.float32))))
    return sums


def run_phase(label: str, cfg, args, mesh=None) -> dict:
    """One trainer run; prints its findings and frees its state."""
    import jax

    from repro.launch import train

    run = train.train(cfg, args, mesh=mesh)
    losses = [h["loss"] for h in run.history]
    if not np.all(np.isfinite(losses)):
        raise SystemExit(f"chip_smoke[{label}]: non-finite loss {losses}")
    out = {
        "losses": losses,
        "checksums": state_checks(run.state),
        "compile_s": run.compile_s,
        "round_s": run.round_s,
        "state": run.state,
    }
    stats = jax.devices()[0].memory_stats() or {}
    print(f"[smoke:{label}] compile_s={run.compile_s:.2f}")
    print(f"[smoke:{label}] seconds_per_round (smoke figure, not a benchmark)="
          f"{[round(t, 3) for t in run.round_s]}")
    print(f"[smoke:{label}] peak_bytes_in_use (device 0, process so far)="
          f"{stats.get('peak_bytes_in_use', 'not reported')}")
    print(f"[smoke:{label}] losses={losses}")
    return out


def free(result: dict) -> None:
    import jax

    for leaf in jax.tree.leaves(result.pop("state")):
        leaf.delete()


def compare(label: str, got: dict, want: dict, rtol: float, atol: float) -> None:
    """Losses and per-leaf checksums of two phases agree within tolerance;
    the largest differences are printed before anything is asserted."""
    if got["checksums"].keys() != want["checksums"].keys():
        raise SystemExit(f"chip_smoke[{label}]: state layouts differ")
    rel = lambda g, w: abs(g - w) / max(abs(w), 1e-30)  # noqa: E731
    print(f"[smoke:{label}] loss rel diffs="
          f"{[float(f'{rel(g, w):.3e}') for g, w in zip(got['losses'], want['losses'])]}")
    worst = {}
    for name, w in want["checksums"].items():
        field = name.split("[")[0]
        worst[field] = max(worst.get(field, 0.0), rel(got["checksums"][name], w))
    print(f"[smoke:{label}] max checksum rel diff per field="
          f"{ {k: float(f'{v:.3e}') for k, v in worst.items()} }")
    np.testing.assert_allclose(got["losses"], want["losses"], rtol=rtol, atol=atol,
                               err_msg=f"{label}: losses")
    for name, w in want["checksums"].items():
        if name.startswith(ASSERTED_FIELDS):
            np.testing.assert_allclose(got["checksums"][name], w, rtol=rtol, atol=atol,
                                       err_msg=f"{label}: checksum of {name}")
    print(f"[smoke:{label}] agrees (rtol {rtol}, atol {atol})")


def one_chip(seed: int) -> None:
    from repro.kernels import api

    cfg = smoke_config()
    kw = dict(seed=seed, steps=3, global_batch=2, seq_len=2048)
    main = run_phase("main", cfg, train_args(**kw))
    free(main)

    api.reset_counters()
    fused = run_phase("fused", cfg, train_args("--use-fused", **kw))
    free(fused)
    mode, launches = api.resolve_mode(), api.launch_counts()
    print(f"[smoke:fused] dispatch mode={mode} launches={launches}")
    if mode != "kernel" or not sum(launches.values()):
        raise SystemExit(f"chip_smoke: fused phase ran no kernels ({mode}, {launches})")
    compare("fused vs main", fused, main, RTOL, ATOL)


def node_per_chip(state, n_nodes: int) -> None:
    """Every node-stacked leaf: each chip's shard holds exactly one node,
    and the chips hold distinct nodes."""
    import jax

    for f in ("params", "x_ref", "v", "z"):
        for leaf in jax.tree.leaves(getattr(state, f)):
            nodes = set()
            for shard in leaf.addressable_shards:
                rows = range(*shard.index[0].indices(leaf.shape[0]))
                if len(rows) != 1:
                    raise SystemExit(f"chip_smoke: {f} shard on {shard.device} "
                                     f"holds nodes {list(rows)}")
                nodes.add(rows[0])
            if nodes != set(range(n_nodes)):
                raise SystemExit(f"chip_smoke: {f} nodes {nodes} != {n_nodes} chips")


def four_chips(seed: int) -> None:
    import jax

    from repro.launch.mesh import make_test_mesh

    if len(jax.devices()) < 4:
        raise SystemExit(f"chip_smoke: --four-chips needs 4 devices, found {len(jax.devices())}")
    cfg = smoke_config()
    mesh = make_test_mesh((4, 1), ("data", "model"))
    kw = dict(seed=seed, steps=2, global_batch=4, seq_len=2048)
    roll = run_phase("roll", cfg, train_args("--gossip", "roll", **kw), mesh=mesh)
    node_per_chip(roll["state"], 4)
    print("[smoke:roll] one node per chip: every node-stacked leaf")
    free(roll)
    dense = run_phase("dense", cfg, train_args("--gossip", "dense", **kw), mesh=mesh)
    free(dense)
    compare("roll vs dense", roll, dense, RTOL, ATOL)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--four-chips", action="store_true",
                   help="run only the four-chip gossip phase (needs 4 chips)")
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: JAX found no TPU (platform {dev.platform!r})")

    from repro.launch.compile_cache import use_compile_cache

    print(f"[smoke] compile cache: {use_compile_cache()}")
    print(f"[smoke] device_kind={dev.device_kind} count={len(jax.devices())}")
    cfg = smoke_config()
    print(f"[smoke] config {cfg.name}: params={n_params(cfg)} "
          f"reduced={reduced_fields(cfg)}")
    if args.four_chips:
        four_chips(args.seed)
    else:
        one_chip(args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
