"""Wire-true transport bench: MEASURED link bytes, not analytic models.

Three measurement planes, one committed artifact:

  * **HLO collective link bytes** — every (channel x topology) cell of the
    sharded engine is lowered on an 8-fake-device mesh and the compiled,
    partitioned HLO is parsed (``repro.launch.hlo_analysis``): the reported
    bytes are what actually crosses collective-permute / all-gather per
    round, so the packed neighbor-replica and compressed-allgather wire
    modes are scored against the dense pre-wire-true fallback on the SAME
    compiled programs the engine runs.
  * **comm/compute overlap** — the same sharded round with ``overlap=False``
    vs ``True``, timed post-compilation: the rounds/sec row the double-
    buffered channel buys (the message rolls while tau local steps run).
  * **elastic socket bytes** — 2-process packed-transport runs against the
    dense round protocol, counting REAL framed bytes through the
    coordinator's ``MessageSocket``s (``ElasticResult.socket_bytes``).

The acceptance bar asserted in CI: packed choco + top_k:0.1 moves >= 4x
fewer collective-permute bytes than the dense replica gossip it replaces,
and the packed elastic protocol moves fewer socket bytes than the dense
contrib/gather exchange.

The HLO/overlap plane runs in a subprocess pinned to the CPU (8 fake host
devices; the parent may already hold the accelerator, and a chip belongs to
one process at a time); the elastic plane spawns real CPU worker processes.

-> benchmarks/results/BENCH_transport.json
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: (tag, make_train_job kwargs, scenario name or None).  Tags are
#: {channel}/{topology}/{wire}: topology "ring" is the static shift ring,
#: "fault" is the fault-rewritten dropout_ring schedule (W_t mutated, so
#: shift structure is gone), "allgather" forces the gathered wire on the
#: static ring.
HLO_CONFIGS = (
    ("dense/ring/raw", dict(), None),
    ("sync/ring/packed", dict(compression="top_k:0.1"), None),
    ("choco/ring/dense", dict(channel="choco", compression="top_k:0.1",
                              wire_mode="dense"), None),
    ("choco/ring/neighbor", dict(channel="choco", compression="top_k:0.1"),
     None),
    ("choco/ring/allgather", dict(channel="choco", compression="top_k:0.1",
                                  wire_mode="allgather"), None),
    ("async2/ring/neighbor", dict(channel="async:2", compression="qsgd"),
     None),
    ("sync/fault/allgather", dict(compression="top_k:0.1"), "dropout_ring"),
    ("choco/fault/dense", dict(channel="choco", compression="top_k:0.1",
                               wire_mode="dense"), "dropout_ring"),
    ("choco/fault/allgather", dict(channel="choco", compression="top_k:0.1"),
     "dropout_ring"),
    ("async2/fault/allgather", dict(channel="async:2", compression="qsgd"),
     "dropout_ring"),
)

SEQ, GLOBAL_BATCH = 16, 8


def _child(smoke: bool) -> None:
    """Runs under XLA_FLAGS=--xla_force_host_platform_device_count=8."""
    import time

    import jax
    import numpy as np

    from repro.launch.distributed import make_train_job
    from repro.launch.hlo_analysis import analyze_module
    from repro.launch.mesh import make_test_mesh
    from repro.models import ModelConfig
    from repro.scenarios import make_scenario

    # Data-only mesh: with a model axis in play, within-node resharding
    # traffic (all-reduce/all-gather over "model") buries the gossip signal
    # for a tiny probe model.  8 nodes x 1-device model keeps every counted
    # collective a wire (inter-node) transfer, and the larger probe dims
    # make the dense-vs-payload gap unambiguous.
    mesh = make_test_mesh((8, 1), ("data", "model"))
    cfg = ModelConfig(
        name="lm-probe", arch_type="dense", n_layers=1, d_model=64,
        n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=512,
        block_unit=("attn",), tie_embeddings=True,
    )

    rows = []
    for tag, kw, scen in HLO_CONFIGS:
        scenario = make_scenario(scen, seed=0) if scen else None
        job = make_train_job(cfg, mesh, tau=3, lr=1e-2, alpha=0.1,
                             gossip="roll", scenario=scenario, **kw)
        compiled = job.lower(SEQ, GLOBAL_BATCH).compile()
        costs = analyze_module(compiled.as_text())
        rows.append({
            "bench": "transport",
            "name": f"transport/hlo/{tag}",
            "channel": tag.split("/")[0],
            "scenario": scen,
            "measured_link_kb": round(costs.total_link_bytes / 1e3, 2),
            "collective_link_bytes": {
                k: round(v, 1) for k, v in costs.collective_link_bytes.items()
            },
            "collective_counts": costs.collective_counts,
        })

    # ---- comm/compute overlap: measured rounds/sec, same compiled engine --
    rounds = 16 if smoke else 64
    for overlap in (False, True):
        job = make_train_job(
            cfg, mesh, tau=3, lr=1e-2, alpha=0.1, gossip="roll",
            channel="choco", compression="top_k:0.1", overlap=overlap,
        )
        step = jax.jit(
            job.step_fn,
            in_shardings=(job.state_shardings, job.batch_shardings),
            out_shardings=(job.state_shardings, None),
        )
        state = job.init_state(jax.random.key(0))
        bkey = jax.random.key(1)
        n = job.n_nodes
        bshape = (job.round_len, n, GLOBAL_BATCH // n, SEQ)
        batches = {
            "tokens": jax.random.randint(bkey, bshape, 0, cfg.vocab_size),
            "targets": jax.random.randint(
                jax.random.fold_in(bkey, 1), bshape, 0, cfg.vocab_size),
        }
        state, _ = step(state, batches)       # compile
        jax.block_until_ready(state.params)
        t0 = time.perf_counter()
        for _ in range(rounds):
            state, _ = step(state, batches)
        jax.block_until_ready(state.params)
        wall = time.perf_counter() - t0
        assert all(np.all(np.isfinite(np.asarray(l)))
                   for l in jax.tree.leaves(state.params))
        rows.append({
            "bench": "transport",
            "name": f"transport/overlap/{'on' if overlap else 'off'}",
            "channel": "choco",
            "overlap": overlap,
            "rounds": rounds,
            "rounds_per_sec": round(rounds / wall, 2),
            "us_per_call": round(wall / rounds * 1e6, 1),
        })
    print(json.dumps(rows))


def _elastic_rows(smoke: bool) -> list:
    from repro.runtime.config import RuntimeConfig
    from repro.runtime.launch import launch

    hyper = (("lr", 0.05), ("tau", 4), ("alpha", 0.1),
             ("channel", "choco"), ("compression", "top_k:0.25"),
             ("overlap", True))
    cfg = RuntimeConfig(
        n_nodes=4, n_rounds=4 if smoke else 8, batch_size=4, hyper=hyper,
        snapshot_every=4,
    )
    rows = []
    bytes_by_mode = {}
    for mode in ("auto", "off"):
        res = launch(cfg.with_(packed_transport=mode), 2)
        bytes_by_mode[mode] = res.socket_bytes
        rows.append({
            "bench": "transport",
            "name": f"transport/elastic/{'packed' if mode == 'auto' else 'dense'}",
            "channel": "choco",
            "packed_transport": mode,
            "n_rounds": cfg.n_rounds,
            "socket_kb_per_round": round(
                res.socket_bytes["total"] / cfg.n_rounds / 1e3, 2),
            "socket_bytes": res.socket_bytes,
            "rounds_per_sec": round(res.rounds_per_sec, 3),
        })
    rows.append({
        "bench": "transport",
        "name": "transport/elastic/packed_vs_dense",
        "channel": "choco",
        "bytes_ratio": round(
            bytes_by_mode["off"]["total"] / bytes_by_mode["auto"]["total"], 2),
    })
    return rows


def run(smoke: bool = False) -> list:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(REPO, "src") + os.pathsep + env.get(
        "PYTHONPATH", "")
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.transport_bench", "--child"]
        + (["--smoke"] if smoke else []),
        capture_output=True, text=True, timeout=1800, env=env, cwd=REPO,
    )
    if out.returncode != 0:
        raise RuntimeError(
            f"transport HLO child failed:\n{out.stdout}\n{out.stderr[-4000:]}"
        )
    rows = json.loads(out.stdout.splitlines()[-1])

    by_name = {r["name"]: r for r in rows}
    dense = by_name["transport/hlo/choco/ring/dense"]["measured_link_kb"]
    packed = by_name["transport/hlo/choco/ring/neighbor"]["measured_link_kb"]
    rows.append({
        "bench": "transport",
        "name": "transport/hlo/choco_packed_vs_dense",
        "channel": "choco",
        "bytes_ratio": round(dense / packed, 2),
    })
    fdense = by_name["transport/hlo/choco/fault/dense"]["measured_link_kb"]
    fpacked = by_name["transport/hlo/choco/fault/allgather"]["measured_link_kb"]
    rows.append({
        "bench": "transport",
        "name": "transport/hlo/fault_allgather_vs_dense",
        "channel": "choco",
        "bytes_ratio": round(fdense / fpacked, 2),
    })
    off = by_name["transport/overlap/off"]["rounds_per_sec"]
    on = by_name["transport/overlap/on"]["rounds_per_sec"]
    rows.append({
        "bench": "transport",
        "name": "transport/overlap/gain",
        "channel": "choco",
        "overlap_speedup": round(on / off, 3),
    })

    rows += _elastic_rows(smoke)
    return rows


def main(smoke: bool = False) -> list:
    from .common import run_stamp

    rows = run(smoke=smoke)
    os.makedirs("benchmarks/results", exist_ok=True)
    with open("benchmarks/results/BENCH_transport.json", "w") as f:
        json.dump({"run": run_stamp(), "rows": rows}, f, indent=1)
    return rows


if __name__ == "__main__":
    p = argparse.ArgumentParser()
    p.add_argument("--child", action="store_true")
    p.add_argument("--smoke", action="store_true")
    args = p.parse_args()
    if args.child:
        _child(args.smoke)
    else:
        for r in main(smoke=args.smoke):
            print(r["name"], {k: v for k, v in r.items()
                              if k not in ("bench", "name")})
