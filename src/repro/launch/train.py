"""End-to-end decentralized LM training driver.

Runs real training with the decentralized runtime on whatever devices exist
(on this container: CPU; on a pod: the production mesh) — one jitted round
per iteration, checkpointing, metrics logging.

  PYTHONPATH=src python -m repro.launch.train --arch yi-9b --reduced \
      --steps 100 --tau 4 --algorithm dse_mvr --out /tmp/run1

Elastic multi-process mode (``repro.runtime``): ``--num-processes N`` runs
the SAME decentralized rounds across N real OS processes with coordinator-
driven membership (kill a worker and it drops out of W_t; restart it and it
resyncs through the checkpoint bundle):

  PYTHONPATH=src python -m repro.launch.train --num-processes 4 \
      --problem lm --steps 20 --tau 4 --algorithm dse_mvr

``--coordinator HOST:PORT --process-id I`` instead runs ONE worker role
joining an external coordinator (the multi-host path: one command per box).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time
from typing import Any, Dict, List

import jax
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.configs import get_config, get_reduced
from repro.core import ALGORITHMS
from repro.data import TokenPipeline, make_lm_tokens
from repro.launch.compile_cache import use_compile_cache
from repro.launch.distributed import make_train_job
from repro.launch.mesh import make_production_mesh, make_test_mesh
from repro.models import ModelConfig


def make_mesh_for_devices():
    n = len(jax.devices())
    if n >= 512:
        return make_production_mesh(multi_pod=(n >= 512 * 2))
    # largest (data, model) grid that fits the device count
    data = max(1, n // 2)
    model = n // data
    return make_test_mesh((data, model), ("data", "model"))


def _main_elastic(args):
    """--num-processes path: coordinator here, workers as real processes."""
    from repro.runtime import RuntimeConfig, launch

    cfg = RuntimeConfig(
        problem=args.problem,
        algorithm=args.algorithm,
        hyper=(
            ("lr", args.lr), ("tau", args.tau), ("alpha", args.alpha),
            ("compression", args.compression), ("channel", args.channel),
        ),
        n_nodes=args.n_nodes,
        n_rounds=args.steps,
        batch_size=args.global_batch // max(args.n_nodes, 1) or 1,
        seed=args.seed,
        host_devices=args.host_devices,
        jax_distributed=args.jax_distributed,
    )
    print(f"[train] elastic runtime: {args.num_processes} processes x "
          f"{cfg.host_devices} devices, {cfg.n_nodes} nodes, "
          f"{cfg.n_rounds} rounds ({cfg.problem}/{cfg.algorithm})")
    res = launch(cfg, args.num_processes, stream_path=args.telemetry_out,
                 trace_path=args.trace_out, http_port=args.http_port)
    print(f"[train] done: {res.rounds_per_sec:.2f} rounds/s, "
          f"final epoch {res.epochs[-1]}, wall {res.wall_s:.1f}s "
          f"(logs: {res.run_dir})")
    if res.trace_path:
        print(f"[train] trace: {res.trace_path} "
              f"(load in Perfetto / chrome://tracing)")
    if res.diagnostics:
        d = res.diagnostics
        anomalies = ", ".join(
            f"{a['kind']}@r{a['step']}" for a in d["anomalies"]
        ) or "none"
        print(f"[train] diagnostics: verdict={d['verdict']} "
              f"anomalies=[{anomalies}]")
    if args.out:
        os.makedirs(args.out, exist_ok=True)
        summary = {
            "config": cfg.to_config(),
            "n_processes": args.num_processes,
            "rounds_per_sec": res.rounds_per_sec,
            "epochs": res.epochs,
            "round_seconds": res.round_seconds,
            "resync_seconds": res.resync_seconds,
            "active_log": res.active_log.astype(int).tolist(),
            "wall_s": res.wall_s,
            "diagnostics": res.diagnostics,
        }
        with open(os.path.join(args.out, "elastic_summary.json"), "w") as f:
            json.dump(summary, f, indent=1)
    return res


def build_parser() -> argparse.ArgumentParser:
    """The train CLI's arguments (also parsed by ``chip_smoke.py``)."""
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="yi-9b")
    p.add_argument("--reduced", action="store_true", help="use the smoke-scale config")
    p.add_argument("--steps", type=int, default=50, help="communication rounds")
    p.add_argument("--tau", type=int, default=4)
    p.add_argument("--lr", type=float, default=0.1)
    p.add_argument("--alpha", type=float, default=0.05)
    p.add_argument("--algorithm", default="dse_mvr", choices=sorted(ALGORITHMS))
    p.add_argument("--gossip", default="roll", choices=["roll", "dense"])
    p.add_argument("--use-fused", action="store_true",
                   help="route update arithmetic through the fused-op backend")
    p.add_argument("--compression", default=None,
                   help="gossip wire codec (repro.compression spec, e.g. "
                        "qsgd, top_k:0.1, rand_k:0.1, low_rank:2)")
    p.add_argument("--channel", default=None,
                   help="gossip channel protocol (sync, choco, choco:0.8, "
                        "async:2); default is synchronous gossip")
    p.add_argument("--seq-len", type=int, default=128)
    p.add_argument("--global-batch", type=int, default=8)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--out", default=None)
    p.add_argument("--ckpt-every", type=int, default=0)
    p.add_argument("--profile", default=None, metavar="DIR",
                   help="bracket the training loop in jax.profiler.start_trace/"
                        "stop_trace writing a TensorBoard-loadable trace to DIR")
    p.add_argument("--telemetry-out", default=None, metavar="FILE",
                   help="record per-channel link-byte counters and loss "
                        "gauges to a run-stamped JSONL file")
    p.add_argument("--trace-out", default=None, metavar="FILE",
                   help="elastic mode: stitch every process's spans into one "
                        "Chrome trace-event / Perfetto JSON file (per-round "
                        "trace ids across coordinator + workers)")
    p.add_argument("--http-port", type=int, default=None, metavar="PORT",
                   help="elastic mode: serve the live fleet-health plane "
                        "(/metrics /healthz /trace /diagnostics) from the "
                        "coordinator on PORT (0 = ephemeral)")
    # elastic multi-process runtime (repro.runtime)
    p.add_argument("--num-processes", type=int, default=0, metavar="N",
                   help="run the rounds across N real worker processes via "
                        "the elastic runtime (coordinator in this process)")
    p.add_argument("--coordinator", default=None, metavar="HOST:PORT",
                   help="join an external elastic coordinator as one worker "
                        "role (requires --process-id)")
    p.add_argument("--process-id", type=int, default=0,
                   help="this worker's id under --coordinator")
    p.add_argument("--problem", default="lm",
                   help="elastic-mode problem registry name "
                        "(repro.runtime.problems: mlp_blobs, pseudo_mnist, lm)")
    p.add_argument("--n-nodes", type=int, default=8,
                   help="elastic-mode logical node count (>= --num-processes)")
    p.add_argument("--host-devices", type=int, default=1,
                   help="per-process XLA host-device fan-out in elastic mode")
    p.add_argument("--jax-distributed", action="store_true",
                   help="elastic mode: jax.distributed.initialize the group "
                        "(fixed membership — no kill/rejoin chaos)")
    return p


@dataclasses.dataclass
class TrainRun:
    """What :func:`train` leaves behind: the final state, the per-round
    history, the step's compile seconds and each round's seconds (host
    clock, up to the loss reaching the host)."""

    state: Any
    history: List[Dict[str, float]]
    compile_s: float
    round_s: List[float]


def train_round(step, state, make_batch, shardings, r: int):
    """Round ``r`` of :func:`train`'s loop: its batches on the host
    (``make_batch(r)``), put on the devices (``shardings``), one call of the
    donated round ``step``, and the wait for the loss.  Returns ``(state,
    loss)``.

    Under ``jax.profiler`` the round is a ``StepTraceAnnotation("round")``
    holding one ``TraceAnnotation`` per host phase (``repro/host/batch``,
    ``repro/host/put``, ``repro/host/step``: the dispatch,
    ``repro/host/sync``: the wait), on the device trace's clock, so each
    idle gap of the device falls in the host phase that left it idle."""
    with jax.profiler.StepTraceAnnotation("round", step_num=r):
        with jax.profiler.TraceAnnotation("repro/host/batch"):
            batch = make_batch(r)
        with jax.profiler.TraceAnnotation("repro/host/put"):
            batch = jax.device_put(batch, shardings)
        with jax.profiler.TraceAnnotation("repro/host/step"):
            state, metrics = step(state, batch)
        with jax.profiler.TraceAnnotation("repro/host/sync"):
            loss = float(metrics["loss"])
    return state, loss


def train(cfg: ModelConfig, args: argparse.Namespace, mesh=None) -> TrainRun:
    """The single-process sharded training loop behind the CLI: build the
    job on ``mesh`` (default: :func:`make_mesh_for_devices`), shard the
    initial state, compile one round ahead of time and run ``args.steps``
    rounds of ``args`` (a :func:`build_parser` namespace)."""
    mesh = mesh if mesh is not None else make_mesh_for_devices()
    print(f"[train] arch={cfg.name} mesh={dict(zip(mesh.axis_names, mesh.devices.shape))}")

    job = make_train_job(
        cfg, mesh, algorithm=args.algorithm, tau=args.tau,
        lr=args.lr, alpha=args.alpha, gossip=args.gossip,
        use_fused=args.use_fused, compression=args.compression,
        channel=args.channel,
    )
    n = job.n_nodes
    rl = job.round_len  # batches per jitted round (1 for every-step methods)
    print(f"[train] {n} decentralized nodes ({job.profile.name} profile), "
          f"algorithm={args.algorithm}, round_len={rl}")
    if args.global_batch % max(n, 1):
        raise SystemExit(f"global batch {args.global_batch} not divisible by {n} nodes")

    # data: synthetic markov token stream, one shard per node
    tokens = make_lm_tokens(2_000_000 if not args.reduced else 200_000,
                            cfg.vocab_size, seed=args.seed)
    pipe = TokenPipeline(tokens, args.seq_len, args.global_batch, seed=args.seed)

    state = job.init_state(jax.random.key(args.seed))

    def host_batch():
        xs, ys = [], []
        for _ in range(rl):
            x, y = pipe.batch()
            xs.append(x.reshape(n, args.global_batch // n, args.seq_len))
            ys.append(y.reshape(n, args.global_batch // n, args.seq_len))
        return {"tokens": np.stack(xs), "targets": np.stack(ys)}

    first = jax.device_put(host_batch(), job.batch_shardings)
    t_c = time.perf_counter()
    step = job.jit_step().lower(state, first).compile()
    compile_s = time.perf_counter() - t_c
    print(f"[train] step compiled in {compile_s:.1f}s")

    def make_batch(r):
        return first if r == 0 else host_batch()   # round 0 runs the compile's batch

    ckpt = CheckpointManager(os.path.join(args.out, "ckpt")) if args.out and args.ckpt_every else None

    tel = None
    link = None
    if args.telemetry_out:
        from repro.compression.channels import link_bytes_per_round
        from repro.telemetry import Telemetry

        tel = Telemetry(config=vars(args))
        link = link_bytes_per_round(job.algorithm.comm, state.params)
    from repro.telemetry.spans import profile_trace

    history = []
    round_s = []
    t0 = time.time()
    with profile_trace(args.profile):
        for r in range(args.steps):
            t_r = time.perf_counter()
            state, loss = train_round(step, state, make_batch, job.batch_shardings, r)
            round_s.append(time.perf_counter() - t_r)
            if tel is not None:
                tel.gauge("train_loss", loss, step=r + 1)
                tel.record_link_bytes(link, step=r)
            history.append({"round": r + 1, "loss": loss, "t": round(time.time() - t0, 2)})
            if (r + 1) % max(1, args.steps // 20) == 0 or r == 0:
                print(f"[train] round {r+1:4d}/{args.steps}  loss={loss:.4f}  "
                      f"({(time.time()-t0)/(r+1):.2f}s/round)")
            if ckpt and (r + 1) % args.ckpt_every == 0:
                ckpt.save(r + 1, jax.tree.map(np.asarray, state.params), {"loss": loss})
    if tel is not None:
        tel.record_kernel_launches()
        n_rec = tel.export_jsonl(args.telemetry_out)
        print(f"[train] telemetry: {n_rec} records -> {args.telemetry_out}")
    if args.out:
        os.makedirs(args.out, exist_ok=True)
        with open(os.path.join(args.out, "history.json"), "w") as f:
            json.dump(history, f, indent=1)
    print(f"[train] done: loss {history[0]['loss']:.4f} -> {history[-1]['loss']:.4f}")
    return TrainRun(state, history, compile_s, round_s)


def main(argv=None):
    args = build_parser().parse_args(argv)

    if args.coordinator:
        from repro.runtime.worker import run_worker

        return run_worker(args.coordinator, args.process_id)
    if args.num_processes:
        return _main_elastic(args)

    use_compile_cache()
    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    return train(cfg, args).history


if __name__ == "__main__":
    main()
