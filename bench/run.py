#!/usr/bin/env python3
"""Benchmark of the decentralized DSE-MVR trainer on the chip.

    python3 bench/run.py --workload NAME --seed N --seconds S --trace 0|1

Runs one cell of ``BENCHMARK.json`` from the root of a checkout, on the chips
of the machine it is started on:

1. set-up (timed as ``setup_s``, from the process's start): imports, the
   chip check, the cell's traffic pool from the seed, the trainer's job, its
   state from the benchmark's weights (one jitted call), the round step's
   compile (or load from ``<checkout>/.jax_cache``), and the cell's
   ``check_rounds`` first rounds through the same round loop as the window,
   on rows that all differ, with the readings the check compares;
2. the window: rounds until ``--seconds`` have passed, each as
   ``repro.launch.train.train`` runs it (numpy batch, ``device_put``, the
   step, a sync on the loss).  ``--trace 1`` records the window's first
   ``TRACE_S`` seconds with the profiler and reports the per-layer metrics
   of those rounds instead of the end-to-end ones;
3. the check: the program's state is freed, and the float32 reference
   (``bench/reference.py``, on the model of the configuration's
   architecture module, ``bench/models/``) follows the same first rounds
   from the same weights; ``bench/check.py`` compares.

The last line of stdout is one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, ``breakdown`` with ``--trace 1``,
``checks`` last); the numbers compared and their limits are also the last
lines of stderr.  Without a TPU, with fewer chips than the cell asks for, or
on a device kind missing from ``bench/peaks.json``, it exits non-zero and
prints no result.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
# a --trace 1 run records the window's first rounds, up to this many seconds:
# a short trace, and one that stays small on disk
TRACE_S = 10.0
ROOT = HERE.parent
sys.path[:0] = [str(ROOT / "src"), str(HERE)]

import harness  # noqa: E402


def log(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


class Fail(SystemExit):
    """A run that cannot be measured: non-zero exit, no result line."""


def check_devices(chips: int):
    """The chips of the run and their peak row; no TPU, too few chips or an
    unknown device kind fails the run."""
    import jax

    import flops

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise Fail(f"bench: JAX found no TPU (platform {devs[0].platform!r}); nothing measured")
    if len(devs) < chips:
        raise Fail(f"bench: the cell needs {chips} chips, JAX found {len(devs)}")
    try:
        peak = flops.peaks(devs[0].device_kind)
    except KeyError as e:
        raise Fail(f"bench: {e.args[0]}") from None
    return devs[:chips], peak


def use_cache(root: pathlib.Path) -> dict:
    """JAX's persistent compilation cache at ``<checkout>/.jax_cache``
    whatever ``JAX_COMPILATION_CACHE_DIR`` holds; returns counters of cache
    hits, misses and compile requests."""
    import jax
    from jax import monitoring

    jax.config.update("jax_compilation_cache_dir", str(root / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    counts = {"hits": 0, "misses": 0, "compiles": 0}
    names = {"/jax/compilation_cache/cache_hits": "hits",
             "/jax/compilation_cache/cache_misses": "misses",
             "/jax/compilation_cache/compile_requests_use_cache": "compiles"}

    def on_event(event, **_):
        if event in names:
            counts[names[event]] += 1

    monitoring.register_event_listener(on_event)
    return counts


class HostClock:
    """Per round: wall time, the main thread's and the whole process's CPU
    time, and Python's garbage-collection pauses, so that a round that
    stalls says whether the host was working (its own CPU, a collection)
    or waiting (CPU idle: the runtime, or the machine's other load)."""

    def __init__(self):
        self.rounds: list = []        # (wall, thread cpu, process cpu, gc) seconds
        self._gc = 0.0
        self._gc_t = None

    def on_gc(self, phase, info):
        if phase == "start":
            self._gc_t = time.perf_counter()
        elif self._gc_t is not None:
            self._gc += time.perf_counter() - self._gc_t
            self._gc_t = None

    def begin(self):
        self._t = (time.thread_time(), time.process_time(), self._gc)

    def end(self, wall: float):
        th, pr, g = self._t
        self.rounds.append((wall, time.thread_time() - th, time.process_time() - pr, self._gc - g))

    def summary(self, k: int = 3) -> str:
        worst = sorted(self.rounds, reverse=True)[:k]
        med = sorted(self.rounds)[len(self.rounds) // 2] if self.rounds else (0, 0, 0, 0)
        fmt = lambda r: f"{r[0]:.4f} s (main thread cpu {r[1]:.4f}, process cpu {r[2]:.4f}, gc {r[3]:.4f})"  # noqa: E731
        return (f"host per round: median {fmt(med)}; longest " + "; ".join(fmt(r) for r in worst)
                + f"; gc in the window {sum(r[3] for r in self.rounds):.4f} s")


def memory_peak(step, devices) -> int:
    """Peak bytes on the fullest chip: the larger of the runtime's peak of
    live arrays and the timed step's own need (its arguments, outputs not
    aliased to them, and temporaries, from the compiled program), since
    the runtime's count leaves out a program's temporaries."""
    a = step.memory_analysis()
    need = (a.argument_size_in_bytes + a.output_size_in_bytes - a.alias_size_in_bytes
            + a.temp_size_in_bytes)
    stats = [d.memory_stats() or {} for d in devices]
    live = max(st.get("peak_bytes_in_use", 0) for st in stats)
    log(f"memory: runtime peak of live arrays {live} B; the step's own need {need} B "
        f"(arguments {a.argument_size_in_bytes}, outputs {a.output_size_in_bytes}, "
        f"aliased {a.alias_size_in_bytes}, temporaries {a.temp_size_in_bytes}); "
        f"runtime stats of the first chip: {stats[0]}")
    return max(live, need)


def measure(cell: harness.Cell, seed: int, seconds: float, trace: bool, devices, peak: dict,
            t_start: float = T0, root: pathlib.Path = ROOT) -> dict:
    """One run of ``cell``; returns the result object (without printing)."""
    import jax

    import check
    import flops
    from reference import Reference
    from traffic import Traffic

    cache = use_cache(root)
    m, tr = cell.model, cell.traffic
    marks = [("imports", time.perf_counter())]
    job = harness.build_job(m, tr, devices)
    nodes = job.n_nodes
    marks.append(("job", time.perf_counter()))
    feed = Traffic.make(tr, m, nodes, seed)
    marks.append(("traffic", time.perf_counter()))
    trainer = harness.Trainer(cell, job, feed)
    key = jax.random.key(seed)
    trainer.init(key)
    marks.append(("state", time.perf_counter()))
    before = dict(cache)
    trainer.compile()
    marks.append(("step", time.perf_counter()))
    hits, misses = cache["hits"] - before["hits"], cache["misses"] - before["misses"]
    log(f"round step {'from the cache' if hits and not misses else 'compiled'} "
        f"(cache hits {hits}, misses {misses})")
    prog = trainer.check_rounds(key, tr["check_rounds"])
    marks.append(("check rounds", time.perf_counter()))
    setup_s = time.perf_counter() - t_start
    log("set-up seconds: " + ", ".join(
        f"{name} {t - prev:.2f}" for (name, t), (_, prev) in zip(marks, [("start", t_start)] + marks))
        + f"; cache hits {cache['hits']}, misses {cache['misses']} in all")
    log(f"check rounds: losses {prog['loss']}")

    tmp = tempfile.TemporaryDirectory(prefix="bench_trace_") if trace else None
    if trace:
        jax.profiler.start_trace(tmp.name)
    ann = jax.profiler.TraceAnnotation if trace else None
    compiles = cache["compiles"]
    rounds = failed = 0
    traced = None                     # (rounds, seconds) under the profiler
    round_s = []
    host = HostClock()                # what the host did in each round, for stalls
    gc.callbacks.append(host.on_gc)
    t0 = time.perf_counter()
    while True:
        t_r = time.perf_counter()
        host.begin()
        loss = trainer.round(ann)
        round_s.append(time.perf_counter() - t_r)
        host.end(round_s[-1])
        rounds += 1
        failed += not math.isfinite(loss)
        elapsed = time.perf_counter() - t0
        if trace and traced is None and (elapsed >= TRACE_S or elapsed >= seconds):
            traced = (rounds, elapsed)
            jax.profiler.stop_trace()
            ann = None
        if elapsed >= seconds:
            break
    window_s = time.perf_counter() - t0
    gc.callbacks.remove(host.on_gc)
    compiles = cache["compiles"] - compiles
    tr_summary = None
    step_text = trainer.compiled.as_text()
    program = step_text.split(None, 2)[1].rstrip(",")     # "HloModule jit_train_step, ..."
    if trace:
        import trace_reduce

        paths = sorted(pathlib.Path(tmp.name).rglob("*.xplane.pb"))
        scopes = trace_reduce.scopes_from_hlo(step_text)
        tr_summary = trace_reduce.load(paths[-1], scopes) if paths else None
        tmp.cleanup()
    rs = sorted(round_s)
    log(f"window: {rounds} rounds in {window_s:.3f} s; round seconds p50 "
        f"{rs[len(rs) // 2]:.4f}, p95 {rs[min(len(rs) - 1, int(0.95 * len(rs)))]:.4f}, "
        f"max {rs[-1]:.4f} (host clock); compile requests in the window: {compiles}")
    log(host.summary())

    mem_peak = memory_peak(trainer.compiled, devices)
    trainer.free()

    t_ref = time.perf_counter()
    ref = Reference(cell.arch, m, tr, nodes, devices).run(
        trainer.x0(key), [feed.round(r) for r in range(tr["check_rounds"])])
    where: dict = {}
    values = check.numbers(prog, ref, where)
    log(f"reference: {time.perf_counter() - t_ref:.2f} s, losses {ref['loss']}; "
        f"leaves left out of update_gap: {check.excluded_leaves(ref)}; widest gaps at {where}")
    correct, checks = check.verdict(values, cell.checks["limits"])
    correct = correct and failed == 0

    if trace:                         # per-layer metrics cover the traced rounds
        rounds_m, window_m = traced
    else:
        rounds_m, window_m = rounds, window_s
    run = {
        "rounds": rounds_m, "window_s": window_m, "setup_s": setup_s, "chips": len(devices),
        "positions_per_round": flops.positions_per_round(tr, nodes),
        "round_flops": flops.round_flops(cell.arch, m, tr, nodes), "peak": peak,
        "trace": tr_summary, "program": program,
        # what a reader needs to count a kernel's work from the configuration
        "model": m, "traffic": tr, "nodes": nodes, "arch": cell.arch,
    }
    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    for spec in cell.metrics(kind):
        v = harness.metric_reader(spec["name"], root)(run)
        if v is not None:
            metrics[spec["name"]] = {"value": v, "unit": spec["unit"]}
    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind, "count": len(devices),
              "memory_peak_bytes": mem_peak}
    result = {"correct": bool(correct), "attempted": rounds, "failed": failed,
              "metrics": metrics, "device": device}
    if tr_summary is not None:
        device["busy_s"] = tr_summary.busy_s()
        device["window_s"] = tr_summary.window_s
        result["breakdown"] = {"device_ops": tr_summary.top_ops(10),
                               "idle_gaps": tr_summary.idle_gaps(10)}
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    cell = harness.load_cell(args.workload)
    devices, peak = check_devices(cell.chips)
    log(f"{cell.name}: {devices[0].device_kind} x{len(devices)}, seed {args.seed}, "
        f"{args.seconds} s window, trace {args.trace}")
    result = measure(cell, args.seed, args.seconds, bool(args.trace), devices, peak)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    print(f"check correct: {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
