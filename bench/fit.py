#!/usr/bin/env python3
"""Does each cell fit a TPU v5e?  Compiles, for a described ``v5e:2x2`` and
without a chip, each cell's round step and its reference's two steps at the
cell's own sizes, and prints ``memory_analysis()`` of each.

    JAX_PLATFORMS=cpu python bench/fit.py [workload ...]

A script run by hand (a compile takes up to a minute or two), not a test.
"""
from __future__ import annotations

import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path[:0] = [os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "src"),
                os.path.dirname(os.path.abspath(__file__))]

GB = 1e9


def _mem(label, compiled):
    a = compiled.memory_analysis()
    total = (a.argument_size_in_bytes + a.output_size_in_bytes
             - a.alias_size_in_bytes + a.temp_size_in_bytes)
    print(f"  {label}: argument {a.argument_size_in_bytes / GB:.2f} GB, "
          f"output {a.output_size_in_bytes / GB:.2f} GB, alias {a.alias_size_in_bytes / GB:.2f} GB, "
          f"temp {a.temp_size_in_bytes / GB:.2f} GB -> {total / GB:.2f} GB per device", flush=True)
    return total


def fit(name: str, topo) -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    import harness
    from reference import Reference
    from weights import nest, param_shapes

    cell = harness.load_cell(name)
    tr, m = cell.traffic, cell.model
    devices = list(topo.devices)[: cell.chips]
    print(f"{name}: {cell.chips} chip(s), mesh {tr['mesh']}", flush=True)
    job = harness.build_job(m, tr, devices)
    nodes = job.n_nodes
    _mem("round step", job.lower(tr["seq_len"], tr["node_batch"] * nodes).compile())

    ref = Reference(m, tr, nodes, devices)
    ctl = Reference(m, tr, nodes, devices, quant="fp8")
    node = NamedSharding(ref._node.mesh, P("n"))
    sds = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(shape, dt, sharding=node)  # noqa: E731
    x = nest({p: sds((nodes,) + s) for p, (s, _, _) in param_shapes(m).items()})
    st = {k: x for k in ("x", "x_ref", "v", "y", "h_prev")}
    b, S = tr["node_batch"], tr["seq_len"]
    bsh = NamedSharding(ref._node.mesh, P("n"))
    batch = {"tokens": jax.ShapeDtypeStruct((nodes, b, S), jnp.int32, sharding=bsh),
             "targets": jax.ShapeDtypeStruct((nodes, b, S), jnp.int32, sharding=bsh)}
    with jax.default_matmul_precision("highest"):
        for label, r in (("reference", ref), ("control", ctl)):
            for i, step in enumerate(r._local):
                _mem(f"{label} local step {i + 1}/2", step.lower(st, batch).compile())
            _mem(f"{label} comm step", r._comm.lower(st, batch).compile())


def main(argv) -> int:
    import jax
    from jax.experimental import topologies

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    import harness

    names = argv or [w["name"] for w in harness.load_json(harness.ROOT / "BENCHMARK.json")["workloads"]]
    for name in names:
        fit(name, topo)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
