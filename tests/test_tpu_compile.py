"""Main-path kernels and the smoke step, compiled for a TPU v5e at real widths.

No chip is needed: the TPU compiler is installed and compiles for a topology
that is described, not attached.  That catches what interpret mode cannot:
block shapes the TPU tiling refuses, primitives Mosaic cannot lower, and
programs that do not fit the chip's 16 GB.  Nothing runs, so these tests say
nothing about results or times.

The topology is described inside a module fixture, never at import time:
only one process at a time may load the TPU library, and every pytest
worker imports this file.
"""
from __future__ import annotations

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import api
from repro.launch.distributed import make_train_job
from repro.models import Model


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # else the compiler logs to /tmp
        try:
            t = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip is cached but cannot be read back
        # without one; keep the persistent cache out of it
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield t
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _smoke_config():
    """chip_smoke.py's model: gemma2-2b widths, 4 layers, 32k vocabulary."""
    return dataclasses.replace(get_config("gemma2-2b"), n_layers=4, vocab_size=32_000)


def _compile(fn, *args, mode="kernel"):
    with api.dispatch_mode(mode):
        return jax.jit(fn).lower(*args).compile()


LEAF = 2304 * 9216   # one Gemma-2 MLP matrix


@pytest.fixture(scope="module")
def node_params(one_chip):
    """One node's f32 parameter tree of the smoke config (385,194,240
    elements): the bucket ``tree_apply`` flattens."""
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32, sharding=one_chip),
        Model(_smoke_config()).param_shapes(),
    )


@pytest.mark.parametrize("name", ["mvr_update", "dse_combine_yh", "axpby"])
def test_flat_launcher_compiles_at_node_bucket(node_params, name):
    op = api.get(name)
    scalars = tuple(0.5 for _ in range(op.n_scalars))
    compiled = _compile(
        lambda *t: api.tree_apply(name, *t, scalars=scalars),
        *([node_params] * op.n_inputs),
    )
    assert compiled.as_text().count("tpu_custom_call") > 1
    # a launch holds at most max_bucket f32 elements per operand and result
    bound = 4 * op.tile.max_bucket * (op.n_inputs + op.n_outputs)
    assert compiled.memory_analysis().temp_size_in_bytes <= bound


def test_qsgd_quantize_compiles_on_mlp_leaf(one_chip):
    x = jax.ShapeDtypeStruct((1, LEAF), jnp.float32, sharding=one_chip)
    compiled = _compile(
        lambda a, u: api.tree_apply("qsgd_quantize", a, u, scalars=(127.0,)), x, x
    )
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("name", ["top_k_pack", "top_k_unpack"])
def test_top_k_runs_xla_path_on_mlp_leaf(one_chip, name):
    """top_k:0.1 of one MLP leaf (k ~ 2.1M): no kernel, the XLA gather /
    scatter compiles at this size on every platform."""
    k = LEAF // 10
    idx = jax.ShapeDtypeStruct((1, k), jnp.int32, sharding=one_chip)
    if name == "top_k_pack":
        x = jax.ShapeDtypeStruct((1, LEAF), jnp.float32, sharding=one_chip)
        compiled = _compile(lambda a, i: api.call(name, a, i), x, idx)
    else:
        vals = jax.ShapeDtypeStruct((1, k), jnp.float32, sharding=one_chip)
        compiled = _compile(lambda i, v: api.call(name, i, v, d=LEAF), idx, vals)
    assert "tpu_custom_call" not in compiled.as_text()


def test_flash_attention_compiles_at_gemma_widths(one_chip):
    q = jax.ShapeDtypeStruct((1, 4096, 8, 256), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 4096, 4, 256), jnp.bfloat16, sharding=one_chip)
    compiled = _compile(
        lambda q, k, v: api.call(
            "flash_attention", q, k, v, causal=True, sliding_window=4096, softcap=50.0
        ),
        q, kv, kv,
    )
    assert "tpu_custom_call" in compiled.as_text()


def test_rms_norm_compiles_at_gemma_width(one_chip):
    x = jax.ShapeDtypeStruct((2, 2048, 2304), jnp.bfloat16, sharding=one_chip)
    w = jax.ShapeDtypeStruct((2304,), jnp.float32, sharding=one_chip)
    compiled = _compile(lambda x, w: api.call("rms_norm", x, w, plus_one=True), x, w)
    assert "tpu_custom_call" in compiled.as_text()


def test_wkv_chunk_compiles_at_rwkv6_3b_widths(one_chip):
    r = jax.ShapeDtypeStruct((1, 2048, 40, 64), jnp.float32, sharding=one_chip)
    compiled = _compile(lambda *t: api.call("wkv_chunk", *t, chunk=16), r, r, r, r)
    assert "tpu_custom_call" in compiled.as_text()


def test_smoke_step_fits_one_chip_with_donated_state(topo):
    """chip_smoke.py's main phase (DSE-MVR, tau 4, seq 2048, global batch 2)
    compiles for one v5e, and the state is donated: every float state byte
    is aliased to the output."""
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"))
    job = make_train_job(_smoke_config(), mesh, algorithm="dse_mvr", tau=4, lr=0.01)
    with api.dispatch_mode("ref"):
        mem = job.lower(2048, 2).compile().memory_analysis()
    state_bytes = sum(
        int(np.prod(s.shape)) * s.dtype.itemsize
        for s in jax.tree.leaves(job.abstract_state)
        if jnp.issubdtype(s.dtype, jnp.floating)
    )
    assert state_bytes > 6e9   # params, x_ref, v, z of 385M f32 parameters
    assert mem.alias_size_in_bytes >= state_bytes
