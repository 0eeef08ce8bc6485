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
import re
from collections import Counter

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
    """Forward and backward kernels, window 4096 and softcap 50."""
    q = jax.ShapeDtypeStruct((1, 4096, 8, 256), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 4096, 4, 256), jnp.bfloat16, sharding=one_chip)

    def loss(q, k, v):
        out = api.call("flash_attention", q, k, v, causal=True, sliding_window=4096, softcap=50.0)
        return jnp.sum(out.astype(jnp.float32))

    text = _compile(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv).as_text()
    assert {"fwd", "dq", "dkv"} <= {k.rsplit("_", 1)[-1] for k in _kernels(text)}


# one HLO instruction per line, its named scope in its metadata's op_name:
# how the benchmark reads a compiled step (bench/trace_reduce.py)
_INSTRUCTION = re.compile(r'^\s*(?:ROOT\s+)?%([\w.\-]+) = (?:\([^=]*?\)|\S+) ([\w\-]+)\(')
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
COLLECTIVES = ("all-gather", "collective-permute", "all-reduce", "all-to-all")


def _kernels(text: str) -> dict:
    """``{flash kernel: op_name}`` of the compiled module's TPU custom calls
    named ``flash_attention_<fwd|dq|dkv>`` (op_name "" where the line has none)."""
    out = {}
    for line in text.splitlines():
        m = _INSTRUCTION.match(line)
        if m and 'custom_call_target="tpu_custom_call"' in line and "flash_attention_" in m.group(1):
            scope = _OP_NAME.search(line)
            kind = re.search(r"flash_attention_(fwd|dq|dkv)", m.group(1)).group(0)
            out[f"{m.group(1)}:{kind}"] = scope.group(1) if scope else ""
    return out


def _collectives(text: str) -> Counter:
    ops = (m.group(2) for m in map(_INSTRUCTION.match, text.splitlines()) if m)
    return Counter(op for op in ops if op.startswith(COLLECTIVES))


def _yi9b_bench():
    """The benchmark's Yi-9B: published widths, 2 layers, 8000-word vocabulary."""
    return dataclasses.replace(get_config("yi-9b"), n_layers=2, vocab_size=8000)


def _step_text(mesh, mode, seq, global_batch, **job_kw):
    job = make_train_job(_yi9b_bench(), mesh, algorithm="dse_mvr", tau=4, lr=1e-4,
                         alpha=0.05, **job_kw)
    jax.clear_caches()   # the step's trace caches the dispatch mode it saw
    with api.dispatch_mode(mode):
        return job.lower(seq, global_batch).compile().as_text()


def test_s2k_step_runs_flash_attention_under_the_attention_scope(topo):
    """The benchmark's s2k round step (one node, 2 x 2048 per step): every
    flash kernel (forward, its remat, dq, dkv) carries ``repro/attn`` on its
    own line, and no float32 matmul with two sequence-long dimensions is
    left."""
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"))
    text = _step_text(mesh, "kernel", 2048, 2)
    kernels = _kernels(text)
    assert {k.rsplit(":", 1)[1] for k in kernels} == {
        "flash_attention_fwd", "flash_attention_dq", "flash_attention_dkv"}
    assert all("repro/attn/" in scope for scope in kernels.values()), kernels
    assert any("rematted_computation/" in s and k.endswith("fwd") for k, s in kernels.items())
    assert any("transpose(" in s and k.endswith("dkv") for k, s in kernels.items())
    s_by_s = re.compile(r"= f32\[([\d,]*)\][^ ]* (?:dot|convolution)\(")
    wide = [line for line in text.splitlines()
            if (m := s_by_s.search(line)) and m.group(1).split(",").count("2048") >= 2]
    assert wide == []


def test_ring_step_keeps_its_collectives_with_flash_attention(topo):
    """The ring cell (four nodes, one per chip, roll gossip): the kernel runs
    on each chip's own node, so the step's collectives are the parent path's
    (``_sdpa``) exactly; GSPMD would gather q/k/v around an unpartitioned
    kernel call."""
    mesh = Mesh(np.array(topo.devices).reshape(4, 1), ("data", "model"))
    plain = _step_text(mesh, "ref", 2048, 4, gossip="roll")
    flash = _step_text(mesh, "kernel", 2048, 4, gossip="roll")
    assert _kernels(flash) and not _kernels(plain)
    assert _collectives(plain) and _collectives(flash) == _collectives(plain)


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
