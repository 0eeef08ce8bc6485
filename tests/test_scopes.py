"""The round step's named scopes: ``repro/update`` (DSE update arithmetic),
``repro/mix`` (the gossip mix), ``repro/attn`` and ``repro/mlp`` (each
block's attention and feed-forward layer), read by the benchmark's
``update_ms``, ``mix_ms``, ``attention_ms`` and ``mlp_ms``.

One child process with 4 CPU devices builds the DSE-MVR round step of a
small dense model through ``make_train_job``, on one node and on a ring of
4 (roll gossip), per-leaf and ``use_fused``, each twice: as it is, and with
``jax.named_scope`` replaced by a null context.  The compiled text is read
with the benchmark's own ``trace_reduce.scopes_from_hlo``.  The CPU compiler
drops the metadata of its ``dot``s, so those, and the source file of each
op, are read from the module as lowered (every op there carries both).
"""
from __future__ import annotations

import json
import os
import re
import subprocess
import sys
import textwrap

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "bench"))

import trace_reduce  # noqa: E402

SCOPES = ("repro/update", "repro/mix", "repro/attn", "repro/mlp")
CASES = [(nodes, fused) for nodes in (1, 4) for fused in (False, True)]
SEQ, ROWS, TAU = 16, 2, 3
FLASH_SEQ = 128    # one whole kernel tile

_OPCODE = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+) = (?:\([^=]*?\)|\S+) ([\w\-]+)\(")
_FRAME = re.compile(r"stack_frame_id=(\d+)")


def tag(nodes: int, fused: bool) -> str:
    return f"{nodes}.{'fused' if fused else 'leaf'}"


def build(out: str) -> None:
    """Child process: for each case write the step's compiled and lowered
    text, and whether the step with its scopes nulled compiles to the same
    text (metadata aside) and returns bitwise the same state and loss."""
    import contextlib
    import dataclasses

    import jax
    from jax._src.lib import xla_client

    from repro.configs import get_reduced
    from repro.launch.distributed import make_train_job

    cfg = get_reduced("yi_9b")
    opts = xla_client._xla.HloPrintOptions()
    opts.print_metadata = True
    named_scope = jax.named_scope
    report = {}
    for nodes, fused in CASES:
        devs = np.array(jax.devices()[:nodes]).reshape(nodes, 1)
        mesh = jax.sharding.Mesh(devs, ("data", "model"),
                                 axis_types=(jax.sharding.AxisType.Auto,) * 2)
        rng = np.random.default_rng(0)
        toks = rng.integers(0, cfg.vocab_size, (TAU, nodes, ROWS, SEQ), dtype=np.int32)
        runs = {}
        for scoped in (True, False):
            jax.clear_caches()
            jax.named_scope = named_scope if scoped else (lambda name: contextlib.nullcontext())
            try:
                job = make_train_job(cfg, mesh, tau=TAU, lr=1e-2, alpha=0.1, gossip="roll",
                                     use_fused=fused)
                lowered = job.lower(SEQ, ROWS * nodes)
                compiled = lowered.compile()
            finally:
                jax.named_scope = named_scope
            batch = jax.device_put({"tokens": toks, "targets": (toks + 1) % cfg.vocab_size},
                                   job.batch_shardings)
            state, metrics = compiled(job.init_state(jax.random.key(0)), batch)
            runs[scoped] = (compiled.as_text(),
                            [np.asarray(x) for x in jax.tree.leaves((state, metrics))])
            if scoped:
                with open(os.path.join(out, f"{tag(nodes, fused)}.lowered.hlo"), "w") as f:
                    f.write(lowered.compiler_ir("hlo").get_hlo_module().to_string(opts))
        (text, outs), (plain, plain_outs) = runs[True], runs[False]
        with open(os.path.join(out, f"{tag(nodes, fused)}.compiled.hlo"), "w") as f:
            f.write(text)
        report[tag(nodes, fused)] = {
            "same_text": strip_metadata(text) == strip_metadata(plain),
            "plain_scoped_ops": sum(any(s in op for s in SCOPES)
                                    for op in trace_reduce.scopes_from_hlo(plain).values()),
            "leaves": len(outs),
            "same_outputs": len(outs) == len(plain_outs) and all(
                a.dtype == b.dtype and a.shape == b.shape and a.tobytes() == b.tobytes()
                for a, b in zip(outs, plain_outs)),
        }
    # the ring with the flash kernel forced (interpret mode on the CPU) and
    # with _sdpa, at a sequence the kernel takes
    devs = np.array(jax.devices()[:4]).reshape(4, 1)
    mesh = jax.sharding.Mesh(devs, ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
    for impl in ("xla", "pallas"):
        jax.clear_caches()
        job = make_train_job(dataclasses.replace(cfg, attn_impl=impl), mesh, tau=TAU, lr=1e-2,
                             alpha=0.1, gossip="roll")
        lowered = job.lower(FLASH_SEQ, ROWS * 4)
        with open(os.path.join(out, f"flash.{impl}.compiled.hlo"), "w") as f:
            f.write(lowered.compile().as_text())
        with open(os.path.join(out, f"flash.{impl}.lowered.hlo"), "w") as f:
            f.write(lowered.compiler_ir("hlo").get_hlo_module().to_string(opts))
    with open(os.path.join(out, "report.json"), "w") as f:
        json.dump(report, f)


def strip_metadata(text: str) -> str:
    """HLO text less its debug information: each instruction's
    ``metadata={...}`` and the module's source-location tables."""
    text = re.sub(r", metadata=\{[^}]*\}", "", text)
    return "\n".join(line for line in text.splitlines()
                     if not re.match(r"^(\d+ |FileNames$|FunctionNames$|FileLocations$|StackFrames$)",
                                     line))


def opcodes(text: str) -> dict:
    """``{instruction name: opcode}`` of an HLO module's text."""
    return {m.group(1): m.group(2) for m in map(_OPCODE.match, text.splitlines()) if m}


def source_files(text: str) -> dict:
    """``{instruction name: file of its innermost source frame}`` of a module
    printed with its metadata's stack frames."""
    tables: dict = {}
    section = None
    for line in text.splitlines():
        if line in ("FileNames", "FunctionNames", "FileLocations", "StackFrames"):
            section = tables.setdefault(line, {})
        elif section is not None and (m := re.match(r"^(\d+) (.*)$", line)):
            section[int(m.group(1))] = m.group(2)
        elif line.strip():
            section = None
    field = lambda s, k: int(re.search(rf"{k}=(\d+)", s).group(1))  # noqa: E731
    out = {}
    for line in text.splitlines():
        m, f = _OPCODE.match(line), _FRAME.search(line)
        if m and f:
            loc = tables["FileLocations"][field(tables["StackFrames"][int(f.group(1))],
                                                "file_location_id")]
            out[m.group(1)] = tables["FileNames"][field(loc, "file_name_id")].strip('"')
    return out


@pytest.fixture(scope="module")
def built(tmp_path_factory):
    out = tmp_path_factory.mktemp("scopes")
    env = dict(os.environ, XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu", PYTHONPATH=os.path.join(REPO, "src"))
    code = textwrap.dedent(f"""
        import sys
        sys.path[:0] = [{os.path.join(REPO, 'tests')!r}]
        import test_scopes
        test_scopes.build({str(out)!r})
    """)
    res = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True,
                         timeout=900, env=env)
    assert res.returncode == 0, f"STDOUT:\n{res.stdout}\nSTDERR:\n{res.stderr[-4000:]}"
    with open(out / "report.json") as f:
        report = json.load(f)

    def case(nodes, fused):
        if nodes == "flash":   # the ring step with attn_impl=fused: "xla" or "pallas"
            return tuple((out / f"flash.{fused}.{kind}.hlo").read_text()
                         for kind in ("compiled", "lowered"))
        t = tag(nodes, fused)
        return ((out / f"{t}.compiled.hlo").read_text(), (out / f"{t}.lowered.hlo").read_text(),
                report[t])
    return case


@pytest.mark.parametrize("nodes,fused", CASES)
def test_block_matmuls_are_attention_or_mlp(built, nodes, fused):
    _, lowered, _ = built(nodes, fused)
    scopes = trace_reduce.scopes_from_hlo(lowered)
    dots = [scopes.get(n, "") for n, op in opcodes(lowered).items() if op == "dot"]
    inside = [s for s in dots if "repro/attn" in s or "repro/mlp" in s]
    assert {"repro/attn", "repro/mlp"} <= {s for d in inside for s in SCOPES if s in d}
    # the only matmul outside the blocks is the LM head's (forward and backward)
    assert all("bsd,dv->bsv" in s for s in dots if s not in inside), dots


@pytest.mark.parametrize("nodes,fused", CASES)
def test_update_arithmetic_is_under_the_update_scope(built, nodes, fused):
    compiled, lowered, _ = built(nodes, fused)
    scopes = trace_reduce.scopes_from_hlo(lowered)
    files = source_files(lowered)
    dse = [n for n, f in files.items() if f.endswith(os.path.join("repro", "core", "dse.py"))]
    assert dse and all("repro/update" in scopes.get(n, "") for n in dse), \
        [(n, scopes.get(n)) for n in dse if "repro/update" not in scopes.get(n, "")]
    ops = opcodes(compiled)
    update = {n: s for n, s in trace_reduce.scopes_from_hlo(compiled).items() if "repro/update" in s}
    # both phases run update arithmetic, and none of it is a matmul
    assert any("repro/local_update/" in s for s in update.values())
    assert any("repro/gossip/" in s for s in update.values())
    assert not {ops.get(n) for n in update} & {"dot", "convolution"}
    fused_ops = [s for s in trace_reduce.scopes_from_hlo(compiled).values() if "repro/fused/" in s]
    assert bool(fused_ops) == fused
    assert all("repro/update/" in s for s in fused_ops)


@pytest.mark.parametrize("nodes,fused", CASES)
def test_collectives_are_under_the_mix_scope(built, nodes, fused):
    """The gossip's collective-permutes (y and params, to both neighbours,
    every leaf) are the mix's.  On a ring the fused path's bucketed launcher
    also moves data between chips: it flattens node-sharded leaves into one
    buffer, which all-gathers and permutes them inside ``repro/update``."""
    compiled, _, _ = built(nodes, fused)
    scopes = trace_reduce.scopes_from_hlo(compiled)
    coll = [n for n, op in opcodes(compiled).items() if op.startswith(trace_reduce.COLLECTIVES)]
    mix = [n for n in coll if "repro/mix" in scopes.get(n, "")]
    assert len(mix) == (48 if nodes == 4 else 0)
    assert all(opcodes(compiled)[n] == "collective-permute" for n in mix)
    rest = [scopes.get(n, "") for n in coll if n not in mix]
    if fused and nodes > 1:
        assert rest and all("repro/update/" in s for s in rest)
    else:
        assert rest == []


@pytest.mark.parametrize("nodes,fused", CASES)
def test_no_op_is_under_two_scopes(built, nodes, fused):
    compiled, _, _ = built(nodes, fused)
    scopes = trace_reduce.scopes_from_hlo(compiled).values()
    assert all(sum(s in op for s in SCOPES) <= 1 for op in scopes)
    for s in SCOPES:
        if s != "repro/mix" or nodes > 1:
            assert any(s in op for op in scopes), s


@pytest.mark.parametrize("nodes,fused", CASES)
def test_scopes_are_metadata_only(built, nodes, fused):
    """Nulling ``jax.named_scope`` leaves the compiled step's text, less its
    metadata, and its results bitwise as they were."""
    _, _, report = built(nodes, fused)
    assert report["plain_scoped_ops"] == 0
    assert report["same_text"]
    assert report["leaves"] > 0 and report["same_outputs"]


def test_flash_kernel_is_under_the_attention_scope(built):
    """The flash kernel's forward, its remat and its backward (dq, dkv) all
    sit under ``repro/attn``, and no op of the step carries two scopes."""
    compiled, lowered = built("flash", "pallas")
    for text in (compiled, lowered):
        scopes = trace_reduce.scopes_from_hlo(text).values()
        assert all(sum(s in op for s in SCOPES) <= 1 for op in scopes)
    # parameters of reduction regions keep the region's own short name; they
    # do no work
    ops = opcodes(compiled)
    kernel = [s for n, s in trace_reduce.scopes_from_hlo(compiled).items()
              if "flash_attention_" in s and ops.get(n) != "parameter"]
    assert kernel and all("repro/attn/" in s for s in kernel)
    for part in ("flash_attention_fwd", "flash_attention_dq", "flash_attention_dkv"):
        assert any(part in s for s in kernel), part
    assert any("rematted_computation/" in s and "flash_attention_fwd" in s for s in kernel)


def test_flash_kernel_keeps_the_ring_collectives(built):
    """On a ring of four devices the kernel runs on each device's own node:
    the step's all-gathers, collective-permutes, all-reduces and all-to-alls
    are those of the ``_sdpa`` step, one for one."""
    def collectives(text):
        ops = opcodes(text).values()
        return sorted(op for op in ops if op.startswith(trace_reduce.COLLECTIVES))

    flash, _ = built("flash", "pallas")
    plain, _ = built("flash", "xla")
    assert collectives(plain) and collectives(flash) == collectives(plain)
