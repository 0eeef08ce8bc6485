"""The metrics that read the program's own named scopes (``update_ms``,
``attention_ms``, ``mlp_ms``, ``mix_ms``): on made-up ops, on one recorded
round per kind of cell on a TPU v5e (``data/``: ``yi9b.s512.tau4`` and
``yi9b.ring4.s2k.tau4``, captured from the program that opens the scopes),
and the host spans that ``train()`` puts in a profiler trace."""
from __future__ import annotations

import gzip
import json
import pathlib

import pytest

import harness
import trace_reduce as T

DATA = pathlib.Path(__file__).resolve().parent / "data"
MS = 1_000_000                   # ns
READERS = {"update_ms": "repro/update", "attention_ms": "repro/attn",
           "mlp_ms": "repro/mlp", "mix_ms": "repro/mix"}


def read(name, trace, rounds=1):
    return harness.metric_reader(name)({"trace": trace, "rounds": rounds})


def op(s, e, name, scope=""):
    return T.Op(s * MS, e * MS, name, scope)


def made_up_trace():
    """Two chips, one round each.  On chip 0 a loop spans the local steps,
    an attention op nests in a longer one, the backward and the remat carry
    the scopes below ``transpose(jvp())`` and ``rematted_computation``, and
    the mix's collective starts, overlaps an update op and waits."""
    lu = "jit(train_step)/repro/local_update/while/body/closed_call"
    g = "jit(train_step)/repro/gossip"
    chip0 = [
        op(0, 100, "while.1", "jit(train_step)/repro/local_update/while"),
        op(0, 20, "fusion.2", f"{lu}/checkpoint/repro/attn/dot_general"),
        op(5, 15, "fusion.3", f"{lu}/checkpoint/repro/attn/exp"),
        op(20, 50, "fusion.4", f"{lu}/checkpoint/repro/mlp/dot_general"),
        op(50, 60, "fusion.5", f"{lu}/transpose(jvp(checkpoint))/repro/attn/dot_general"),
        op(60, 70, "fusion.6", f"{lu}/checkpoint/rematted_computation/repro/mlp/mul"),
        op(70, 74, "fusion.7", f"{lu}/rms_norm/mul"),
        op(74, 100, "fusion.8", "jit(train_step)/repro/local_update/while/body/repro/update/add"),
        op(100, 101, "collective-permute-start.9", f"{g}/repro/mix/jit(_roll_static)/concatenate"),
        op(101, 110, "fusion.10", f"{g}/repro/update/sub"),
        op(115, 130, "collective-permute-done.9", f"{g}/repro/mix/jit(_roll_static)/concatenate"),
        op(130, 136, "fusion.11", f"{g}/repro/mix/add"),
    ]
    chip1 = [
        op(0, 40, "fusion.2", f"{lu}/checkpoint/repro/attn/dot_general"),
        op(40, 50, "fusion.4", f"{lu}/checkpoint/repro/mlp/dot_general"),
        op(50, 70, "fusion.8", "jit(train_step)/repro/local_update/while/body/repro/update/add"),
        op(100, 140, "collective-permute-done.9", f"{g}/repro/mix/jit(_roll_static)/concatenate"),
    ]
    return T.Trace({"/device:TPU:0": chip0, "/device:TPU:1": chip1}, [], 0, 200 * MS)


def test_readers_on_made_up_ops():
    t = made_up_trace()
    # chip 0: attention 0-20 (5-15 nested) + 50-60 = 30; chip 1: 40 -> mean 35
    assert read("attention_ms", t) == pytest.approx(35)
    assert read("mlp_ms", t) == pytest.approx((40 + 10) / 2)
    # repro/local_update (the loop, 100) is not repro/update: 26 + 9 and 20
    assert read("update_ms", t) == pytest.approx((35 + 20) / 2)
    assert t.scope_s("repro/local_update") > t.scope_s("repro/update")
    # the mix counts its ops, the done's wait included, not the time in flight
    assert read("mix_ms", t) == pytest.approx((1 + 15 + 6 + 40) / 2)
    assert t.collective_s() * 1e3 == pytest.approx((30 + 40) / 2)
    # per round
    assert read("mlp_ms", t, rounds=5) == pytest.approx(5)


@pytest.mark.parametrize("name", sorted(READERS))
def test_readers_read_nothing_where_there_is_nothing(name):
    assert read(name, None) is None
    plain = T.Trace({"/device:TPU:0": [op(0, 10, "fusion.1", "jit(train_step)/repro/local_update/add")]},
                    [], 0, 20 * MS)
    assert read(name, plain) is None
    assert read(name, T.Trace({}, [], 0, 0)) is None


def test_manifest_entries():
    manifest = harness.load_json(harness.ROOT / "BENCHMARK.json")
    by_name = {x["name"]: x for x in manifest["per_layer"]}
    layers = {"update_ms": "update arithmetic", "attention_ms": "attention", "mlp_ms": "MLP",
              "mix_ms": "gossip"}
    for name, layer in layers.items():
        x = by_name[name]
        assert (x["unit"], x["better"], x["source"], x["moves"], x["layer"]) == (
            "ms", "lower", "device_trace", "tokens_per_s", layer)
    assert "workloads" not in by_name["update_ms"]
    assert by_name["mix_ms"]["workloads"] == ["yi9b.ring4.s2k.tau4"]


def load_fixture(cell, tmp_path):
    raw = tmp_path / f"{cell}.xplane.pb"
    raw.write_bytes(gzip.decompress((DATA / f"{cell}.scoped.round.xplane.pb.gz").read_bytes()))
    scopes = json.loads((DATA / f"{cell}.scoped.scopes.json").read_text())
    return T.load(raw, scopes), scopes


@pytest.mark.parametrize("cell,chips,names", [
    ("yi9b.s512.tau4", 1, ("update_ms", "attention_ms", "mlp_ms")),
    ("yi9b.ring4.s2k.tau4", 2, ("update_ms", "attention_ms", "mlp_ms", "mix_ms")),
])
def test_recorded_chip_round(tmp_path, cell, chips, names):
    """One round of the cell (on two of the ring's four chips): each scope
    reads, no op carries two of them, and together they fit in the busy
    time.  The one-chip round has no mix."""
    t, scopes = load_fixture(cell, tmp_path)
    assert len(t.ops) == chips
    runs, _ = t.program_runs("jit_train_step")
    rounds = runs / chips
    assert rounds == 1
    assert all(sum(s in v for s in READERS.values()) <= 1 for v in scopes.values())
    values = {n: read(n, t, rounds) for n in READERS}
    assert all(values[n] > 0 for n in names), values
    assert all(values[n] is None for n in set(READERS) - set(names)), values
    assert sum(v for v in values.values() if v) <= 1e3 * t.busy_s() / rounds
    # every collective-permute of the ring is the mix's; the one other
    # collective is the all-reduce of the round's loss over the nodes
    coll = [o for ops in t.ops.values() for o in ops if o.collective]
    assert all("repro/mix" in o.scope for o in coll if o.name.startswith("collective-permute"))
    assert all(o.name.startswith("all-reduce") and o.scope.endswith("/reduce_sum")
               for o in coll if "repro/mix" not in o.scope)


def test_train_rounds_carry_host_spans(tmp_path):
    """``train()`` under ``--profile`` writes one ``round`` step annotation
    and one span per host phase for each round, on the host plane."""
    from jax.profiler import ProfileData

    from repro.configs import get_reduced
    from repro.launch.train import build_parser, train

    args = build_parser().parse_args(["--arch", "yi-9b", "--reduced", "--steps", "2", "--tau", "2",
                                      "--global-batch", "2", "--seq-len", "16",
                                      "--profile", str(tmp_path)])
    run = train(get_reduced("yi-9b"), args)
    assert len(run.round_s) == 2
    (path,) = tmp_path.rglob("*.xplane.pb")
    names = [ev.name for plane in ProfileData.from_file(str(path)).planes
             if plane.name.startswith("/host:") for line in plane.lines for ev in line.events]
    for span in ("round", "repro/host/batch", "repro/host/put", "repro/host/step", "repro/host/sync"):
        assert names.count(span) == 2, span
