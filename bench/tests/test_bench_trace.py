"""The trace reduction: interval arithmetic on made-up ops, and the whole
reduction on one recorded round of ``yi9b.s512.tau4`` on a TPU v5e
(``data/``: the profiler's ``.xplane.pb``, gzipped, and the op scopes of
its compiled step)."""
from __future__ import annotations

import gzip
import json
import pathlib

import pytest

import trace_reduce as T

DATA = pathlib.Path(__file__).resolve().parent / "data"


def op(s, e, name="fusion.1", scope=""):
    return T.Op(s, e, name, scope)


def test_union_and_overlap():
    assert T.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert T.overlap([(0, 10)], [(2, 3), (5, 12)]) == 6
    assert T.clip([(0, 10), (20, 30)], 5, 25) == [(5, 10), (20, 25)]


def test_busy_collectives_and_exposed_part():
    """As the TPU op line has them: a loop spans its body, an asynchronous
    collective is a short start and a done that waits."""
    ops = {"/device:TPU:0": [
        op(0, 100, "while.1", "jit(f)/repro/local_update/while"),
        op(10, 40, "fusion.2", "jit(f)/repro/local_update/while/body/dot"),
        op(120, 121, "collective-permute-start.3"),
        op(121, 170, "fusion.4", "jit(f)/repro/gossip/add"),
        op(180, 200, "collective-permute-done.3"),
    ]}
    t = T.Trace(ops, [(100, 120, "bench/sync"), (170, 180, "bench/put")], 0, 200)
    assert t.busy_s() == pytest.approx(170e-9)
    assert t.scope_s("repro/local_update") == pytest.approx(100e-9)
    assert t.collective_s() == pytest.approx(80e-9)            # in flight 120-200
    assert t.collective_exposed_s() == pytest.approx(31e-9)    # 120-121, 170-200
    top = dict(t.top_ops())
    assert top["while.1 repro/local_update/while"] == pytest.approx(70e-9)   # less its body op
    gaps = t.idle_gaps()
    assert gaps == [["bench/sync", pytest.approx(20e-9)], ["bench/put", pytest.approx(10e-9)]]


def test_a_collective_inside_a_loop_is_exposed_where_it_waits():
    """The loop's span covers its whole body; only the body's leaf ops hide
    a collective in flight, so the done's wait and a synchronous
    all-reduce stay exposed."""
    ops = {"/device:TPU:0": [
        op(0, 300, "while.1"),
        op(10, 40, "fusion.2"),
        op(50, 52, "collective-permute-start.3"),
        op(52, 120, "fusion.4"),
        op(120, 150, "collective-permute-done.3"),
        op(160, 180, "all-reduce.5"),
        op(180, 290, "fusion.6"),
        op(300, 350, "fusion.7"),
    ]}
    t = T.Trace(ops, [], 0, 400)
    assert [o.name for o in T._leaves(ops["/device:TPU:0"])] == [
        "fusion.2", "collective-permute-start.3", "fusion.4", "collective-permute-done.3",
        "all-reduce.5", "fusion.6", "fusion.7"]
    assert t.collective_s() == pytest.approx(120e-9)           # 50-150 and 160-180
    assert t.collective_exposed_s() == pytest.approx(52e-9)    # 50-52, 120-150, 160-180
    assert t.n_collectives() == 3


def test_program_runs_in_the_window():
    mods = {"/device:TPU:0": [(0, 90, "jit_train_step(123)"), (100, 190, "jit_train_step(123)"),
                              (195, 199, "jit_other(7)"), (200, 300, "jit_train_step(123)")],
            "/device:TPU:1": [(5, 95, "jit_train_step(123)")]}
    t = T.Trace({}, [], 0, 250, mods)
    runs, seconds = t.program_runs("jit_train_step")
    assert runs == 3 and seconds == pytest.approx(270e-9)     # the last run ends past the window


def test_scopes_from_hlo():
    text = ('  %fusion.7 = bf16[2]{0} fusion(%p), kind=kLoop, calls=%f, '
            'metadata={op_name="jit(train_step)/repro/local_update/while/body/dot_general" '
            'source_file="x.py" source_line=3}\n'
            '  ROOT %tuple.1 = (f32[]) tuple(%a)\n'
            '  ROOT %add.2 = f32[] add(%a, %b), metadata={op_name="jit(train_step)/repro/gossip/add"}\n')
    assert T.scopes_from_hlo(text) == {
        "fusion.7": "jit(train_step)/repro/local_update/while/body/dot_general",
        "add.2": "jit(train_step)/repro/gossip/add"}
    assert T.short_name("%fusion.7 = bf16[2]{0} fusion(%p)") == "fusion.7"


def test_recorded_chip_round(tmp_path):
    raw = tmp_path / "round.xplane.pb"
    raw.write_bytes(gzip.decompress((DATA / "yi9b.s512.tau4.round.xplane.pb.gz").read_bytes()))
    scopes = json.loads((DATA / "yi9b.s512.tau4.scopes.json").read_text())
    t = T.load(raw, scopes)
    assert list(t.ops) == ["/device:TPU:0"]
    assert {h[2] for h in t.host} == {"bench/batch", "bench/put", "bench/step", "bench/sync"}
    assert 0 < t.busy_s() <= t.window_s
    local = t.scope_s("repro/local_update")
    gossip = t.scope_s("repro/gossip")
    assert 0 < local < t.busy_s() and 0 < gossip < t.busy_s()
    assert local + gossip <= t.busy_s() * 1.0001
    assert t.n_collectives() == 0 and t.collective_s() == 0
    runs, seconds = t.program_runs("jit_train_step")
    assert runs == 1 and 0 < seconds <= t.busy_s() * 1.0001
    top = t.top_ops(10)
    assert len(top) == 10 and sum(s for _, s in top) <= t.busy_s()
    assert all(name.split(" ")[0] in scopes for name, _ in top)
    gaps = t.idle_gaps(10)
    assert len(gaps) == 10 and gaps[0][0] == "bench/sync"
    assert all(name.startswith("bench/") or name == "none" for name, _ in gaps)
    every = t.idle_gaps(10**6)
    assert sum(s for _, s in every) == pytest.approx(t.window_s - t.busy_s(), rel=1e-6)


def test_recorded_chip_ring_round(tmp_path):
    """One round of ``yi9b.ring4.s2k.tau4`` on two of its four v5e chips:
    the gossip's collective-permutes are in flight longer than the chip
    waits on them, and the waits are what is exposed."""
    raw = tmp_path / "ring.xplane.pb"
    raw.write_bytes(gzip.decompress((DATA / "yi9b.ring4.s2k.tau4.round.xplane.pb.gz").read_bytes()))
    scopes = json.loads((DATA / "yi9b.ring4.s2k.tau4.scopes.json").read_text())
    t = T.load(raw, scopes)
    assert list(t.ops) == ["/device:TPU:0", "/device:TPU:1"]
    assert t.program_runs("jit_train_step")[0] == 2
    for ops in t.ops.values():
        leaves = {id(o) for o in T._leaves(ops)}
        assert {o.name.split(".")[0] for o in ops if id(o) not in leaves} == {"while"}
        flights = T._flights(ops)
        assert len(flights) == sum(o.pair[1] != "start" for o in ops if o.collective)
        assert all(e > s for s, e in flights)
    coll, exposed = t.collective_s(), t.collective_exposed_s()
    waits = t._per_device(lambda o: o.collective)       # the collectives' own ops
    assert 0 < waits <= exposed < coll < t.busy_s()
    assert exposed == pytest.approx(waits, rel=0.01)
    assert all("gossip" in o.scope for ops in t.ops.values() for o in ops if o.collective)
