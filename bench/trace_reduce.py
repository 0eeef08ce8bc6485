"""From a profiler trace (``.xplane.pb``) to the benchmark's per-layer
numbers, with nothing but ``jax.profiler.ProfileData``.

Device planes are ``/device:TPU:<i>``; their ``XLA Ops`` line holds one event
per executed HLO operation, named by the instruction's text
(``%fusion.12 = bf16[...] fusion(...)``), control flow (``%while``) spanning
the ops of its body; an asynchronous collective shows as a short ``-start``
op and a ``-done`` op that waits for it.  Their ``XLA Modules`` line holds
one event per executed program (``jit_train_step(<fingerprint>)``).  The
events carry no scope, so an op's named-scope
path (``jit(train_step)/repro/local_update/...``) is looked up by its
instruction name in the compiled program's HLO text
(:func:`scopes_from_hlo`).  The host plane's ``python3`` thread holds the
benchmark's ``TraceAnnotation`` spans (``bench/put``, ``bench/step``, ...).
Host and device events are on one clock.

* busy: the union of a device's op intervals inside the window;
* scope time: the union of the intervals of ops whose scope path holds it;
* collective time: the union of the collectives' spans in flight, each
  asynchronous one from its ``-start`` to the end of its ``-done``, a
  synchronous one (``all-reduce.3``) as its own op; its exposed part is what
  of that no leaf op other than a collective overlaps (a leaf has no op
  nested in it: a loop or call spans its body and hides nothing itself);
* program time: the number and device time of a program's runs;
* idle gaps: the holes in the busy union, each named by the benchmark span
  open on the host at the gap's midpoint.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Iterable

__all__ = ["Op", "Trace", "load", "scopes_from_hlo", "union", "overlap", "COLLECTIVES"]

_HLO_LINE = re.compile(r'^\s*(?:ROOT\s+)?%([\w.\-]+) = .*?metadata=\{[^}]*?op_name="([^"]*)"')


def scopes_from_hlo(text: str) -> dict:
    """``{instruction name: op_name scope path}`` of an HLO module's text
    (``compiled.as_text()``)."""
    out = {}
    for line in text.splitlines():
        m = _HLO_LINE.match(line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


def short_name(event_name: str) -> str:
    """``fusion.12`` of ``%fusion.12 = bf16[...] fusion(...)``."""
    return event_name.split(" = ", 1)[0].lstrip("%")

COLLECTIVES = ("collective-permute", "all-gather", "all-reduce", "reduce-scatter",
               "all-to-all", "collective-broadcast")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PREFIX = "bench/"


@dataclasses.dataclass(frozen=True)
class Op:
    start: int          # ns
    end: int            # ns
    name: str           # HLO operation name
    scope: str          # named-scope path ("tf_op" stat), "" if none

    @property
    def collective(self) -> bool:
        return self.name.startswith(COLLECTIVES)

    @property
    def pair(self) -> tuple:
        """``(collective-permute.3, "start" | "done" | "")`` of a collective."""
        head, dot, num = self.name.partition(".")
        for half in ("start", "done"):
            if head.endswith("-" + half):
                return head[: -len(half) - 1] + dot + num, half
        return self.name, ""


def union(spans: Iterable[tuple]) -> list:
    """Merged, sorted ``[(start, end), ...]`` of possibly overlapping spans."""
    out: list = []
    for s, e in sorted(spans):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def length(spans: list) -> int:
    return sum(e - s for s, e in spans)


def overlap(a: list, b: list) -> int:
    """Total length of the intersection of two merged span lists."""
    i = j = tot = 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            tot += e - s
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def clip(spans: list, lo: int, hi: int) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in spans if e > lo and s < hi]


@dataclasses.dataclass
class Trace:
    """Ops per device and host spans of one traced window ``[t0, t1]`` (ns)."""

    ops: dict            # device name -> [Op]
    host: list           # [(start, end, name)] of the benchmark's host spans
    t0: int
    t1: int
    modules: dict = dataclasses.field(default_factory=dict)   # device -> [(start, end, name)]

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) * 1e-9

    def _per_device(self, pick) -> float:
        """Mean over devices of the union length (s) of the ops ``pick`` keeps."""
        if not self.ops:
            return 0.0
        tot = 0
        for ops in self.ops.values():
            tot += length(clip(union((o.start, o.end) for o in ops if pick(o)), self.t0, self.t1))
        return tot / len(self.ops) * 1e-9

    def busy_s(self) -> float:
        return self._per_device(lambda o: True)

    def scope_s(self, scope: str) -> float:
        return self._per_device(lambda o: scope in o.scope)

    def collective_s(self) -> float:
        """Mean over devices of the collectives' time in flight (s)."""
        if not self.ops:
            return 0.0
        tot = sum(length(clip(union(_flights(ops)), self.t0, self.t1)) for ops in self.ops.values())
        return tot / len(self.ops) * 1e-9

    def collective_exposed_s(self) -> float:
        """Mean over devices of the time in flight in which no leaf op
        other than a collective ran (s)."""
        if not self.ops:
            return 0.0
        tot = 0
        for ops in self.ops.values():
            coll = clip(union(_flights(ops)), self.t0, self.t1)
            other = union((o.start, o.end) for o in _leaves(ops) if not o.collective)
            tot += length(coll) - overlap(coll, clip(other, self.t0, self.t1))
        return tot / len(self.ops) * 1e-9

    def program_runs(self, name: str) -> tuple:
        """``(runs, seconds)`` summed over devices: the runs of program
        ``name`` (``jit_train_step``) that lie wholly in the window, and
        their device time."""
        runs = ns = 0
        for mods in self.modules.values():
            for s, e, n in mods:
                if n.split("(")[0] == name and s >= self.t0 and e <= self.t1:
                    runs += 1
                    ns += e - s
        return runs, ns * 1e-9

    def n_collectives(self) -> int:
        return sum(o.collective for ops in self.ops.values() for o in ops)

    def top_ops(self, k: int = 10) -> list:
        """``[[name, seconds], ...]``: the ops with the most self time (their
        span less the ops nested in it, as a loop's body is in the loop),
        summed over devices and divided by their number.  A name is the
        instruction's and the tail of its scope path."""
        tot: dict = {}
        for ops in self.ops.values():
            for o, self_ns in _self_times(ops, self.t0, self.t1):
                tail = "/".join(o.scope.split("/")[-3:])
                key = f"{o.name} {tail}".strip()
                tot[key] = tot.get(key, 0) + self_ns
        n = max(1, len(self.ops))
        return [[name, ns / n * 1e-9] for name, ns in
                sorted(tot.items(), key=lambda kv: -kv[1])[:k]]

    def idle_gaps(self, k: int = 10) -> list:
        """``[[host span, seconds], ...]``: the longest holes in the first
        device's busy union, named by the host span open at each midpoint."""
        if not self.ops:
            return []
        dev = sorted(self.ops)[0]
        busy = clip(union((o.start, o.end) for o in self.ops[dev]), self.t0, self.t1)
        edges = [self.t0] + [x for s in busy for x in s] + [self.t1]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges) - 1, 2)
                if edges[i + 1] > edges[i]]
        gaps.sort(key=lambda g: g[0] - g[1])
        out = []
        for s, e in gaps[:k]:
            mid = (s + e) // 2
            open_ = [h for h in self.host if h[0] <= mid < h[1]]
            name = min(open_, key=lambda h: h[1] - h[0])[2] if open_ else "none"
            out.append([name, (e - s) * 1e-9])
        return out


def _flights(ops: list) -> list:
    """``[(start, end)]`` of each collective in flight: a ``-start`` op to
    the end of its ``-done``, or a synchronous collective's own span."""
    starts: dict = {}
    out = []
    for o in sorted((o for o in ops if o.collective), key=lambda o: o.start):
        key, half = o.pair
        if half == "start":
            starts[key] = o.start
        elif half == "done":
            out.append((starts.pop(key, o.start), o.end))
        else:
            out.append((o.start, o.end))
    return out


def _leaves(ops: list) -> list:
    """The ops with no other op of some length nested in their span (ops
    nest properly on a device's op line; a loop or a call spans the ops of
    its body, while an event of no length marks a point, not a child)."""
    spans = sorted((o for o in ops if o.end > o.start), key=lambda o: (o.start, -o.end))
    parents = set()
    stack: list = []
    for o in spans:
        while stack and stack[-1].end <= o.start:
            stack.pop()
        if stack and o.end <= stack[-1].end and (o.start, o.end) != (stack[-1].start, stack[-1].end):
            parents.add(id(stack[-1]))
        stack.append(o)
    return [o for o in ops if id(o) not in parents]


def _self_times(ops: list, t0: int, t1: int):
    """``(op, ns)`` for each op in ``[t0, t1]``: its span less its children's
    (ops nest properly on a device's op line)."""
    spans = sorted(((max(o.start, t0), min(o.end, t1), o) for o in ops
                    if o.end > t0 and o.start < t1), key=lambda x: (x[0], -x[1]))
    out: list = []
    stack: list = []      # [start, end, op, child ns]
    for s, e, o in spans:
        while stack and stack[-1][1] <= s:
            out.append(_close(stack))
        if stack:
            stack[-1][3] += e - s
        stack.append([s, e, o, 0])
    while stack:
        out.append(_close(stack))
    return out


def _close(stack):
    s, e, o, child = stack.pop()
    return o, max(0, e - s - child)


def load(path: str, scopes: dict | None = None,
         t0: int | None = None, t1: int | None = None) -> Trace:
    """Read ``path``, naming each op's scope from ``scopes``
    (:func:`scopes_from_hlo`); the window is ``[t0, t1]`` if given, else
    from the first benchmark host span's start to the last one's end."""
    scopes = scopes or {}
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    ops: dict = {}
    modules: dict = {}
    host: list = []
    for plane in data.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    lst = ops.setdefault(plane.name, [])
                    for ev in line.events:
                        start = int(ev.start_ns)
                        name = short_name(ev.name)
                        lst.append(Op(start, start + int(ev.duration_ns), name, scopes.get(name, "")))
                elif line.name == MODULES_LINE:
                    mods = modules.setdefault(plane.name, [])
                    for ev in line.events:
                        start = int(ev.start_ns)
                        mods.append((start, start + int(ev.duration_ns), ev.name))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIX):
                        s = int(ev.start_ns)
                        host.append((s, s + int(ev.duration_ns), ev.name))
    if t0 is None or t1 is None:
        t0 = min((h[0] for h in host), default=0)
        t1 = max((h[1] for h in host), default=0)
    return Trace(ops, host, t0, t1, modules)
