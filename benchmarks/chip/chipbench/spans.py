"""The program's own names for its work in a traced window: the part of the
step each device operation belongs to (its scope), and what the trainer's
host thread was doing while the chip ran or stood idle (its spans).

Scopes. The program traces its train step and eval forward under
`jax.named_scope` paths (`repro.core.trace_utils`), which XLA keeps as each
instruction's `op_name` metadata, e.g. `jit(step)/bwd/L2/exchange/...`. An
entry instruction's scope is the innermost component of its `op_name` that
is one of `SCOPES`, so each op belongs to at most one of them. The rule
reads the instruction's own `op_name`, which for a fusion is its root's
(XLA gives a fusion its root's metadata); an instruction without one takes
its called computation's root's, through nested fusions. An instruction XLA
added carries no `op_name`, and one of a helper that JAX lowered apart only
the name of its primitive; these take the scope of the instruction that
reads their result, or failing that of one whose result they read. A
program traced without scopes gives "" for every op.

Spans. The profiler records the trainer's host spans (`trainer/...`,
`repro.core.trace_utils.HOST_SPANS`) on the host's plane of the same
`.xplane.pb`, on the clock of the device's operations. The spans come from
one thread, so they nest; each idle piece of a chip goes to the innermost
span open over it.

The benchmark's harness (`run.py`, `chipbench.hlo`, `chipbench.trace`)
reads neither yet; `trace_spans.py` runs a cell with it and reports what
these give.
"""
from __future__ import annotations

import bisect
import dataclasses
import re
from typing import NamedTuple

from chipbench import hlo, trace as tracing

# The program's scope vocabulary (`repro.core.trace_utils.SCOPES`), kept
# here so that a program that predates it reads as unscoped.
SCOPES = ("aggregate", "transform", "exchange", "loss", "update")
HOST_PLANE = re.compile(r"^/host:")
HOST_SPAN_PREFIX = "trainer/"
EPOCH_SPAN = "trainer/epoch"
WAIT_SPAN = "trainer/wait"
DISPATCH_SPAN = "trainer/dispatch"

_OP_NAME = re.compile(r'op_name="([^"]*)"')
_OPERAND = re.compile(r"%([\w.\-]+)")
_UNWRAP = re.compile(r"^(?:\w+\()+|\)+$")


# ---------------------------------------------------------------------
# Scopes of a compiled program.
# ---------------------------------------------------------------------

def scope_of(op_name: str) -> str:
    """The innermost of `SCOPES` on an `op_name` path, or "". A scope
    opened inside a transformation carries its name, `vmap(aggregate)`."""
    parts = [_UNWRAP.sub("", p) for p in op_name.split("/")]
    parts = [p for p in parts if p in SCOPES]
    return parts[-1] if parts else ""


class _Instr(NamedTuple):
    name: str
    called: list         # computations it calls
    op_name: str
    operands: list       # names of the instructions it reads


def _computations(text: str) -> tuple[list, dict]:
    """The entry computation's [_Instr], and name -> the ROOT _Instr of
    each computation."""
    comps, entry, roots, comp = {}, None, {}, None
    for line in text.splitlines():
        if not line.startswith(" "):
            m = hlo._COMP.match(line)
            if m:
                comp = m.group(2)
                comps.setdefault(comp, [])
                if m.group(1):
                    entry = comp
            elif line.startswith("}"):
                comp = None
            continue
        m = hlo._INSTR.match(line)
        if comp is None or not m:
            continue
        called = []
        for one, many in hlo._CALLS.findall(line):
            called += [one] if one else [x.strip().lstrip("%")
                                         for x in many.split(",")]
        n = _OP_NAME.search(line)
        args = line[m.end():line.find(")", m.end())]
        ins = _Instr(m.group(2), called, n.group(1) if n else "",
                     _OPERAND.findall(args))
        comps[comp].append(ins)
        if m.group(1):
            roots[comp] = ins
    return comps.get(entry, []), roots


def scopes(text: str) -> dict:
    """{entry instruction name: scope} for one compiled module's HLO text
    (the rule in the module docstring)."""
    entry, roots = _computations(text)
    scope, users, names = {}, {}, {}
    for ins in entry:
        op_name, called, seen = ins.op_name, ins.called, set()
        while not op_name and called and called[0] not in seen:
            seen.add(called[0])
            root = roots.get(called[0])
            op_name, called = (root.op_name, root.called) if root else ("", [])
        scope[ins.name], names[ins.name] = scope_of(op_name), op_name
        for x in ins.operands:
            users.setdefault(x, []).append(ins.name)
    # what the program itself emitted outside every scope stays so
    orphan = {n for n, sc in scope.items() if not sc and "/" not in names[n]}
    changed = True
    while changed:
        changed = False
        for ins in entry:
            if scope[ins.name] or ins.name not in orphan:
                continue
            near = users.get(ins.name, []) + ins.operands
            got = next((scope[x] for x in near if scope.get(x)), "")
            if got:
                scope[ins.name], changed = got, True
    return scope


# ---------------------------------------------------------------------
# Host spans.
# ---------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Span:
    """One host span of the trainer (`trainer/epoch` carries the epoch as
    its `step_num`, the others -1), on the device operations' clock."""

    name: str
    start_ns: float
    dur_ns: float
    step_num: int = -1

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def read_host(path: str) -> list[Span]:
    """The trainer's spans in one `.xplane.pb`, by start."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if HOST_PLANE.match(plane.name):
            out += [Span(ev.name, float(ev.start_ns), float(ev.duration_ns),
                         int(dict(ev.stats).get("step_num", -1)))
                    for line in plane.lines for ev in line.events
                    if ev.name.startswith(HOST_SPAN_PREFIX)]
    return sorted(out, key=lambda s: s.start_ns)


@dataclasses.dataclass
class SpanTrace:
    """A traced window (`chipbench.trace.DeviceTrace`) with the scope of
    each op of each program (module -> {instruction: scope}) and the
    trainer's host spans."""

    trace: object
    scopes: dict
    host: list

    def scope(self, op) -> str:
        return self.scopes.get(op.module, {}).get(op.name, "")

    def to_json(self) -> dict:
        return {"trace": self.trace.to_json(), "scopes": self.scopes,
                "host": [dataclasses.astuple(s) for s in self.host]}

    @staticmethod
    def from_json(d: dict) -> "SpanTrace":
        return SpanTrace(tracing.DeviceTrace.from_json(d["trace"]),
                         d["scopes"], [Span(*row) for row in d["host"]])


# ---------------------------------------------------------------------
# Host spans against device idle time.
# ---------------------------------------------------------------------

def innermost(host) -> list[tuple[float, float, str]]:
    """The spans as disjoint (start, end, name) pieces, each named by the
    innermost span open over it: the latest to open."""
    cuts = sorted({t for s in host for t in (s.start_ns, s.end_ns)})
    out = []
    for a, b in zip(cuts, cuts[1:]):
        mid = (a + b) / 2
        open_ = [s for s in host if s.start_ns <= mid < s.end_ns]
        if open_:
            name = max(open_, key=lambda s: (s.start_ns, -s.dur_ns)).name
            if out and out[-1][2] == name and out[-1][1] == a:
                out[-1] = (out[-1][0], b, name)
            else:
                out.append((a, b, name))
    return out


def idle(st: SpanTrace, device: int) -> list[tuple[float, float]]:
    """The intervals in which no operation ran on `device`, from the first
    operation or span of the trace to the last."""
    ops = st.trace.ops.get(device, [])
    starts = [o.start_ns for o in ops] + [s.start_ns for s in st.host]
    ends = [o.end_ns for o in ops] + [s.end_ns for s in st.host]
    if not starts:
        return []
    out, t = [], min(starts)
    for s, e in tracing.merge(tracing.spans(ops)):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if max(ends) > t:
        out.append((t, max(ends)))
    return out


def span_over(pieces, start: float, end: float) -> str:
    """The name of the `innermost` piece that covers most of [start, end),
    or "" where no span does."""
    cover = {}
    for a, b, name in pieces:
        if a < end and b > start:
            cover[name] = cover.get(name, 0.0) + min(b, end) - max(a, start)
    return max(cover, key=cover.get) if cover else ""


def idle_by_span(st: SpanTrace, device: int) -> dict:
    """{span name: ns} of `device`'s idle time, each piece of it under the
    innermost span open over it ("" where none is)."""
    pieces = innermost(st.host)
    out, k = {}, 0
    for s, e in idle(st, device):
        t = s
        while k < len(pieces) and pieces[k][1] <= s:
            k += 1
        j = k
        while j < len(pieces) and pieces[j][0] < e:
            a, b, name = pieces[j]
            lo, hi = max(a, s), min(b, e)
            if lo > t:
                out[""] = out.get("", 0.0) + lo - t
            out[name] = out.get(name, 0.0) + hi - lo
            t = hi
            j += 1
        if e > t:
            out[""] = out.get("", 0.0) + e - t
    return out


# ---------------------------------------------------------------------
# What the per-layer metrics of the scopes and spans would read.
# ---------------------------------------------------------------------

def _devices(st: SpanTrace, chips: int) -> list[int]:
    return [d for d in st.trace.devices if st.trace.ops[d]][:chips]


def scope_ms(st: SpanTrace, scope: str, chips: int = 1):
    """Device time per epoch of the ops in `scope`, in every program of the
    window, averaged over the chips used; None when no op carries it."""
    devs = _devices(st, chips)
    if not devs or st.trace.epochs <= 0:
        return None
    tot = [sum(o.dur_ns for o in st.trace.ops[d] if st.scope(o) == scope)
           for d in devs]
    if not any(tot):
        return None
    return sum(tot) / len(devs) / 1e6 / st.trace.epochs


def host_stall_ms(st: SpanTrace, chips: int = 1):
    """Per epoch, the idle time of a chip under a child span of
    `trainer/epoch` other than `trainer/wait`, averaged over the chips
    used; None when the trace holds no trainer span."""
    devs = _devices(st, chips)
    if not devs or not st.host or st.trace.epochs <= 0:
        return None
    skip = ("", EPOCH_SPAN, WAIT_SPAN)
    stall = [sum(ns for name, ns in idle_by_span(st, d).items()
                 if name not in skip) for d in devs]
    return sum(stall) / len(devs) / 1e6 / st.trace.epochs


def idle_gaps(st: SpanTrace, device: int, top: int = 10) -> list:
    """The longest idle gaps between two operations of `device`, each named
    by the operations around it and the innermost span open over most of
    it, with its length in seconds."""
    pieces = innermost(st.host)
    ops = st.trace.ops.get(device, [])
    gaps, end, before = [], None, None
    for o in ops:
        if end is not None and o.start_ns > end:
            gaps.append((end, o.start_ns, before.name, o.name))
        if end is None or o.end_ns > end:
            end, before = o.end_ns, o
    out = []
    for s, e, a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        span = span_over(pieces, s, e)
        out.append([f"after {a} before {b}" + (f" in {span}" if span else ""),
                    (e - s) / 1e9])
    return out


def summary(st: SpanTrace, chips: int = 1) -> dict:
    """What the scopes and spans say of the window: what the per-layer
    metrics `exchange_ms`, `update_ms` and `host_stall_ms` would read over
    the chips used, and of chip 0: each scope's device time per epoch
    (ms), the share of the train step's device time under a scope, each
    span's idle time per epoch (ms) and the share of idle time under a
    child of `trainer/epoch`, the lag from each `trainer/dispatch` to the
    next start of the train step (the program with the most device time),
    least and most (a negative or an epoch-long one would say the host's
    and the device's clocks disagree), and the named idle gaps."""
    tr = st.trace
    if not tr.devices:
        return {}
    dev, per_epoch = tr.devices[0], 1e6 * max(tr.epochs, 1)
    ops = tr.ops[dev]
    time_of = {}
    for m in tr.modules.get(dev, []):
        time_of[m.module] = time_of.get(m.module, 0.0) + m.dur_ns
    step = max(time_of, key=time_of.get) if time_of else ""
    by_scope = {}
    for o in ops:
        by_scope[st.scope(o)] = by_scope.get(st.scope(o), 0.0) + o.dur_ns
    step_ns = sum(o.dur_ns for o in ops if o.module == step)
    scoped_ns = sum(o.dur_ns for o in ops if o.module == step and st.scope(o))
    by_span = idle_by_span(st, dev)
    idle_ns = sum(by_span.values())
    child_ns = sum(v for k, v in by_span.items() if k not in ("", EPOCH_SPAN))
    starts = sorted(m.start_ns for m in tr.modules.get(dev, [])
                    if m.module == step)
    lags = []
    for s in st.host:
        if s.name == DISPATCH_SPAN:
            i = bisect.bisect_left(starts, s.start_ns)
            if i < len(starts):
                lags.append((starts[i] - s.start_ns) / 1e6)
    return {"exchange_ms": scope_ms(st, "exchange", chips),
            "update_ms": scope_ms(st, "update", chips),
            "host_stall_ms": host_stall_ms(st, chips),
            "scope_ms": {k: v / per_epoch
                         for k, v in sorted(by_scope.items())},
            "step_scoped_share": scoped_ns / step_ns if step_ns else None,
            "idle_ms": {k: v / per_epoch for k, v in sorted(by_span.items())},
            "idle_child_share": child_ns / idle_ns if idle_ns else None,
            "dispatch_lag_ms": [min(lags), max(lags)] if lags else None,
            "idle_gaps": idle_gaps(st, dev)}
