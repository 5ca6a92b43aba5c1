"""Reduce a profiler trace of the window to the device's operations, and the
interval arithmetic the per-layer metrics share.

The JAX profiler writes an `.xplane.pb`; `jax.profiler.ProfileData` reads
it. Each chip is a plane named `/device:TPU:<n>`; its line `XLA Ops` holds
one event per executed HLO operation, named by its HLO instruction, with
the compiler's category for it among its stats (`hlo_category`); its line
`XLA Modules` holds one event per program run, named by the program's
HloModule. Host threads are other planes and are not read here.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass(frozen=True)
class Op:
    """One operation on one chip: its HLO instruction name, the program it
    ran in, its interval, the compiler's category for it, and what the
    program's HLO says of it (`chipbench.hlo.facts`): the opcodes inside it
    and whether the aggregation engines emitted any of them."""

    device: int
    name: str
    module: str
    start_ns: float
    dur_ns: float
    category: str = ""
    opcodes: tuple = ()
    engine: bool = False

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class DeviceTrace:
    """The device operations of one traced window, per chip, and the
    window's length (host clock) and epoch count."""

    ops: dict            # device -> [Op], sorted by start
    modules: dict        # device -> [Op] of whole programs
    window_s: float
    epochs: int

    @property
    def devices(self) -> list[int]:
        return sorted(self.ops)

    def to_json(self) -> dict:
        return {"window_s": self.window_s, "epochs": self.epochs,
                "ops": [dataclasses.astuple(o) for d in self.devices
                        for o in self.ops[d]],
                "modules": [dataclasses.astuple(o) for d in sorted(self.modules)
                            for o in self.modules[d]]}

    @staticmethod
    def from_json(d: dict) -> "DeviceTrace":
        ops, modules = {}, {}
        for row in d["ops"]:
            o = Op(*row[:6], tuple(row[6]), *row[7:])
            ops.setdefault(o.device, []).append(o)
        for row in d.get("modules", []):
            o = Op(*row[:6], tuple(row[6]), *row[7:])
            modules.setdefault(o.device, []).append(o)
        return DeviceTrace(ops=ops, modules=modules,
                           window_s=float(d["window_s"]),
                           epochs=int(d["epochs"]))


def _stat(stats: dict, key: str) -> str:
    v = stats.get(key, "")
    return v if isinstance(v, str) else str(v)


def _instruction(event_name: str) -> str:
    """`%fusion.12 = f32[...] fusion(...), ...` -> `fusion.12`: the chip's
    trace names an operation by its whole HLO instruction."""
    m = re.match(r"^%?([\w.\-]+)\s*=", event_name)
    return m.group(1) if m else event_name


def _module_key(event_name: str) -> str:
    """`jit_step(1234)` -> `jit_step`: the HloModule name of a program run."""
    return event_name.split("(", 1)[0]


def read_xplane(path: str, facts: dict | None = None) -> tuple[dict, dict]:
    """(ops, modules) per device from one `.xplane.pb`. `facts` maps an
    HloModule name to `chipbench.hlo.facts` of its text; an operation is
    placed in the program run whose interval holds its start."""
    import bisect

    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    facts = facts or {}
    raw, modules = {}, {}
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        dev = int(m.group(1))
        for line in plane.lines:
            if line.name == MODULES_LINE:
                rows = modules.setdefault(dev, [])
                for ev in line.events:
                    rows.append(Op(dev, ev.name, _module_key(ev.name),
                                   float(ev.start_ns), float(ev.duration_ns)))
            elif line.name == OPS_LINE:
                rows = raw.setdefault(dev, [])
                for ev in line.events:
                    rows.append((_instruction(ev.name), float(ev.start_ns),
                                 float(ev.duration_ns),
                                 _stat(dict(ev.stats), "hlo_category")))
    ops = {}
    for dev, rows in raw.items():
        mods = sorted(modules.get(dev, []), key=lambda o: o.start_ns)
        starts = [o.start_ns for o in mods]
        out = ops.setdefault(dev, [])
        for name, start, dur, cat in rows:
            i = bisect.bisect_right(starts, start) - 1
            mod = mods[i].module if i >= 0 and start < mods[i].end_ns else ""
            opcodes, engine = facts.get(mod, {}).get(name, ((), False))
            out.append(Op(dev, name, mod, start, dur, cat,
                          tuple(sorted(opcodes)), bool(engine)))
    for d in (ops, modules):
        for rows in d.values():
            rows.sort(key=lambda o: o.start_ns)
    return ops, modules


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(trace_dir: str, window_s: float, epochs: int,
         facts: dict | None = None) -> DeviceTrace:
    ops, modules = read_xplane(find_xplane(trace_dir), facts)
    return DeviceTrace(ops=ops, modules=modules, window_s=window_s,
                       epochs=epochs)


# ---------------------------------------------------------------------
# Interval arithmetic, in nanoseconds.
# ---------------------------------------------------------------------

def merge(intervals) -> list[tuple[float, float]]:
    """The union of (start, end) intervals as disjoint sorted intervals."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def length(intervals) -> float:
    return sum(e - s for s, e in merge(intervals))


def spans(ops) -> list[tuple[float, float]]:
    return [(o.start_ns, o.end_ns) for o in ops]


def busy_ns(trace: DeviceTrace, device: int) -> float:
    """Time in which some operation ran on `device`."""
    return length(spans(trace.ops.get(device, [])))


def gaps(trace: DeviceTrace, device: int):
    """(length_ns, op before, op after) of every idle gap between two
    operations of `device`."""
    ops = trace.ops.get(device, [])
    out, end, before = [], None, None
    for o in ops:
        if end is not None and o.start_ns > end:
            out.append((o.start_ns - end, before.name, o.name))
        if end is None or o.end_ns > end:
            end, before = o.end_ns, o
    return out
