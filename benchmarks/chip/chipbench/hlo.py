"""What each operation of a compiled XLA program computes and which module
of the program emitted it, from the program's own HLO text.

The device trace names each operation by its HLO instruction name in the
program that ran (`fusion.12`, `copy-start.3`); the name alone says nothing
of what it computes. The compiled module's text does: each instruction
carries metadata with a `stack_frame_id`, which the module's `StackFrames`,
`FileLocations`, `FileNames` and `FunctionNames` tables resolve to the
Python call chain that emitted it, and a fusion's body (its called
computations) holds the instructions it fused. An instruction of the entry
computation is described by everything inside it: the set of opcodes it
holds, and whether an instruction in it was emitted from a module of the
aggregation engines (`AGGREGATION_FILES`). The per-layer metrics under
`metrics/` attribute operations to layers by these facts, each with its
rule in its own file.
"""
from __future__ import annotations

import re

AGGREGATION_FILES = ("repro/kernels/aggregate.py",
                     "repro/kernels/gcn_spmm.py")
# Constants are shared between computations, so their metadata names
# whichever caller made them first: they attribute nothing.
_CONSTANTS = {"constant", "broadcast", "iota", "parameter"}

_COMP = re.compile(r"^(ENTRY )?%?([\w.\-]+) .*\{\s*$")
_INSTR = re.compile(r"^\s*(ROOT )?%?([\w.\-]+) = .*?\s([a-z][\w\-]*)\(")
_CALLS = re.compile(r"(?:calls|to_apply|body|condition)=%?([\w.\-]+)"
                    r"|(?:branch_computations|called_computations)="
                    r"\{([^}]*)\}")
_FRAME = re.compile(r"stack_frame_id=(\d+)")
_TABLE_ROW = re.compile(r"^(\d+) (.*)$")


def _tables(text: str) -> dict:
    """The module's FileNames, FunctionNames, FileLocations, StackFrames."""
    out, cur = {}, None
    for line in text.splitlines():
        s = line.strip()
        if s in ("FileNames", "FunctionNames", "FileLocations",
                 "StackFrames"):
            cur = out.setdefault(s, {})
            continue
        m = _TABLE_ROW.match(s)
        if cur is not None and m:
            cur[int(m.group(1))] = m.group(2)
        elif s and cur is not None and not m:
            cur = None
    return out


def _frame_files(tables: dict) -> dict:
    """stack frame id -> the set of source files on its call chain."""
    files = {k: v.strip('"') for k, v in tables.get("FileNames", {}).items()}
    loc_file = {}
    for k, v in tables.get("FileLocations", {}).items():
        m = re.search(r"file_name_id=(\d+)", v)
        if m:
            loc_file[k] = files.get(int(m.group(1)), "")
    frames = {}
    for k, v in tables.get("StackFrames", {}).items():
        loc = re.search(r"file_location_id=(\d+)", v)
        parent = re.search(r"parent_frame_id=(\d+)", v)
        frames[k] = (int(loc.group(1)) if loc else 0,
                     int(parent.group(1)) if parent else 0)
    out = {}

    def chain(fid):
        if fid in out:
            return out[fid]
        seen, acc, f = set(), set(), fid
        while f in frames and f not in seen:
            seen.add(f)
            loc, parent = frames[f]
            acc.add(loc_file.get(loc, ""))
            f = parent
        out[fid] = acc
        return acc

    for fid in frames:
        chain(fid)
    return out


def _computations(text: str) -> tuple[dict, str | None]:
    """name -> [(instruction, opcode, called computations, frame id)], and
    the entry computation's name."""
    comps, entry, cur = {}, None, None
    for line in text.splitlines():
        if not line.startswith(" "):
            m = _COMP.match(line)
            if m:
                cur = comps.setdefault(m.group(2), [])
                if m.group(1):
                    entry = m.group(2)
            elif line.startswith("}"):
                cur = None
            continue
        if cur is None:
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        called = []
        for one, many in _CALLS.findall(line):
            called += [one] if one else [x.strip().lstrip("%")
                                         for x in many.split(",")]
        f = _FRAME.search(line)
        cur.append((m.group(2), m.group(3), called,
                    int(f.group(1)) if f else None))
    return comps, entry


def facts(text: str) -> dict:
    """{entry instruction name: (opcodes, engine)} for one compiled
    module's HLO text: every opcode inside the instruction (itself and the
    computations it calls), and whether any of them other than a constant
    was emitted from a module of the aggregation engines."""
    frame_files = _frame_files(_tables(text))
    comps, entry = _computations(text)
    memo = {}

    def from_engine(op, fid):
        return (op not in _CONSTANTS and fid is not None
                and any(f.endswith(AGGREGATION_FILES)
                        for f in frame_files.get(fid, ())))

    def contents(comp, seen):
        if comp in memo:
            return memo[comp]
        if comp in seen:
            return frozenset(), False
        seen.add(comp)
        ops, engine = set(), False
        for _, op, called, fid in comps.get(comp, []):
            ops.add(op)
            engine = engine or from_engine(op, fid)
            for c in called:
                o, e = contents(c, seen)
                ops |= o
                engine = engine or e
        memo[comp] = (frozenset(ops), engine)
        return memo[comp]

    out = {}
    for name, op, called, fid in comps.get(entry, []):
        ops, engine = {op}, from_engine(op, fid)
        for c in called:
            o, e = contents(c, set())
            ops |= o
            engine = engine or e
        out[name] = (frozenset(ops), engine)
    return out


def module_name(text: str) -> str:
    """The `HloModule` name of a compiled module's text."""
    m = re.match(r"HloModule ([\w.\-]+)", text)
    return m.group(1) if m else ""
