"""Per-layer metric readers, one file each under `benchmarks/chip/metrics/`,
found by the metric's name. A reader is a module with a function
`read(ctx) -> float | None`; it returns None when the trace holds nothing
for it to read, and the harness then leaves the metric out of the line."""
from __future__ import annotations

import dataclasses
import importlib.util
import os

from chipbench.spec import CHIP_DIR, UnknownName


@dataclasses.dataclass
class Context:
    """What a reader may read: the traced window's device operations, the
    cell, the graph's size and the chip's peaks."""

    trace: object        # chipbench.trace.DeviceTrace
    config: dict
    traffic: dict
    chips: int
    nodes: int
    edges: int           # nonzeros of the propagation matrix
    peaks: dict


def load_reader(name: str, chip_dir: str = CHIP_DIR):
    path = os.path.join(chip_dir, "metrics", f"{name}.py")
    if not os.path.exists(path):
        raise UnknownName(f"no reader for metric {name!r} ({path})")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_all(names, ctx: Context, chip_dir: str = CHIP_DIR) -> dict:
    """{name: value} for each metric whose reader found something."""
    out = {}
    for name in names:
        value = load_reader(name, chip_dir).read(ctx)
        if value is not None:
            out[name] = value
    return out
