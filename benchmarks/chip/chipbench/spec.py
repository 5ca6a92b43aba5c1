"""Find a cell, its configuration, its traffic, its limits and the peaks of
a device by name. Every one of them is a file of its own under
`benchmarks/chip/`, so a new cell, configuration or metric is a new file."""
from __future__ import annotations

import dataclasses
import json
import os

CHIP_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECKOUT = os.path.dirname(os.path.dirname(CHIP_DIR))


class UnknownName(KeyError):
    """A cell, configuration, traffic mix, metric or device kind that has
    no file or entry."""


@dataclasses.dataclass(frozen=True)
class Cell:
    """One entry of `BENCHMARK.json` `workloads` with everything it names
    loaded: the configuration as it is run, the traffic (the training
    job's scale and knobs), the correctness limits, and the end-to-end and
    per-layer metric entries that apply to this cell."""

    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: tuple
    per_layer: tuple


def _load_json(path: str, what: str, name: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise UnknownName(f"no {what} named {name!r} ({path})") from None


def load_benchmark(root: str = CHECKOUT) -> dict:
    return _load_json(os.path.join(root, "BENCHMARK.json"), "benchmark",
                      "BENCHMARK.json")


def load_config(name: str, chip_dir: str = CHIP_DIR) -> dict:
    return _load_json(os.path.join(chip_dir, "configs", f"{name}.json"),
                      "configuration", name)


def load_traffic(name: str, chip_dir: str = CHIP_DIR) -> dict:
    return _load_json(os.path.join(chip_dir, "traffic", f"{name}.json"),
                      "traffic mix", name)


def load_limits(cell: str, chip_dir: str = CHIP_DIR) -> dict:
    return _load_json(os.path.join(chip_dir, "limits", f"{cell}.json"),
                      "limits of cell", cell)


def applies(metric: dict, cell: str, reported: set | None = None) -> bool:
    """Whether a metric entry is reported in `cell`: named in its
    `workloads`, or, without that key, everywhere its moved end-to-end
    metric is (`reported`: the end-to-end metrics of the cell)."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    if reported is None or "moves" not in metric:
        return True
    return metric["moves"] in reported


def load_cell(name: str, root: str = CHECKOUT,
              chip_dir: str = CHIP_DIR) -> Cell:
    bench = load_benchmark(root)
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise UnknownName(f"no cell named {name!r}; have {sorted(by_name)}")
    w = by_name[name]
    e2e = tuple(m for m in bench["end_to_end"] if applies(m, name))
    names = {m["name"] for m in e2e}
    per = tuple(m for m in bench["per_layer"] if applies(m, name, names))
    return Cell(name=name, chips=int(w["chips"]),
                config=load_config(w["config"], chip_dir),
                traffic=load_traffic(w["traffic"], chip_dir),
                limits=load_limits(name, chip_dir),
                end_to_end=e2e, per_layer=per)


def peaks_for(device_kind: str, chip_dir: str = CHIP_DIR) -> dict:
    """The published peaks of one chip of `device_kind`. A device that is
    not in `peaks.json` is an error, never a default."""
    table = _load_json(os.path.join(chip_dir, "peaks.json"), "peak table",
                       "peaks.json")
    if device_kind not in table["devices"]:
        raise UnknownName(f"no peaks for device kind {device_kind!r}; "
                          f"have {sorted(table['devices'])}")
    return table["devices"][device_kind]
