#!/usr/bin/env python3
"""Readings from which a cell's correctness limits are set, on the chip.

  python3 benchmarks/chip/calibrate.py --workload <cell> --seeds 12 \
      --control-seeds 3 --fault-seeds 3 [--first-seed N] [--out FILE]

For each seed, one process runs, at the cell's own size:

  program   the cell's run as the benchmark makes it (a window of a few
            epochs), and its compared numbers against the reference
  control   the reference computed in bfloat16 in the program's place,
            against the float32 reference, on the program's partition
  faults    the program with each fault of `chipbench.faults` planted
            (a state left unchanged reads 1 by construction, and is not run)

and prints every reading as one JSON object. The limits of
`limits/<cell>.json` are set from these readings (see PERF.md); the
benchmark's own runs never run this script.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import run
from chipbench import compare, faults, graph as graphgen, job, reference, spec

WINDOW_S = 0.05


def control_readings(cell: spec.Cell, seed: int) -> dict:
    import jax.numpy as jnp
    g = graphgen.from_cell(cell.config, cell.traffic, seed)
    pipeline, *_ = job.build(cell.config, cell.traffic, g)
    part = pipeline.pg.part_of.copy()
    del pipeline
    args = (g, cell.config, cell.traffic["variant"], part,
            int(cell.traffic["partitions"]))
    ref = reference.run(reference.problem(*args), seed, job.RECORDED_STEPS)
    ctl = reference.run(reference.problem(*args, dtype=jnp.bfloat16), seed,
                        job.RECORDED_STEPS)
    read = compare.readings(ctl.losses, ctl.grads_1, ctl.params_0,
                            ctl.params_out, ctl.buffers, ref)
    read.pop("_where")
    return read


def program_readings(cell: spec.Cell, seed: int, fault: str | None) -> dict:
    wrap, patches = faults.plant(fault) if fault else (None, ())
    r = run.run_cell(cell, seed, WINDOW_S, False, time.perf_counter(),
                     out=lambda m: None, wrap_step=wrap, patches=patches)
    return {k: v["value"] for k, v in r["checks"].items()}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--fault-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2**31 + 1000)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    run.require_chips(cell.chips)
    run.configure_cache()
    seeds = [args.first_seed + i for i in range(args.seeds)]
    out = {"cell": cell.name, "program": {}, "control": {}, "faults": {}}
    for s in seeds:
        out["program"][s] = program_readings(cell, s, None)
        print("program", s, out["program"][s], flush=True)
    for s in seeds[:args.control_seeds]:
        out["control"][s] = control_readings(cell, s)
        print("control", s, out["control"][s], flush=True)
    for name in faults.FAULTS:
        if name == "state_unchanged":
            continue
        out["faults"][name] = {}
        for s in seeds[:args.fault_seeds]:
            out["faults"][name][s] = program_readings(cell, s, name)
            print("fault", name, s, out["faults"][name][s], flush=True)
    text = json.dumps(out)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text)
    print(text, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
