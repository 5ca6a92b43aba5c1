#!/usr/bin/env python3
"""Trace one cell of the chip benchmark and report what the program's named
scopes and the trainer's host spans say of the window.

  python3 benchmarks/chip/trace_spans.py --workload <cell> --seed <n> \
      --seconds <s> [--out <file.json>]

Runs the cell as `run.py --trace 1` does, through `run.run_cell`, and keeps
two things that run reads and then discards: each compiled program's HLO
text, for the scope of each op (`chipbench.spans.scopes`), and the
profiler's `.xplane.pb`, for the trainer's host spans
(`chipbench.spans.read_host`). The last line of standard output is
`run.py`'s result object with a `spans` key added
(`chipbench.spans.summary`): what `exchange_ms`, `update_ms` and
`host_stall_ms` read, the time per epoch of each scope and of the idle
time under each span, the share of the train step's device time under a
scope, the share of idle time under a child span, the lag from a dispatch
to its step, and the idle gaps named by the span over them. `--out` writes
the traced window itself (`chipbench.spans.SpanTrace.to_json`) with that
result, for reading again without the chip.

Without a TPU, or with fewer chips than the cell asks for, it exits 2.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import run  # noqa: E402
from chipbench import hlo, spans, spec, trace as tracing  # noqa: E402


@contextlib.contextmanager
def keeping(kept: dict):
    """While open, `run.run_cell` leaves in `kept` the scopes of every
    program it reads (`scopes`), the traced window (`trace`) and the
    trainer's spans (`host`)."""
    facts, load = hlo.facts, tracing.load

    def facts_and_scopes(text):
        kept.setdefault("scopes", {})[hlo.module_name(text)] = \
            spans.scopes(text)
        return facts(text)

    def load_and_spans(trace_dir, *args, **kwargs):
        kept["trace"] = load(trace_dir, *args, **kwargs)
        kept["host"] = spans.read_host(tracing.find_xplane(trace_dir))
        return kept["trace"]

    hlo.facts, tracing.load = facts_and_scopes, load_and_spans
    try:
        yield kept
    finally:
        hlo.facts, tracing.load = facts, load


def traced(cell: spec.Cell, seed: int, seconds: float, t_start: float,
           out=print, **kwargs) -> tuple[dict, spans.SpanTrace]:
    """One traced run of `cell` (`run.run_cell`, whose keyword arguments
    pass through): its result with the `spans` summary, and the window."""
    with keeping({}) as kept:
        result = run.run_cell(cell, seed, seconds, True, t_start, out=out,
                              **kwargs)
    st = spans.SpanTrace(kept["trace"], kept.get("scopes", {}),
                         kept["host"])
    result["spans"] = spans.summary(st, cell.chips)
    return result, st


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    try:
        run.require_chips(cell.chips)
    except run.NoChip as e:
        print(f"trace_spans.py: {e}", file=sys.stderr)
        return 2
    import jax
    cache = run.configure_cache()
    # JAX keys its compile cache without the programs' metadata, so a
    # program that differs from a cached one only in its named scopes would
    # load that one, metadata and all: key this run's by the metadata too.
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)

    def out(msg):
        print(msg, flush=True)

    out(f"compile cache: {cache}")
    result, st = traced(cell, args.seed, args.seconds, T_START, out=out)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(dict(st.to_json(), result=result), f)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
