#!/usr/bin/env python3
"""Chip benchmark of full-graph PipeGCN training.

  python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
      --seconds <s> --trace <0|1>

Runs one cell of `BENCHMARK.json` on the chips of this machine: makes the
cell's graph from the seed, builds the training job as the training CLI
builds it, and runs the program's entry point `train_pipegcn` once, through
a warm-up and a window of `--seconds`. Then it checks the program's first
training steps against the plain reference (`chipbench.reference`) and
prints, as the last line of standard output, one JSON object: `correct`,
`attempted` and `failed` epochs, the metrics, and the device. With
`--trace 0` the metrics are the cell's end-to-end ones; with `--trace 1`
the window is traced and they are its per-layer ones, read from the device
trace by the readers under `metrics/`. Every number compared and its limit
are the last lines of standard error and the last key of the JSON line.

Without a TPU, or with fewer chips than the cell asks for, it prints no
result and exits 2.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(CHECKOUT, "src"))

from chipbench import compare, graph as graphgen, job, readers  # noqa: E402
from chipbench import hlo, reference, spec, trace as tracing, work  # noqa: E402

GIB = float(1 << 30)


class NoChip(RuntimeError):
    """The machine holds no TPU, or fewer chips than the cell asks for."""


def device_info(chips: int) -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": min(len(devs), chips)}


def require_chips(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devs[0].platform!r}; the benchmark "
                     "runs on the chip only")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")


def configure_cache() -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    or where `JAX_COMPILATION_CACHE_DIR` says; every program is cached, so
    only a checkout's first run of a cell compiles."""
    import jax
    path = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(CHECKOUT, ".jax_cache"))
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def peak_bytes(chips: int) -> tuple[int | None, dict]:
    """Peak device memory of the fullest chip, and each chip's readings.

    On the TPU, XLA holds a program's temporaries in space it reserves
    apart from the arrays: `peak_bytes_in_use` counts the arrays alone, and
    `peak_bytes_reserved` the reservation (for the COO step, its E x F
    gathers). A chip's peak is the sum of the two; a backend that reports
    no reservation adds nothing."""
    import jax
    per = {}
    for d in jax.local_devices()[:chips]:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            per[d.id] = {"in_use": int(stats["peak_bytes_in_use"]),
                         "reserved": int(stats.get("peak_bytes_reserved",
                                                   0))}
    peaks = [r["in_use"] + r["reserved"] for r in per.values()]
    return (max(peaks) if peaks else None), per


def breakdown(tr) -> dict:
    """The device operations that took most time (seconds per chip over the
    traced window) and the longest idle gaps of chip 0, each named by the
    operations around it."""
    per = {}
    for d in tr.devices:
        for o in tr.ops[d]:
            key = f"{o.module}/{o.name}"
            per[key] = per.get(key, 0.0) + o.dur_ns / 1e9
    n = max(len(tr.devices), 1)
    top = sorted(per.items(), key=lambda kv: -kv[1])[:10]
    gaps = sorted(tracing.gaps(tr, tr.devices[0]), key=lambda g: -g[0])[:10] \
        if tr.devices else []
    return {"device_ops": [[k, v / n] for k, v in top],
            "idle_gaps": [[f"after {a} before {b}", g / 1e9]
                          for g, a, b in gaps]}


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool,
             t_start: float, out=print, peaks: dict | None = None,
             wrap_step=None, patches=()) -> dict:
    """One run of `cell`; returns the result object. `peaks`, `wrap_step`
    and `patches` are for tests on the CPU and for the calibration, which
    plant faults; the benchmark's own runs leave them unset."""
    import jax
    config, traffic = cell.config, cell.traffic
    dev = device_info(cell.chips)
    if peaks is None:
        peaks = spec.peaks_for(dev["kind"])
    out(f"device: {dev['kind']} x{len(jax.devices())} ({dev['platform']}), "
        f"cell {cell.name} on {cell.chips} chip(s), jax {jax.__version__}")
    t = time.perf_counter()
    phases = {"start_s": t - t_start}
    g = graphgen.from_cell(config, traffic, seed)
    phases["graph_s"] = time.perf_counter() - t
    t = time.perf_counter()
    pipeline, mc, pc, mesh = job.build(config, traffic, g)
    phases["build_s"] = time.perf_counter() - t
    edges = work.propagation_edges(g, config["kind"])
    pg = pipeline.pg
    out(f"graph: {g.num_nodes} nodes, {g.num_edges} directed edges, "
        f"{pg.num_parts} partitions, max_inner {pg.max_inner}, slot "
        f"{pg.slot}, padded nnz {pg.max_nnz}, real halo rows "
        f"{int(pg.halo_owner_mask.sum())}, layout {pipeline.layout}")
    if pipeline.topo.tile_rows is not None:
        from repro.analysis.cost import graph_layout_report
        out(f"tiles: {graph_layout_report(pg)}")

    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    window = job.Window(
        seconds,
        on_open=(lambda: jax.profiler.start_trace(trace_dir)) if trace
        else None,
        on_close=jax.profiler.stop_trace if trace else None,
        log=lambda line: out(f"trainer: {line}"))
    try:
        res, rec = job.train(config, traffic, pipeline, mc, pc, mesh, seed,
                             window, wrap_step=wrap_step, patches=patches)
        if window.t_close is None:
            raise RuntimeError("the trainer returned before the window "
                               "closed")
        setup_s = window.t_open - t_start
        phases["train_until_window_s"] = (window.t_open - t_start
                                          - sum(phases.values()))
        epoch_s = window.elapsed / window.epochs
        flops = work.epoch_flops(config, g.num_nodes, edges)
        mem, mem_per_chip = peak_bytes(cell.chips)
        out(f"set-up {setup_s:.3f} s {phases}; window {window.elapsed:.6f} "
            f"s over {window.epochs} epochs (from epoch {window.first_epoch});"
            f" compiles inside the window {window.compiles_in_window}; "
            f"{flops:.6e} operations per epoch, orders "
            f"{work.orders(config)}; peak device memory {mem} B (peak bytes in "
            f"use and reserved, per chip: {mem_per_chip})")
        failed = int(res.anomalies.get("skipped_steps", 0))
        rec = rec.to_host()
        part = pg.part_of.copy()
        tr = None
        if trace:
            facts = {}
            for compiled in rec.compiled():
                text = compiled.as_text()
                facts[hlo.module_name(text)] = hlo.facts(text)
                ma = compiled.memory_analysis()
                out(f"program {hlo.module_name(text)}: arguments "
                    f"{ma.argument_size_in_bytes} B, outputs "
                    f"{ma.output_size_in_bytes} B, temporaries "
                    f"{ma.temp_size_in_bytes} B")
            tr = tracing.load(trace_dir, window.elapsed, window.epochs, facts)
            out(f"traced programs: {sorted(facts)}")
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    del res, pipeline, pg, mc, mesh
    gc.collect()

    e2e = {
        "epoch_ms": {"value": epoch_s * 1e3, "unit": "ms"},
        "mfu": {"value": 100.0 * flops / epoch_s
                / (cell.chips * peaks["flops_bf16"]), "unit": "%"},
        "peak_hbm_gib": {"value": (mem or 0) / GIB, "unit": "GiB"},
        "setup_s": {"value": setup_s, "unit": "s"},
    }
    device = dict(dev, memory_peak_bytes=mem)
    result = {"attempted": window.epochs, "failed": failed}
    if trace:
        ctx = readers.Context(trace=tr, config=config, traffic=traffic,
                              chips=cell.chips, nodes=g.num_nodes,
                              edges=edges, peaks=peaks)
        names = [m["name"] for m in cell.per_layer]
        values = readers.read_all(names, ctx)
        units = {m["name"]: m["unit"] for m in cell.per_layer}
        metrics = {k: {"value": v, "unit": units[k]}
                   for k, v in values.items()}
        devs = tr.devices
        device["busy_s"] = (sum(tracing.busy_ns(tr, d) for d in devs)
                            / max(len(devs), 1) / 1e9)
        device["window_s"] = tr.window_s
        result["breakdown"] = breakdown(tr)
    else:
        metrics = {m["name"]: e2e[m["name"]] for m in cell.end_to_end}

    # -- correctness: the reference follows the recorded steps -----------
    t = time.perf_counter()
    prob = reference.problem(g, config, traffic["variant"], part,
                             int(traffic["partitions"]))
    ref = reference.run(prob, seed, len(rec.losses))
    del prob
    read = compare.readings(rec.losses, compare.grads_from_adam(
        rec.opt_state_1), rec.params_in, rec.params_out, rec.buffers, ref)
    limits = dict(cell.limits)
    if "placement" in limits:
        read["placement"] = cell.chips - min(rec.placement.values())
    correct, checks = compare.verdict(read, limits)
    out(f"reference: {time.perf_counter() - t:.3f} s; program losses "
        f"{rec.losses}, reference losses {ref.losses}; worst leaves "
        f"{read['_where']}; placement {rec.placement}")
    head = {"correct": correct, "attempted": result.pop("attempted"),
            "failed": result.pop("failed"), "metrics": metrics,
            "device": device}
    return {**head, **result, "checks": checks}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    try:
        require_chips(cell.chips)
    except NoChip as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 2
    cache = configure_cache()

    def out(msg):
        print(msg, flush=True)

    out(f"compile cache: {cache}")
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      T_START, out=out)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
