"""Paper Fig. 3 + Tab. 4 (throughput columns) — PipeGCN speedup over vanilla
partition-parallel training.

Three views:
  (a) schedule-analytic speedup on the paper's hardware model (measured
      boundary bytes + FLOPs of the real shards) — expect the paper's
      1.7×–2.2× band where comm ratio is 60–85 %;
  (b) measured epochs/s of the actual jitted JAX step on this CPU (no real
      interconnect, so (b) validates step cost parity, not overlap);
  (c) COO vs block-sparse vs FUSED aggregation engine step time on the
      SAME partitioned graph (the topology carries both the COO shards and
      the tile streams, so only ``ModelConfig.agg`` changes). On CPU the
      Pallas kernels run in interpret mode, so (c) is an engine-dispatch/
      parity check, not an MXU speedup measurement — but the fused-vs-
      unfused pair is gated at 1.1× so a fused path that added real work
      fails the bench job.
  (c') matmul-ordering sweep (aggregate-first / transform-first / auto) on
      the fused engine, with the analytic per-layer FLOP totals from
      repro.analysis.cost in the derived column.
  (c'') natural vs rcm node layout under the tile engines on the same
      partitioning — the reordered tile stream is shorter, so the step is
      gated to be no slower (interleaved min-of-ratios, <=1.1x), mirroring
      the PR-4 fused-engine gate.
  (d) SPMD step time vs partitions-per-device (n_local) at fixed P=8 on
      forced host devices — the decoupled partition/device axis; on real
      hardware this is the knob that trades per-device memory for
      interconnect fan-out.
  (e) fused-deferred vs blocking per-layer boundary exchange (2 vs 2L-1
      collectives per step) on the same graph/model — the fused schedule
      must be no slower; on real interconnects fewer, larger messages off
      the critical path is where the win compounds.
  (f) split-phase overlap vs unsplit schedule on a planar lattice (the
      low-boundary regime where the split has a real interior phase):
      identical tile work re-sliced into boundary-first + interior-behind-
      the-collective, gated at <= 1.0x the unsplit step (interleaved
      min-of-ratios). The CPU sim can't show the latency hiding — the
      gate proves the re-slicing itself costs nothing.
"""
from __future__ import annotations

import dataclasses
import time

import jax

from benchmarks.common import PAPER_GPU, emit, epoch_model
from repro.core import ModelConfig, PipeConfig
from repro.core.pipegcn import PipeGCN
from repro.core.trainer import make_jitted_train_step
from repro.data import GraphDataPipeline
from repro.graph.synthetic import model_template
from repro.optim import adam

CASES = [("reddit-sim", 2), ("reddit-sim", 4),
         ("products-sim", 5), ("products-sim", 10),
         ("yelp-sim", 3), ("yelp-sim", 6)]


def _measure_step(pipeline, mc, variant: str, iters: int,
                  pipe_kw: dict | None = None, split=None) -> float:
    model = PipeGCN(mc, dataclasses.replace(PipeConfig.named(variant),
                                            **(pipe_kw or {})), split=split)
    opt = adam(1e-2)
    params = model.init_params(jax.random.PRNGKey(0))
    bufs = model.init_buffers(pipeline.topo)
    state = opt.init(params)
    step = make_jitted_train_step(model, opt)
    key = jax.random.PRNGKey(1)
    # warmup (buffers are donated: thread them through)
    loss, params, state, bufs = step(pipeline.topo, params, state,
                                     bufs, pipeline.train_data, key)
    jax.block_until_ready(loss)
    t0 = time.perf_counter()
    for _ in range(iters):
        loss, params, state, bufs = step(pipeline.topo, params, state,
                                         bufs, pipeline.train_data, key)
    jax.block_until_ready(loss)
    return (time.perf_counter() - t0) / iters


def run_engine_comparison(quick: bool = False):
    """(c): one partitioned graph, three aggregation engines. The
    fused-vs-unfused record pair (`fused` vs `blocksparse` — identical tile
    streams, the only delta is whether the dense weight contracts inside
    the Pallas grid pass) is GATED: on CPU-interpret both execute the same
    math, so fused must stay ≤ 1.1× the unfused step time (parity guard —
    the interpreter can't show the MXU/HBM win, but it does catch a fused
    path that added real work). 4 partitions even in quick mode: at p2 the
    per-pallas_call dispatch constants dominate the ms-scale step and the
    ratio measures overhead, not work."""
    name, parts = ("tiny", 4) if quick else ("small", 4)
    pipeline = GraphDataPipeline.build(name, parts, kind="sage",
                                       agg="blocksparse")
    tpl = model_template(name)
    mc = ModelConfig(kind="sage", feat_dim=pipeline.dataset.feat_dim,
                     hidden=tpl["hidden"], num_layers=tpl["num_layers"],
                     num_classes=pipeline.dataset.num_classes, dropout=0.0)
    out = {}
    # step times are a few ms; compile dominates, so generous iters are
    # cheap and keep the fused/unfused ratio out of timer noise.
    iters = 12 if quick else 10
    out["coo"] = _measure_step(pipeline, dataclasses.replace(mc, agg="coo"),
                               "pipegcn", iters=iters)
    emit(f"fig3/engine_step/{name}/p{parts}/coo", out["coo"] * 1e6,
         f"epochs_per_s={1.0 / out['coo']:.2f}")
    # The gated pair is measured INTERLEAVED (unfused, fused) per round and
    # the gate takes the min per-round ratio: machine-state drift across a
    # long bench run (cache/thermal/CI-neighbor noise) hits both sides of a
    # round roughly equally and cancels, where a sequential min-of-times
    # still failed spuriously when the fused rounds simply ran later.
    ratios = []
    for _ in range(3 if quick else 2):
        t_un = _measure_step(pipeline,
                             dataclasses.replace(mc, agg="blocksparse"),
                             "pipegcn", iters=iters)
        t_fz = _measure_step(pipeline, dataclasses.replace(mc, agg="fused"),
                             "pipegcn", iters=iters)
        out["blocksparse"] = min(out.get("blocksparse", t_un), t_un)
        out["fused"] = min(out.get("fused", t_fz), t_fz)
        ratios.append(t_fz / t_un)
    emit(f"fig3/engine_step/{name}/p{parts}/blocksparse",
         out["blocksparse"] * 1e6,
         f"epochs_per_s={1.0 / out['blocksparse']:.2f},"
         f"blocksparse_over_coo={out['blocksparse'] / out['coo']:.2f}x")
    ratio = min(ratios)
    emit(f"fig3/engine_step/{name}/p{parts}/fused", out["fused"] * 1e6,
         f"epochs_per_s={1.0 / out['fused']:.2f},"
         f"fused_over_unfused={ratio:.3f}x")
    assert ratio <= 1.1, (
        f"fused engine regressed: {ratio:.2f}x the unfused blocksparse "
        f"step time on CPU-interpret (per-round ratios {ratios})")
    return out


def run_layout_comparison(quick: bool = False):
    """(c''): natural vs rcm node layout on the SAME partitioning, stepped
    under the blocksparse and fused engines. The reorder shrinks the tile
    stream, so even CPU-interpret (which executes every grid step in
    Python) must get no slower — gated with the interleaved min-of-ratios
    discipline of the PR-4 engine gate (each round measures natural then
    rcm so machine drift cancels; rcm <= 1.1x natural)."""
    name, parts = ("tiny", 4) if quick else ("small", 4)
    tpl = model_template(name)
    pipes = {}
    for layout in ("natural", "rcm"):
        pipes[layout] = GraphDataPipeline.build(name, parts, kind="sage",
                                                agg="blocksparse",
                                                layout=layout)
    mc0 = ModelConfig(kind="sage",
                      feat_dim=pipes["natural"].dataset.feat_dim,
                      hidden=tpl["hidden"], num_layers=tpl["num_layers"],
                      num_classes=pipes["natural"].dataset.num_classes,
                      dropout=0.0)
    iters = 10 if quick else 8
    out = {}
    for agg in ("blocksparse", "fused"):
        mcs = {lay: dataclasses.replace(mc0, agg=agg, layout=lay)
               for lay in pipes}
        ratios, best = [], {}
        for _ in range(3 if quick else 2):
            t_nat = _measure_step(pipes["natural"], mcs["natural"],
                                  "pipegcn", iters=iters)
            t_rcm = _measure_step(pipes["rcm"], mcs["rcm"], "pipegcn",
                                  iters=iters)
            best["natural"] = min(best.get("natural", t_nat), t_nat)
            best["rcm"] = min(best.get("rcm", t_rcm), t_rcm)
            ratios.append(t_rcm / t_nat)
        ratio = min(ratios)
        n_nat = pipes["natural"].topo.tile_rows.shape[-1]
        n_rcm = pipes["rcm"].topo.tile_rows.shape[-1]
        emit(f"fig3/layout_step/{name}/p{parts}/{agg}/rcm",
             best["rcm"] * 1e6,
             f"natural_us={best['natural'] * 1e6:.0f},"
             f"rcm_over_natural={ratio:.3f}x,"
             f"tile_stream={n_nat}->{n_rcm}")
        out[agg] = ratio
        assert ratio <= 1.1, (
            f"rcm layout regressed the {agg} step: {ratio:.2f}x the "
            f"natural-layout step time on CPU-interpret "
            f"(per-round ratios {ratios})")
    return out


def run_order_comparison(quick: bool = False):
    """Matmul-ordering sweep: the same graph/model stepped under
    aggregate-first, transform-first, and the cost-model "auto" choice
    (which may mix per layer). CPU step times are reported for the
    trajectory; the real signal is the analytic FLOP ratio in `derived`
    (from repro.analysis.cost), which is hardware-independent."""
    from repro.analysis.cost import gcn_order_report
    name, parts = ("tiny", 2) if quick else ("small", 4)
    pipeline = GraphDataPipeline.build(name, parts, kind="sage",
                                       agg="fused")
    tpl = model_template(name)
    mc = ModelConfig(kind="sage", feat_dim=pipeline.dataset.feat_dim,
                     hidden=tpl["hidden"], num_layers=tpl["num_layers"],
                     num_classes=pipeline.dataset.num_classes, dropout=0.0,
                     agg="fused")
    topo = pipeline.topo
    n_tiles = topo.tile_rows.shape[-1]
    combined = topo.max_inner + topo.halo_size
    from repro.kernels.gcn_spmm import TILE
    nnz_eff = n_tiles * TILE * TILE
    report = gcn_order_report(mc.layer_dims(), topo.max_inner, combined,
                              nnz_eff, train=True, fused=True)
    flops = {o: sum(r["costs"][o].flops for r in report)
             for o in ("aggregate-first", "transform-first")}
    auto_flops = sum(r["costs"][r["chosen"]].flops for r in report)
    out = {}
    for order in ("aggregate-first", "transform-first", "auto"):
        t = _measure_step(pipeline,
                          dataclasses.replace(mc, matmul_order=order),
                          "pipegcn", iters=4 if quick else 6)
        out[order] = t
        model_flops = auto_flops if order == "auto" else flops[order]
        emit(f"fig3/order_step/{name}/p{parts}/{order}", t * 1e6,
             f"epochs_per_s={1.0 / t:.2f},"
             f"model_flops_per_part={model_flops:.3e}")
    # the cost model's choice can never be worse than either fixed order
    assert auto_flops <= min(flops.values()) + 1e-6
    return out


def run_fuse_comparison(quick: bool = False):
    """Fused-deferred vs blocking per-layer exchange on the same graph and
    model: 2 vs 2L-1 boundary collectives per step. Acceptance: the fused
    schedule's step time is no worse than per-layer (the packed collective
    moves identical bytes in fewer, larger messages and sits off the
    critical path)."""
    name, parts = ("tiny", 2) if quick else ("small", 4)
    pipeline = GraphDataPipeline.build(name, parts, kind="sage")
    tpl = model_template(name)
    mc = ModelConfig(kind="sage", feat_dim=pipeline.dataset.feat_dim,
                     hidden=tpl["hidden"], num_layers=tpl["num_layers"],
                     num_classes=pipeline.dataset.num_classes, dropout=0.0)
    out = {}
    # step time is a few ms; compile dominates, so generous iters are cheap
    # and keep the fused/perlayer ratio out of timer noise
    iters = 10 if quick else 20
    for fuse in (False, True):
        sched = "fused" if fuse else "perlayer"
        t = _measure_step(pipeline, mc, "pipegcn", iters,
                          pipe_kw={"fuse_exchange": fuse})
        out[sched] = t
        detail = f"epochs_per_s={1.0 / t:.2f}"
        if fuse:
            detail += f",fused_over_perlayer={t / out['perlayer']:.3f}x"
        emit(f"fig3/fuse_step/{name}/p{parts}/{sched}", t * 1e6, detail)
    # Gate, not just report: the bound is loose (1.5x) to stay clear of
    # CPU timer noise — the two schedules measure within a few percent —
    # while still failing the bench job on a real fused-path regression.
    ratio = out["fused"] / out["perlayer"]
    assert ratio < 1.5, (
        f"fused schedule regressed: {ratio:.2f}x the per-layer step time")
    return out


def run_overlap_comparison(quick: bool = False):
    """(f): split-phase vs unsplit schedule, same graph/model/engine. The
    lattice datasets are the only ones where the rcm layout clusters a
    boundary tail small enough for a feasible split (the power-law sims
    are 96-100% boundary, so the split degenerates there and falls back).
    The split executes the SAME tiles — a static suffix/prefix re-slicing
    of one stream into two pallas_calls with the exchange issued between
    them — so even CPU-interpret must not get slower: gated at <= 1.0x
    with the interleaved min-of-ratios discipline (each round measures
    unsplit then split so machine drift cancels; min per-round ratio)."""
    from benchmarks.common import emit_meta
    name, parts = ("grid-tiny", 4) if quick else ("grid-sim", 4)
    pipeline = GraphDataPipeline.build(name, parts, kind="sage",
                                       agg="blocksparse", layout="rcm")
    sp = pipeline.split_spec()
    assert sp is not None, f"{name} must admit a feasible split under rcm"
    tpl = model_template(name)
    mc = ModelConfig(kind="sage", feat_dim=pipeline.dataset.feat_dim,
                     hidden=tpl["hidden"], num_layers=tpl["num_layers"],
                     num_classes=pipeline.dataset.num_classes, dropout=0.0,
                     agg="blocksparse", layout="rcm")
    iters = 10 if quick else 8
    ratios, best = [], {}
    for _ in range(4 if quick else 3):
        t_un = _measure_step(pipeline, mc, "pipegcn", iters,
                             pipe_kw={"overlap": "none"})
        t_sp = _measure_step(pipeline, mc, "pipegcn", iters,
                             pipe_kw={"overlap": "split-phase"}, split=sp)
        best["unsplit"] = min(best.get("unsplit", t_un), t_un)
        best["split"] = min(best.get("split", t_sp), t_sp)
        ratios.append(t_sp / t_un)
    ratio = min(ratios)
    n_tiles = pipeline.topo.tile_rows.shape[-1]
    emit(f"fig3/overlap_step/{name}/p{parts}/split", best["split"] * 1e6,
         f"unsplit_us={best['unsplit'] * 1e6:.0f},"
         f"split_over_unsplit={ratio:.3f}x,"
         f"bnd_tiles={sp.fwd_bnd_tiles}/{n_tiles}")
    emit_meta("overlap_split", {f"{name}/p{parts}": {
        "fwd_bnd_tiles": sp.fwd_bnd_tiles, "t_bnd_tiles": sp.t_bnd_tiles,
        "n_tiles": n_tiles, "row_tail": sp.row_tail,
        "col_tail": sp.col_tail}})
    assert ratio <= 1.0, (
        f"split-phase schedule regressed: {ratio:.3f}x the unsplit step "
        f"time on CPU-interpret (per-round ratios {ratios}) — the split "
        f"re-slices the identical tile stream, so any slowdown is real "
        f"added work, not hidden latency")
    return ratio


_LOCAL_SWEEP_SCRIPT = """
import os, sys, time
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
from repro.core import ModelConfig, PipeConfig
from repro.core.pipegcn import PipeGCN
from repro.data import GraphDataPipeline
from repro.launch.mesh import make_partition_mesh

name, iters = sys.argv[1], int(sys.argv[2])
n_locals = [int(x) for x in sys.argv[3].split(",")]
P = 8
pipeline = GraphDataPipeline.build(name, P, kind="sage")
mc = ModelConfig(kind="sage", feat_dim=pipeline.dataset.feat_dim, hidden=64,
                 num_layers=2, num_classes=pipeline.dataset.num_classes,
                 dropout=0.0)
model = PipeGCN(mc, PipeConfig.named("pipegcn"))
params = model.init_params(jax.random.PRNGKey(0))
key = jax.random.PRNGKey(1)
for nl in n_locals:
    mesh = make_partition_mesh(P, parts_per_device=nl)
    step = model.make_spmd_step(mesh, pipeline.topo, "parts")
    bufs = model.init_buffers(pipeline.topo)
    loss, _, _, bufs = step(pipeline.topo, params, bufs,
                            pipeline.train_data, key)   # warmup/compile
    jax.block_until_ready(loss)
    t0 = time.perf_counter()
    for _ in range(iters):
        loss, _, _, bufs = step(pipeline.topo, params, bufs,
                                pipeline.train_data, key)
    jax.block_until_ready(loss)
    dt = (time.perf_counter() - t0) / iters
    print(f"RESULT,{nl},{dt * 1e6:.2f}", flush=True)
"""


def run_local_sweep(quick: bool = False):
    """Step time vs partitions-per-device at fixed P=8: the same 8-partition
    graph on 8, 4, 2 (and 1) forced host devices. Needs its own process so
    the forced device count doesn't leak into the caller's jax runtime; the
    child is pinned to the CPU, so on an accelerator host it never competes
    with its parent for the chip."""
    import os
    import subprocess
    import sys

    name = "tiny" if quick else "small"
    n_locals = "1,2,4" if quick else "1,2,4,8"
    iters = 2 if quick else 4
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-c", _LOCAL_SWEEP_SCRIPT, name, str(iters),
         n_locals], env=env, capture_output=True, text=True, timeout=900)
    if proc.returncode != 0:
        raise RuntimeError(f"local sweep failed:\n{proc.stderr[-2000:]}")
    out = {}
    for line in proc.stdout.splitlines():
        if line.startswith("RESULT,"):
            _, nl, us = line.split(",")
            out[int(nl)] = float(us)
            emit(f"fig3/spmd_step_local/{name}/p8/nl{nl}", float(us),
                 f"n_dev={8 // int(nl)},step_per_s={1e6 / float(us):.2f}")
    return out


def run(quick: bool = False):
    cases = CASES[:2] if quick else CASES
    out = []
    for name, parts in cases:
        pipeline = GraphDataPipeline.build(name, parts, kind="sage")
        tpl = model_template(name)
        mc = ModelConfig(kind="sage", feat_dim=pipeline.dataset.feat_dim,
                         hidden=tpl["hidden"], num_layers=tpl["num_layers"],
                         num_classes=pipeline.dataset.num_classes,
                         dropout=0.0)
        m = epoch_model(pipeline.pg, mc, PAPER_GPU)
        emit(f"fig3/speedup_model/{name}/p{parts}", m.t_vanilla * 1e6,
             f"pipegcn_speedup={m.speedup:.2f}x,comm_ratio={m.comm_ratio:.2f}")

        # measured per-step wall time of both variants (cost parity on CPU)
        wall = {}
        for variant in ("vanilla", "pipegcn"):
            t = _measure_step(pipeline, mc, variant, iters=3 if quick else 5)
            wall[variant] = t
            emit(f"fig3/measured_step/{name}/p{parts}/{variant}", t * 1e6,
                 f"epochs_per_s={1.0 / t:.2f}")
        out.append((name, parts, m.speedup, wall))
    run_engine_comparison(quick=quick)
    run_layout_comparison(quick=quick)
    run_order_comparison(quick=quick)
    run_fuse_comparison(quick=quick)
    run_overlap_comparison(quick=quick)
    run_local_sweep(quick=quick)
    return out


if __name__ == "__main__":
    run()
