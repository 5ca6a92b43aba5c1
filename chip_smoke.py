#!/usr/bin/env python3
"""Smoke test of PipeGCN full-graph training on a TPU.

Trains the GraphSAGE model of the paper's Reddit setting (Tab. 3: 4 layers,
hidden 256, dropout 0.5) at Reddit's published widths — 602 input features,
41 classes — on the synthetic `reddit-sim` graph cut into 4 partitions,
through `train_pipegcn`, the entry point the training CLI calls. Weights are
random, made from a seed.

  python chip_smoke.py             one chip: the CLI default path (COO
                                   engine, vanilla and PipeGCN-GF), the
                                   Pallas tile engines (blocksparse, fused)
                                   against a COO reference, and the SPMD
                                   step with all 4 partitions on the chip
  python chip_smoke.py --chips 4   four chips, and nothing else: vanilla and
                                   PipeGCN on a 4-device mesh, one partition
                                   per chip, against the sim backend on one
                                   chip of the same process

Every run must give finite losses and no step skipped by the health guard;
the tile engines must agree with COO, and the mesh with the sim backend,
within RTOL; the tile engines' compiled step must hold the Mosaic kernels
(`tpu_custom_call`); on the mesh, topology, data and buffers must be split
over every chip. Each run prints its compile seconds, steady step time and
the device's peak memory so far: bring-up facts, not benchmark numbers.
Only when every check passed is the last line of stdout a JSON object
naming the device. Without a TPU the script exits non-zero before any work.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402

from repro.core import HealthConfig, ModelConfig, PipeConfig  # noqa: E402
from repro.core.pipegcn import PipeGCN  # noqa: E402
from repro.core.trainer import (make_jitted_train_step,  # noqa: E402
                                make_spmd_train_step, place_on_mesh,
                                train_pipegcn)
from repro.data import GraphDataPipeline  # noqa: E402
from repro.graph.synthetic import make_dataset, model_template  # noqa: E402
from repro.launch.mesh import (configure_compile_cache,  # noqa: E402
                               make_partition_mesh)
from repro.optim import adam  # noqa: E402

DATASET = "reddit-sim"
WIDTHS = dict(feat_dim=602, num_classes=41)   # Reddit's published widths
PARTS = 4
EPOCHS = 5
TIMED_STEPS = 5
SEED = 0
# XLA runs f32 matmuls on the TPU at DEFAULT precision: one MXU pass over
# operands rounded to bf16 (8 significant bits, a relative step of 2^-8)
# with f32 accumulation, while Mosaic's kernels round on their own. So the
# COO and tile engines, and the sim and mesh backends, differ by bf16
# roundings in every layer of every step. Four bf16 steps (2^-6, 1.6%)
# bound the relative loss gap after EPOCHS steps; a dropped tile or a
# misrouted block moves the loss by far more.
RTOL = 4 * 2.0 ** -8


def build(agg: str, dataset: str, widths: dict) -> GraphDataPipeline:
    """The partitioned graph as the CLI builds it for `--agg agg`: natural
    node layout for COO, rcm with tile streams for the tile engines."""
    return GraphDataPipeline.build(make_dataset(dataset, **widths), PARTS,
                                   kind="sage", agg=agg)


def model_config(pipeline, dataset: str, agg: str,
                 dropout: float | None = None) -> ModelConfig:
    """The dataset's Tab. 3 template, with the CLI's defaults."""
    tpl = model_template(dataset)
    ds = pipeline.dataset
    return ModelConfig(kind="sage", feat_dim=ds.feat_dim,
                       hidden=tpl["hidden"], num_layers=tpl["num_layers"],
                       num_classes=ds.num_classes,
                       dropout=tpl["dropout"] if dropout is None else dropout,
                       multilabel=ds.multilabel, agg=agg,
                       matmul_order="auto", layout=pipeline.layout)


def peak_bytes() -> int | None:
    """Largest `peak_bytes_in_use` over the local devices, where reported
    (a process-lifetime peak: it covers every run so far)."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def split_over(tree) -> int:
    """Fewest devices any array of `tree` is split over (1 when an array
    sits on one device or is copied whole to every device)."""
    return min((1 if x.sharding.is_fully_replicated
                else len(x.sharding.device_set))
               for x in jax.tree.leaves(tree))


def run(name: str, pipeline, mc: ModelConfig, variant: str, lr: float,
        epochs: int, timed_steps: int, mesh=None, log=print) -> dict:
    """Compile and time the train step `train_pipegcn` builds for this
    setup, then train `epochs` epochs through `train_pipegcn` itself."""
    rec = {"name": name, "agg": mc.agg, "variant": variant,
           "backend": "sim" if mesh is None else f"spmd/{mesh.devices.size}"}
    try:
        pc = PipeConfig.named(variant)
        model = PipeGCN(mc, pc, split=pipeline.split_spec())
        opt = adam(lr)
        health = HealthConfig()
        params = model.init_params(jax.random.PRNGKey(SEED))
        opt_state = opt.init(params)
        topo, data = pipeline.topo, pipeline.train_data
        buffers = model.init_buffers(topo)
        if mesh is None:
            step = make_jitted_train_step(model, opt, health)
        else:
            step = make_spmd_train_step(model, opt, mesh, topo, health=health)
            (topo, data), buffers, (params, opt_state) = place_on_mesh(
                model, mesh, "parts", (topo, data), buffers,
                (params, opt_state))
            rec["split_over"] = {"topology": split_over(topo),
                                 "data": split_over(data),
                                 "buffers": split_over(buffers)}
        key = jax.random.PRNGKey(SEED + 1)
        t0 = time.perf_counter()
        compiled = step.lower(topo, params, opt_state, buffers, data,
                              key).compile()
        rec["compile_s"] = time.perf_counter() - t0
        rec["kernels"] = "tpu_custom_call" in compiled.as_text()
        out = compiled(topo, params, opt_state, buffers, data, key)
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range(timed_steps):
            out = compiled(topo, out[1], out[2], out[3], data, key)
        jax.block_until_ready(out)
        rec["step_ms"] = (time.perf_counter() - t0) / timed_steps * 1e3
        if mesh is not None:
            rec["split_over"]["buffers_after_steps"] = split_over(out[3])
        del out, compiled
        res = train_pipegcn(pipeline, mc, pc, epochs=epochs, lr=lr,
                            seed=SEED, eval_every=1, health=health,
                            mesh=mesh)
        rec["losses"] = [float(x) for x in res.history["loss"]]
        rec["val_acc"] = res.final_metrics["val"]
        rec["skipped_steps"] = res.anomalies["skipped_steps"]
        rec["peak_bytes"] = peak_bytes()
        log(f"run {name}: backend {rec['backend']}, compile "
            f"{rec['compile_s']:.3f} s, steady step {rec['step_ms']:.3f} ms "
            f"over {timed_steps} steps, peak device memory so far "
            f"{rec['peak_bytes']} B, tpu_custom_call {rec['kernels']}, "
            f"losses {rec['losses']}, val {rec['val_acc']:.4f}, skipped "
            f"steps {rec['skipped_steps']}"
            + (f", split over {rec['split_over']}" if mesh is not None
               else ""))
    except Exception as e:    # a failed run is reported; the others go on
        rec["error"] = f"{type(e).__name__}: {e}"
        log(f"run {name}: FAILED {rec['error']}")
    return rec


def one_chip_phase(dataset: str = DATASET, widths: dict = WIDTHS,
                   epochs: int = EPOCHS, timed_steps: int = TIMED_STEPS,
                   log=print):
    """The one-chip runs and the pairs whose losses must agree."""
    natural = build("coo", dataset, widths)
    tiles = build("fused", dataset, widths)
    on_one = make_partition_mesh(PARTS, parts_per_device=PARTS)
    gf = "pipegcn-gf"
    setups = [
        ("coo/vanilla", natural, "coo", "vanilla", None),
        ("coo/pipegcn-gf", natural, "coo", gf, None),
        ("coo-rcm/pipegcn-gf", tiles, "coo", gf, None),
        ("blocksparse/pipegcn-gf", tiles, "blocksparse", gf, None),
        ("fused/pipegcn-gf", tiles, "fused", gf, None),
        ("spmd-coo/pipegcn-gf", natural, "coo", gf, on_one),
        ("spmd-fused/pipegcn-gf", tiles, "fused", gf, on_one),
    ]
    lr = model_template(dataset)["lr"]
    recs = [run(name, p, model_config(p, dataset, agg), variant, lr, epochs,
                timed_steps, mesh=mesh, log=log)
            for name, p, agg, variant, mesh in setups]
    # same graph layout, seed and variant: COO is the reference
    pairs = [("blocksparse/pipegcn-gf", "coo-rcm/pipegcn-gf"),
             ("fused/pipegcn-gf", "coo-rcm/pipegcn-gf")]
    return recs, pairs


def mesh_phase(n_chips: int, dataset: str = DATASET, widths: dict = WIDTHS,
               epochs: int = EPOCHS, timed_steps: int = TIMED_STEPS,
               log=print):
    """Vanilla and PipeGCN on an `n_chips` mesh, PARTS // n_chips
    partitions per chip, each against the sim backend on one chip. Dropout
    is off here: the sim backend draws one mask over all partitions, the
    mesh one stream per partition, so only without dropout do the two
    compute the same losses."""
    if len(jax.devices()) < n_chips:
        raise SystemExit(f"the mesh phase needs {n_chips} devices, found "
                         f"{len(jax.devices())}")
    mesh = make_partition_mesh(PARTS, parts_per_device=PARTS // n_chips)
    lr = model_template(dataset)["lr"]
    recs, pairs = [], []
    for agg in ("coo", "fused"):
        p = build(agg, dataset, widths)
        mc = model_config(p, dataset, agg, dropout=0.0)
        for variant in ("vanilla", "pipegcn"):
            ref, got = f"sim-{agg}/{variant}", f"mesh-{agg}/{variant}"
            recs.append(run(ref, p, mc, variant, lr, epochs, timed_steps,
                            log=log))
            recs.append(run(got, p, mc, variant, lr, epochs, timed_steps,
                            mesh=mesh, log=log))
            pairs.append((got, ref))
    return recs, pairs


def rel_gap(got, ref) -> float:
    """Largest relative loss difference over the epochs."""
    return max(abs(a - b) / abs(b) for a, b in zip(got, ref))


def check(recs, pairs, on_tpu: bool, n_chips: int = 1, log=print):
    """Every failed check, as a message (empty when all passed)."""
    fails = []
    by = {r["name"]: r for r in recs}
    for r in recs:
        if "error" in r:
            fails.append(f"{r['name']}: {r['error']}")
            continue
        if not all(math.isfinite(x) for x in r["losses"]):
            fails.append(f"{r['name']}: non-finite loss {r['losses']}")
        if r["skipped_steps"]:
            fails.append(f"{r['name']}: {r['skipped_steps']} steps skipped "
                         "by the health guard")
        if on_tpu and r["agg"] != "coo" and not r["kernels"]:
            fails.append(f"{r['name']}: no tpu_custom_call in the compiled "
                         "step, so the Pallas kernels did not compile")
        if "split_over" in r and n_chips > 1:
            short = {k: v for k, v in r["split_over"].items() if v != n_chips}
            if short:
                fails.append(f"{r['name']}: not split over {n_chips} "
                             f"devices: {short}")
    for got, ref in pairs:
        if "error" in by[got] or "error" in by[ref]:
            continue
        gap = rel_gap(by[got]["losses"], by[ref]["losses"])
        log(f"agreement {got} vs {ref}: largest relative loss gap "
            f"{gap:.3e} (tolerance {RTOL:.3e})")
        if not gap <= RTOL:
            fails.append(f"{got} vs {ref}: relative loss gap {gap:.3e} > "
                         f"{RTOL:.3e}")
    return fails


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: the one-chip runs; 4: the mesh phase only")
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform!r}); the smoke "
              "runs on the chip only", file=sys.stderr)
        return 2
    cache = configure_compile_cache()
    print(f"device: {dev.device_kind} x{len(jax.devices())}, jax "
          f"{jax.__version__}, compile cache {cache}", flush=True)

    def log(msg):
        print(msg, flush=True)

    if args.chips == 1:
        recs, pairs = one_chip_phase(log=log)
    else:
        recs, pairs = mesh_phase(args.chips, log=log)
    fails = check(recs, pairs, on_tpu=True, n_chips=args.chips, log=log)
    for f in fails:
        print(f"FAIL {f}", file=sys.stderr)
    if fails:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
