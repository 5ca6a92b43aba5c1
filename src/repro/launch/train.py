"""Training launcher.

Two workload kinds behind one CLI:

  GCN full-graph training (the paper):
    python -m repro.launch.train --workload gcn --dataset reddit-sim \
        --partitions 4 --variant pipegcn-gf --epochs 300 \
        --agg blocksparse      # Pallas block-sparse aggregation engine

  Transformer LM training (assigned archs, reduced or full config):
    python -m repro.launch.train --workload lm --arch qwen3-8b --reduced \
        --steps 50 --batch 8 --seq 128
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp

from repro.checkpoint import save_checkpoint
from repro.core import ModelConfig, PipeConfig, train_pipegcn
from repro.data import GraphDataPipeline, TokenStream
from repro.graph.synthetic import model_template
from repro.models.model import LM
from repro.optim import adamw, linear_warmup_cosine


def run_gcn(args) -> dict:
    pipeline = GraphDataPipeline.build(args.dataset, args.partitions,
                                       kind=args.gcn_kind, seed=args.seed,
                                       agg=args.agg, layout=args.layout)
    mesh = None
    if args.spmd:
        # Partition count is a convergence knob, device count a hardware
        # fact: the mesh is sized partitions // parts_per_device and each
        # device hosts parts_per_device co-resident partitions.
        from repro.launch.mesh import make_partition_mesh
        mesh = make_partition_mesh(args.partitions, args.parts_per_device)
    tpl = model_template(args.dataset)
    mc = ModelConfig(kind=args.gcn_kind, feat_dim=pipeline.dataset.feat_dim,
                     hidden=args.hidden or tpl["hidden"],
                     num_layers=args.layers or tpl["num_layers"],
                     num_classes=pipeline.dataset.num_classes,
                     dropout=tpl["dropout"],
                     multilabel=pipeline.dataset.multilabel,
                     agg=args.agg, matmul_order=args.matmul_order,
                     layout=pipeline.layout)
    import dataclasses
    pc = dataclasses.replace(PipeConfig.named(args.variant, gamma=args.gamma),
                             fuse_exchange=not args.no_fuse_exchange,
                             overlap=args.overlap, wire=args.wire,
                             slice_boundary=args.slice_boundary,
                             guard_exchange=args.guard_exchange,
                             max_staleness=args.max_staleness)
    faults = None
    if args.fault_rate > 0.0:
        from repro.core import FaultPlan
        faults = FaultPlan(rate=args.fault_rate, rate_kind=args.fault_kind,
                           seed=args.fault_seed)
    health = None
    if args.no_health:
        from repro.core import HealthConfig
        health = HealthConfig(enabled=False)
    elastic = None
    if args.elastic:
        from repro.core import ElasticConfig
        elastic = ElasticConfig(detect_after=args.elastic_detect_after,
                                warm_staleness=args.elastic_warm,
                                max_recoveries=args.elastic_max_recoveries,
                                rejoin=not args.elastic_no_rejoin,
                                parts_per_device=args.parts_per_device)
    res = train_pipegcn(pipeline, mc, pc, epochs=args.epochs,
                        lr=args.lr or tpl["lr"], seed=args.seed,
                        eval_every=args.eval_every, log=print, mesh=mesh,
                        health=health, faults=faults,
                        ckpt_dir=args.ckpt_dir,
                        checkpoint_every=args.ckpt_every,
                        resume=args.resume,
                        checkpoint_keep=args.ckpt_keep or None,
                        elastic=elastic)
    out = {"workload": "gcn", "dataset": args.dataset,
           "partitions": args.partitions, "variant": args.variant,
           "spmd": bool(args.spmd),
           "parts_per_device": args.parts_per_device,
           "agg": args.agg,
           "matmul_order": args.matmul_order,
           "layout": pipeline.layout,
           "fuse_exchange": pc.fuse_exchange,
           "overlap": pc.overlap,
           "wire": pc.wire,
           "slice_boundary": pc.slice_boundary,
           "guard_exchange": pc.guard_exchange,
           "fault_rate": args.fault_rate,
           "split_feasible": pipeline.split_spec() is not None,
           "elastic": bool(args.elastic),
           "anomalies": res.anomalies,
           "resumed_from": res.resumed_from,
           "recoveries": res.recoveries,
           "preempted": res.preempted,
           "final": res.final_metrics, "epochs_per_sec": res.epochs_per_sec,
           "history": res.history}
    if args.ckpt_dir and not args.ckpt_every:
        # legacy params-only export; with --ckpt-every the trainer already
        # wrote full-state step dirs into the same directory
        save_checkpoint(args.ckpt_dir, args.epochs, res.params)
    print(json.dumps({k: out[k] for k in
                      ("final", "epochs_per_sec")}, indent=1))
    return out


def run_lm(args) -> dict:
    from repro.configs import get_arch
    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    lm = LM(cfg)
    params = lm.init_params(jax.random.PRNGKey(args.seed))
    opt = adamw(linear_warmup_cosine(args.lr or 3e-4, 10, args.steps),
                max_grad_norm=1.0)
    opt_state = opt.init(params)

    def add_stubs(batch, b):
        batch = {k: jnp.asarray(v) for k, v in batch.items()}
        if cfg.is_encdec:
            batch["audio_embed"] = jnp.zeros(
                (b, cfg.num_audio_frames, cfg.d_model), lm.dtype)
        if cfg.num_image_tokens:
            batch["image_embed"] = jnp.zeros(
                (b, cfg.num_image_tokens, cfg.d_model), lm.dtype)
        return batch

    @jax.jit
    def step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(lm.loss_fn)(params, batch)
        params, opt_state = opt.apply(params, grads, opt_state)
        return loss, params, opt_state

    stream = iter(TokenStream(cfg.vocab_size, args.seq, args.batch,
                              seed=args.seed))
    losses = []
    t0 = time.perf_counter()
    for i in range(args.steps):
        batch = add_stubs(next(stream), args.batch)
        loss, params, opt_state = step(params, opt_state, batch)
        losses.append(float(loss))
        if i % max(args.steps // 10, 1) == 0:
            print(f"step {i:5d} loss {losses[-1]:.4f}", flush=True)
    dt = time.perf_counter() - t0
    out = {"workload": "lm", "arch": args.arch, "reduced": args.reduced,
           "first_loss": losses[0], "last_loss": losses[-1],
           "steps_per_sec": args.steps / dt}
    if args.ckpt_dir:
        save_checkpoint(args.ckpt_dir, args.steps, params)
    print(json.dumps(out, indent=1))
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", choices=["gcn", "lm"], default="gcn")
    # gcn
    ap.add_argument("--dataset", default="reddit-sim")
    ap.add_argument("--partitions", type=int, default=4)
    ap.add_argument("--variant", default="pipegcn",
                    help="vanilla|pipegcn|pipegcn-g|pipegcn-f|pipegcn-gf")
    ap.add_argument("--gcn-kind", default="sage", choices=["sage", "gcn"])
    ap.add_argument("--agg", default="coo",
                    choices=["coo", "blocksparse", "fused"],
                    help="aggregation engine for the Eq. 3/4 SpMM (fused = "
                         "blocksparse tiles + single-pass aggregate+"
                         "transform Pallas kernels)")
    ap.add_argument("--matmul-order", default="auto",
                    choices=["auto", "aggregate-first", "transform-first"],
                    help="layer contraction order for P·H·W: (P·H)·W costs "
                         "2·nnz·F_in, P·(H·W) costs 2·nnz·F_out; auto picks "
                         "per layer via the static FLOP model")
    ap.add_argument("--layout", default="auto",
                    choices=["auto", "natural", "rcm"],
                    help="intra-partition node layout: rcm = bandwidth-"
                         "reducing reorder + halo clustering (fewer "
                         "nonempty tiles for the tile engines, numerically "
                         "invisible); auto = rcm iff --agg uses tiles")
    ap.add_argument("--spmd", action="store_true",
                    help="run the step under shard_map on a device mesh "
                         "instead of the single-device sim backend")
    ap.add_argument("--parts-per-device", type=int, default=1,
                    help="co-resident partitions per device for --spmd "
                         "(partitions must be a multiple; mesh size = "
                         "partitions // parts_per_device)")
    ap.add_argument("--overlap", default="auto",
                    choices=["auto", "none", "split-phase"],
                    help="split-phase exchange/compute overlap: run the "
                         "boundary-tile phase first, issue the collective, "
                         "and compute the interior phase while it is in "
                         "flight; auto = on iff the layout clusters a "
                         "boundary tail and --agg consumes tiles")
    ap.add_argument("--no-fuse-exchange", action="store_true",
                    help="revert stale variants to the blocking per-layer "
                         "boundary exchange (2L-1 collectives/step instead "
                         "of the fused-deferred 2)")
    ap.add_argument("--wire", default="f32",
                    choices=["f32", "bf16", "int8", "int4", "auto"],
                    help="boundary wire format (default f32 = native "
                         "dtype): bf16 halves the exchanged bytes; "
                         "int8/int4 are blockwise-scaled quantization "
                         "(~4x/~8x smaller, per-128-column f32 scales ride "
                         "in the payload — see docs/wire-format.md); auto "
                         "picks bf16-vs-int8 per layer by wire bytes")
    ap.add_argument("--slice-boundary", action="store_true",
                    help="feature-dimension slicing: layers the cost model "
                         "runs transform-first ship the post-transform "
                         "width F_out <= F_in instead of F_in (default "
                         "off; incompatible with --overlap split-phase)")
    ap.add_argument("--guard-exchange", action="store_true",
                    help="per-row checksums on every boundary wire; rows "
                         "failing verification fall back to the stale "
                         "buffer (one extra step of staleness) instead of "
                         "landing garbage — see README 'Fault tolerance'")
    ap.add_argument("--max-staleness", type=int, default=8,
                    help="effective-staleness bound of the guarded "
                         "exchange; exceeding it aborts the run loudly")
    ap.add_argument("--fault-rate", type=float, default=0.0,
                    help="i.i.d. per-(step,layer,direction,pair) exchange "
                         "fault probability injected into the wires "
                         "(testing/chaos; combine with --guard-exchange)")
    ap.add_argument("--fault-kind", default="drop",
                    choices=["drop", "corrupt", "delay"],
                    help="background fault kind for --fault-rate")
    ap.add_argument("--fault-seed", type=int, default=0)
    ap.add_argument("--elastic", action="store_true",
                    help="arm the elastic runtime (requires "
                         "--guard-exchange and --ckpt-every): a device "
                         "whose every forward exchange falls back "
                         "--elastic-detect-after consecutive steps is "
                         "declared lost; the trainer restores the latest "
                         "checkpoint, remaps its partitions onto the "
                         "survivors, and resumes — see docs/architecture.md "
                         "'Elasticity'")
    ap.add_argument("--elastic-detect-after", type=int, default=2,
                    help="consecutive whole-device fallback steps before a "
                         "device is declared lost")
    ap.add_argument("--elastic-warm", type=int, default=1,
                    help="staleness count stamped on remapped exchanges at "
                         "recovery (must be < --elastic-detect-after)")
    ap.add_argument("--elastic-max-recoveries", type=int, default=2,
                    help="device-loss recovery budget before the loss is "
                         "re-raised as fatal")
    ap.add_argument("--elastic-no-rejoin", action="store_true",
                    help="stay on the survivor layout instead of scaling "
                         "back up at a checkpoint boundary once the lost "
                         "device is healthy")
    ap.add_argument("--no-health", action="store_true",
                    help="disable the numerical health guard (skip-and-"
                         "rollback of non-finite steps; on by default)")
    ap.add_argument("--gamma", type=float, default=0.95)
    ap.add_argument("--epochs", type=int, default=300)
    ap.add_argument("--eval-every", type=int, default=20)
    ap.add_argument("--hidden", type=int, default=0)
    ap.add_argument("--layers", type=int, default=0)
    # lm
    ap.add_argument("--arch", default="qwen3-8b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    # common
    ap.add_argument("--lr", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0,
                    help="checkpoint the FULL training state (params, "
                         "optimizer, pipeline buffers, PRNG key, epoch) "
                         "into --ckpt-dir every N epochs (atomic saves)")
    ap.add_argument("--ckpt-keep", type=int, default=0,
                    help="retain only the newest N committed checkpoints "
                         "in --ckpt-dir (0 = keep everything)")
    ap.add_argument("--resume", action="store_true",
                    help="resume bit-exactly from the latest checkpoint "
                         "in --ckpt-dir (gcn workload)")
    args = ap.parse_args()
    from repro.launch.mesh import configure_compile_cache
    configure_compile_cache()
    if args.workload == "gcn":
        run_gcn(args)
    else:
        run_lm(args)


if __name__ == "__main__":
    main()
