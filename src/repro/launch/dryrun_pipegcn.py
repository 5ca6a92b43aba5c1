"""Production-mesh dry-run for the PipeGCN core itself.

The graph is partitioned one-partition-per-chip: the 16×16 pod mesh flattens
to 256 partitions (the multi-pod mesh to 512), shard_map'ed over
("data","model") (+"pod"). Topology arrays are ShapeDtypeStructs sized from
the paper's largest setting (ogbn-papers100M scale per Tab. 3: 111M nodes /
3-layer / 48 hidden / feat 128), so this proves the production sharding +
collective program of the paper's own workload compiles.

Run: python -m repro.launch.dryrun_pipegcn [--multi-pod] [--variant pipegcn-gf]
"""
import os
os.environ["XLA_FLAGS"] = (os.environ.get("REPRO_XLA_EXTRA", "") +
                           " --xla_force_host_platform_device_count=512").strip()

import argparse
import json

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as PS

from repro.core.config import ModelConfig, PipeConfig
from repro.core.pipegcn import PipeGCN, ShardedData, Topology
from repro.launch.dryrun import collective_bytes
from repro.launch.mesh import (HBM_BW, ICI_BW, PEAK_FLOPS_BF16,
                               make_production_mesh)

# papers100M-scale per-partition sizing (111M nodes / 256 parts ≈ 434K inner;
# halo slots sized from METIS-like cut ratios at 0.4% per peer pair).
PROD = dict(max_inner=434_176, slot=2_048, max_nnz=6_553_600,
            feat_dim=128, hidden=48, num_layers=3, num_classes=172)
# Reddit-scale variant (Tab. 3 row 1) for the 2-pod mesh: smaller graph.
SMALL = dict(max_inner=1_024, slot=256, max_nnz=524_288,
             feat_dim=602, hidden=256, num_layers=4, num_classes=41)


def synthetic_topology_sds(mesh, sizes) -> tuple:
    n = int(np.prod(list(mesh.shape.values())))
    axes = tuple(mesh.axis_names)
    part = PS(axes)

    def sds(shape, dtype, spec):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))

    mi, sl, nz = sizes["max_inner"], sizes["slot"], sizes["max_nnz"]
    topo = Topology(
        edge_row=sds((n, nz), jnp.int32, part),
        edge_col=sds((n, nz), jnp.int32, part),
        edge_w=sds((n, nz), jnp.float32, part),
        send_idx=sds((n, n, sl), jnp.int32, part),
        send_mask=sds((n, n, sl), jnp.bool_, part),
        inner_mask=sds((n, mi), jnp.bool_, part))
    data = ShardedData(
        x=sds((n, mi, sizes["feat_dim"]), jnp.float32, part),
        labels=sds((n, mi), jnp.int32, part),
        train_mask=sds((n, mi), jnp.bool_, part),
        eval_mask=sds((n, mi), jnp.bool_, part))
    return topo, data


def dryrun_pipegcn(multi_pod: bool, variant: str = "pipegcn",
                   sizes=None, compress: bool = False,
                   fuse: bool = True, overlap: str = "auto") -> dict:
    import dataclasses
    mesh = make_production_mesh(multi_pod=multi_pod)
    sizes = sizes or (SMALL if multi_pod else PROD)
    axes = tuple(mesh.axis_names)
    n = int(np.prod(list(mesh.shape.values())))

    topo_sds, data_sds = synthetic_topology_sds(mesh, sizes)
    mc = ModelConfig(kind="sage", feat_dim=sizes["feat_dim"],
                     hidden=sizes["hidden"], num_layers=sizes["num_layers"],
                     num_classes=sizes["num_classes"], dropout=0.0)
    pc = dataclasses.replace(PipeConfig.named(variant),
                             compress_boundary=compress,
                             fuse_exchange=fuse, overlap=overlap)
    split = None
    if overlap == "split-phase":
        # Synthetic split spec mirroring what split_spec_from derives from a
        # real rcm-layout graph: the boundary tail is the last row block,
        # the transpose cut sits at the last full inner block. The COO
        # engine's phased path only reads the row/col cuts, so the tile
        # counts are placeholders here.
        from repro.kernels.gcn_spmm import TILE, SplitSpec
        mi = sizes["max_inner"]
        hb0 = mi // TILE
        split = SplitSpec(row_tail=max(hb0 - 1, 1) * TILE,
                          col_tail=hb0 * TILE,
                          fwd_bnd_tiles=1, t_bnd_tiles=1)
    model = PipeGCN(mc, pc, split=split)
    params_sds = jax.eval_shape(
        lambda: model.init_params(jax.random.PRNGKey(0)))
    params_sds = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=NamedSharding(mesh, PS())), params_sds)
    bufs_sds = jax.eval_shape(
        lambda: model.init_buffers(topo_sds, leading=True))
    bufs_sds = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=NamedSharding(mesh, PS(axes))),
        bufs_sds)
    key_sds = jax.ShapeDtypeStruct((2,), jnp.uint32,
                                   sharding=NamedSharding(mesh, PS()))

    step = model.make_spmd_step(mesh, topo_sds, axis_name=axes)
    # step is jitted; lower with SDS args
    lowered = step.lower(tuple(topo_sds), params_sds, bufs_sds,
                         tuple(data_sds), key_sds)
    compiled = lowered.compile()

    result = {"arch": f"pipegcn-{variant}", "multi_pod": multi_pod,
              "compress": compress, "fuse_exchange": pc.fuse_exchange,
              "chips": n, "sizes": sizes}
    # per-step boundary-collective count: jaxpr-traced (schedule truth) +
    # the analytic 2 (fused) vs 2L-1 (per-layer) expectation
    from repro.core.trace_utils import (collective_counts,
                                        expected_boundary_collectives)
    counts = collective_counts(step, topo_sds, params_sds, bufs_sds,
                               data_sds, key_sds)
    result["boundary_collectives_per_step"] = counts["all_to_all"]
    result["boundary_collectives_expected"] = expected_boundary_collectives(
        mc.num_layers, pc.fused, train=True)
    # traced overlap schedule: phase sizes + where the collectives sit in
    # the (aggregation scatter | exchange) event stream. The split only
    # repositions collectives — counts above must be unchanged either way.
    result["overlap"] = pc.overlap
    if model._split_active() is not None:
        from repro.core.trace_utils import traced_step_events
        mi = sizes["max_inner"]
        result["overlap_phase_rows"] = {
            "row_tail": split.row_tail,
            "fwd_boundary_rows": mi - split.row_tail,
            "fwd_interior_rows": split.row_tail,
            "col_tail": split.col_tail,
            "t_boundary_rows": mi - split.col_tail + n * sizes["slot"],
        }
        # COO engine: each phase is one segment_sum (a scatter-add eqn), so
        # an all_to_all between two scatter-adds was issued mid-layer.
        result["overlap_events"] = traced_step_events(
            step, topo_sds, params_sds, bufs_sds, data_sds, key_sds,
            names=("scatter-add", "all_to_all"))
    mem = compiled.memory_analysis()
    if mem is not None:
        result["bytes_per_device"] = int(
            getattr(mem, "argument_size_in_bytes", 0)
            + getattr(mem, "temp_size_in_bytes", 0))
    cost = compiled.cost_analysis()
    if cost:
        result["flops_per_device"] = float(cost.get("flops", 0.0))
        result["bytes_accessed_per_device"] = float(
            cost.get("bytes accessed", 0.0))
    coll = collective_bytes(compiled.as_text())
    coll.pop("f32_activation_bytes", None)
    result["collective_bytes_per_device"] = coll
    result["collective_total_bytes"] = int(sum(coll.values()))
    # intended wire bytes of the boundary exchanges (the CPU backend promotes
    # bf16 collectives to f32, hiding compression in the HLO measurement)
    dims = [sizes["feat_dim"]] + [sizes["hidden"]] * (sizes["num_layers"] - 1)
    slots = n * sizes["slot"]
    fwd_w = sum(dims)
    bwd_w = sum(dims[1:])
    dtype_bytes = 2 if compress else 4
    result["boundary_wire_bytes"] = int(slots * (fwd_w + bwd_w) * dtype_bytes)
    result["t_collective_wire"] = (
        result["boundary_wire_bytes"]
        + coll.get("all-reduce", 0)) / ICI_BW
    result["t_compute"] = result.get("flops_per_device", 0) / PEAK_FLOPS_BF16
    result["t_memory"] = result.get("bytes_accessed_per_device", 0) / HBM_BW
    result["t_collective"] = result["collective_total_bytes"] / ICI_BW
    terms = {k: result[f"t_{k}"] for k in ("compute", "memory", "collective")}
    result["bottleneck"] = max(terms, key=terms.get)
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--variant", default="pipegcn")
    ap.add_argument("--compress", action="store_true")
    ap.add_argument("--no-fuse", action="store_true",
                    help="per-layer blocking exchange (2L-1 collectives) "
                         "instead of the fused-deferred schedule (2)")
    ap.add_argument("--both", action="store_true",
                    help="also run the vanilla baseline for comparison")
    ap.add_argument("--overlap", default="auto",
                    choices=["auto", "none", "split-phase"],
                    help="split-phase overlap schedule: boundary phase, "
                         "issue exchange, interior phase behind it (the "
                         "dry-run synthesizes the split spec and reports "
                         "the traced phase sizes + collective positions)")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    variants = [args.variant] + (["vanilla"] if args.both else [])
    results = []
    for v in variants:
        r = dryrun_pipegcn(args.multi_pod, v, compress=args.compress,
                           fuse=not args.no_fuse, overlap=args.overlap)
        results.append(r)
        print(f"[pipegcn dryrun OK] variant={v} chips={r['chips']} "
              f"bottleneck={r['bottleneck']} "
              f"boundary_colls={r['boundary_collectives_per_step']} "
              f"overlap={r['overlap']} "
              f"coll={r['collective_total_bytes']:,}B", flush=True)
        if "overlap_events" in r:
            print(f"  overlap schedule: phases {r['overlap_phase_rows']} "
                  f"events {' '.join('A' if e == 'all_to_all' else 'S' for e in r['overlap_events'])}",
                  flush=True)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        json.dump(results, open(args.out, "w"), indent=1)
        print("wrote", args.out)


if __name__ == "__main__":
    main()
