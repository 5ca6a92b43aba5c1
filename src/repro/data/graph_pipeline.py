"""End-to-end graph data pipeline: dataset -> normalization -> partition ->
padded shards -> device arrays. One call site for every example/benchmark.

The `agg` knob mirrors ``ModelConfig.agg``: building with
``agg="blocksparse"`` or ``agg="fused"`` additionally extracts the
per-partition block-sparse tile streams onto the Topology, so any
aggregation engine can run on the same partitioned graph (the COO shards
are always present). ``agg="auto"``, the default, resolves here, once, to
a concrete engine (`repro.kernels.aggregate.choose_engine`): COO off the
TPU, and on it the tile engine where the partitions' tile slots are
estimated faster than their padded edge rows and the tiles fit."""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.pipegcn import ShardedData, Topology, shard_data, topology_from
from repro.graph.csr import mean_normalized, sym_normalized
from repro.graph.halo import (PartitionedGraph, build_partitioned_graph,
                              tile_slot_count)
from repro.graph.partition import partition_graph
from repro.graph.synthetic import GraphDataset, make_dataset
from repro.kernels.aggregate import AUTO_TILE_ENGINE, choose_engine


def to_local_layout(tree, n_local: int, axis: int = 0):
    """Reshape every (…, P, …) leading-partition array in a pytree to the
    physical per-device view (…, n_dev, n_local, …) used by the
    multi-partition-per-device SPMD path (device-major: partition p lives
    on device p // n_local). `axis` is the partition axis (0 for Topology /
    ShardedData arrays, 1 for k-step staleness buffer queues)."""

    def r(x):
        p = x.shape[axis]
        if p % n_local:
            raise ValueError(
                f"partition axis {axis} has size {p}, not a multiple of "
                f"n_local={n_local}")
        shape = x.shape[:axis] + (p // n_local, n_local) + x.shape[axis + 1:]
        return x.reshape(shape)

    return jax.tree.map(r, tree)


def from_local_layout(tree, axis: int = 0):
    """Inverse of `to_local_layout`: merge the (n_dev, n_local) pair at
    `axis` back into a flat partition axis."""

    def r(x):
        shape = (x.shape[:axis] + (x.shape[axis] * x.shape[axis + 1],)
                 + x.shape[axis + 2:])
        return x.reshape(shape)

    return jax.tree.map(r, tree)


def device_bytes_limit() -> int | None:
    """Memory of JAX's first device, where its backend reports one."""
    stats = jax.devices()[0].memory_stats()
    return stats.get("bytes_limit") if stats else None


def resolve_agg(prop, part, num_parts: int, layout: str = "auto",
                platform: str | None = None,
                bytes_limit: int | None = None):
    """Resolve `agg="auto"`: the `choose_engine` dict, and the partitioned
    graph whose tile slots and padded edge rows it counted, in the layout
    the tile engine would use (None off the TPU, where nothing is
    counted). `platform` defaults to JAX's backend and `bytes_limit` to
    the first device's memory; tests pass both."""
    from repro.graph.reorder import resolve_layout
    platform = platform or jax.default_backend()
    if platform != "tpu":
        return choose_engine(platform), None
    pg = build_partitioned_graph(
        prop, part, num_parts,
        layout=resolve_layout(layout, AUTO_TILE_ENGINE))
    if bytes_limit is None:
        bytes_limit = device_bytes_limit()
    return choose_engine(platform, parts=num_parts,
                         slots=tile_slot_count(pg),
                         edge_rows=num_parts * pg.max_nnz,
                         bytes_limit=bytes_limit), pg


@dataclasses.dataclass
class GraphDataPipeline:
    """Device-ready view of one partitioned graph dataset: the Topology,
    the three ShardedData splits (train/val/test share the packed
    feature/label arrays), and the build-time knobs that shaped them
    (`agg` engine, resolved node `layout`). Construct via `build`; eval
    metrics route back through `metric` (unpacks the node permutation)."""

    dataset: GraphDataset
    pg: PartitionedGraph
    topo: Topology
    train_data: ShardedData
    val_data: ShardedData
    test_data: ShardedData
    agg: str = "coo"               # resolved engine ("auto" never stored)
    layout: str = "natural"        # resolved node layout ("auto" never stored)
    # what `agg` was resolved from: "how" ("auto" | "forced"), and for
    # "auto" the platform, the reason and the counts behind the choice
    # (`repro.kernels.aggregate.choose_engine`)
    agg_choice: dict = dataclasses.field(
        default_factory=lambda: {"agg": "coo", "how": "forced"})

    @staticmethod
    def build(name_or_ds, num_parts: int, kind: str = "sage",
              seed: int = 0, partition_method: str = "bfs+refine",
              agg: str = "auto", layout: str = "auto") -> "GraphDataPipeline":
        """`agg` picks the aggregation engine ("auto" | "coo" |
        "blocksparse" | "fused"); "auto" (the default, matching
        ModelConfig.agg and the CLI) is resolved by `resolve_agg`: by
        JAX's backend, the tile slots and padded edge rows of the
        partitioned graph, counted in the layout the tile engine would
        use, and the first device's memory. `layout` picks the
        intra-partition node order ("natural" | "rcm" | "auto"): "rcm"
        applies the
        bandwidth-reducing + halo-clustering permutation of
        repro.graph.reorder — fewer nonempty tiles for the block-sparse
        engines, numerically invisible everywhere — and "auto" (the
        default, matching ModelConfig.layout and the CLI) resolves to
        "rcm" exactly when the resolved aggregation engine consumes
        tiles. Features/labels/masks are remapped ONCE here (pack_nodes
        routes through the reordered local_of); results are unpermuted
        only at the eval/metric boundary (`metric` goes through
        unpack_nodes)."""
        ds = (make_dataset(name_or_ds) if isinstance(name_or_ds, str)
              else name_or_ds)
        from repro.graph.reorder import TILE_ENGINES, resolve_layout
        prop = mean_normalized(ds.graph) if kind == "sage" else sym_normalized(ds.graph)
        part = partition_graph(ds.graph, num_parts, seed=seed,
                               method=partition_method)
        pg = None
        if agg == "auto":
            choice, pg = resolve_agg(prop, part, num_parts, layout)
            agg = choice["agg"]
        else:
            choice = {"agg": agg, "how": "forced"}
        layout = resolve_layout(layout, agg)
        if pg is None or pg.layout != layout:
            pg = build_partitioned_graph(prop, part, num_parts, layout=layout)
        topo = topology_from(pg, with_tiles=(agg in TILE_ENGINES))
        # x/labels/train_mask are split-independent: pack them ONCE and share
        # the arrays across the three views; only eval_mask differs per split.
        base = shard_data(pg, ds.features, ds.labels, ds.train_mask,
                          ds.val_mask)
        return GraphDataPipeline(
            dataset=ds, pg=pg, topo=topo,
            train_data=base._replace(eval_mask=base.train_mask),
            val_data=base,
            test_data=base._replace(
                eval_mask=jnp.asarray(pg.pack_nodes(np.asarray(ds.test_mask)))),
            agg=agg, layout=layout, agg_choice=choice)

    def split_spec(self):
        """`SplitSpec` of this pipeline's partitioned graph for the
        split-phase overlap schedule (`PipeConfig.overlap`), or None when
        the split is infeasible (single partition, no boundary sends, or a
        layout whose boundary rows are not clustered into a tail — e.g.
        "natural"). Memoized with the tile extraction on `pg`, so calling
        this after `build` costs nothing for tile-engine pipelines."""
        from repro.core.pipegcn import split_spec_from
        return split_spec_from(self.pg)

    def device_layout(self, num_devices: int):
        """Explicit (n_dev, n_local, ...) per-device view of (topo, data)
        for num_devices hosts — the physical layout `make_spmd_step` induces
        when sharding the flat partition axis over a smaller mesh."""
        if self.topo.num_parts % num_devices:
            raise ValueError(
                f"num_parts={self.topo.num_parts} is not a multiple of "
                f"num_devices={num_devices}")
        n_local = self.topo.num_parts // num_devices
        topo = Topology(*to_local_layout(tuple(self.topo), n_local))
        data = ShardedData(*to_local_layout(tuple(self.train_data), n_local))
        return topo, data

    def elastic_views(self, plan):
        """Remapped (topo, train_data, val_data) for an
        `repro.core.elastic.ElasticPlan` — the padded survivor layout of
        this pipeline's device arrays (pads appended and masked out; the
        partitioned graph is NOT rebuilt)."""
        from repro.core.elastic import remap_data, remap_topology
        return (remap_topology(self.topo, plan),
                remap_data(self.train_data, plan),
                remap_data(self.val_data, plan))

    def metric(self, logits_packed) -> dict:
        """Global accuracy (single-label) or F1-micro (multilabel) on
        train/val/test splits, computed from packed (P, max_inner, C)
        logits. Logits from an elastically remapped run carry extra pad
        partitions; only the real leading `num_parts` rows are unpacked."""
        ds = self.dataset
        logits = self.pg.unpack_nodes(
            np.asarray(logits_packed)[:self.pg.num_parts])
        out = {}
        for split, mask in (("train", ds.train_mask), ("val", ds.val_mask),
                            ("test", ds.test_mask)):
            if ds.multilabel:
                pred = logits[mask] > 0
                true = ds.labels[mask] > 0.5
                tp = np.sum(pred & true)
                fp = np.sum(pred & ~true)
                fn = np.sum(~pred & true)
                out[split] = float(2 * tp / max(2 * tp + fp + fn, 1))
            else:
                pred = logits[mask].argmax(-1)
                out[split] = float(np.mean(pred == ds.labels[mask]))
        return out
