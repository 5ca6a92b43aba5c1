"""`agg="auto"`: the aggregation engine the graph pipeline resolves from
the platform, the tile slots, the padded edge rows and the device's memory
(`repro.kernels.aggregate.choose_engine`), and how the trainer runs it.

The platform and the memory limit are arguments of the resolver,
`graph_pipeline.resolve_agg`, so the TPU side of the rule is checked here
on the CPU; nothing here runs a kernel compiled for a chip.
"""
import functools

import jax
import numpy as np
import pytest

from repro.core import ModelConfig, PipeConfig, train_pipegcn
from repro.data import GraphDataPipeline, graph_pipeline
from repro.graph.csr import mean_normalized
from repro.graph.halo import extract_partition_tiles, tile_slot_count
from repro.graph.partition import partition_graph
from repro.graph.synthetic import make_dataset
from repro.kernels.aggregate import (AUTO_TILE_ENGINE, C_COO_S, C_TILE_S,
                                     TILE_MEMORY_SHARE, choose_engine,
                                     describe_choice)

GIB = 2 ** 30


def _dense():
    """512 nodes at degree ~96 over 2 partitions: hundreds of edges per
    128 x 128 tile slot."""
    return make_dataset("tiny", num_nodes=512, avg_degree=96.0)


def _sparse():
    """8,192 nodes at degree ~2 over 2 partitions: a handful of edges per
    tile slot, far below C_TILE_S / C_COO_S."""
    return make_dataset("tiny", num_nodes=8192, avg_degree=2.0)


def _resolve(ds, parts, bytes_limit=16 * GIB):
    """`resolve_agg` on a TPU with `bytes_limit` of memory, on the
    partition `build` makes by default."""
    part = partition_graph(ds.graph, parts, seed=0, method="bfs+refine")
    return graph_pipeline.resolve_agg(mean_normalized(ds.graph), part, parts,
                                      platform="tpu", bytes_limit=bytes_limit)


def _on_tpu(monkeypatch, bytes_limit=16 * GIB):
    """Make `build` resolve "auto" as on a TPU with `bytes_limit`."""
    monkeypatch.setattr(graph_pipeline, "resolve_agg", functools.partial(
        graph_pipeline.resolve_agg, platform="tpu", bytes_limit=bytes_limit))


def test_cpu_resolves_to_coo_and_builds_what_coo_builds():
    auto = GraphDataPipeline.build("tiny", 4, kind="sage")
    coo = GraphDataPipeline.build("tiny", 4, kind="sage", agg="coo")
    assert auto.agg == "coo" and auto.layout == coo.layout == "natural"
    assert auto.agg_choice == {
        "agg": "coo", "how": "auto", "platform": "cpu",
        "why": "the tile kernels run compiled only on a TPU"}
    assert coo.agg_choice == {"agg": "coo", "how": "forced"}
    for field in ("edge_row", "edge_col", "edge_w", "send_idx", "perm"):
        np.testing.assert_array_equal(getattr(auto.pg, field),
                                      getattr(coo.pg, field))
    for a, b in zip(auto.topo, coo.topo):
        assert (a is None) == (b is None)
        if a is not None:
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_tpu_on_a_dense_graph_resolves_to_the_tile_engine_on_rcm(
        monkeypatch):
    choice, counted = _resolve(_dense(), 2)
    assert choice["agg"] == AUTO_TILE_ENGINE and counted.layout == "rcm"
    assert choice["why"] == "the tiles are estimated faster and fit"
    _on_tpu(monkeypatch)
    p = GraphDataPipeline.build(_dense(), 2)
    assert p.agg == AUTO_TILE_ENGINE and p.layout == "rcm"
    assert p.topo.tile_rows is not None
    assert p.agg_choice == choice


def test_tpu_on_a_sparse_graph_resolves_to_coo_on_the_natural_layout(
        monkeypatch):
    _on_tpu(monkeypatch)
    p = GraphDataPipeline.build(_sparse(), 2)
    c = p.agg_choice
    assert p.agg == "coo" and p.layout == "natural"
    assert p.topo.tile_rows is None
    assert c["why"] == "the tiles are estimated slower"
    assert c["edge_rows"] / c["tile_slots"] < C_TILE_S / C_COO_S


def test_tpu_with_too_little_memory_for_the_tiles_resolves_to_coo(
        monkeypatch):
    tile_bytes = _resolve(_dense(), 2)[0]["tile_bytes"]
    _on_tpu(monkeypatch, int(tile_bytes / TILE_MEMORY_SHARE) - 1)
    p = GraphDataPipeline.build(_dense(), 2)
    assert p.agg == "coo" and p.layout == "natural"
    assert p.agg_choice["why"] == "the tile values would not fit"
    unknown = choose_engine("tpu", parts=2, slots=1, edge_rows=10 ** 6,
                            bytes_limit=None)
    assert unknown["agg"] == "coo"


@pytest.mark.parametrize("agg,graph", [("coo", _dense),
                                       ("blocksparse", _sparse),
                                       ("fused", _sparse)])
def test_an_explicit_engine_is_never_overridden(agg, graph, monkeypatch):
    def never(*a, **k):
        raise AssertionError("an explicit engine was resolved")
    monkeypatch.setattr(graph_pipeline, "resolve_agg", never)
    p = GraphDataPipeline.build(graph(), 2, agg=agg)
    assert p.agg == agg
    assert p.agg_choice == {"agg": agg, "how": "forced"}
    assert (p.topo.tile_rows is not None) == (agg != "coo")


def _train(pipeline, mc, lines):
    return train_pipegcn(pipeline, mc, PipeConfig(stale=True), epochs=2,
                         lr=0.01, seed=0, eval_every=2, log=lines.append)


def _model(pipeline, agg):
    ds = pipeline.dataset
    return ModelConfig(kind="sage", feat_dim=ds.feat_dim, hidden=32,
                       num_layers=2, num_classes=ds.num_classes,
                       dropout=0.0, agg=agg)


def test_auto_model_trains_on_the_pipelines_engine():
    pipeline = GraphDataPipeline.build("tiny", 2, agg="blocksparse")
    auto_lines, named_lines = [], []
    auto = _train(pipeline, _model(pipeline, "auto"), auto_lines)
    named = _train(pipeline, _model(pipeline, "blocksparse"), named_lines)
    assert "aggregation engine: blocksparse (forced)" in auto_lines
    assert any(ln.startswith("matmul order") and "agg=blocksparse" in ln
               for ln in auto_lines)
    assert auto.history["loss"] == named.history["loss"]
    for a, b in zip(jax.tree.leaves(auto.params),
                    jax.tree.leaves(named.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    cpu = GraphDataPipeline.build("tiny", 2)
    cpu_lines = []
    _train(cpu, _model(cpu, "auto"), cpu_lines)
    assert ("aggregation engine: coo (auto on cpu: the tile kernels run "
            "compiled only on a TPU)") in cpu_lines


def test_an_explicit_model_engine_is_used_as_given():
    tiles = GraphDataPipeline.build("tiny", 2, agg="blocksparse")
    lines = []
    _train(tiles, _model(tiles, "fused"), lines)
    assert "aggregation engine: fused (forced)" in lines
    coo = GraphDataPipeline.build("tiny", 2)
    with pytest.raises(ValueError, match="needs Topology fields"):
        _train(coo, _model(coo, "fused"), [])


def test_the_choice_holds_the_counts_the_rule_used(monkeypatch):
    _on_tpu(monkeypatch)
    p = GraphDataPipeline.build(_dense(), 2)
    c = p.agg_choice
    P, slots = p.topo.tile_rows.shape
    assert c["tile_slots"] == P * slots
    assert c["edge_rows"] == P * p.pg.max_nnz
    assert c["tile_bytes"] == p.topo.tile_vals.nbytes
    assert c["bytes_limit"] == 16 * GIB
    assert c["tile_ms"] == pytest.approx(P * slots * C_TILE_S * 1e3)
    assert c["coo_ms"] == pytest.approx(P * p.pg.max_nnz * C_COO_S * 1e3)
    line = describe_choice(c)
    assert line.startswith(f"{AUTO_TILE_ENGINE} (auto on tpu: ")
    assert f"{P * slots:,} tile slots" in line
    assert f"{P * p.pg.max_nnz:,} padded edge rows" in line


@pytest.mark.parametrize("name,parts,layout", [("tiny", 4, "natural"),
                                               ("tiny", 4, "rcm"),
                                               ("grid-tiny", 4, "rcm"),
                                               ("grid-tiny", 1, "natural")])
def test_slot_count_is_the_extracted_stream_length(name, parts, layout):
    p = GraphDataPipeline.build(name, parts, agg="coo", layout=layout)
    count = tile_slot_count(p.pg)
    assert count == extract_partition_tiles(p.pg).n_tiles
