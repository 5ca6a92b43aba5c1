"""CPU tests of the chip benchmark's yardstick: work counts, lookup by name,
the peak table, the graph generator and the trace reduction."""
from __future__ import annotations

import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = os.path.join(HERE, "..", "..", "benchmarks", "chip")
sys.path.insert(0, CHIP)

from chipbench import graph, spec, trace, work  # noqa: E402
from chipbench.readers import Context, load_reader  # noqa: E402

CELLS = tuple(w["name"] for w in spec.load_benchmark()["workloads"])


def _sage(fin, hidden, classes, layers, kind="sage"):
    return {"feat_dim": fin, "hidden": hidden, "num_classes": classes,
            "num_layers": layers, "kind": kind}


# -- work counts against hand counts -----------------------------------

def test_work_aggregate_first_by_hand():
    # 2 layers 8 -> 8 -> 8: aggregate-first everywhere (layer 0: 8 < 2*8;
    # layer 1: 2*8 = 2*8, a tie goes to aggregate-first).
    cfg = _sage(8, 8, 8, 2)
    N, E = 10, 30
    assert work.orders(cfg) == ["aggregate-first", "aggregate-first"]
    aggs = work.aggregations(cfg)
    assert [(a.layer, a.width, a.backward) for a in aggs] == [
        (0, 8, False), (1, 8, False), (1, 8, True)]
    # transform: layer 0 fwd + weight grad = 2 * (2*N*16*8); layer 1 adds
    # the input gradient: 3 * (2*N*16*8)
    transform = 2 * (2 * N * 16 * 8) + 3 * (2 * N * 16 * 8)
    aggregation = 3 * (2 * E * 8)
    assert work.epoch_flops(cfg, N, E) == transform + aggregation
    assert aggs[0].bytes(N, E) == 8 * E + 2 * 4 * N * 8


def test_work_transform_first_by_hand():
    # layer 0: 64 -> 4: P.(H.W) aggregates 4 columns twice (8) against 64
    # once: transform-first, backward aggregation needed in layer 0 too.
    cfg = _sage(64, 4, 4, 2)
    N, E = 100, 1000
    assert work.orders(cfg) == ["transform-first", "aggregate-first"]
    aggs = work.aggregations(cfg)
    assert [(a.layer, a.width, a.backward) for a in aggs] == [
        (0, 4, False), (0, 4, True), (1, 4, False), (1, 4, True)]
    transform = 2 * (2 * N * 128 * 4) + 3 * (2 * N * 8 * 4)
    assert work.epoch_flops(cfg, N, E) == transform + 4 * (2 * E * 4)


def test_work_gcn_counts_self_loops_and_no_concat():
    cfg = _sage(8, 8, 8, 1, kind="gcn")
    g = graph.generate(64, 4, 8, 6.0, 1.0, seed=3)
    assert work.propagation_edges(g, "gcn") == g.num_edges + 64
    assert work.transform_flops(cfg, 64) == 2 * (2 * 64 * 8 * 8)


def test_roofline_least_time_is_bytes_bound_for_sparse_aggregation():
    cfg = _sage(602, 256, 41, 4)
    peaks = spec.peaks_for("TPU v5 lite")
    t1, bound = work.aggregation_least_seconds(cfg, 16384, 974496, peaks)
    t4, _ = work.aggregation_least_seconds(cfg, 16384, 974496, peaks, 4)
    assert bound == "bytes"
    assert t4 == pytest.approx(t1 / 4)


# -- lookup by name ----------------------------------------------------

@pytest.mark.parametrize("name", CELLS)
def test_cell_loads_by_name(name):
    cell = spec.load_cell(name)
    assert cell.config["name"] == name.split(".")[0]
    assert cell.traffic["num_nodes"] > 0
    assert {m["name"] for m in cell.end_to_end} >= {"epoch_ms", "setup_s"}
    assert cell.per_layer
    assert set(cell.limits) >= {"loss_gap", "grad_gap", "update_gap"}
    for m in cell.per_layer:
        assert load_reader(m["name"]).read is not None


@pytest.mark.parametrize("name", ["products-sage.pipegcn-gf.1chip",
                                  "reddit-sage.pipegcn-gf.4chip"])
def test_cells_left_out_are_unknown(name):
    """Cells that PERF.md leaves for a later PR: not in BENCHMARK.json, so
    not found."""
    with pytest.raises(spec.UnknownName):
        spec.load_cell(name)


def test_unknown_names_are_refused():
    with pytest.raises(spec.UnknownName):
        spec.load_cell("no-such-cell")
    with pytest.raises(spec.UnknownName):
        spec.load_config("no-such-config")
    with pytest.raises(spec.UnknownName):
        spec.load_traffic("no-such-traffic")
    with pytest.raises(spec.UnknownName):
        load_reader("no_such_metric")


def test_peaks_refuse_unknown_device_kind():
    assert spec.peaks_for("TPU v5 lite")["flops_bf16"] == 197e12
    with pytest.raises(spec.UnknownName):
        spec.peaks_for("cpu")


def test_benchmark_names_files_that_exist():
    bench = spec.load_benchmark()
    for c in bench["configs"]:
        assert os.path.exists(os.path.join(spec.CHECKOUT, c["file"]))
    for w in bench["workloads"]:
        spec.load_cell(w["name"])


# -- the copied generator ----------------------------------------------

def test_generator_same_seed_same_graph():
    a = graph.generate(512, 5, 16, 12.0, 0.8, seed=2**31 + 9)
    b = graph.generate(512, 5, 16, 12.0, 0.8, seed=2**31 + 9)
    c = graph.generate(512, 5, 16, 12.0, 0.8, seed=2**31 + 10)
    for f in ("indptr", "indices", "features", "labels", "train_mask"):
        np.testing.assert_array_equal(getattr(a, f), getattr(b, f))
    assert not np.array_equal(a.features, c.features)


def test_generator_structure_seed_fixes_the_edges():
    """A cell's traffic fixes the edges, so every run of the cell has the
    same shapes and work; the run's seed still changes the inputs."""
    a = graph.generate(512, 5, 16, 12.0, 0.8, seed=1, structure_seed=42)
    b = graph.generate(512, 5, 16, 12.0, 0.8, seed=2, structure_seed=42)
    np.testing.assert_array_equal(a.indptr, b.indptr)
    np.testing.assert_array_equal(a.indices, b.indices)
    np.testing.assert_array_equal(a.labels, b.labels)
    assert not np.array_equal(a.features, b.features)
    assert not np.array_equal(a.train_mask, b.train_mask)


@pytest.mark.parametrize("config,traffic", [
    (w["config"], w["traffic"]) for w in spec.load_benchmark()["workloads"]])
def test_generator_gives_the_cells_shape(config, traffic):
    cell = spec.Cell(name="", chips=1, config=spec.load_config(config),
                     traffic=spec.load_traffic(traffic), limits={},
                     end_to_end=(), per_layer=())
    g = graph.from_cell(cell.config, cell.traffic, seed=7)
    assert g.num_nodes == cell.traffic["num_nodes"]
    assert g.features.shape == (g.num_nodes, cell.config["feat_dim"])
    assert g.labels.max() == cell.config["num_classes"] - 1
    # the target degree, less the duplicates the union drops
    deg = g.num_edges / g.num_nodes
    assert 0.85 * cell.config["avg_degree"] < deg <= cell.config["avg_degree"]
    # undirected: every edge appears in both directions
    dst, src = g.coo()
    fwd = set(zip(dst[:5000].tolist(), src[:5000].tolist()))
    back = set(zip(src.tolist(), dst.tolist()))
    assert fwd <= back
    assert g.train_mask.sum() == int(0.6 * g.num_nodes)


# -- the trace reduction -----------------------------------------------

def _op(dev, name, start, dur, opcodes=(), engine=False):
    return trace.Op(dev, name, "jit_step", float(start), float(dur),
                    opcodes=tuple(opcodes), engine=engine)


def test_interval_union_and_exposure():
    assert trace.merge([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert trace.length([(0, 2), (1, 3), (5, 8)]) == 6
    assert trace.length([]) == 0
    assert trace.merge([(3, 3), (4, 2)]) == []


def test_idle_share_from_union_of_device_ops():
    ops = {0: [_op(0, "a", 0, 4e6), _op(0, "b", 2e6, 4e6),
               _op(0, "c", 8e6, 2e6)]}
    tr = trace.DeviceTrace(ops=ops, modules={}, window_s=0.02, epochs=1)
    ctx = Context(trace=tr, config={}, traffic={}, chips=1, nodes=1,
                  edges=1, peaks={})
    # busy 0-6 and 8-10 ms = 8 ms of 20 ms
    assert load_reader("device_idle_pct").read(ctx) == pytest.approx(60.0)
    assert trace.gaps(tr, 0) == [(2e6, "b", "c")]


def test_attribution_by_op_kind_and_exposed_collectives():
    """Two chips: a gather and a scatter from the engines (aggregation), a
    fused matmul (dense), the exchange's own gather (neither), and an
    all-to-all (a collective: neither), half hidden under a matmul on chip
    0 and wholly exposed on chip 1."""
    def chip(d, hidden):
        return [
            _op(d, "fusion", 0, 2e6, ("gather", "multiply"), True),
            _op(d, "fusion.12", 2e6, 3e6, ("scatter", "add"), True),
            _op(d, "fusion.8", 5e6, 1e6, ("gather",), False),
            _op(d, "convolution_add_fusion", 6e6, 2e6,
                ("convolution", "add"), True),
            _op(d, "all-to-all.1", 8e6 - hidden, 2e6, ("all-to-all",)),
        ]
    tr = trace.DeviceTrace(ops={0: chip(0, 1e6), 1: chip(1, 0)}, modules={},
                           window_s=0.011, epochs=2)
    ctx = Context(trace=tr, config={}, traffic={}, chips=2, nodes=1,
                  edges=1, peaks={})
    assert load_reader("spmm_ms").read(ctx) == pytest.approx(5 / 2)
    assert load_reader("dense_ms").read(ctx) == pytest.approx(2 / 2)
    # the all-to-all, hidden or exposed, is neither aggregation nor dense
    a2a = tr.ops[1][-1]
    assert load_reader("dense_ms").is_collective(a2a)
    assert not load_reader("spmm_ms").is_aggregation(a2a)
    assert not load_reader("dense_ms").is_dense(a2a)


RECORDED = os.path.join(HERE, "recorded_trace.json")


@pytest.fixture(scope="module")
def recorded():
    with open(RECORDED) as f:
        return trace.DeviceTrace.from_json(json.load(f))


def test_recorded_trace_attribution(recorded):
    """A window of a chip trace, recorded: every aggregation, dense and
    collective operation is attributed to one metric only, and the
    per-epoch times add up to no more than the device's busy time."""
    ctx = Context(trace=recorded, config={}, traffic={}, chips=1, nodes=1,
                  edges=1, peaks={})
    spmm = load_reader("spmm_ms")
    dense = load_reader("dense_ms")
    ops = recorded.ops[recorded.devices[0]]
    both = [o for o in ops if spmm.is_aggregation(o) and dense.is_dense(o)]
    assert not both
    s, d = spmm.read(ctx), dense.read(ctx)
    assert s and d
    busy_ms = trace.busy_ns(recorded, recorded.devices[0]) / 1e6
    assert s + d <= busy_ms / recorded.epochs + 1e-9


def test_hlo_facts_find_the_engine_ops_of_a_compiled_program():
    """On a program compiled here that aggregates through the program's
    COO engine and then transforms: the gather and the scatter-add carry
    the engine's source frames, the matmul does not."""
    import jax
    import jax.numpy as jnp

    from chipbench import hlo
    from repro.data import GraphDataPipeline
    from repro.kernels.aggregate import get_engine

    p = GraphDataPipeline.build("tiny", 2, kind="sage")
    eng = get_engine("coo")
    ts = tuple(getattr(p.topo, f) for f in eng.fields)

    def f(ts, x, w):
        z = jax.vmap(lambda t, xx: eng.spmm(t, xx, p.topo.max_inner))(ts, x)
        return jnp.tanh(z @ w)

    x = jnp.ones((2, p.topo.max_inner + p.topo.halo_size, 16))
    text = jax.jit(f).lower(ts, x, jnp.ones((16, 8))).compile().as_text()
    assert hlo.module_name(text) == "jit_f"
    facts = hlo.facts(text).values()
    engine_ops = set().union(*(ops for ops, engine in facts if engine))
    assert {"gather", "scatter"} <= engine_ops
    assert any("dot" in ops and not engine for ops, engine in facts)
    assert not any("dot" in ops for ops, engine in facts if engine)
