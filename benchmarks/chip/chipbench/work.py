"""Operations and bytes that one training epoch of a GCN/GraphSAGE model
requires, counted from the graph's real nodes and real directed edges
(halo edges are edges of their receiving partition, so each directed edge
counts once), never from padded shapes.

The layer math runs in float32 at XLA's DEFAULT precision, which on the TPU
is one bf16 pass of the MXU with f32 accumulation, so the bf16 peak is the
denominator of `mfu`. Elementwise work (activations, loss, Adam) is not
counted: it is a few operations per node and parameter against hundreds
per node in the matmuls.

Per layer l with widths F_in -> F_out, N nodes and E edges, and the
self-concatenation of SAGE (c = 2; GCN: c = 1):

  transform  forward 2*N*(c*F_in)*F_out; backward the same once for the
             weight gradient and once more for the input gradient, which
             layer 0 does not need.
  aggregation (P.H).W aggregates F_in columns forward and, except in layer
             0, F_in columns backward; P.(H.W) aggregates F_out columns
             forward and F_out backward in every layer (the weight gradient
             needs P^T.dU). Each layer is counted in the order that needs
             fewer operations, whatever order the program runs: the count
             is what the algorithm requires, so a costlier choice shows as
             a lower share.

One aggregation of width F is 2*E*F operations and reads at least the edge
list once (an int32 source index and an f32 weight per edge: the row index
of a CSR is implicit), the source features once and writes the output once,
in f32: 8*E + 4*N*F + 4*N*F bytes. That is a lower bound, so a share of the
roofline built on it cannot pass 100%.
"""
from __future__ import annotations

import dataclasses

F32 = 4
EDGE_BYTES = 8


@dataclasses.dataclass(frozen=True)
class Aggregation:
    layer: int
    width: int
    backward: bool

    def flops(self, edges: int) -> float:
        return 2.0 * edges * self.width

    def bytes(self, nodes: int, edges: int) -> float:
        return EDGE_BYTES * edges + 2.0 * F32 * nodes * self.width


def layer_dims(config: dict) -> list[tuple[int, int]]:
    L = int(config["num_layers"])
    dims = ([int(config["feat_dim"])] + [int(config["hidden"])] * (L - 1)
            + [int(config["num_classes"])])
    return [(dims[i], dims[i + 1]) for i in range(L)]


def orders(config: dict) -> list[str]:
    """The cheaper order of each layer: "aggregate-first" = (P.H).W,
    "transform-first" = P.(H.W); a tie goes to aggregate-first."""
    out = []
    for ell, (fin, fout) in enumerate(layer_dims(config)):
        agg_first = fin * (1 if ell == 0 else 2)
        transform_first = 2 * fout
        out.append("transform-first" if transform_first < agg_first
                   else "aggregate-first")
    return out


def aggregations(config: dict) -> list[Aggregation]:
    """Every aggregation one training epoch requires."""
    out = []
    for ell, ((fin, fout), order) in enumerate(zip(layer_dims(config),
                                                   orders(config))):
        width = fin if order == "aggregate-first" else fout
        out.append(Aggregation(ell, width, backward=False))
        if ell > 0 or order == "transform-first":
            out.append(Aggregation(ell, width, backward=True))
    return out


def transform_flops(config: dict, nodes: int) -> float:
    c = 2 if config["kind"] == "sage" else 1
    total = 0.0
    for ell, (fin, fout) in enumerate(layer_dims(config)):
        one = 2.0 * nodes * c * fin * fout
        total += one * (3 if ell > 0 else 2)
    return total


def epoch_flops(config: dict, nodes: int, edges: int) -> float:
    """Operations of one training epoch's forward and backward."""
    return (transform_flops(config, nodes)
            + sum(a.flops(edges) for a in aggregations(config)))


def aggregation_least_seconds(config: dict, nodes: int, edges: int,
                              peaks: dict, chips: int = 1) -> tuple[float, str]:
    """Least time one chip needs for one epoch's aggregations when `chips`
    share the work evenly, and what bounds it ("bytes", "flops" or "both"
    when the calls differ)."""
    total, bound = 0.0, set()
    for a in aggregations(config):
        t_flops = a.flops(edges) / chips / peaks["flops_bf16"]
        t_bytes = a.bytes(nodes, edges) / chips / peaks["hbm_bytes_per_s"]
        total += max(t_flops, t_bytes)
        bound.add("bytes" if t_bytes >= t_flops else "flops")
    return total, (bound.pop() if len(bound) == 1 else "both")


def propagation_edges(graph, kind: str) -> int:
    """Nonzeros of the propagation matrix: the graph's directed edges for
    SAGE-mean; plus one self loop per node for GCN."""
    return graph.num_edges + (graph.num_nodes if kind == "gcn" else 0)
