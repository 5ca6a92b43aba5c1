"""The benchmark's own graph generator, seeded by `--seed`.

A community stochastic block model with a power-law R-MAT overlay, class-
mean Gaussian features and random train/val/test splits: the recipe of the
program's `graph/synthetic.py` `_make_sim`, copied here so that a later
change there cannot change what a cell trains on (here the edges and the
features draw from separate generators). The generator returns plain numpy
arrays; `job` wraps them into the program's types.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class Graph:
    """An undirected graph as in-neighbour CSR (row v lists the u that v
    aggregates from), with node features, labels and split masks."""

    indptr: np.ndarray      # (N+1,) int64
    indices: np.ndarray     # (E,) int32
    features: np.ndarray    # (N, F) float32
    labels: np.ndarray      # (N,) int32, or (N, C) float32 if multilabel
    train_mask: np.ndarray  # (N,) bool
    val_mask: np.ndarray
    test_mask: np.ndarray
    num_classes: int
    multilabel: bool

    @property
    def num_nodes(self) -> int:
        return len(self.indptr) - 1

    @property
    def num_edges(self) -> int:
        """Directed edges (each undirected edge counts twice)."""
        return len(self.indices)

    def coo(self) -> tuple[np.ndarray, np.ndarray]:
        """(dst, src) of every directed edge, dst-sorted."""
        dst = np.repeat(np.arange(self.num_nodes, dtype=np.int64),
                        np.diff(self.indptr))
        return dst, self.indices.astype(np.int64)


def coo_to_csr(src, dst, n: int) -> tuple[np.ndarray, np.ndarray]:
    """Deduplicated in-neighbour CSR (indptr, indices) of edges src->dst."""
    src = np.asarray(src, np.int64)
    dst = np.asarray(dst, np.int64)
    if len(src):
        key = np.unique(dst * n + src)
        dst, src = np.divmod(key, n)
    indptr = np.zeros(n + 1, np.int64)
    np.add.at(indptr, dst + 1, 1)
    return np.cumsum(indptr), src.astype(np.int32)


def symmetric(src, dst, n: int) -> tuple[np.ndarray, np.ndarray]:
    return coo_to_csr(np.concatenate([src, dst]), np.concatenate([dst, src]),
                      n)


def _csr_edges(indptr, indices) -> tuple[np.ndarray, np.ndarray]:
    n = len(indptr) - 1
    return (indices.astype(np.int64),
            np.repeat(np.arange(n, dtype=np.int64), np.diff(indptr)))


def sbm(n: int, blocks_n: int, p_in: float, p_out: float,
        rng: np.random.Generator):
    """Stochastic block model by expected-count binomial sampling per
    block pair; returns (indptr, indices, block of each node)."""
    blocks = rng.integers(0, blocks_n, size=n)
    order = np.argsort(blocks, kind="stable")
    starts = np.searchsorted(blocks[order], np.arange(blocks_n))
    ends = np.searchsorted(blocks[order], np.arange(blocks_n) + 1)
    srcs, dsts = [], []
    for a in range(blocks_n):
        na = ends[a] - starts[a]
        for b in range(a, blocks_n):
            nb = ends[b] - starts[b]
            p = p_in if a == b else p_out
            pairs = na * nb if a != b else na * (na - 1) // 2
            if pairs <= 0 or p <= 0:
                continue
            m = rng.binomial(pairs, min(p, 1.0))
            if m == 0:
                continue
            i = order[starts[a] + rng.integers(0, na, size=m)]
            j = order[starts[b] + rng.integers(0, nb, size=m)]
            keep = i != j
            srcs.append(i[keep])
            dsts.append(j[keep])
    src = np.concatenate(srcs) if srcs else np.zeros(0, np.int64)
    dst = np.concatenate(dsts) if dsts else np.zeros(0, np.int64)
    return (*symmetric(src, dst, n), blocks)


def rmat(n: int, m: int, rng: np.random.Generator, a: float = 0.57,
         b: float = 0.19, c: float = 0.19):
    """R-MAT power-law graph (Chakrabarti et al.), undirected."""
    scale = int(np.ceil(np.log2(max(n, 2))))
    src = np.zeros(m, np.int64)
    dst = np.zeros(m, np.int64)
    for _ in range(scale):
        r = rng.random(m)
        bit_s = (r >= a + b).astype(np.int64)
        r2 = rng.random(m)
        bit_d = np.where(bit_s == 0, (r2 >= a / (a + b)).astype(np.int64),
                         (r2 >= c / max(c + (1 - a - b - c), 1e-9)
                          ).astype(np.int64))
        src = (src << 1) | bit_s
        dst = (dst << 1) | bit_d
    src %= n
    dst %= n
    keep = src != dst
    return symmetric(src[keep], dst[keep], n)


def generate(num_nodes: int, num_classes: int, feat_dim: int,
             avg_degree: float, signal: float, seed: int,
             multilabel: bool = False, rmat_frac: float = 0.3,
             structure_seed: int | None = None) -> Graph:
    """One graph of the configuration's shape. `structure_seed` makes the
    edges and the class of each node (by default `seed` does); `seed`
    makes the features, the extra labels and the splits. Of the average
    degree, `1 - rmat_frac` comes from the block model (three quarters of
    it inside a node's own class block) and `rmat_frac` from the power-law
    overlay."""
    srng = np.random.default_rng(seed if structure_seed is None
                                 else structure_seed)
    rng = np.random.default_rng(seed)
    p_out = avg_degree * (1 - rmat_frac) * 0.25 / num_nodes
    p_in = avg_degree * (1 - rmat_frac) * 0.75 * num_classes / num_nodes
    ip1, ix1, blocks = sbm(num_nodes, num_classes, p_in, p_out, srng)
    ip2, ix2 = rmat(num_nodes, int(num_nodes * avg_degree * rmat_frac / 2),
                    srng)
    s1, d1 = _csr_edges(ip1, ix1)
    s2, d2 = _csr_edges(ip2, ix2)
    indptr, indices = coo_to_csr(np.concatenate([s1, s2]),
                                 np.concatenate([d1, d2]), num_nodes)
    means = rng.normal(0.0, 1.0, size=(num_classes, feat_dim))
    x = rng.normal(0.0, 1.0, size=(num_nodes, feat_dim))
    feats = (x + signal * means[blocks]).astype(np.float32)
    if multilabel:
        proj = rng.normal(size=(feat_dim, num_classes)).astype(np.float32)
        extra = (feats @ proj > 0)
        noise = rng.random((num_nodes, num_classes)) < 0.15
        labels = (extra & noise).astype(np.float32)
        labels[np.arange(num_nodes), blocks] = 1.0
    else:
        labels = blocks.astype(np.int32)
    perm = rng.permutation(num_nodes)
    n_tr, n_va = int(0.6 * num_nodes), int(0.2 * num_nodes)
    masks = [np.zeros(num_nodes, bool) for _ in range(3)]
    masks[0][perm[:n_tr]] = True
    masks[1][perm[n_tr:n_tr + n_va]] = True
    masks[2][perm[n_tr + n_va:]] = True
    return Graph(indptr=indptr, indices=indices, features=feats,
                 labels=labels, train_mask=masks[0], val_mask=masks[1],
                 test_mask=masks[2], num_classes=num_classes,
                 multilabel=multilabel)


def from_cell(config: dict, traffic: dict, seed: int) -> Graph:
    """The graph a cell trains on: the configuration's widths and degree at
    the traffic's node count. The traffic fixes the edges (`graph_seed`),
    so every run of a cell has the same sizes and the same work, and every
    program it compiles is in the cache after its first run; `seed` makes
    the features and the splits."""
    return generate(num_nodes=int(traffic["num_nodes"]),
                    structure_seed=int(traffic["graph_seed"]),
                    num_classes=int(config["num_classes"]),
                    feat_dim=int(config["feat_dim"]),
                    avg_degree=float(config["avg_degree"]),
                    signal=float(config["signal"]), seed=seed,
                    multilabel=bool(config["multilabel"]),
                    rmat_frac=float(config["rmat_frac"]))
