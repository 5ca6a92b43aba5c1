"""Pluggable aggregation engines for the PipeGCN hot path (Eq. 3/4 SpMM).

The training loop calls aggregation through a narrow interface:

    z      = engine.spmm(tslice, comb, num_rows)      # z = P_local · comb
    dcomb  = engine.spmm_t(tslice, dz, num_cols)      # δcomb = P_localᵀ · δz
    u, z   = engine.aggregate_transform(tslice, comb, w, b, num_rows)
             #  u = (P_local · comb) @ w + b   (aggregate-first layer fwd)
    dcomb  = engine.aggregate_transform_t(tslice, du, w, num_cols)
             #  δcomb = P_localᵀ · (du @ wᵀ)   (aggregate-first layer bwd)

`tslice` is the tuple of per-partition Topology fields named by
``engine.fields`` — the model layer stays agnostic to the storage format.
The ``aggregate_transform*`` pair defaults to COMPOSING the two primitive
ops (an SpMM plus a dense matmul, with the (rows, F_in) intermediate
materialized between them), so "coo" and plain "blocksparse" behave exactly
as before; the "fused" engine overrides it with single-pass Pallas kernels
in which the intermediate never leaves VMEM. Three implementations:

  coo         padded COO + `jax.ops.segment_sum` (the verified fallback;
              exact in float64, works for any shape).
  blocksparse MXU-shaped Pallas kernels over TILE×TILE tiles
              (`repro.kernels.gcn_spmm`). Inputs are zero-padded to tile /
              feature-block multiples only when needed (topology-padded
              shapes skip the pad entirely) and the result is sliced back,
              so callers never see the padded shapes. Computes in the
              caller's dtype (f32 in production; f64 under the x64
              exactness tests).
  fused       blocksparse storage + the fused aggregate+transform kernels:
              forward epilogue matmul (u = z@w + b on the run-flush, with
              optional fused bias+ReLU and z as an optional second output)
              and backward prologue matmul (dcomb = Pᵀ·(du@wᵀ)). Computes
              in the caller's dtype (f32 in production; f64 under the x64
              exactness tests, where it matches "coo" to 1e-12).

Every engine computes the layer's f32 sums at f32 accuracy: COO sums
edges in f32, and the tile kernels contract f32 operands at
`Precision.HIGHEST`.

Select with ``ModelConfig.agg`` ("auto" | "coo" | "blocksparse" |
"fused"); the tile engines need tile fields on the Topology
(``topology_from(pg, with_tiles=True)`` or ``GraphDataPipeline.build(...,
agg="blocksparse")``). "auto", the default, is resolved once by the graph
pipeline through `choose_engine` below: COO off the TPU, and on it the
tile engine where the tiles are estimated faster and fit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.kernels.gcn_spmm import FEAT_BLOCK, TILE


def _scoped(method):
    """Trace an engine method under the `aggregate` named scope (see
    `repro.core.trace_utils`), so that its ops, a fused kernel's epilogue
    included, carry it in their HLO metadata."""
    @functools.wraps(method)
    def run(self, *args, **kwargs):
        # imported here: importing repro.core imports this module
        from repro.core.trace_utils import AGGREGATE
        with jax.named_scope(AGGREGATE):
            return method(self, *args, **kwargs)
    return run


def _ceil_to(n: int, m: int) -> int:
    return -(-n // m) * m


def _phase_mask(in_boundary, phase: str):
    """Edge-level phase membership from a boundary predicate."""
    if phase == "boundary":
        return in_boundary
    if phase == "interior":
        return ~in_boundary
    raise ValueError(f"phase must be 'boundary' or 'interior', got {phase!r}")


def _pad2(x, rows: int, cols: int):
    """Zero-pad a 2-D array up to (rows, cols), skipping the op entirely
    when the shape already matches (the common case after topology padding:
    `jnp.pad` is not free even for zero-width pads — it still emits a
    copy)."""
    r, c = x.shape
    if (r, c) == (rows, cols):
        return x
    return jnp.pad(x, ((0, rows - r), (0, cols - c)))


class AggregationEngine:
    """Interface + default fused-op composition shared by all engines."""

    name: str
    fields: tuple[str, ...]

    def spmm(self, tslice, comb, num_rows: int):
        raise NotImplementedError

    def spmm_t(self, tslice, dz, num_cols: int):
        raise NotImplementedError

    def aggregate_transform(self, tslice, comb, w, b, num_rows: int,
                            relu: bool = False, with_z: bool = True):
        """u = (P·comb) @ w + b (optionally ReLU'd), plus the aggregation
        residual z = P·comb (None when `with_z=False`, e.g. at eval).
        Default: compose the primitive SpMM with a dense matmul — the
        (num_rows, F_in) intermediate round-trips through HBM; the matmul
        stays in the caller's named scope, out of `aggregate`."""
        z = self.spmm(tslice, comb, num_rows)
        u = z @ w + b
        if relu:
            u = jax.nn.relu(u)
        return u, (z if with_z else None)

    def aggregate_transform_t(self, tslice, du, w, num_cols: int):
        """δcomb = Pᵀ·(du @ wᵀ). Default: compose — the (rows, F_in)
        dz intermediate round-trips through HBM."""
        return self.spmm_t(tslice, du @ w.T, num_cols)

    def spmm_phased(self, tslice, comb, num_rows: int, split, phase: str):
        """One phase of z = P·comb under the split-phase overlap schedule
        (`split` is a kernels.gcn_spmm.SplitSpec, `phase` is "boundary" |
        "interior"). Contract shared by all engines: rows OUTSIDE the
        phase (below split.row_tail for "boundary", at/above it for
        "interior") are unspecified; each phase's own rows are
        bit-identical to the unsplit `spmm` on the same inputs."""
        raise NotImplementedError

    def spmm_t_phased(self, tslice, dz, num_cols: int, split, phase: str):
        """One phase of δcomb = Pᵀ·δz; the phase cut is at
        split.col_tail. Same unspecified-rows contract as spmm_phased."""
        raise NotImplementedError


class CooEngine(AggregationEngine):
    """Padded-COO aggregation via segment_sum (scatter-add)."""

    name = "coo"
    fields = ("edge_row", "edge_col", "edge_w")

    @_scoped
    def spmm(self, tslice, comb, num_rows: int):
        edge_row, edge_col, edge_w = tslice
        vals = comb[edge_col] * edge_w[:, None]
        return jax.ops.segment_sum(vals, edge_row, num_segments=num_rows)

    @_scoped
    def spmm_t(self, tslice, dz, num_cols: int):
        edge_row, edge_col, edge_w = tslice
        vals = dz[edge_row] * edge_w[:, None]
        return jax.ops.segment_sum(vals, edge_col, num_segments=num_cols)

    # Phased variants compose via index masks rather than stream slices:
    # out-of-phase edges get weight 0, so each phase's own rows see the
    # IDENTICAL segment_sum term sequence as the unsplit call (zeroed
    # terms add exact 0.0) — bitwise parity engine-cross-engine with the
    # tile engines' sliced streams, which is what the SPMD parity matrix
    # gates on. Out-of-phase rows come out zero (a valid value for
    # "unspecified").
    @_scoped
    def spmm_phased(self, tslice, comb, num_rows: int, split, phase: str):
        edge_row, edge_col, edge_w = tslice
        keep = _phase_mask(edge_row >= split.row_tail, phase)
        vals = comb[edge_col] * jnp.where(keep, edge_w, 0)[:, None]
        return jax.ops.segment_sum(vals, edge_row, num_segments=num_rows)

    @_scoped
    def spmm_t_phased(self, tslice, dz, num_cols: int, split, phase: str):
        edge_row, edge_col, edge_w = tslice
        keep = _phase_mask(edge_col >= split.col_tail, phase)
        vals = dz[edge_row] * jnp.where(keep, edge_w, 0)[:, None]
        return jax.ops.segment_sum(vals, edge_col, num_segments=num_cols)


class BlockSparseEngine(AggregationEngine):
    """Block-sparse aggregation on the Pallas SpMM kernels.

    Pads rows to TILE and features to FEAT_BLOCK multiples per call when
    the caller's shapes are not already multiples (the tile grid is fixed
    offline by `build_tile_topology`, so row padding is only about matching
    the kernel's static output shape). Computes in the CALLER'S dtype —
    f32 in production; f64 under the `jax_enable_x64` exactness tests,
    where the tile values are upcast and the result stays
    1e-12-comparable to the COO engine even across node layouts (the
    cross-layout parity bar of tests/test_reorder.py and the SPMD matrix).
    """

    name = "blocksparse"
    fields = ("tile_rows", "tile_cols", "tile_vals",
              "tile_t_out", "tile_t_in", "tile_t_perm")

    def _vals(self, tslice, like):
        tile_vals = tslice[2]
        return tile_vals.astype(like.dtype)

    @_scoped
    def spmm(self, tslice, comb, num_rows: int):
        tile_rows, tile_cols = tslice[:2]
        combined, f = comb.shape
        rpad = _ceil_to(num_rows, TILE)
        fpad = _ceil_to(f, FEAT_BLOCK)
        combp = _pad2(comb, _ceil_to(combined, TILE), fpad)
        z = ops.spmm(tile_rows, tile_cols, self._vals(tslice, comb),
                     combp, rpad)
        assert z.shape == (rpad, fpad), (z.shape, rpad, fpad)
        return z[:num_rows, :f]

    @_scoped
    def spmm_t(self, tslice, dz, num_cols: int):
        t_out, t_in, t_perm = tslice[3:]
        num_rows, f = dz.shape
        cpad = _ceil_to(num_cols, TILE)
        fpad = _ceil_to(f, FEAT_BLOCK)
        dzp = _pad2(dz, _ceil_to(num_rows, TILE), fpad)
        d = ops.spmm_t(t_out, t_in, t_perm, self._vals(tslice, dz),
                       dzp, cpad)
        assert d.shape == (cpad, fpad), (d.shape, cpad, fpad)
        return d[:num_cols, :f]

    # Phased variants: static suffix/prefix slices of the streams (the
    # phase-aware topology padding makes the cut uniform across
    # partitions). Tiles of one output block live entirely in one phase,
    # so each phase's own rows are BITWISE the unsplit result — same
    # tiles, same accumulation order. Out-of-phase rows are unwritten
    # kernel output (garbage, never to be read).
    @_scoped
    def spmm_phased(self, tslice, comb, num_rows: int, split, phase: str):
        tile_rows, tile_cols = tslice[:2]
        combined, f = comb.shape
        rpad = _ceil_to(num_rows, TILE)
        fpad = _ceil_to(f, FEAT_BLOCK)
        combp = _pad2(comb, _ceil_to(combined, TILE), fpad)
        z = ops.spmm_phased(tile_rows, tile_cols, self._vals(tslice, comb),
                            combp, rpad, split.fwd_bnd_tiles, phase)
        assert z.shape == (rpad, fpad), (z.shape, rpad, fpad)
        return z[:num_rows, :f]

    @_scoped
    def spmm_t_phased(self, tslice, dz, num_cols: int, split, phase: str):
        t_out, t_in, t_perm = tslice[3:]
        num_rows, f = dz.shape
        cpad = _ceil_to(num_cols, TILE)
        fpad = _ceil_to(f, FEAT_BLOCK)
        dzp = _pad2(dz, _ceil_to(num_rows, TILE), fpad)
        d = ops.spmm_t_phased(t_out, t_in, t_perm, self._vals(tslice, dz),
                              dzp, cpad, split.t_bnd_tiles, phase)
        assert d.shape == (cpad, fpad), (d.shape, cpad, fpad)
        return d[:num_cols, :f]


class FusedBlockSparseEngine(BlockSparseEngine):
    """Blocksparse tiles + fused aggregate⊗transform Pallas kernels.

    The primitive spmm/spmm_t (used by the transform-first ordering) are
    inherited; the `aggregate_transform*` pair runs the single-pass fused
    kernels, in the caller's dtype like the parent. The phased variants
    are inherited too: under the split-phase overlap schedule the layer
    falls back to the composed (aggregate, then dense transform) path —
    the fused epilogue would write out-of-phase garbage rows through the
    dense weight — and the cost model is told `fused=False` accordingly
    (see PipeGCN.layer_orders).
    """

    name = "fused"

    @_scoped
    def aggregate_transform(self, tslice, comb, w, b, num_rows: int,
                            relu: bool = False, with_z: bool = True):
        tile_rows, tile_cols = tslice[:2]
        combined, fin = comb.shape
        fout = w.shape[1]
        rpad = _ceil_to(num_rows, TILE)
        fin_p = _ceil_to(fin, FEAT_BLOCK)
        fout_p = _ceil_to(fout, FEAT_BLOCK)
        combp = _pad2(comb, _ceil_to(combined, TILE), fin_p)
        wp = _pad2(w, fin_p, fout_p)
        bp = _pad2(b.reshape(1, -1), 1, fout_p)
        u, z = ops.spmm_fused(tile_rows, tile_cols, self._vals(tslice, comb),
                              combp, wp, bp, rpad, relu=relu, with_z=with_z)
        assert u.shape == (rpad, fout_p), (u.shape, rpad, fout_p)
        u = u[:num_rows, :fout]
        if with_z:
            assert z.shape == (rpad, fin_p), (z.shape, rpad, fin_p)
            z = z[:num_rows, :fin]
        return u, z

    @_scoped
    def aggregate_transform_t(self, tslice, du, w, num_cols: int):
        t_out, t_in, t_perm = tslice[3:]
        num_rows, fout = du.shape
        fin = w.shape[0]
        cpad = _ceil_to(num_cols, TILE)
        fin_p = _ceil_to(fin, FEAT_BLOCK)
        fout_p = _ceil_to(fout, FEAT_BLOCK)
        dup = _pad2(du, _ceil_to(num_rows, TILE), fout_p)
        wp = _pad2(w, fin_p, fout_p)
        d = ops.spmm_fused_t(t_out, t_in, t_perm, self._vals(tslice, du),
                             dup, wp, cpad)
        assert d.shape == (cpad, fin_p), (d.shape, cpad, fin_p)
        return d[:num_cols, :fin]


ENGINES = {e.name: e for e in (CooEngine(), BlockSparseEngine(),
                               FusedBlockSparseEngine())}

#: The tile engine "auto" names when the tiles win.
AUTO_TILE_ENGINE = "fused"
#: Device time per 128-column feature block of one aggregation, on a TPU
#: v5e: per padded tile slot of the tile engine, and per padded edge row
#: of the COO engine. Each is the `aggregate` scope's device time in a
#: traced window of the reddit-sage.pipegcn-gf.1chip benchmark cell run
#: under that engine, over units x feature-block passes in the window:
#: tiles 8,372.6 ms / (16,220 slots x 959) (seed 2147494001), COO
#: 9,561.7 ms / (1,630,304 rows x 436) (seed 2147494003); PERF.md has the
#: runs. They break even at 40 padded edges per slot.
C_TILE_S = 0.54e-6
C_COO_S = 13.5e-9
#: Largest share of one device's memory the tile values may take: the
#: step also holds the features, the activations and per-call copies of
#: the tiles.
TILE_MEMORY_SHARE = 0.25


def choose_engine(platform: str, parts: int = 0, slots: int = 0,
                  edge_rows: int = 0, bytes_limit: int | None = None) -> dict:
    """The engine `agg="auto"` runs, and the numbers it was chosen on.

    The tile engine only where all three hold: the platform is a TPU (off
    it the kernels are interpreted, so the counts are not even needed);
    the tiles' estimated time, `parts x slots x C_TILE_S`, is below the
    COO engine's, `edge_rows x C_COO_S` (both per feature block, which
    both engines stream alike); and the tile values, `parts x slots x
    TILE^2 x 4` bytes, take at most TILE_MEMORY_SHARE of `bytes_limit`,
    the device's memory (unknown: no tiles). `slots` is one partition's
    padded tile stream, `edge_rows` the padded COO rows of all
    partitions.

    A mesh runs the same rule. Both of its departures there err toward
    COO: C_TILE_S includes the per-partition slices of `tile_vals` that
    the vmapped step pays and a mesh with one partition per device does
    not, and the memory test counts every partition's tiles against one
    device where a mesh spreads them over its devices."""
    choice = {"agg": "coo", "how": "auto", "platform": platform}
    if platform != "tpu":
        choice["why"] = "the tile kernels run compiled only on a TPU"
        return choice
    tile_ms = parts * slots * C_TILE_S * 1e3
    coo_ms = edge_rows * C_COO_S * 1e3
    tile_bytes = parts * slots * TILE * TILE * 4
    choice.update(tile_slots=parts * slots, edge_rows=edge_rows,
                  tile_ms=tile_ms, coo_ms=coo_ms, tile_bytes=tile_bytes,
                  bytes_limit=bytes_limit)
    if tile_ms >= coo_ms:
        choice["why"] = "the tiles are estimated slower"
    elif (bytes_limit is None
          or tile_bytes > TILE_MEMORY_SHARE * bytes_limit):
        choice["why"] = "the tile values would not fit"
    else:
        choice.update(agg=AUTO_TILE_ENGINE,
                      why="the tiles are estimated faster and fit")
    return choice


def describe_choice(choice: dict) -> str:
    """One line for the trainer's log: the engine, and for `auto` the
    counts and estimates it was chosen on."""
    if choice["how"] != "auto":
        return f"{choice['agg']} ({choice['how']})"
    line = f"{choice['agg']} (auto on {choice['platform']}: {choice['why']}"
    if "tile_ms" in choice:
        limit = choice["bytes_limit"]
        line += (f"; {choice['tile_slots']:,} tile slots x "
                 f"{C_TILE_S * 1e6:g} us = {choice['tile_ms']:.4g} ms vs "
                 f"{choice['edge_rows']:,} padded edge rows x "
                 f"{C_COO_S * 1e9:g} ns = {choice['coo_ms']:.4g} ms per "
                 f"feature block; tile values {choice['tile_bytes']:,} B vs "
                 + (f"{TILE_MEMORY_SHARE:.0%} of {limit:,} B" if limit
                    else "an unknown device memory"))
    return line + ")"


def get_engine(name: str):
    """Look up an aggregation engine ("coo" | "blocksparse" | "fused")."""
    try:
        return ENGINES[name]
    except KeyError:
        raise KeyError(
            f"unknown aggregation engine {name!r}; have {sorted(ENGINES)}"
        ) from None
