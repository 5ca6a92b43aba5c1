"""Block-sparse SpMM Pallas TPU kernels — the GCN neighbor-aggregation
hot spot, forward (z = P·H, Eq. 3) and transpose (δcomb = Pᵀ·δz, Eq. 4 /
Alg. 1 lines 17–30), plus the offline tile extraction that feeds them.

TPU adaptation (DESIGN.md §2.4): CSR gather/scatter is VPU-hostile; instead
the propagation matrix is tiled into TILE×TILE *dense* blocks (MXU-shaped),
only nonzero tiles are stored, and the kernel contracts each nonzero tile
against the matching feature row-block on the MXU:

    out[r·T:(r+1)·T, :] += tile_vals[t] @ h[c·T:(c+1)·T, :]

Tiles are sorted by output block; the (row-major) grid revisits the same
output block for consecutive tiles of one run, accumulating in VMEM, and
flushes when the output block changes — the canonical TPU block-sparse
reduction pattern. Tile coordinates arrive via scalar prefetch
(PrefetchScalarGridSpec) so the index stream is resident before the DMA of
each tile.

Every dot whose operands are f32 contracts at `Precision.HIGHEST`
(`#tpu.contract_precision<fp32>`, see `_precision`), so the kernels compute
the same f32 sums as the COO engine's `segment_sum` to f32 rounding, not
one bf16 pass of the MXU, which is Mosaic's default.

The transpose kernel (`spmm_block_sparse_t`) reuses the SAME tile values:
it walks the tiles in a column-major order (a prefetched permutation into
`tile_vals`) and contracts each tile transposed (dot_general over dim 0),
accumulating into the *column* block — so the manual backward runs
block-sparse without storing a second copy of P.

The FUSED kernels (`spmm_block_sparse_fused` / `spmm_block_sparse_fused_t`)
additionally contract the dense layer weight in the same grid pass, so the
(rows, F_in)-sized aggregation intermediates never round-trip through HBM:

  forward   u[r] = z[r] @ W + b   with z[r] = Σ_run tile @ h[c]   (epilogue
            matmul on the run-flush: the z accumulator lives in VMEM and the
            (TILE, F_out) output block is produced in the same pass, with
            optional fused bias+ReLU; z is an optional second output for the
            backward's weight-gradient residual)
  backward  dcomb[c] += tileᵀ @ (du[r] @ Wᵀ)                      (prologue
            matmul per tile slot: du's row block is transformed to F_in
            inside the kernel, so the (rows, F_in) dz intermediate is never
            materialized; the MXU recompute per extra tile in a row block is
            the price, accounted by the `analysis.cost` ordering model)

Tile extraction (`build_tile_topology`) works directly on COO triples and
never materializes a dense (N, N) matrix: tiles are bucketed with one
`np.unique` over block keys and one flat-key scatter-add into the
(n_tiles·T·T,) value buffer — O(nnz + n_tiles·T²) memory, the block-sparse
footprint (multi-index `np.add.at` was 2-10× slower at large nnz; see
benchmarks/bench_kernels.py for the extraction timing record).

The engines behind one interface live in `repro.kernels.aggregate`; the
training path selects them via ``ModelConfig.agg``.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TILE = 128          # MXU-shaped adjacency tile
FEAT_BLOCK = 128    # feature columns per grid step


def resolve_interpret(interpret: bool | None) -> bool:
    """`interpret=None` auto-detect shared by every kernel entry point (the
    jitted ops.py wrappers AND direct callers): compiled on a TPU,
    interpreted on the CPU (the test platform — kernel bodies execute in
    Python for validation), and an error on any other backend, where these
    Mosaic kernels have no lowering and silently interpreting them would
    hide that the accelerator is not running them."""
    if interpret is not None:
        return interpret
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"the Pallas TPU kernels cannot run on backend {backend!r}: they "
        "compile for a TPU and are interpreted only on the CPU; use "
        "agg='coo' there, or pass interpret=True explicitly")


def _precision(*dtypes):
    """Contract precision of a kernel dot: `Precision.HIGHEST` when every
    operand is f32, which Mosaic lowers to `#tpu.contract_precision<fp32>`
    (an f32-accurate product, as the COO engine's f32 sums are); the
    default otherwise. Mosaic's default contracts f32 operands in one bf16
    pass. bf16 operands need no more; f64 runs only in interpret mode,
    which computes it exactly either way."""
    if all(jnp.dtype(d) == jnp.float32 for d in dtypes):
        return jax.lax.Precision.HIGHEST
    return None


def _acc_dtype(dtype) -> jnp.dtype:
    """VMEM accumulator dtype: f32 for f32/bf16 inputs (MXU-native), f64
    when the caller runs in f64 (interpret mode only — used by the exactness
    tests, where the fused engine must match the COO engine at 1e-12)."""
    return jnp.promote_types(dtype, jnp.float32)


# ----------------------------------------------------------------------
# Forward kernel: z = P · h
# ----------------------------------------------------------------------

def _kernel(rows_ref, cols_ref, vals_ref, h_ref, out_ref, acc_ref):
    """Grid: (num_feature_blocks, num_tiles) — tiles innermost so the output
    block for one row-run stays resident in VMEM."""
    t = pl.program_id(1)

    first_of_run = jnp.logical_or(
        t == 0, rows_ref[t] != rows_ref[jnp.maximum(t - 1, 0)])

    @pl.when(first_of_run)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(vals_ref[...], h_ref[...],
                            precision=_precision(vals_ref.dtype, h_ref.dtype),
                            preferred_element_type=acc_ref.dtype)

    last = t == pl.num_programs(1) - 1
    last_of_run = jnp.logical_or(
        last, rows_ref[t] != rows_ref[jnp.minimum(t + 1,
                                                  pl.num_programs(1) - 1)])

    @pl.when(last_of_run)
    def _():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def spmm_block_sparse(tile_rows, tile_cols, tile_vals, h, num_rows: int,
                      interpret: bool | None = None):
    """z = P_blocksparse · h.

    tile_rows/cols: (n_tiles,) int32 sorted by row; tile_vals: (n_tiles,T,T);
    h: (C, F) with C = num_col_blocks·T, F % FEAT_BLOCK == 0.
    num_rows: output rows (multiple of T). Rows with no tiles stay zero only
    if every row-block has ≥1 tile — callers pad with an explicit zero tile
    per empty row-block (build_tile_topology does this).
    interpret=None auto-detects (see `resolve_interpret`).
    """
    n_tiles = tile_rows.shape[0]
    f = h.shape[1]
    assert f % FEAT_BLOCK == 0 and num_rows % TILE == 0
    grid = (f // FEAT_BLOCK, n_tiles)

    return pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,      # tile_rows, tile_cols
            grid=grid,
            in_specs=[
                pl.BlockSpec((None, TILE, TILE),
                             lambda fb, t, rows, cols: (t, 0, 0)),
                pl.BlockSpec((TILE, FEAT_BLOCK),
                             lambda fb, t, rows, cols: (cols[t], fb)),
            ],
            out_specs=pl.BlockSpec((TILE, FEAT_BLOCK),
                                   lambda fb, t, rows, cols: (rows[t], fb)),
            scratch_shapes=[pltpu.VMEM((TILE, FEAT_BLOCK),
                                       _acc_dtype(h.dtype))],
        ),
        out_shape=jax.ShapeDtypeStruct((num_rows, f), h.dtype),
        interpret=resolve_interpret(interpret),
    )(tile_rows, tile_cols, tile_vals, h)


# ----------------------------------------------------------------------
# Transpose kernel: δcomb = Pᵀ · δz  (same tiles, column-major walk)
# ----------------------------------------------------------------------

def _kernel_t(out_ref_s, in_ref_s, perm_ref, vals_ref, dz_ref, out_ref,
              acc_ref):
    """Grid: (num_feature_blocks, num_tiles). The tile stream is sorted by
    Pᵀ's output block (= P's column block); `perm` points each stream slot
    at its tile in the forward `tile_vals`, so no transposed copy of P is
    ever stored. The contraction  valsᵀ @ dz  is a dot_general over dim 0
    of both operands (MXU-friendly, no in-kernel transpose)."""
    t = pl.program_id(1)

    first_of_run = jnp.logical_or(
        t == 0, out_ref_s[t] != out_ref_s[jnp.maximum(t - 1, 0)])

    @pl.when(first_of_run)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        vals_ref[...], dz_ref[...],
        dimension_numbers=(((0,), (0,)), ((), ())),
        precision=_precision(vals_ref.dtype, dz_ref.dtype),
        preferred_element_type=acc_ref.dtype)

    last = t == pl.num_programs(1) - 1
    last_of_run = jnp.logical_or(
        last, out_ref_s[t] != out_ref_s[jnp.minimum(t + 1,
                                                    pl.num_programs(1) - 1)])

    @pl.when(last_of_run)
    def _():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def spmm_block_sparse_t(t_out, t_in, t_perm, tile_vals, dz, num_cols: int,
                        interpret: bool | None = None):
    """δcomb = Pᵀ_blocksparse · δz, reusing the forward tile values.

    t_out:  (n_tiles,) int32 output (column) block per stream slot, sorted
            ascending — every column block must appear ≥ once (zero fillers).
    t_in:   (n_tiles,) int32 input (row) block of δz consumed per slot.
    t_perm: (n_tiles,) int32 index into tile_vals for each slot.
    tile_vals: (n_tiles, T, T) forward tile values (NOT transposed).
    dz: (R, F) with R = num_row_blocks·T, F % FEAT_BLOCK == 0.
    num_cols: output rows of the transpose product (multiple of T).
    interpret=None auto-detects (see `resolve_interpret`).
    """
    n_tiles = t_out.shape[0]
    f = dz.shape[1]
    assert f % FEAT_BLOCK == 0 and num_cols % TILE == 0
    grid = (f // FEAT_BLOCK, n_tiles)

    return pl.pallas_call(
        _kernel_t,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,      # t_out, t_in, t_perm
            grid=grid,
            in_specs=[
                pl.BlockSpec((None, TILE, TILE),
                             lambda fb, t, to, ti, tp: (tp[t], 0, 0)),
                pl.BlockSpec((TILE, FEAT_BLOCK),
                             lambda fb, t, to, ti, tp: (ti[t], fb)),
            ],
            out_specs=pl.BlockSpec((TILE, FEAT_BLOCK),
                                   lambda fb, t, to, ti, tp: (to[t], fb)),
            scratch_shapes=[pltpu.VMEM((TILE, FEAT_BLOCK),
                                       _acc_dtype(dz.dtype))],
        ),
        out_shape=jax.ShapeDtypeStruct((num_cols, f), dz.dtype),
        interpret=resolve_interpret(interpret),
    )(t_out, t_in, t_perm, tile_vals, dz)


# ----------------------------------------------------------------------
# Split-phase entry points: boundary tiles first, interior tiles second,
# so the boundary exchange can be issued between the two pallas_calls.
# ----------------------------------------------------------------------

class SplitSpec(NamedTuple):
    """Static description of the interior/boundary phase split of one
    partitioned graph's tile streams (uniform across partitions — the
    phase-aware padding in `pad_tile_topology_phased` makes it so).

    The RCM+halo-clustered layout (graph/reorder.py) packs every
    boundary-destined row into one contiguous tail run per partition, so a
    row threshold splits the forward stream and a column threshold splits
    the transpose stream. All four fields are plain python ints: phase
    boundaries are trace-time constants, the phased kernels below are
    ordinary static slices of the prefetched streams.
    """

    row_tail: int       # first forward boundary-phase output row (B0·T)
    col_tail: int       # first transpose boundary-phase output row (HB0·T)
    fwd_bnd_tiles: int  # boundary-suffix length of the forward stream
    t_bnd_tiles: int    # boundary-suffix length of the transpose stream


def spmm_block_sparse_phased(tile_rows, tile_cols, tile_vals, h,
                             num_rows: int, n_bnd: int, phase: str,
                             interpret: bool | None = None):
    """One phase of z = P·h: the boundary phase runs the last `n_bnd`
    stream slots (output row blocks ≥ row_tail//T — the halo-clustered
    tail runs), the interior phase runs the rest. The output has the FULL
    (num_rows, f) shape but only the phase's own row blocks are written:
    rows outside the phase are UNSPECIFIED (not zero) and must never be
    read — callers combine the two phases' row ranges before any
    cross-row reduction. Running boundary then interior touches each
    output block exactly once, so the pair costs the same tile work as
    one unsplit pass.
    """
    n = tile_rows.shape[0]
    if not 0 < n_bnd < n:
        raise ValueError(f"phase split needs 0 < n_bnd < n_tiles, got "
                         f"{n_bnd}/{n}")
    sl = _phase_slice(n, n_bnd, phase)
    return spmm_block_sparse(tile_rows[sl], tile_cols[sl], tile_vals[sl],
                             h, num_rows, interpret)


def spmm_block_sparse_t_phased(t_out, t_in, t_perm, tile_vals, dz,
                               num_cols: int, n_bnd: int, phase: str,
                               interpret: bool | None = None):
    """One phase of δcomb = Pᵀ·δz. The transpose boundary phase is the
    last `n_bnd` slots of the column-major stream: output rows ≥
    col_tail — the inner tail feeding the gradient send plus the halo
    rows themselves. `tile_vals` is passed whole (t_perm indexes the full
    array); only the slot streams are sliced. Same unspecified-rows
    contract as the forward phases.
    """
    n = t_out.shape[0]
    if not 0 < n_bnd < n:
        raise ValueError(f"phase split needs 0 < n_bnd < n_tiles, got "
                         f"{n_bnd}/{n}")
    sl = _phase_slice(n, n_bnd, phase)
    return spmm_block_sparse_t(t_out[sl], t_in[sl], t_perm[sl], tile_vals,
                               dz, num_cols, interpret)


def _phase_slice(n: int, n_bnd: int, phase: str) -> slice:
    if phase == "boundary":
        return slice(n - n_bnd, n)
    if phase == "interior":
        return slice(0, n - n_bnd)
    raise ValueError(f"phase must be 'boundary' or 'interior', got {phase!r}")


def boundary_rdma_supported() -> bool:
    """Whether the in-kernel RDMA boundary push is available. The split
    schedule itself is backend-agnostic (the collective is issued between
    the two phases either way); on real TPU the send can additionally be
    initiated from inside the boundary-phase kernel via
    `start_boundary_rdma` so it overlaps even the boundary flush."""
    return jax.default_backend() == "tpu"


def start_boundary_rdma(src_ref, dst_ref, send_sem, recv_sem, neighbor):
    """Start an async device-to-device copy of gathered boundary rows
    (TPU-only follow-up path; the interpret-mode schedule uses the XLA
    collective between the phases instead). Returns the started copy —
    callers `.wait()` at the next sync point, after the interior phase.
    """
    if not boundary_rdma_supported():
        raise NotImplementedError(
            "in-kernel RDMA needs a real TPU backend; the split-phase "
            "schedule falls back to the XLA collective between phases")
    copy = pltpu.make_async_remote_copy(
        src_ref=src_ref, dst_ref=dst_ref, send_sem=send_sem,
        recv_sem=recv_sem, device_id=(neighbor,),
        device_id_type=pltpu.DeviceIdType.LOGICAL)
    copy.start()
    return copy


# ----------------------------------------------------------------------
# Fused aggregate+transform kernels: the dense weight contraction happens
# in the SAME grid pass as the block-sparse aggregation, so the
# (rows, F_in)-sized intermediates (z forward, du·Wᵀ backward) never
# round-trip through HBM between two ops.
# ----------------------------------------------------------------------

def _kernel_fused(rows_ref, cols_ref, vals_ref, h_ref, w_ref, b_ref,
                  u_ref, *rest, relu: bool, with_z: bool):
    """Grid: (n_tiles,). The z-accumulator holds one output row block over
    the FULL (padded) F_in axis in VMEM; on the last tile of a row run the
    epilogue matmul contracts it against the resident weight block and adds
    the bias (u = acc @ W + b, optional ReLU) straight into the (TILE,
    F_out) output block — also VMEM-resident across the run — so z is never
    read back from HBM for the transform. With `with_z` the accumulator is
    additionally flushed as a second output (the residual the training
    backward needs for the weight gradient)."""
    if with_z:
        z_ref, acc_ref = rest
    else:
        (acc_ref,) = rest
    t = pl.program_id(0)

    first_of_run = jnp.logical_or(
        t == 0, rows_ref[t] != rows_ref[jnp.maximum(t - 1, 0)])

    @pl.when(first_of_run)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(vals_ref[...], h_ref[...],
                            precision=_precision(vals_ref.dtype, h_ref.dtype),
                            preferred_element_type=acc_ref.dtype)

    last = t == pl.num_programs(0) - 1
    last_of_run = jnp.logical_or(
        last, rows_ref[t] != rows_ref[jnp.minimum(t + 1,
                                                  pl.num_programs(0) - 1)])

    @pl.when(last_of_run)
    def _():
        u = jnp.dot(acc_ref[...], w_ref[...],
                    precision=_precision(acc_ref.dtype, w_ref.dtype),
                    preferred_element_type=acc_ref.dtype) + b_ref[...]
        if relu:
            u = jnp.maximum(u, 0)
        u_ref[...] = u.astype(u_ref.dtype)
        if with_z:
            z_ref[...] = acc_ref[...].astype(z_ref.dtype)


def spmm_block_sparse_fused(tile_rows, tile_cols, tile_vals, h, w, b,
                            num_rows: int, relu: bool = False,
                            with_z: bool = True,
                            interpret: bool | None = None):
    """Fused u = (P_blocksparse · h) @ w + b (optional ReLU epilogue).

    h: (C, F_in), w: (F_in, F_out), b: (1, F_out); C and num_rows multiples
    of TILE, F_in/F_out multiples of FEAT_BLOCK (zero-padded by the engine).
    Returns (u, z) with z = P·h when `with_z` (the backward residual),
    else (u, None). VMEM per grid step is one (TILE, F_in) accumulator +
    the (F_in, F_out) weight + one (TILE, F_out) output block — GCN layer
    widths (≤ a few thousand features) fit comfortably in 16 MB.
    """
    n_tiles = tile_rows.shape[0]
    fin = h.shape[1]
    fout = w.shape[1]
    assert w.shape[0] == fin and b.shape == (1, fout)
    assert fin % FEAT_BLOCK == 0 and fout % FEAT_BLOCK == 0
    assert num_rows % TILE == 0
    acc = _acc_dtype(h.dtype)

    out_shape = [jax.ShapeDtypeStruct((num_rows, fout), h.dtype)]
    out_specs = [pl.BlockSpec((TILE, fout),
                              lambda t, rows, cols: (rows[t], 0))]
    if with_z:
        out_shape.append(jax.ShapeDtypeStruct((num_rows, fin), h.dtype))
        out_specs.append(pl.BlockSpec((TILE, fin),
                                      lambda t, rows, cols: (rows[t], 0)))

    outs = pl.pallas_call(
        partial(_kernel_fused, relu=relu, with_z=with_z),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,      # tile_rows, tile_cols
            grid=(n_tiles,),
            in_specs=[
                pl.BlockSpec((None, TILE, TILE),
                             lambda t, rows, cols: (t, 0, 0)),
                pl.BlockSpec((TILE, fin),
                             lambda t, rows, cols: (cols[t], 0)),
                pl.BlockSpec((fin, fout), lambda t, rows, cols: (0, 0)),
                pl.BlockSpec((1, fout), lambda t, rows, cols: (0, 0)),
            ],
            out_specs=out_specs,
            scratch_shapes=[pltpu.VMEM((TILE, fin), acc)],
        ),
        out_shape=out_shape,
        interpret=resolve_interpret(interpret),
    )(tile_rows, tile_cols, tile_vals, h, w, b)
    return (outs[0], outs[1]) if with_z else (outs[0], None)


def _kernel_fused_t(out_ref_s, in_ref_s, perm_ref, vals_ref, du_ref, w_ref,
                    out_ref, acc_ref):
    """Grid: (n_tiles,), column-major tile walk (see `_kernel_t`). Each slot
    transforms its du row block to F_in as a PROLOGUE (du @ Wᵀ via
    dot_general over the F_out axes of both operands — no transposed W is
    materialized) and contracts the tile transposed against the result, so
    the (rows, F_in) dz intermediate never exists in HBM. A row block
    revisited by k tiles pays the prologue k times — MXU FLOPs traded for
    an HBM round-trip, priced by the `analysis.cost` ordering model."""
    t = pl.program_id(0)

    first_of_run = jnp.logical_or(
        t == 0, out_ref_s[t] != out_ref_s[jnp.maximum(t - 1, 0)])

    @pl.when(first_of_run)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    dz = jax.lax.dot_general(           # (TILE, F_out) @ (F_in, F_out)ᵀ
        du_ref[...], w_ref[...],
        dimension_numbers=(((1,), (1,)), ((), ())),
        precision=_precision(du_ref.dtype, w_ref.dtype),
        preferred_element_type=acc_ref.dtype)
    acc_ref[...] += jax.lax.dot_general(
        vals_ref[...], dz,
        dimension_numbers=(((0,), (0,)), ((), ())),
        precision=_precision(vals_ref.dtype, dz.dtype),
        preferred_element_type=acc_ref.dtype)

    last = t == pl.num_programs(0) - 1
    last_of_run = jnp.logical_or(
        last, out_ref_s[t] != out_ref_s[jnp.minimum(t + 1,
                                                    pl.num_programs(0) - 1)])

    @pl.when(last_of_run)
    def _():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def spmm_block_sparse_fused_t(t_out, t_in, t_perm, tile_vals, du, w,
                              num_cols: int, interpret: bool | None = None):
    """Fused δcomb = Pᵀ_blocksparse · (du @ wᵀ), reusing forward tiles.

    du: (R, F_out), w: (F_in, F_out); R and num_cols multiples of TILE,
    F_in/F_out multiples of FEAT_BLOCK. The transpose stream (t_out sorted,
    ≥1 tile per column block via zero fillers) is the same one
    `spmm_block_sparse_t` consumes.
    """
    n_tiles = t_out.shape[0]
    fout = du.shape[1]
    fin = w.shape[0]
    assert w.shape[1] == fout
    assert fin % FEAT_BLOCK == 0 and fout % FEAT_BLOCK == 0
    assert num_cols % TILE == 0

    return pl.pallas_call(
        _kernel_fused_t,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,      # t_out, t_in, t_perm
            grid=(n_tiles,),
            in_specs=[
                pl.BlockSpec((None, TILE, TILE),
                             lambda t, to, ti, tp: (tp[t], 0, 0)),
                pl.BlockSpec((TILE, fout),
                             lambda t, to, ti, tp: (ti[t], 0)),
                pl.BlockSpec((fin, fout), lambda t, to, ti, tp: (0, 0)),
            ],
            out_specs=pl.BlockSpec((TILE, fin),
                                   lambda t, to, ti, tp: (to[t], 0)),
            scratch_shapes=[pltpu.VMEM((TILE, fin), _acc_dtype(du.dtype))],
        ),
        out_shape=jax.ShapeDtypeStruct((num_cols, fin), du.dtype),
        interpret=resolve_interpret(interpret),
    )(t_out, t_in, t_perm, tile_vals, du, w)


# ----------------------------------------------------------------------
# Tile extraction (numpy, offline preprocessing — never densifies)
# ----------------------------------------------------------------------

class TileTopology(NamedTuple):
    """Block-sparse topology of one propagation shard, for P and Pᵀ.

    The forward stream (rows/cols/vals) is GROUPED by row_block (ascending
    runs — the kernels' flush contract) with the col_blocks of each run
    serpentine (ascending in even runs, descending in odd ones — see
    `_run_major_order`; do NOT assume cols ascend within a run); the
    transpose stream (t_out/t_in/t_perm) walks the SAME vals array grouped
    by col_block via `t_perm`, rows serpentine likewise. Both streams
    carry ≥1 tile per output block (zero fillers) so every output block
    gets flushed.
    """

    rows: np.ndarray        # (n_tiles,) int32 row block, sorted
    cols: np.ndarray        # (n_tiles,) int32 col block
    vals: np.ndarray        # (n_tiles, T, T) float32
    t_out: np.ndarray       # (n_tiles,) int32 Pᵀ output block, sorted
    t_in: np.ndarray        # (n_tiles,) int32 Pᵀ input (δz) block
    t_perm: np.ndarray      # (n_tiles,) int32 index into vals
    num_row_blocks: int
    num_col_blocks: int

    @property
    def n_tiles(self) -> int:
        return len(self.rows)


def build_tile_topology(row, col, val, num_rows: int, num_cols: int,
                        tile: int = TILE) -> TileTopology:
    """Bucket a COO triple into TILE×TILE tiles without densifying.

    Memory is O(nnz + n_tiles·T²) — the block-sparse footprint itself —
    never O(num_rows·num_cols). Explicit zeros (padded edges) are dropped.
    Zero filler tiles are appended for row blocks with no tiles (so the
    forward kernel flushes them) and for column blocks with no tiles (so
    the transpose kernel flushes those).
    """
    row = np.asarray(row, np.int64)
    col = np.asarray(col, np.int64)
    val = np.asarray(val, np.float32)
    keep = val != 0
    row, col, val = row[keep], col[keep], val[keep]

    nrb = -(-num_rows // tile)
    ncb = -(-num_cols // tile)
    key = (row // tile) * ncb + (col // tile)
    uk, inv = np.unique(key, return_inverse=True)
    # Scatter-add over FLATTENED (tile, r%T, c%T) keys into a flat f32
    # buffer: multi-index np.add.at was the preprocessing bottleneck at
    # large nnz (2-10x slower — the fancy-index ufunc loop), and
    # np.bincount(weights=...) loses to the flat add.at on every measured
    # regime because it allocates an f64 output of n_tiles·T² bins before
    # the f32 cast (see benchmarks/bench_kernels.run_tile_extraction).
    # Duplicate (r, c) entries still sum, matching COO semantics.
    flat = (inv.astype(np.int64) * (tile * tile)
            + (row % tile) * tile + (col % tile))
    vals = np.zeros(len(uk) * tile * tile, np.float32)
    np.add.at(vals, flat, val)
    vals = vals.reshape(len(uk), tile, tile)
    rows = (uk // ncb).astype(np.int32)
    cols = (uk % ncb).astype(np.int32)

    fill_r, fill_c = _fillers(rows, cols, nrb, ncb)
    if len(fill_r) or len(fill_c):
        rows = np.concatenate([rows, fill_r,
                               np.zeros(len(fill_c), np.int32)])
        cols = np.concatenate([cols, np.zeros(len(fill_r), np.int32),
                               fill_c])
        vals = np.concatenate(
            [vals, np.zeros((len(fill_r) + len(fill_c), tile, tile),
                            np.float32)])

    # Run-major ordering with a serpentine minor axis: the stream stays
    # grouped by output block (the kernels' flush contract — rows ascending
    # for P, cols ascending for Pᵀ), but the input-block order alternates
    # direction between consecutive runs. The last input block of one run
    # then tends to equal the first of the next, and Pallas skips the
    # input-block DMA whenever the block index is unchanged between
    # consecutive grid steps — longer flush-free, fetch-free sequences on a
    # bandwidth-reduced layout whose runs overlap near the diagonal. Any
    # within-run order is valid (the accumulator is per run), so this only
    # permutes the floating-point accumulation order.
    order = _run_major_order(rows, cols)
    rows, cols, vals = rows[order], cols[order], vals[order]
    t_perm = _run_major_order(cols, rows).astype(np.int32)
    return TileTopology(rows=rows, cols=cols, vals=vals,
                        t_out=cols[t_perm], t_in=rows[t_perm], t_perm=t_perm,
                        num_row_blocks=nrb, num_col_blocks=ncb)


def _fillers(rows, cols, nrb: int, ncb: int):
    """Zero filler tiles: one per empty row block (so the forward kernel
    flushes it) and one per empty column block (so the transpose kernel
    does)."""
    return (np.setdiff1d(np.arange(nrb, dtype=np.int32), rows),
            np.setdiff1d(np.arange(ncb, dtype=np.int32), cols))


def tile_blocks(row, col, val, num_rows: int, num_cols: int,
                tile: int = TILE) -> tuple[np.ndarray, np.ndarray]:
    """(row block, col block) of every tile `build_tile_topology` stores
    for this COO triple, fillers included, in no particular order — its
    stream before padding, from the indices alone: no tile values are
    made."""
    keep = np.asarray(val) != 0
    row = np.asarray(row, np.int64)[keep]
    col = np.asarray(col, np.int64)[keep]
    nrb = -(-num_rows // tile)
    ncb = -(-num_cols // tile)
    uk = np.unique((row // tile) * ncb + (col // tile))
    rows = (uk // ncb).astype(np.int32)
    cols = (uk % ncb).astype(np.int32)
    fill_r, fill_c = _fillers(rows, cols, nrb, ncb)
    return (np.concatenate([rows, fill_r, np.zeros(len(fill_c), np.int32)]),
            np.concatenate([cols, np.zeros(len(fill_r), np.int32), fill_c]))


def _run_major_order(major, minor) -> np.ndarray:
    """Sort by `major` ascending (run grouping), `minor` serpentine: minor
    ascends in even runs and descends in odd runs (run parity = rank of the
    major value among the distinct majors present)."""
    _, inv = np.unique(major, return_inverse=True)
    minor = minor.astype(np.int64)
    return np.lexsort((np.where(inv % 2 == 1, -minor, minor), major))


def pad_tile_topology(tt: TileTopology, n_tiles: int) -> TileTopology:
    """Pad the tile streams to `n_tiles` with zero tiles (uniform shapes
    across partitions for SPMD stacking). Padding appends zero tiles at the
    tail of both streams pointing at the last output block of each, which
    preserves sortedness and adds exact zeros."""
    k = n_tiles - tt.n_tiles
    if k < 0:
        raise ValueError(f"cannot shrink tile topology {tt.n_tiles}->{n_tiles}")
    if k == 0:
        return tt
    tile = tt.vals.shape[-1]
    pad_i = np.arange(tt.n_tiles, tt.n_tiles + k, dtype=np.int32)
    return TileTopology(
        rows=np.concatenate([tt.rows, np.full(k, tt.rows[-1], np.int32)]),
        cols=np.concatenate([tt.cols, np.zeros(k, np.int32)]),
        vals=np.concatenate([tt.vals, np.zeros((k, tile, tile), np.float32)]),
        t_out=np.concatenate([tt.t_out, np.full(k, tt.t_out[-1], np.int32)]),
        t_in=np.concatenate([tt.t_in, np.zeros(k, np.int32)]),
        t_perm=np.concatenate([tt.t_perm, pad_i]),
        num_row_blocks=tt.num_row_blocks, num_col_blocks=tt.num_col_blocks)


def pad_tile_topology_phased(tt: TileTopology, b0: int, hb0: int,
                             n_int_f: int, n_bnd_f: int,
                             n_int_t: int, n_bnd_t: int) -> TileTopology:
    """Pad each PHASE GROUP of both streams independently to the given
    uniform lengths (cross-partition maxima), so the interior/boundary
    suffix split lands at the same static slot in every partition's
    stream and the phased kernels can slice with trace-time constants.

    The forward stream is cut at the first slot with row block ≥ `b0`,
    the transpose stream at the first slot with col block ≥ `hb0`. Pads
    are zero tiles appended at the END of their group, addressed at the
    group's LAST output block so run grouping stays intact in both
    streams (interior fwd pads: row b0-1; boundary fwd pads: row nrb-1;
    interior transpose pads: col hb0-1; boundary transpose pads: col
    ncb-1 — every output block carries ≥1 real-or-filler tile, so those
    runs exist). A pad occupies one slot in EACH stream; its (row, col)
    pair is chosen from the four group combinations so both streams pad
    to their target group lengths with one shared vals entry. The
    concatenated [interior; boundary] streams remain valid inputs for
    the unsplit kernels — zero tiles add exact 0.0, so split and unsplit
    schedules on the same padded topology are bit-identical.
    """
    cut_f = int(np.searchsorted(tt.rows, b0))
    cut_t = int(np.searchsorted(tt.t_out, hb0))
    fi = n_int_f - cut_f                       # fwd interior pads
    fb = n_bnd_f - (tt.n_tiles - cut_f)        # fwd boundary pads
    ti = n_int_t - cut_t                       # transpose interior pads
    tb = n_bnd_t - (tt.n_tiles - cut_t)        # transpose boundary pads
    if min(fi, fb, ti, tb) < 0 or fi + fb != ti + tb:
        raise ValueError(f"inconsistent phase pad targets: "
                         f"{(fi, fb, ti, tb)} for {tt.n_tiles} tiles")
    if fi + fb == 0:
        return tt
    # Pair the group memberships: bb pads sit in both boundary groups,
    # then leftovers pair boundary-with-interior, the rest is (int, int).
    bb = min(fb, tb)
    bi = fb - bb            # (fwd boundary, transpose interior)
    ib = tb - bb            # (fwd interior, transpose boundary)
    ii = fi - ib
    tile = tt.vals.shape[-1]
    nrb, ncb = tt.num_row_blocks, tt.num_col_blocks
    # Pad coordinates in fwd-stream placement order: interior group tail
    # first (ii + ib pads), then boundary group tail (bi + bb pads).
    pad_rows = np.array([b0 - 1] * (ii + ib) + [nrb - 1] * (bi + bb),
                        np.int32)
    pad_cols = np.array([hb0 - 1] * ii + [ncb - 1] * ib
                        + [hb0 - 1] * bi + [ncb - 1] * bb, np.int32)
    rows = np.concatenate([tt.rows[:cut_f], pad_rows[:fi],
                           tt.rows[cut_f:], pad_rows[fi:]])
    cols = np.concatenate([tt.cols[:cut_f], pad_cols[:fi],
                           tt.cols[cut_f:], pad_cols[fi:]])
    zi = np.zeros((fi, tile, tile), np.float32)
    zb = np.zeros((fb, tile, tile), np.float32)
    vals = np.concatenate([tt.vals[:cut_f], zi, tt.vals[cut_f:], zb])
    # Original slot i of the unpadded vals now lives at remap[i]; pads at
    # pad_idx (fwd placement order, aligned with pad_rows/pad_cols).
    remap = np.arange(tt.n_tiles, dtype=np.int64)
    remap[cut_f:] += fi
    pad_idx = np.concatenate([
        np.arange(cut_f, cut_f + fi, dtype=np.int64),
        np.arange(tt.n_tiles + fi, tt.n_tiles + fi + fb, dtype=np.int64)])
    t_int_pads = np.concatenate([pad_idx[:ii], pad_idx[fi:fi + bi]])
    t_bnd_pads = np.concatenate([pad_idx[ii:fi], pad_idx[fi + bi:]])
    t_perm = np.concatenate([remap[tt.t_perm[:cut_t]], t_int_pads,
                             remap[tt.t_perm[cut_t:]],
                             t_bnd_pads]).astype(np.int32)
    return TileTopology(
        rows=rows, cols=cols, vals=vals,
        t_out=cols[t_perm], t_in=rows[t_perm], t_perm=t_perm,
        num_row_blocks=nrb, num_col_blocks=ncb)


def build_tiles(dense_or_coo, num_rows: int, num_cols: int,
                tile: int = TILE):
    """Legacy forward-only extraction: (tile_rows, tile_cols, tile_vals).

    Accepts a dense (R, C) matrix or a (row, col, val) COO triple. The COO
    path never densifies (see build_tile_topology); the dense path simply
    converts the caller's existing matrix to COO first.
    """
    if isinstance(dense_or_coo, tuple):
        row, col, val = dense_or_coo
    else:
        dense = np.asarray(dense_or_coo)
        row, col = np.nonzero(dense)
        val = dense[row, col]
    tt = build_tile_topology(row, col, val, num_rows, num_cols, tile)
    return tt.rows, tt.cols, tt.vals


def tile_density(tile_rows, num_rows: int, num_cols: int,
                 tile: int = TILE) -> float:
    """Fraction of tiles stored vs the dense tile grid."""
    nrb = -(-num_rows // tile)
    ncb = -(-num_cols // tile)
    return len(tile_rows) / float(nrb * ncb)
