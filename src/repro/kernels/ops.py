"""Jitted public wrappers for the Pallas kernels.

`interpret` defaults to auto (`gcn_spmm.resolve_interpret`): compiled on a
TPU, interpreted on the CPU (the test platform — kernel bodies execute in
Python for validation), and an error on any other backend.
"""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels import flash_attention as _fa
from repro.kernels import gcn_spmm as _spmm


@partial(jax.jit, static_argnames=("num_rows", "interpret"))
def spmm(tile_rows, tile_cols, tile_vals, h, num_rows: int,
         interpret: bool | None = None):
    """Block-sparse aggregation z = P·h (see gcn_spmm.py)."""
    return _spmm.spmm_block_sparse(tile_rows, tile_cols, tile_vals, h,
                                   num_rows, interpret=interpret)


@partial(jax.jit, static_argnames=("num_cols", "interpret"))
def spmm_t(t_out, t_in, t_perm, tile_vals, dz, num_cols: int,
           interpret: bool | None = None):
    """Block-sparse transpose aggregation δcomb = Pᵀ·δz (see gcn_spmm.py)."""
    return _spmm.spmm_block_sparse_t(t_out, t_in, t_perm, tile_vals, dz,
                                     num_cols, interpret=interpret)


@partial(jax.jit, static_argnames=("num_rows", "n_bnd", "phase", "interpret"))
def spmm_phased(tile_rows, tile_cols, tile_vals, h, num_rows: int,
                n_bnd: int, phase: str, interpret: bool | None = None):
    """One phase (interior | boundary) of z = P·h — static suffix/prefix
    slice of the tile stream; out-of-phase rows are unspecified (see
    gcn_spmm.spmm_block_sparse_phased)."""
    return _spmm.spmm_block_sparse_phased(tile_rows, tile_cols, tile_vals,
                                          h, num_rows, n_bnd, phase,
                                          interpret=interpret)


@partial(jax.jit, static_argnames=("num_cols", "n_bnd", "phase", "interpret"))
def spmm_t_phased(t_out, t_in, t_perm, tile_vals, dz, num_cols: int,
                  n_bnd: int, phase: str, interpret: bool | None = None):
    """One phase of δcomb = Pᵀ·δz (see gcn_spmm.spmm_block_sparse_t_phased)."""
    return _spmm.spmm_block_sparse_t_phased(t_out, t_in, t_perm, tile_vals,
                                            dz, num_cols, n_bnd, phase,
                                            interpret=interpret)


@partial(jax.jit, static_argnames=("num_rows", "relu", "with_z", "interpret"))
def spmm_fused(tile_rows, tile_cols, tile_vals, h, w, b, num_rows: int,
               relu: bool = False, with_z: bool = True,
               interpret: bool | None = None):
    """Fused u = (P·h)@w + b (+ReLU), z optional (see gcn_spmm.py)."""
    return _spmm.spmm_block_sparse_fused(tile_rows, tile_cols, tile_vals,
                                         h, w, b, num_rows, relu=relu,
                                         with_z=with_z, interpret=interpret)


@partial(jax.jit, static_argnames=("num_cols", "interpret"))
def spmm_fused_t(t_out, t_in, t_perm, tile_vals, du, w, num_cols: int,
                 interpret: bool | None = None):
    """Fused δcomb = Pᵀ·(du@wᵀ), prologue matmul (see gcn_spmm.py)."""
    return _spmm.spmm_block_sparse_fused_t(t_out, t_in, t_perm, tile_vals,
                                           du, w, num_cols,
                                           interpret=interpret)


@partial(jax.jit, static_argnames=("causal", "window", "q_block", "kv_block",
                                   "interpret"))
def attention(q, k, v, causal: bool = True, window: int = 0,
              q_block: int = _fa.DEFAULT_Q_BLOCK,
              kv_block: int = _fa.DEFAULT_KV_BLOCK,
              interpret: bool | None = None):
    """Flash GQA attention (see flash_attention.py)."""
    return _fa.flash_attention(q, k, v, causal=causal, window=window,
                               q_block=q_block, kv_block=kv_block,
                               interpret=interpret)


build_tiles = _spmm.build_tiles
build_tile_topology = _spmm.build_tile_topology
tile_density = _spmm.tile_density
