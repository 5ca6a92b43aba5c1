"""PipeGCN core: partition-parallel full-graph GCN training with pipelined
(one-iteration-deferred) boundary feature / feature-gradient communication,
per the paper's Alg. 1 and Eq. 3–4, plus the §3.4 EMA smoothing.

Design notes
------------
* Staleness in feature *gradients* breaks `jax.grad` semantics (a cotangent
  produced at iteration t must be applied at t+1 on a different device), so —
  exactly like the paper's Alg. 1 — the backward pass is written by hand.
  With ``PipeConfig.vanilla()`` the same code performs synchronous exchanges
  and is verified against ``jax.grad`` of a pure forward to float64 tolerance.

* One implementation, two backends:
    - ``sim``  : partitions as a leading axis; exchange = transpose. 1 device.
    - ``spmd`` : runs inside ``jax.shard_map``; exchange = ``lax.all_to_all``.
  The layer math is shared; only the 4 sync points differ (feature exchange,
  gradient exchange, weight-grad reduce, loss reduce).

* The aggregation SpMM (Eq. 3 forward, Eq. 4 transpose) is pluggable:
  ``ModelConfig.agg`` selects between the padded-COO ``segment_sum`` engine
  ("coo", the verified fallback), the MXU-shaped Pallas block-sparse engine
  ("blocksparse"), and the fused aggregate⊗transform engine ("fused", which
  contracts the dense layer weight in the same Pallas grid pass — see
  repro.kernels.gcn_spmm / aggregate). The tile engines need tile streams
  on the Topology — ``topology_from(pg, with_tiles=True)`` attaches them.
  All engines run under both backends; the layer math never sees the
  storage format.

* The layer matmul ORDER is itself a knob (``ModelConfig.matmul_order``):
  aggregate-first (z = P·H then z·W, the paper's Eq. 3 order),
  transform-first (H·W then P·(H·W) — cheaper when F_out < F_in), or
  "auto", which resolves per layer from the static FLOP model in
  ``repro.analysis.cost`` (``layer_orders``). Under transform-first the
  aggregation residual z is never materialized; the weight gradient is
  computed as combᵀ·(Pᵀ·du) instead of zᵀ·du.

* Pipeline state (the "stale buffers") is explicit and threaded through the
  step function — this is what makes the deferred collectives free of data
  dependence on current-iteration compute (the XLA scheduler can overlap
  them, which is the TPU-native analogue of the paper's second cudaStream).

* Fused deferred exchange (``PipeConfig.fuse_exchange``, default on): in
  stale mode no current-step compute consumes the exchange results, so the
  per-layer sends are packed along the feature axis (static offset table,
  see ``pack_offsets``) and shipped in ONE collective after the forward
  plus ONE after the backward — 2 per step instead of 2L-1 — with the
  unpacked results landing straight in the t+1 FIFOs/EMA buffers. Packing
  commutes with the exchange (pure data movement), so the schedules are
  bit-identical; vanilla mode keeps the blocking per-layer exchange.

State layout (per layer ℓ = 1..L; widths follow the layer inputs):
  feat_buf[ℓ] : (P*slot, F_{ℓ-1})  stale boundary features   (Eq. 3 h^(t-1))
  grad_buf[ℓ] : (max_inner, F_{ℓ-1}) stale boundary-gradient contributions,
                already exchanged+scattered to owner rows    (Eq. 4 δ^(t-1))
With smoothing on, the same buffers hold the EMA (γ·old + (1−γ)·fresh);
receiver-side EMA is equivalent to the paper's per-node EMA because the
exchange+scatter is a fixed linear map.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.codec import fused_exchange_encoded, make_codec
from repro.core.config import ModelConfig, PipeConfig
from repro.core.faults import BWD, FWD, apply_faults
from repro.graph.halo import PartitionedGraph, extract_partition_tiles
from repro.kernels.aggregate import get_engine
from repro.kernels.gcn_spmm import TILE, SplitSpec


class Topology(NamedTuple):
    """Device-ready padded partition topology (leading axis = partition).

    The COO fields are always present; the `tile_*` fields (block-sparse
    streams for the Pallas engine, see repro.kernels.gcn_spmm) are attached
    by ``topology_from(pg, with_tiles=True)`` and stay None otherwise —
    None fields are empty pytree subtrees, so every jit/shard_map/tree_map
    over a Topology works unchanged with or without tiles.
    """

    edge_row: jax.Array    # (P, max_nnz) int32
    edge_col: jax.Array    # (P, max_nnz) int32 (combined-array columns)
    edge_w: jax.Array      # (P, max_nnz) f32
    send_idx: jax.Array    # (P, P, slot) int32
    send_mask: jax.Array   # (P, P, slot) bool
    inner_mask: jax.Array  # (P, max_inner) bool
    tile_rows: jax.Array | None = None    # (P, n_tiles) int32
    tile_cols: jax.Array | None = None    # (P, n_tiles) int32
    tile_vals: jax.Array | None = None    # (P, n_tiles, T, T) f32
    tile_t_out: jax.Array | None = None   # (P, n_tiles) int32
    tile_t_in: jax.Array | None = None    # (P, n_tiles) int32
    tile_t_perm: jax.Array | None = None  # (P, n_tiles) int32

    @property
    def num_parts(self) -> int:
        # peer axis: works both with ((P), P, slot) and squeezed (P, slot)
        return self.send_idx.shape[-2]

    @property
    def max_inner(self) -> int:
        return self.inner_mask.shape[-1]

    @property
    def slot(self) -> int:
        return self.send_idx.shape[-1]

    @property
    def halo_size(self) -> int:
        return self.num_parts * self.slot


class ShardedData(NamedTuple):
    """Per-partition node data (leading axis = partition)."""

    x: jax.Array           # (P, max_inner, F)
    labels: jax.Array      # (P, max_inner) int32 or (P, max_inner, C) f32
    train_mask: jax.Array  # (P, max_inner) bool
    eval_mask: jax.Array   # (P, max_inner) bool (val or test)


def topology_from(pg: PartitionedGraph, with_tiles: bool = False) -> Topology:
    """Lift a PartitionedGraph to device arrays; `with_tiles=True` also
    extracts the block-sparse tile streams the "blocksparse" engine needs."""
    tiles = {}
    if with_tiles:
        pt = extract_partition_tiles(pg)
        tiles = dict(tile_rows=jnp.asarray(pt.rows),
                     tile_cols=jnp.asarray(pt.cols),
                     tile_vals=jnp.asarray(pt.vals),
                     tile_t_out=jnp.asarray(pt.t_out),
                     tile_t_in=jnp.asarray(pt.t_in),
                     tile_t_perm=jnp.asarray(pt.t_perm))
    return Topology(
        edge_row=jnp.asarray(pg.edge_row), edge_col=jnp.asarray(pg.edge_col),
        edge_w=jnp.asarray(pg.edge_w), send_idx=jnp.asarray(pg.send_idx),
        send_mask=jnp.asarray(pg.send_mask),
        inner_mask=jnp.asarray(pg.inner_mask), **tiles)


def shard_data(pg: PartitionedGraph, x, labels, train_mask, eval_mask) -> ShardedData:
    return ShardedData(
        x=jnp.asarray(pg.pack_nodes(np.asarray(x, np.float32))),
        labels=jnp.asarray(pg.pack_nodes(np.asarray(labels))),
        train_mask=jnp.asarray(pg.pack_nodes(np.asarray(train_mask))),
        eval_mask=jnp.asarray(pg.pack_nodes(np.asarray(eval_mask))))


# ----------------------------------------------------------------------
# Per-partition primitives (no partition axis; sim backend vmaps them).
# The SpMM itself (z = P·comb and δcomb = Pᵀ·δz) lives behind the
# aggregation-engine interface in repro.kernels.aggregate.
# ----------------------------------------------------------------------

def _gather_send(h, send_idx, send_mask):
    """(max_inner,F) -> (P, slot, F) payload for each peer."""
    p, slot = send_idx.shape
    out = h[send_idx.reshape(-1)].reshape(p, slot, -1)
    return jnp.where(send_mask[..., None], out, 0.0)


def _gather_send_tail(h_tail, send_idx, send_mask, row_tail):
    """`_gather_send` reading from a boundary-phase tail slice.

    `h_tail` holds only rows [row_tail, max_inner) of the layer output —
    exactly the rows the boundary phase produced. Every REAL send index is
    >= row_tail by construction of the split (`boundary_row_split`); padded
    (masked-out) slots carry index 0, which is clamped onto the first tail
    row and then zeroed by the mask, exactly like `_gather_send` does."""
    p, slot = send_idx.shape
    idx = jnp.maximum(send_idx.reshape(-1) - row_tail, 0)
    out = h_tail[idx].reshape(p, slot, -1)
    return jnp.where(send_mask[..., None], out, 0.0)


def split_spec_from(pg: PartitionedGraph, tile: int = TILE) -> SplitSpec | None:
    """The split-phase schedule spec of a partitioned graph, or None when
    the split is infeasible (P=1 / no sends / boundary rows not clustered
    into a proper tail — see `repro.graph.halo.boundary_row_split`). The
    tile-group sizes come from the same memoized `extract_partition_tiles`
    call that `topology_from(pg, with_tiles=True)` uses, so the phase cut
    and the padded tile streams are consistent by construction."""
    pt = extract_partition_tiles(pg, tile)
    if pt.fwd_bnd is None:
        return None
    return SplitSpec(row_tail=pt.b0 * tile, col_tail=pt.hb0 * tile,
                     fwd_bnd_tiles=pt.fwd_bnd, t_bnd_tiles=pt.t_bnd)


def _scatter_recv(contrib, send_idx, send_mask, max_inner):
    """(P, slot, F) received gradient blocks -> (max_inner, F) scatter-add."""
    p, slot, f = contrib.shape
    contrib = jnp.where(send_mask[..., None], contrib, 0.0)
    flat_idx = send_idx.reshape(-1)
    return jnp.zeros((max_inner, f), contrib.dtype).at[flat_idx].add(
        contrib.reshape(p * slot, f))


def _scatter_invalid_rows(inv, send_idx, max_inner):
    """(P, slot) invalid-contribution mask -> (max_inner,) owner rows whose
    `_scatter_recv` sum is incomplete (any contributing slot was invalid).
    Those rows fall back to the stale buffer wholesale — a partial sum is
    not one-step-stale data, it is wrong data."""
    return jnp.zeros((max_inner,), bool).at[send_idx.reshape(-1)].max(
        inv.reshape(-1))


# ----------------------------------------------------------------------
# Hierarchical exchange: P partitions on P // n_local devices.
#
# Partition p lives on device p // n_local (device-major layout, matching
# how a (P, ...) array shards over a 1-D mesh axis). Per device, the send
# tensor s[l, j] is the payload from co-resident partition l to global
# partition j. The exchange blocks the global P axis as (n_dev, n_local):
# the two local axes are permuted by pure reshapes/transposes (the
# co-resident partition pairs — including the whole exchange when
# n_dev == 1 — never touch the interconnect; XLA's AllToAll keeps the
# self-chunk in HBM) and only the device axis crosses the wire, in ONE
# all_to_all of (n_local x n_local) blocks. Boundary traffic per device
# stays O(P * slot * F) with no redundant self-sends.
# ----------------------------------------------------------------------

def _hier_pack(s, n_local):
    """(n_local, P, ...) send tensor -> (n_dev, l_src, l_dst, ...) blocks,
    device-major along axis 0 (the only axis the all_to_all splits)."""
    n_dev = s.shape[1] // n_local
    a = s.reshape((n_local, n_dev, n_local) + s.shape[2:])
    return jnp.swapaxes(a, 0, 1)


def _hier_unpack(recv, n_local):
    """(n_dev, l_src, l_dst, ...) received blocks -> (n_local, P, ...):
    row l = payloads addressed to co-resident partition l, indexed by
    global sender id (device-major, matching the send layout)."""
    n_dev = recv.shape[0]
    r = jnp.moveaxis(recv, 2, 0)
    return r.reshape((n_local, n_dev * n_local) + recv.shape[3:])


def hierarchical_exchange(s, axis_name, n_local):
    """Per-device exchange of (n_local, P, slot, F) boundary payloads:
    local shuffle (reshape/transpose) for co-resident partition pairs fused
    with a single inter-device all_to_all for the remote blocks."""
    blocks = _hier_pack(s, n_local)
    recv = jax.lax.all_to_all(blocks, axis_name, 0, 0, tiled=True)
    return _hier_unpack(recv, n_local)


def hierarchical_exchange_host(S):
    """Single-process reference evaluation of `hierarchical_exchange` on a
    global (n_dev, n_local, P, ...) payload with the device axis explicit:
    the all_to_all is replaced by its definition (device d's chunk j lands
    on device j at position d, i.e. a transpose of the two device axes)."""
    n_local = S.shape[1]
    blocks = jax.vmap(lambda s: _hier_pack(s, n_local))(S)
    recv = jnp.swapaxes(blocks, 0, 1)
    return jax.vmap(lambda r: _hier_unpack(r, n_local))(recv)


def flat_exchange_reference(S):
    """The flat global exchange R[i, j] = S[j, i] over global partition ids,
    reshaped to the same (n_dev, n_local, P, ...) device layout — the
    specification `hierarchical_exchange` must match."""
    n_dev, n_local, p = S.shape[:3]
    flat = S.reshape((n_dev * n_local, p) + S.shape[3:])
    return jnp.swapaxes(flat, 0, 1).reshape(S.shape)


# ----------------------------------------------------------------------
# Fused deferred exchange: packing per-layer payloads into one collective.
#
# In stale mode the exchanged boundary data is consumed only at step t+1,
# so the per-layer sends have no consumer inside the current step — they
# can be concatenated along the feature axis (layer widths differ; the
# offset table is static at trace time) and shipped in a single collective
# per direction. The exchange is pure data movement, so packing commutes
# with it exactly: fused and per-layer schedules are bit-identical.
# ----------------------------------------------------------------------

def pack_widths(payloads) -> tuple[int, ...]:
    """Static per-layer feature widths of a payload list (the pack layout)."""
    return tuple(int(p.shape[-1]) for p in payloads)


def pack_offsets(widths) -> tuple[int, ...]:
    """Static start offset of each layer's slice in the packed feature axis."""
    out, off = [], 0
    for w in widths:
        out.append(off)
        off += int(w)
    return tuple(out)


def pack_payloads(payloads):
    """Per-layer (..., P, slot, F_l) sends -> one (..., P, slot, ΣF_l)."""
    if len(payloads) == 1:
        return payloads[0]
    return jnp.concatenate(payloads, axis=-1)


def unpack_payloads(packed, widths):
    """Inverse of `pack_payloads` given the static width table."""
    if len(widths) == 1:
        return [packed]
    offsets = pack_offsets(widths)
    return [jax.lax.slice_in_dim(packed, o, o + w, axis=packed.ndim - 1)
            for o, w in zip(offsets, widths)]


# ----------------------------------------------------------------------
# Backends: the four sync points.
# ----------------------------------------------------------------------

class _ExchangeBase:
    """Shared fused-exchange, layered on each backend's `exchange`."""

    def fused_exchange(self, payloads):
        """Exchange a list of per-layer (..., P, slot, F_l) payloads in ONE
        collective: pack along the feature axis, exchange the packed buffer
        once, unpack at the static offsets. Exactly equivalent to
        [self.exchange(p) for p in payloads]."""
        recv = self.exchange(pack_payloads(payloads))
        return unpack_payloads(recv, pack_widths(payloads))


class SimBackend(_ExchangeBase):
    """Partitions as leading axis on a single device."""

    is_spmd = False
    lead_axis = True   # arrays carry a leading (local-)partition axis

    def pmap(self, f):
        return jax.vmap(f)

    def exchange(self, s):
        # s: (P_dev, P_peer, slot, F); R[i, j] = S[j, i]
        return jnp.swapaxes(s, 0, 1)

    def part_ids(self, num_parts):
        """Global partition id of every leading-axis slot (all P here)."""
        return jnp.arange(num_parts)

    def psum(self, x):
        return jnp.sum(x, axis=0)

    def psum_scalar(self, x):
        return jnp.sum(x)

    def dropout_mask(self, key, rate, shape_per_part, num_parts):
        shape = (num_parts,) + tuple(shape_per_part)
        keep = jax.random.bernoulli(key, 1.0 - rate, shape)
        return keep.astype(jnp.float32) / (1.0 - rate)


class SpmdBackend(_ExchangeBase):
    """Runs inside shard_map over `axis_name` (a mesh axis or tuple of axes
    — the production mesh flattens ("data","model") into the partition
    axis). With `n_local` > 1 each device hosts n_local co-resident
    partitions as a leading local axis (same layout the sim backend uses
    for all P), and the boundary exchange goes hierarchical: a local
    shuffle for co-resident pairs + one inter-device all_to_all."""

    is_spmd = True

    def __init__(self, axis_name="parts", n_local: int = 1):
        self.axis_name = axis_name
        self.n_local = n_local
        self.lead_axis = n_local > 1

    def pmap(self, f):
        return f

    def _global_part_offset(self):
        """Global partition id of this device's local partition 0."""
        return jax.lax.axis_index(self.axis_name) * self.n_local

    def part_ids(self, num_parts):
        """Global partition ids this device sends as: a traced scalar for
        the flat layout, a (n_local,) vector for co-resident partitions."""
        base = self._global_part_offset()
        if not self.lead_axis:
            return base
        return base + jnp.arange(self.n_local)

    def exchange(self, s):
        # s: (P, slot, F) per device, or (n_local, P, slot, F) when >1
        # partition is co-resident.
        if not self.lead_axis:
            return jax.lax.all_to_all(s, self.axis_name, 0, 0, tiled=True)
        return hierarchical_exchange(s, self.axis_name, self.n_local)

    def psum(self, x):
        if self.lead_axis:                 # fold co-resident partitions first
            x = jnp.sum(x, axis=0)
        return jax.lax.psum(x, self.axis_name)

    def psum_scalar(self, x):
        return jax.lax.psum(x, self.axis_name)

    def dropout_mask(self, key, rate, shape_per_part, num_parts):
        base = self._global_part_offset()
        if not self.lead_axis:
            key = jax.random.fold_in(key, base)
            keep = jax.random.bernoulli(key, 1.0 - rate, tuple(shape_per_part))
            return keep.astype(jnp.float32) / (1.0 - rate)
        # One independent stream per global partition id, so the mask a
        # partition sees is invariant to how partitions map onto devices.
        keys = jax.vmap(lambda l: jax.random.fold_in(key, base + l))(
            jnp.arange(self.n_local))
        keep = jax.vmap(
            lambda k: jax.random.bernoulli(k, 1.0 - rate,
                                           tuple(shape_per_part)))(keys)
        return keep.astype(jnp.float32) / (1.0 - rate)


# ----------------------------------------------------------------------
# Losses (masked, globally normalized).
# ----------------------------------------------------------------------

def _ce_loss_and_grad(logits, labels, mask, total, backend):
    """Masked softmax cross-entropy; returns (local_sum, dlogits)."""
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None].astype(jnp.int32),
                             axis=-1)[..., 0]
    loss_local = jnp.sum((lse - ll) * mask)
    probs = jax.nn.softmax(logits, axis=-1)
    onehot = jax.nn.one_hot(labels, logits.shape[-1], dtype=logits.dtype)
    dlogits = (probs - onehot) * mask[..., None] / total
    return loss_local, dlogits


def _bce_loss_and_grad(logits, labels, mask, total, backend):
    """Masked multi-label sigmoid BCE (Yelp-style); total counts node·class."""
    z, y = logits, labels.astype(logits.dtype)
    per = jnp.maximum(z, 0) - z * y + jnp.log1p(jnp.exp(-jnp.abs(z)))
    loss_local = jnp.sum(per * mask[..., None])
    dlogits = (jax.nn.sigmoid(z) - y) * mask[..., None] / total
    return loss_local, dlogits


# ----------------------------------------------------------------------
# The module.
# ----------------------------------------------------------------------

@dataclasses.dataclass
class PipeGCN:
    """Composable partition-parallel GCN with pipelined communication.

    All methods are pure; state (params / pipeline buffers / rng) is explicit
    so the step can be jitted, shard_mapped, scanned, and checkpointed.
    """

    model: ModelConfig
    pipe: PipeConfig
    # Split-phase overlap spec (ISSUE 6) — static trace-time constants from
    # `split_spec_from(pg)`; None disables the split regardless of
    # `pipe.overlap` (the schedule falls back to the unsplit `_step_impl`
    # body, e.g. for P=1 or layouts without a clustered boundary tail).
    split: SplitSpec | None = None

    # ---------------- parameters & state ----------------

    def init_params(self, key: jax.Array, dtype=jnp.float32) -> dict:
        params = {}
        for ell, (fin, fout) in enumerate(self.model.layer_dims()):
            fan_in = 2 * fin if self.model.kind == "sage" else fin
            key, sub = jax.random.split(key)
            scale = jnp.sqrt(2.0 / (fan_in + fout)).astype(dtype)
            params[f"w{ell}"] = jax.random.normal(sub, (fan_in, fout), dtype) * scale
            params[f"b{ell}"] = jnp.zeros((fout,), dtype)
        return params

    def init_buffers(self, topo: Topology, dtype=jnp.float32,
                     leading: bool = True) -> dict:
        """Zero pipeline state (Alg. 1 line 6: boundary features start at 0).

        With staleness_steps k>1, each buffer is a FIFO queue along a new
        leading axis of size k (slot 0 = oldest = consumed).

        Buffer widths follow `payload_widths`: the layer input width fin,
        except for sliced layers (`PipeConfig.slice_boundary`), whose
        exchange — and therefore whose stale state — carries the
        post-transform width fout.

        Under `guard_exchange` the dict gains an "es" leaf: int32
        consecutive-fallback counters of shape (2, L, P) per partition —
        (direction, layer, peer) — with NO staleness-queue axis (the
        counter tracks the stream, not one queue slot)."""
        p = topo.num_parts
        k = self.pipe.staleness_steps
        q = (k,) if k > 1 else ()
        lead = q + ((p,) if leading else ())
        feat, grad = [], []
        for w in self.payload_widths(topo):
            feat.append(jnp.zeros(lead + (topo.halo_size, w), dtype))
            grad.append(jnp.zeros(lead + (topo.max_inner, w), dtype))
        out = {"feat": tuple(feat), "grad": tuple(grad)}
        if self.pipe.guard_exchange:
            out["es"] = jnp.zeros(
                ((p,) if leading else ()) + (2, self.model.num_layers, p),
                jnp.int32)
        return out

    # ---------------- pipeline-buffer semantics ----------------

    def _consume_buffer(self, buf):
        """The stale state a step reads: t-k (FIFO head) or t-1 (plain/EMA)."""
        return buf[0] if self.pipe.staleness_steps > 1 else buf

    def _update_buffer(self, buf, fresh, smooth: bool):
        """Next-step buffer from the freshly exchanged payload: FIFO push,
        EMA (γ·old + (1−γ)·fresh), or plain replacement."""
        if self.pipe.staleness_steps > 1:
            return jnp.concatenate([buf[1:], fresh[None]], axis=0)
        if smooth:
            return self.pipe.gamma * buf + (1 - self.pipe.gamma) * fresh
        return fresh

    def _update_buffer_guarded(self, buf, fresh, smooth: bool, valid):
        """`_update_buffer` with per-row fallback (guard_exchange): rows of
        `fresh` whose checksum failed keep their previous value — the FIFO
        re-pushes the newest entry, EMA/replace keep the old row — so a lost
        payload is one extra step of staleness, not a zero/garbage write.
        `valid=None` (guard off) and all-True masks are bitwise identical
        to the unguarded update (pure `jnp.where` select semantics)."""
        if valid is None:
            return self._update_buffer(buf, fresh, smooth)
        v = valid[..., None]
        if self.pipe.staleness_steps > 1:
            pushed = jnp.where(v, fresh, buf[-1])
            return jnp.concatenate([buf[1:], pushed[None]], axis=0)
        if smooth:
            upd = self.pipe.gamma * buf + (1 - self.pipe.gamma) * fresh
            return jnp.where(v, upd, buf)
        return jnp.where(v, fresh, buf)

    # ---------------- shared layer math ----------------

    @property
    def engine(self):
        """The aggregation engine selected by ``ModelConfig.agg``."""
        return get_engine(self.model.agg)

    def _agg_slice(self, topo: Topology):
        """The Topology fields the selected engine consumes (still carrying
        the leading partition axis; sliced/vmapped by the backend)."""
        engine = self.engine
        tslice = tuple(getattr(topo, f) for f in engine.fields)
        if any(t is None for t in tslice):
            raise ValueError(
                f"aggregation engine {engine.name!r} needs Topology fields "
                f"{engine.fields}, but some are None — build the topology "
                "with topology_from(pg, with_tiles=True) or "
                f"GraphDataPipeline.build(..., agg={engine.name!r})")
        return tslice

    def _split_active(self) -> SplitSpec | None:
        """The SplitSpec the step should run with, or None for unsplit.

        "none" and a missing spec always mean unsplit; "split-phase" uses
        the spec whenever one exists (degenerate graphs still fall back —
        there is no boundary tail to phase); "auto" additionally requires
        an engine that consumes tile streams (the split only repositions
        collectives around the tile phases; for COO it is a pure masking
        overhead, kept reachable via the explicit "split-phase" for the
        cross-engine parity tests). Feature slicing always disables the
        split: the sliced send only exists after the dense transform, so
        there is no boundary-first phase to overlap (the explicit
        "split-phase" + slice_boundary combination is already rejected by
        PipeConfig). The guarded exchange also disables the split: the
        split body has no validity-mask path (and PipeConfig rejects the
        explicit combination)."""
        if (self.pipe.overlap == "none" or self.split is None
                or self.pipe.slice_boundary or self.pipe.guard_exchange):
            return None
        if self.pipe.overlap == "split-phase":
            return self.split
        from repro.graph.reorder import TILE_ENGINES
        return self.split if self.engine.name in TILE_ENGINES else None

    def layer_orders(self, topo: Topology, train: bool = True,
                     fused: bool | None = None) -> tuple[str, ...]:
        """Per-layer matmul ordering the step actually runs with.

        `_base_orders` resolves the ModelConfig knob ("auto" via the static
        cost model, with wire-byte pricing folded in when slice_boundary is
        on); on top of that, every SLICED layer is forced to
        "transform-first" in every mode — the sliced exchange and its stale
        buffers carry the post-transform width, so the order backing them
        must not drift between train/eval or across `fused` overrides
        (buffer shapes are part of the step signature)."""
        orders = self._base_orders(topo, train=train, fused=fused)
        sl = self.sliced_layers(topo)
        if not sl:
            return orders
        return tuple("transform-first" if ell in sl else o
                     for ell, o in enumerate(orders))

    def sliced_layers(self, topo: Topology) -> frozenset:
        """Layers whose boundary exchange ships the post-transform width.

        Empty unless `PipeConfig.slice_boundary`. A layer is sliced when
        the TRAIN-mode base ordering picks transform-first for it and
        fout <= fin (slicing a widening layer would grow the wire). Layer 0
        never slices: its payload is the raw input features, needed at full
        width on the consumer. Computed from `_base_orders(train=True)`
        only, so the sliced set — and with it every buffer width — is
        identical for train and eval steps."""
        if not self.pipe.slice_boundary:
            return frozenset()
        dims = self.model.layer_dims()
        orders = self._base_orders(topo, train=True)
        return frozenset(
            ell for ell in range(1, self.model.num_layers)
            if orders[ell] == "transform-first"
            and dims[ell][1] <= dims[ell][0])

    def payload_widths(self, topo: Topology) -> tuple[int, ...]:
        """Per-layer feature width of the boundary exchange payload: fin,
        or fout for sliced layers. Stale buffers, wire-format resolution,
        and the byte accounting all key off this table."""
        dims = self.model.layer_dims()
        sl = self.sliced_layers(topo)
        return tuple(dims[ell][1] if ell in sl else dims[ell][0]
                     for ell in range(self.model.num_layers))

    def wire_codecs(self, topo: Topology) -> tuple:
        """Per-layer boundary codec (repro.core.codec) the step encodes
        with. A concrete `PipeConfig.wire` applies uniformly; "auto" picks
        per layer by wire bytes over the payload widths
        (repro.analysis.cost.choose_wire_formats — int4 is explicit-only).
        Under `guard_exchange` every codec is wrapped in a ChecksumCodec
        (one extra wire column per row, verified on decode)."""
        L = self.model.num_layers
        g = self.pipe.guard_exchange
        if self.pipe.wire != "auto":
            return (make_codec(self.pipe.wire, self.pipe.wire_block,
                               guard=g),) * L
        from repro.analysis.cost import choose_wire_formats
        fmts = choose_wire_formats(self.payload_widths(topo),
                                   block=self.pipe.wire_block)
        return tuple(make_codec(f, self.pipe.wire_block, guard=g)
                     for f in fmts)

    def _base_orders(self, topo: Topology, train: bool = True,
                     fused: bool | None = None) -> tuple[str, ...]:
        """Per-layer matmul ordering, resolved statically (trace-time).

        "auto" feeds the static FLOP model (`repro.analysis.cost`) the
        shard's effective sparse work: n_tiles·T² for the tile engines
        (padded tiles do real MXU work — computed via `tile_density`), the
        padded COO length otherwise. Everything here is a Python int from
        array *shapes*, so the choice is identical on every backend and
        every partition and never enters the traced program.

        `fused` overrides the cost model's fused-epilogue assumption: the
        split-phase schedule runs the fused engine through the composed
        phased path (the in-kernel epilogue would write garbage through
        the dense weight for out-of-phase rows), so it prices fused=False.
        """
        mo = self.model.matmul_order
        L = self.model.num_layers
        if mo != "auto":
            return (mo,) * L
        engine = self.engine
        combined = topo.max_inner + topo.halo_size
        from repro.graph.reorder import TILE_ENGINES
        if engine.name in TILE_ENGINES and topo.tile_rows is not None:
            # MEASURED tile stream length of this very topology (every
            # stored tile does a full T×T MXU contraction per feature
            # column) — with a reordered layout this is the post-reorder
            # tile count, not a uniform-density estimate, so the argmin
            # tracks the layout. The propagation shard is shared by every
            # layer; the per-layer list keeps the cost-model contract
            # explicit.
            nnz_eff = [topo.tile_rows.shape[-1] * TILE * TILE] * L
        else:
            nnz_eff = [topo.edge_row.shape[-1]] * L       # padded COO work
        from repro.analysis.cost import choose_gcn_orders
        if fused is None:
            fused = engine.name == "fused"
        kw = {}
        if self.pipe.slice_boundary:
            # Co-decision with the wire codec: price each ordering's
            # boundary bytes (transform-first ships the sliced fout width)
            # so "auto" weighs comm against FLOPs. Formats here resolve on
            # the UNSLICED fin widths — the sliced set is itself derived
            # from this choice, so pricing must not depend on it.
            from repro.analysis.cost import (DEFAULT_FLOPS_PER_WIRE_BYTE,
                                             choose_wire_formats,
                                             wire_bytes_per_row)
            if self.pipe.wire == "auto":
                fmts = choose_wire_formats(
                    [f for f, _ in self.model.layer_dims()],
                    block=self.pipe.wire_block)
            else:
                fmts = (self.pipe.wire,) * L
            kw = dict(
                slot_rows=float(topo.halo_size),
                wire_bytes_fn=lambda ell, f, fmts=fmts: wire_bytes_per_row(
                    fmts[ell], f, self.pipe.wire_block),
                slice_boundary=True,
                comm_flops_per_byte=DEFAULT_FLOPS_PER_WIRE_BYTE)
        return choose_gcn_orders(self.model.layer_dims(), topo.max_inner,
                                 combined, nnz_eff, train=train,
                                 fused=fused, tile=TILE, **kw)

    def _layer_forward(self, tslice, w, b, h_prev, halo, drop_mask,
                       order: str = "aggregate-first",
                       fuse_relu: bool = False, with_z: bool = True):
        """One GCN/SAGE layer on one partition. Returns (u, (comb, z)).

        `order` picks the contraction of P·comb·W: aggregate-first routes
        through ``engine.aggregate_transform`` (the fused engine contracts
        the weight inside the Pallas grid pass; other engines compose),
        transform-first applies the dense matmul before the SpMM. z is the
        aggregation residual the aggregate-first backward needs for the
        weight gradient — None under transform-first (gw is computed from
        comb and Pᵀ·du there) or when `with_z=False` (eval). With
        `fuse_relu` the returned u is already activated — inside the fused
        kernel's epilogue when possible (GCN kind, aggregate-first), as a
        plain jnp op otherwise.
        """
        max_inner = h_prev.shape[0]
        fin = h_prev.shape[-1]
        comb = jnp.concatenate([h_prev, halo], axis=0)
        if drop_mask is not None:
            comb = comb * drop_mask
        sage = self.model.kind == "sage"
        w1 = w[:fin] if sage else w
        applied_act = False
        if order == "transform-first":
            u = self.engine.spmm(tslice, comb @ w1, max_inner) + b
            z = None
        else:
            in_kernel_relu = fuse_relu and not sage
            u, z = self.engine.aggregate_transform(
                tslice, comb, w1, b, max_inner,
                relu=in_kernel_relu, with_z=with_z)
            applied_act = in_kernel_relu
        if sage:
            u = u + comb[:max_inner] @ w[fin:]
        if fuse_relu and not applied_act:
            u = jax.nn.relu(u)
        return u, (comb, z)

    def _layer_backward(self, tslice, w, du, comb, z, drop_mask, max_inner,
                        order: str = "aggregate-first",
                        need_dcomb: bool = True):
        """Manual VJP of one layer, weight gradient included. Returns
        (gW, dH_inner_local, dB_halo); the d-terms are None when
        `need_dcomb=False` (layer 0 — Alg. 1 stops the backward there,
        though transform-first still needs Pᵀ·du for its weight gradient).
        """
        combined = comb.shape[0]
        fin = comb.shape[-1]
        sage = self.model.kind == "sage"
        w1 = w[:fin] if sage else w
        if order == "transform-first":
            dhw = self.engine.spmm_t(tslice, du, combined)
            gw = comb.T @ dhw                 # = zᵀ·du without z: combᵀPᵀdu
            if sage:
                gw = jnp.concatenate([gw, comb[:max_inner].T @ du], axis=0)
            if not need_dcomb:
                return gw, None, None
            dcomb = dhw @ w1.T
        else:
            gw = z.T @ du
            if sage:
                gw = jnp.concatenate([gw, comb[:max_inner].T @ du], axis=0)
            if not need_dcomb:
                return gw, None, None
            dcomb = self.engine.aggregate_transform_t(tslice, du, w1,
                                                      combined)
        if sage:
            dcomb = dcomb.at[:max_inner].add(du @ w[fin:].T)
        if drop_mask is not None:
            dcomb = dcomb * drop_mask
        return gw, dcomb[:max_inner], dcomb[max_inner:]

    # ---------------- forward/backward step (per partition view) --------

    def _step_impl(self, backend, topo: Topology, params, buffers, data,
                   key, train: bool, step_idx=None, faults=None):
        """Runs per-partition under `backend`. In sim the arrays keep their
        leading partition axis and per-partition ops are vmapped; in spmd this
        body executes inside shard_map with squeezed arrays.

        `faults` (a compiled FaultTables) injects drop/corrupt faults into
        the encoded wires at `step_idx`; under `pipe.guard_exchange` the
        decode verifies per-row checksums and failed rows fall back to
        their stale buffer entry (see faults.py / _update_buffer_guarded).
        `faults=None` traces exactly the historical fault-free step."""
        sp = self._split_active()
        if sp is not None and faults is None:
            # the split schedule has no injection points; numerics are
            # identical, so a faulted run just takes the unsplit body
            return self._step_impl_split(backend, topo, params, buffers,
                                         data, key, train, sp)
        L = self.model.num_layers
        dims = self.model.layer_dims()
        pipe = self.pipe
        P = topo.num_parts
        max_inner = topo.max_inner

        tslice = self._agg_slice(topo)
        send_idx, send_mask = topo.send_idx, topo.send_mask
        lead = backend.lead_axis
        if lead:
            gather = jax.vmap(_gather_send)
            scatter = jax.vmap(partial(_scatter_recv, max_inner=max_inner))
            scatter_inv = jax.vmap(
                partial(_scatter_invalid_rows, max_inner=max_inner))
        else:
            gather = _gather_send
            scatter = partial(_scatter_recv, max_inner=max_inner)
            scatter_inv = partial(_scatter_invalid_rows, max_inner=max_inner)

        guard = pipe.guard_exchange
        pids = backend.part_ids(P) if faults is not None else None
        # per-layer peer-validity verdicts (guard only): bool (..., P) per
        # direction, folded into the "es" consecutive-fallback counters
        feat_pv = [None] * L
        grad_pv = [None] * L

        h = data.x
        fuse = pipe.fused        # stale + fuse_exchange: deferred collectives
        orders = self.layer_orders(topo, train=train)   # static, per layer
        sliced = self.sliced_layers(topo)
        codecs = self.wire_codecs(topo)
        pw = self.payload_widths(topo)
        sage = self.model.kind == "sage"
        residuals = []
        new_feat = []
        pending_feat = []        # fused mode: per-layer wires, exchanged once
        feat_dtypes = []         # ... and their pre-encode dtypes
        dropout_rate = self.model.dropout if train else 0.0

        def ship_feat(ell, payload):
            """Encode one layer's (..., P, slot, pw) feature send, exchange
            it (or queue it for the fused collective), decode, and return
            the (..., P*slot, pw) halo the layer consumes this step."""
            dtype = payload.dtype
            wire = codecs[ell].encode(payload)
            if faults is not None:
                wire = apply_faults(wire, faults, step_idx, FWD, ell,
                                    pids, guard)
            if fuse:
                # Stale mode: the exchange result is consumed only at t+1,
                # so defer the wire into the packed buffer and read this
                # step's halo straight from the pipeline state.
                pending_feat.append(wire)
                feat_dtypes.append(dtype)
                new_feat.append(None)   # filled after the fused exchange
                return self._consume_buffer(buffers["feat"][ell])
            fresh, vrows = land_feat(ell, backend.exchange(wire), dtype)
            if pipe.stale:
                halo = self._consume_buffer(buffers["feat"][ell])
                new_feat.append(self._update_buffer_guarded(
                    buffers["feat"][ell], fresh, pipe.smooth_feat, vrows))
            else:
                halo = fresh
                new_feat.append(buffers["feat"][ell])
            return halo

        def land_feat(ell, recv, dtype):
            """Decode one received feature wire to the (..., P·slot, pw)
            halo layout; under the guard also verify per-row checksums,
            returning the (..., P·slot) valid-row mask and folding the
            per-peer verdict into `feat_pv`."""
            if guard:
                fresh, valid = codecs[ell].decode_checked(recv, pw[ell],
                                                          dtype)
                feat_pv[ell] = jnp.all(valid, axis=-1)
                vrows = valid.reshape(valid.shape[:-2] + (P * topo.slot,))
            else:
                fresh = codecs[ell].decode(recv, pw[ell], dtype)
                vrows = None
            fresh = fresh.reshape(fresh.shape[:-3] + (P * topo.slot, pw[ell]))
            return fresh, vrows

        for ell in range(L):
            fin, fout = dims[ell]
            if dropout_rate > 0.0:
                dkey = jax.random.fold_in(key, ell)
                dm = backend.dropout_mask(
                    dkey, dropout_rate,
                    (max_inner + P * topo.slot, fin), P)
            else:
                dm = None

            act = ell < L - 1
            # Eval never needs residuals: skip the z output (the fused
            # kernel then skips its HBM write) and fuse the ReLU epilogue.
            fuse_relu = act and not train
            if ell in sliced:
                # Sliced boundary (order forced transform-first): transform
                # the inner rows FIRST and ship the fout-wide result rows —
                # the consumer aggregates already-transformed halo rows, so
                # the wire carries fout <= fin columns. Dropout applies
                # owner-side before the transform (a halo row arrives with
                # its owner's inner-row mask baked in, instead of the
                # consumer's halo mask) — identical to the unsliced
                # schedule at dropout 0.
                w, b = params[f"w{ell}"], params[f"b{ell}"]
                w1 = w[:fin] if sage else w
                h_in = h * dm[..., :max_inner, :] if dm is not None else h
                hw = h_in @ w1
                halo = ship_feat(ell, gather(hw, send_idx, send_mask))
                src = jnp.concatenate([hw, halo], axis=-2)
                if not lead:
                    u = self.engine.spmm(tslice, src, max_inner) + b
                else:
                    u = jax.vmap(lambda ts, s: self.engine.spmm(
                        ts, s, max_inner))(tslice, src) + b
                if sage:
                    u = u + h_in @ w[fin:]
                if fuse_relu:
                    u = jax.nn.relu(u)
                # residual slot 0 holds the masked inner rows (the sliced
                # backward needs h_in, never the full comb)
                residuals.append((h_in, None, u, dm))
            else:
                halo = ship_feat(ell, gather(h, send_idx, send_mask))
                if not lead:
                    u, (comb, z) = self._layer_forward(
                        tslice, params[f"w{ell}"], params[f"b{ell}"], h,
                        halo, dm, order=orders[ell], fuse_relu=fuse_relu,
                        with_z=train)
                else:
                    fwd = jax.vmap(
                        lambda ts, h_, halo_, dm_, w_=params[f"w{ell}"],
                               b_=params[f"b{ell}"], o_=orders[ell]:
                        self._layer_forward(ts, w_, b_, h_, halo_, dm_,
                                            order=o_, fuse_relu=fuse_relu,
                                            with_z=train),
                        in_axes=(0, 0, 0, 0 if dm is not None else None))
                    u, (comb, z) = fwd(tslice, h, halo, dm)
                residuals.append((comb, z, u, dm))
            h = jax.nn.relu(u) if act and not fuse_relu else u

        if fuse:
            # ONE collective for all L layers' boundary features, issued
            # after the last layer. Nothing downstream of it is consumed
            # this step (results land in the t+1 buffers), so XLA is free
            # to overlap it with the loss/backward/optimizer compute.
            for ell, recv in enumerate(
                    fused_exchange_encoded(backend, pending_feat)):
                # decode restores the layer's own pre-pack dtype: undoes
                # the wire encoding AND any promotion from packing layers
                # of different dtypes into one buffer
                fresh, vrows = land_feat(ell, recv, feat_dtypes[ell])
                new_feat[ell] = self._update_buffer_guarded(
                    buffers["feat"][ell], fresh, pipe.smooth_feat, vrows)

        logits = h

        # -- loss ---------------------------------------------------------
        mask = data.train_mask.astype(logits.dtype)
        if self.model.multilabel:
            count_local = jnp.sum(mask) * self.model.num_classes
        else:
            count_local = jnp.sum(mask)
        total = jnp.maximum(backend.psum_scalar(count_local), 1.0)
        loss_fn = _bce_loss_and_grad if self.model.multilabel else _ce_loss_and_grad
        loss_local, dlogits = loss_fn(logits, data.labels, mask, total, backend)
        loss = backend.psum_scalar(loss_local) / total

        if not train:
            return loss, logits, None, None

        # -- manual backward (Alg. 1 lines 17–30) --------------------------
        grads = {}
        new_grad = [None] * L
        pending_grad = []        # fused mode: (ell, wire, dtype), one exchange
        combined = max_inner + P * topo.slot

        def ship_grad(ell, db, compute_dtype):
            """Encode one layer's (..., P, slot, pw) gradient send, exchange
            it (or queue it for the fused collective), decode, scatter to
            owner rows, and return the contribution the backward consumes
            this step (stale buffer in pipelined mode, fresh in vanilla)."""
            # dtype the scatter sees: the payload's own under the identity
            # codec, the compute dtype after any lossy wire
            dtype = db.dtype if codecs[ell].name == "f32" else compute_dtype
            wire = codecs[ell].encode(db)
            if faults is not None:
                wire = apply_faults(wire, faults, step_idx, BWD, ell,
                                    pids, guard)
            if fuse:
                # Deferred: the stale contribution comes from the t-1 (or
                # t-k) buffer; the fresh wire joins the packed buffer for
                # the single post-backward collective.
                pending_grad.append((ell, wire, dtype))
                return self._consume_buffer(buffers["grad"][ell])
            fresh_contrib, vrows = land_grad(ell, backend.exchange(wire),
                                             dtype)
            if pipe.stale:
                contrib = self._consume_buffer(buffers["grad"][ell])
                new_grad[ell] = self._update_buffer_guarded(
                    buffers["grad"][ell], fresh_contrib, pipe.smooth_grad,
                    vrows)
            else:
                contrib = fresh_contrib
                new_grad[ell] = buffers["grad"][ell]
            return contrib

        def land_grad(ell, recv, dtype):
            """Decode one received gradient wire and scatter it to owner
            rows. Under the guard, rows failing their checksum are zeroed
            before the scatter-add and every owner row any of them touched
            is marked invalid (a partial peer sum is wrong, not stale);
            the per-peer verdict lands in `grad_pv` (masked pad slots are
            exempt — they carry no data)."""
            if not guard:
                db_recv = codecs[ell].decode(recv, pw[ell], dtype)
                return scatter(db_recv, send_idx, send_mask), None
            db_recv, valid = codecs[ell].decode_checked(recv, pw[ell], dtype)
            inv = (~valid) & send_mask.astype(bool)
            grad_pv[ell] = ~jnp.any(inv, axis=-1)
            db_recv = jnp.where(valid[..., None], db_recv, 0)
            fresh_contrib = scatter(db_recv, send_idx, send_mask)
            return fresh_contrib, ~scatter_inv(inv, send_idx)

        j = dlogits
        for ell in reversed(range(L)):
            comb, z, u, dm = residuals[ell]
            du = j if ell == L - 1 else j * (u > 0).astype(j.dtype)
            grads[f"b{ell}"] = backend.psum(jnp.sum(du, axis=-2))
            if ell in sliced:
                # Sliced backward (transform-first, fout-wide exchange):
                # ship the PRE-w1 halo rows of dhw = Pᵀ·du back to their
                # owners and fold the (stale) owner contributions into the
                # inner dhw rows before the weight gradient and the w1ᵀ
                # application — scatter commutes with both by linearity, so
                # vanilla mode reproduces the unsliced step exactly.
                fin, fout = dims[ell]
                w = params[f"w{ell}"]
                w1 = w[:fin] if sage else w
                h_in = comb      # residual slot 0 = masked inner rows
                if not lead:
                    dhw = self.engine.spmm_t(tslice, du, combined)
                else:
                    dhw = jax.vmap(lambda ts, d: self.engine.spmm_t(
                        ts, d, combined))(tslice, du)
                db = dhw[..., max_inner:, :]
                db = db.reshape(db.shape[:-2] + (P, topo.slot, fout))
                contrib = ship_grad(ell, db, j.dtype)
                dhw_eff = dhw[..., :max_inner, :] + contrib
                gw = jnp.swapaxes(h_in, -1, -2) @ dhw_eff
                if sage:
                    gw = jnp.concatenate(
                        [gw, jnp.swapaxes(h_in, -1, -2) @ du], axis=-2)
                grads[f"w{ell}"] = backend.psum(gw)
                dh = dhw_eff @ w1.T
                if sage:
                    dh = dh + du @ w[fin:].T
                if dm is not None:
                    dh = dh * dm[..., :max_inner, :]
                j = dh           # owner contributions already folded in
                continue
            need_dcomb = ell > 0    # Alg. 1 stops the backward at layer 0
            if not lead:
                gw_local, dh_local, db = self._layer_backward(
                    tslice, params[f"w{ell}"], du, comb, z, dm, max_inner,
                    order=orders[ell], need_dcomb=need_dcomb)
            else:
                bwd = jax.vmap(
                    lambda ts, du_, comb_, z_, dm_, w_=params[f"w{ell}"],
                           o_=orders[ell]:
                    self._layer_backward(ts, w_, du_, comb_, z_, dm_,
                                         max_inner, order=o_,
                                         need_dcomb=need_dcomb),
                    in_axes=(0, 0, 0, 0 if z is not None else None,
                             0 if dm is not None else None))
                gw_local, dh_local, db = bwd(tslice, du, comb, z, dm)
            grads[f"w{ell}"] = backend.psum(gw_local)
            if ell == 0:
                new_grad[ell] = buffers["grad"][ell]
                break
            db = db.reshape(db.shape[:-2] + (P, topo.slot, dims[ell][0]))
            # -- boundary gradient communication ---------------------------
            j = dh_local + ship_grad(ell, db, j.dtype)

        if fuse and pending_grad:
            # ONE collective for all L-1 boundary-gradient sends (layer 0
            # sends nothing — Alg. 1 stops its backward at the first layer).
            recvs = fused_exchange_encoded(backend,
                                           [w_ for _, w_, _ in pending_grad])
            for (ell, _, dtype), recv in zip(pending_grad, recvs):
                # decode restores this layer's pre-pack dtype (see forward)
                fresh_contrib, vrows = land_grad(ell, recv, dtype)
                new_grad[ell] = self._update_buffer_guarded(
                    buffers["grad"][ell], fresh_contrib, pipe.smooth_grad,
                    vrows)

        new_buffers = {"feat": tuple(new_feat), "grad": tuple(new_grad)}
        if guard:
            # Consecutive-fallback counters per (direction, layer, peer):
            # a valid arrival resets to 0, a fallback increments. Layer 0
            # ships no backward gradient — always "valid". Partition-local
            # bookkeeping: no extra collective enters the step.
            ones = jnp.ones_like(feat_pv[0])
            gv = [pv if pv is not None else ones for pv in grad_pv]
            ok = jnp.stack([jnp.stack(feat_pv, axis=-2),
                            jnp.stack(gv, axis=-2)], axis=-3)
            new_buffers["es"] = jnp.where(ok, 0, buffers["es"] + 1)
        return loss, logits, grads, new_buffers

    # ---------------- split-phase step (ISSUE 6) ----------------

    def _step_impl_split(self, backend, topo: Topology, params, buffers,
                         data, key, train: bool, sp: SplitSpec):
        """`_step_impl` under the split-phase overlap schedule.

        Each layer's aggregation is cut into a *boundary* phase (the tile
        groups whose output rows feed the send gather: rows >= sp.row_tail
        forward, comb rows >= sp.col_tail transposed) and an *interior*
        phase. Per layer the boundary phase runs FIRST, the rows the next
        exchange needs are gathered from its tail, the collective is issued
        (or, in fused mode, the single packed collective once the last
        payload is ready), and only then does the interior phase — the bulk
        of the SpMM — execute: the collective is in flight behind it. The
        received halo is consumed strictly later (the next layer in vanilla
        mode; step t+1 in stale mode), so nothing waits on the wire.

        Numerics: each phase is bit-identical to the unsplit kernel on its
        own rows and the dense transform/activation/gather/scatter algebra
        is row-local, so reassembling [interior; boundary] reproduces the
        unsplit step exactly — the split only REPOSITIONS each collective
        between the two phase kernels (counts are unchanged; see
        trace_utils.expected_split_events). The fused engine runs through
        the composed phased path (its in-kernel epilogue would push
        unspecified out-of-phase rows through the dense weight), hence
        `layer_orders(..., fused=False)`.
        """
        L = self.model.num_layers
        dims = self.model.layer_dims()
        pipe = self.pipe
        P = topo.num_parts
        max_inner = topo.max_inner
        combined = max_inner + P * topo.slot
        rt, ct = sp.row_tail, sp.col_tail
        sage = self.model.kind == "sage"
        engine = self.engine

        tslice = self._agg_slice(topo)
        send_idx, send_mask = topo.send_idx, topo.send_mask
        lead = backend.lead_axis
        if lead:
            gather = jax.vmap(_gather_send)
            gather_tail = jax.vmap(partial(_gather_send_tail, row_tail=rt))
            scatter = jax.vmap(partial(_scatter_recv, max_inner=max_inner))
        else:
            gather = _gather_send
            gather_tail = partial(_gather_send_tail, row_tail=rt)
            scatter = partial(_scatter_recv, max_inner=max_inner)

        def spmm_phase(src, phase):
            if lead:
                return jax.vmap(lambda ts, s, p_=phase: engine.spmm_phased(
                    ts, s, max_inner, sp, p_))(tslice, src)
            return engine.spmm_phased(tslice, src, max_inner, sp, phase)

        def spmm_t_phase(src, phase):
            if lead:
                return jax.vmap(lambda ts, s, p_=phase: engine.spmm_t_phased(
                    ts, s, combined, sp, p_))(tslice, src)
            return engine.spmm_t_phased(tslice, src, combined, sp, phase)

        fuse = pipe.fused
        # fused=False: the split runs the composed (non-epilogue) path.
        orders = self.layer_orders(topo, train=train, fused=False)
        # Slicing never reaches the split (`_split_active` rejects it), but
        # every wire codec does: the phase split repositions the exchange,
        # the codec only changes what the exchange carries.
        codecs = self.wire_codecs(topo)
        residuals = []
        new_feat = [None] * L
        pending_feat = []
        feat_dtypes = []
        dropout_rate = self.model.dropout if train else 0.0

        # -- boundary feature communication helpers ------------------------
        # land_feat: per-layer schedule — exchange now, land into halo/buffer.
        # defer_feat: fused schedule — queue the payload, read stale state.
        # flush_feat: the ONE packed collective, payload order [0..L-1]
        # (identical to the unsplit fused pack, hence bit-identical).
        def land_feat(ell, send, send_dtype):
            fresh = codecs[ell].decode(backend.exchange(send), dims[ell][0],
                                       send_dtype)
            fresh = fresh.reshape(
                fresh.shape[:-3] + (P * topo.slot, dims[ell][0]))
            if pipe.stale:
                halo = self._consume_buffer(buffers["feat"][ell])
                new_feat[ell] = self._update_buffer(
                    buffers["feat"][ell], fresh, pipe.smooth_feat)
            else:
                halo = fresh
                new_feat[ell] = buffers["feat"][ell]
            return halo

        def defer_feat(ell, send, send_dtype):
            pending_feat.append(send)
            feat_dtypes.append(send_dtype)
            return self._consume_buffer(buffers["feat"][ell])

        def flush_feat():
            for ell, fresh in enumerate(
                    fused_exchange_encoded(backend, pending_feat)):
                fresh = codecs[ell].decode(fresh, dims[ell][0],
                                           feat_dtypes[ell])
                fresh = fresh.reshape(
                    fresh.shape[:-3] + (P * topo.slot, dims[ell][0]))
                new_feat[ell] = self._update_buffer(
                    buffers["feat"][ell], fresh, pipe.smooth_feat)

        def prep_send(ell, payload):
            return codecs[ell].encode(payload), payload.dtype

        # -- forward -------------------------------------------------------
        # Layer 0's payload is x itself — available before any compute, so
        # its exchange is issued (or queued) ahead of the loop. For L == 1
        # the fused pack is complete right away and flushes here too.
        h = data.x
        send, send_dtype = prep_send(0, gather(h, send_idx, send_mask))
        if fuse:
            halo = defer_feat(0, send, send_dtype)
            if L == 1:
                flush_feat()
        else:
            halo = land_feat(0, send, send_dtype)

        for ell in range(L):
            fin, fout = dims[ell]
            w, b = params[f"w{ell}"], params[f"b{ell}"]
            w1 = w[:fin] if sage else w
            if dropout_rate > 0.0:
                dkey = jax.random.fold_in(key, ell)
                dm = backend.dropout_mask(
                    dkey, dropout_rate, (combined, fin), P)
            else:
                dm = None
            comb = jnp.concatenate([h, halo], axis=-2)
            if dm is not None:
                comb = comb * dm
            order = orders[ell]
            src = comb @ w1 if order == "transform-first" else comb
            act = ell < L - 1

            # boundary phase: only rows [rt, max_inner) of raw_b are valid.
            raw_b = spmm_phase(src, "boundary")
            tail_b = raw_b[..., rt:, :]
            u_bt = tail_b + b if order == "transform-first" else tail_b @ w1 + b
            if sage:
                u_bt = u_bt + comb[..., rt:max_inner, :] @ w[fin:]
            h_bt = jax.nn.relu(u_bt) if act else u_bt

            # issue the NEXT layer's exchange between the phases: its
            # payload rows all live in the tail just produced.
            if ell + 1 < L:
                send, send_dtype = prep_send(
                    ell + 1, gather_tail(h_bt, send_idx, send_mask))
                if fuse:
                    halo = defer_feat(ell + 1, send, send_dtype)
                    if ell + 1 == L - 1:
                        flush_feat()   # last payload queued -> issue now
                else:
                    halo = land_feat(ell + 1, send, send_dtype)

            # interior phase overlaps the in-flight collective.
            raw_i = spmm_phase(src, "interior")
            head_i = raw_i[..., :rt, :]
            if order == "transform-first":
                u_ih = head_i + b
                z = None
            else:
                u_ih = head_i @ w1 + b
                z = (jnp.concatenate([head_i, tail_b], axis=-2)
                     if train else None)
            if sage:
                u_ih = u_ih + comb[..., :rt, :] @ w[fin:]
            u = jnp.concatenate([u_ih, u_bt], axis=-2)
            residuals.append((comb, z, u, dm))
            h = jnp.concatenate([jax.nn.relu(u_ih), h_bt], axis=-2) if act else u

        logits = h

        # -- loss ---------------------------------------------------------
        mask = data.train_mask.astype(logits.dtype)
        if self.model.multilabel:
            count_local = jnp.sum(mask) * self.model.num_classes
        else:
            count_local = jnp.sum(mask)
        total = jnp.maximum(backend.psum_scalar(count_local), 1.0)
        loss_fn = _bce_loss_and_grad if self.model.multilabel else _ce_loss_and_grad
        loss_local, dlogits = loss_fn(logits, data.labels, mask, total, backend)
        loss = backend.psum_scalar(loss_local) / total

        if not train:
            return loss, logits, None, None

        # -- manual backward ----------------------------------------------
        # Transposed mirror of the forward: the boundary phase of Pᵀ·δ
        # produces comb rows >= ct — a superset of the halo rows that form
        # the gradient send — so the exchange is issued (fused: flushed at
        # the LAST backward layer ell == 1) between the transpose phases.
        grads = {}
        new_grad = [None] * L
        pending_grad = []

        def flush_grad():
            recvs = fused_exchange_encoded(backend,
                                           [d for _, d, _ in pending_grad])
            for (ell, _, db_dtype), db_recv in zip(pending_grad, recvs):
                db_recv = codecs[ell].decode(db_recv, dims[ell][0], db_dtype)
                fresh_contrib = scatter(db_recv, send_idx, send_mask)
                new_grad[ell] = self._update_buffer(
                    buffers["grad"][ell], fresh_contrib, pipe.smooth_grad)

        j = dlogits
        for ell in reversed(range(L)):
            comb, z, u, dm = residuals[ell]
            fin, _ = dims[ell]
            w = params[f"w{ell}"]
            w1 = w[:fin] if sage else w
            du = j if ell == L - 1 else j * (u > 0).astype(j.dtype)
            grads[f"b{ell}"] = backend.psum(jnp.sum(du, axis=-2))
            if ell == 0:
                # Alg. 1 stops the backward at layer 0: weight grad only,
                # no Pᵀ pass under aggregate-first — reuse the unsplit
                # per-layer backward (need_dcomb=False).
                if not lead:
                    gw_local, _, _ = self._layer_backward(
                        tslice, w, du, comb, z, dm, max_inner,
                        order=orders[0], need_dcomb=False)
                else:
                    bwd = jax.vmap(
                        lambda ts, du_, comb_, z_, dm_, w_=w:
                        self._layer_backward(ts, w_, du_, comb_, z_, dm_,
                                             max_inner, order=orders[0],
                                             need_dcomb=False),
                        in_axes=(0, 0, 0, 0 if z is not None else None,
                                 0 if dm is not None else None))
                    gw_local, _, _ = bwd(tslice, du, comb, z, dm)
                grads[f"w{ell}"] = backend.psum(gw_local)
                new_grad[0] = buffers["grad"][0]
                break

            order = orders[ell]
            # ONE dense op ahead of both phases under aggregate-first
            # (δhw = du·w1ᵀ); transform-first transposes du raw and applies
            # w1ᵀ per phase (the pre-w1 pieces also feed the weight grad).
            src_t = du if order == "transform-first" else du @ w1.T
            if sage:
                sage_t = du @ w[fin:].T

            # boundary phase: comb rows [ct, combined) valid.
            raw_tb = spmm_t_phase(src_t, "boundary")
            dhw_b = raw_tb[..., ct:, :]
            d_bt = dhw_b @ w1.T if order == "transform-first" else dhw_b
            if sage:
                d_bt = d_bt.at[..., :max_inner - ct, :].add(
                    sage_t[..., ct:, :])
            if dm is not None:
                d_bt = d_bt * dm[..., ct:, :]

            # gradient send = the halo rows of the boundary phase; issue
            # the exchange before the interior phase runs.
            db = d_bt[..., max_inner - ct:, :]
            db = db.reshape(db.shape[:-2] + (P, topo.slot, fin))
            db_dtype = db.dtype if codecs[ell].name == "f32" else j.dtype
            wire = codecs[ell].encode(db)
            if fuse:
                pending_grad.append((ell, wire, db_dtype))
                contrib = self._consume_buffer(buffers["grad"][ell])
                if ell == 1:
                    flush_grad()   # last backward payload -> issue now
            else:
                db_recv = codecs[ell].decode(backend.exchange(wire), fin,
                                             db_dtype)
                fresh_contrib = scatter(db_recv, send_idx, send_mask)
                if pipe.stale:
                    contrib = self._consume_buffer(buffers["grad"][ell])
                    new_grad[ell] = self._update_buffer(
                        buffers["grad"][ell], fresh_contrib, pipe.smooth_grad)
                else:
                    contrib = fresh_contrib
                    new_grad[ell] = buffers["grad"][ell]

            # interior phase overlaps the in-flight gradient exchange.
            raw_ti = spmm_t_phase(src_t, "interior")
            dhw_i = raw_ti[..., :ct, :]
            if order == "transform-first":
                d_ih = dhw_i @ w1.T
                dhw_full = jnp.concatenate([dhw_i, dhw_b], axis=-2)
                gw = jnp.swapaxes(comb, -1, -2) @ dhw_full
            else:
                d_ih = dhw_i
                gw = jnp.swapaxes(z, -1, -2) @ du
            if sage:
                gw = jnp.concatenate(
                    [gw, jnp.swapaxes(comb[..., :max_inner, :], -1, -2) @ du],
                    axis=-2)
                d_ih = d_ih + sage_t[..., :ct, :]
            if dm is not None:
                d_ih = d_ih * dm[..., :ct, :]
            grads[f"w{ell}"] = backend.psum(gw)
            j = jnp.concatenate(
                [d_ih, d_bt[..., :max_inner - ct, :]], axis=-2) + contrib

        new_buffers = {"feat": tuple(new_feat), "grad": tuple(new_grad)}
        return loss, logits, grads, new_buffers

    # ---------------- public API ----------------

    def train_step(self, topo: Topology, params, buffers, data: ShardedData,
                   key: jax.Array, step_idx=None, faults=None):
        """Sim-backend step over (P, ...) arrays. Returns
        (loss, grads, new_buffers, logits). `faults` (compiled
        FaultTables) + `step_idx` inject that step's exchange faults."""
        backend = SimBackend()
        loss, logits, grads, new_buffers = self._step_impl(
            backend, topo, params, buffers, data, key, train=True,
            step_idx=step_idx, faults=faults)
        return loss, grads, new_buffers, logits

    def forward(self, topo: Topology, params, data: ShardedData):
        """Inference forward with synchronous (fresh) exchange — used for
        evaluation, like the paper's test-time behaviour."""
        fresh_self = dataclasses.replace(self, pipe=PipeConfig.vanilla())
        backend = SimBackend()
        buffers = fresh_self.init_buffers(topo)
        loss, logits, _, _ = fresh_self._step_impl(
            backend, topo, params, buffers, data, jax.random.PRNGKey(0),
            train=False)
        return loss, logits

    # -- SPMD (shard_map) construction ---------------------------------

    def spmd_buffer_specs(self, buffers, axis_name="parts"):
        """PartitionSpec tree of a buffer dict on a partition mesh: the
        "es" counters carry the partition axis first (no queue axis); every
        other buffer carries it after the k-step staleness queue axis when
        k > 1."""
        from jax.sharding import PartitionSpec as PS

        pspec = PS(axis_name)
        bspec = PS(None, axis_name) if self.pipe.staleness_steps > 1 else pspec
        return {k: jax.tree.map(lambda _: (pspec if k == "es" else bspec), v)
                for k, v in buffers.items()}

    def make_spmd_step(self, mesh, topo: Topology, axis_name="parts",
                       train: bool = True):
        """Build a jitted shard_map step over a 1-D partition mesh axis.

        Arrays with leading partition axis are sharded over `axis_name`;
        params are replicated; the returned function has the same signature
        as `train_step` (plus data), operating on global arrays.

        The partition count is decoupled from the device count: with
        P = num_parts a multiple of the mesh size, each device hosts
        n_local = P // n_devices co-resident partitions (device-major:
        partition p on device p // n_local) and the boundary exchange runs
        hierarchically (`hierarchical_exchange`).
        """
        from jax.sharding import PartitionSpec as PS

        pspec = PS(axis_name)
        axes = (axis_name,) if isinstance(axis_name, str) else tuple(axis_name)
        n_devices = 1
        for a in axes:
            n_devices *= mesh.shape[a]
        if topo.num_parts % n_devices:
            raise ValueError(
                f"num_parts={topo.num_parts} must be a multiple of the mesh "
                f"size {n_devices} (axes {axes})")
        n_local = topo.num_parts // n_devices
        backend = SpmdBackend(axis_name, n_local=n_local)

        kq = self.pipe.staleness_steps

        def per_device(topo_l, params, buffers, data, key, step_idx, faults):
            # shard_map leaves a leading axis of size n_local = P/num_devices.
            # n_local == 1: squeeze it and run the per-partition body.
            # n_local  > 1: keep it — _step_impl treats it exactly like the
            # sim backend's partition axis (vmapped layer math), with the
            # collectives local-axis-aware. Buffer queues (k-step staleness)
            # carry the partition axis at position 1 in both cases; the "es"
            # counters (guard_exchange) never grow a queue axis.
            if n_local == 1:
                topo1 = jax.tree.map(lambda x: x[0], tuple(topo_l))
                bsq = (lambda x: x[:, 0]) if kq > 1 else (lambda x: x[0])
                bufs1 = {k: jax.tree.map(
                    (lambda x: x[0]) if k == "es" else bsq, v)
                    for k, v in buffers.items()}
                data1 = jax.tree.map(lambda x: x[0], tuple(data))
                loss, logits, grads, newb = self._step_impl(
                    backend, Topology(*topo1), params, bufs1,
                    ShardedData(*data1), key, train,
                    step_idx=step_idx, faults=faults)
                logits = logits[None]
                bex = (lambda x: x[:, None]) if kq > 1 else (lambda x: x[None])
                if newb is not None:
                    newb = {k: jax.tree.map(
                        (lambda x: x[None]) if k == "es" else bex, v)
                        for k, v in newb.items()}
            else:
                loss, logits, grads, newb = self._step_impl(
                    backend, Topology(*topo_l), params, buffers,
                    ShardedData(*data), key, train,
                    step_idx=step_idx, faults=faults)
            return loss, logits, grads, newb

        def step(topo_g, params, buffers, data, key, step_idx=None,
                 faults=None):
            bspecs = self.spmd_buffer_specs(buffers, axis_name)
            f = jax.shard_map(
                per_device, mesh=mesh, check_vma=False,
                in_specs=(jax.tree.map(lambda _: pspec, tuple(topo_g)),
                          jax.tree.map(lambda _: PS(), params),
                          bspecs,
                          jax.tree.map(lambda _: pspec, tuple(data)),
                          PS(), PS(), PS()),
                out_specs=(PS(), pspec,
                           jax.tree.map(lambda _: PS(), params) if train else PS(),
                           bspecs if train else PS()))
            return f(tuple(topo_g), params, buffers, tuple(data), key,
                     step_idx, faults)

        return jax.jit(step)
