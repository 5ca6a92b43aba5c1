"""spmm_ms (layer: aggregation): device time per epoch of the aggregation
P.H and its transpose P^T.dZ, averaged over the chips used.

Rule: an operation is aggregation when it holds a gather, a scatter or a
custom call (a Pallas kernel) and the program's HLO records that an
instruction in it was emitted from the aggregation engines
(`repro/kernels/aggregate.py`, `repro/kernels/gcn_spmm.py`; see
`chipbench.hlo`). Under the COO engine these are the edge gathers x[col]
and the scatter-adds into the output rows, forward and backward, in the
train step and in the eval forward; under the tile engines, their Pallas
kernels (a fused kernel's dense epilogue counts here too). The exchange's
own gathers and scatters (`_gather_send`, `_scatter_recv`) are not
aggregation. Returns None when no operation matches."""

SPARSE = {"gather", "scatter", "custom-call"}


def is_aggregation(op) -> bool:
    return op.engine and bool(SPARSE.intersection(op.opcodes))


def per_chip_ms(ctx, pick):
    """Time per epoch of the operations `pick` selects, in ms, averaged
    over the chips used; None when it selects none."""
    devs = [d for d in ctx.trace.devices if ctx.trace.ops[d]][:ctx.chips]
    if not devs or ctx.trace.epochs <= 0:
        return None
    tot = [sum(o.dur_ns for o in ctx.trace.ops[d] if pick(o)) for d in devs]
    if not any(tot):
        return None
    return sum(tot) / len(devs) / 1e6 / ctx.trace.epochs


def read(ctx):
    return per_chip_ms(ctx, is_aggregation)
