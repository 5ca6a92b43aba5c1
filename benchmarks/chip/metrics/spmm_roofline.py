"""spmm_roofline (layer: aggregation): the least time one chip needs for one
epoch's aggregations (chipbench.work: the larger of operations over the
bf16 peak and bytes over HBM bandwidth, per aggregation), over `spmm_ms`,
in percent."""
from chipbench import work
from chipbench.readers import load_reader


def read(ctx):
    ms = load_reader("spmm_ms").read(ctx)
    if not ms:
        return None
    least, _ = work.aggregation_least_seconds(ctx.config, ctx.nodes,
                                             ctx.edges, ctx.peaks, ctx.chips)
    return 100.0 * least * 1e3 / ms
