"""dense_ms (layer: transform): device time per epoch of the dense matmuls,
averaged over the chips used.

Rule: an operation that holds a dot or a convolution (the layer transforms
H.W and their weight and input gradients, as XLA fuses them) and is not
aggregation by `spmm_ms`'s rule (a fused Pallas kernel's epilogue counts
there) and not a collective (an all-to-all, all-reduce, all-gather,
reduce-scatter or collective-permute, or a -start/-done half of one)."""
from chipbench.readers import load_reader

DENSE = {"dot", "convolution"}
COLLECTIVES = ("all-to-all", "all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "ragged-all-to-all")


def is_collective(op) -> bool:
    return any(o.startswith(COLLECTIVES) for o in op.opcodes)


def is_dense(op) -> bool:
    return (bool(DENSE.intersection(op.opcodes))
            and not load_reader("spmm_ms").is_aggregation(op)
            and not is_collective(op))


def read(ctx):
    return load_reader("spmm_ms").per_chip_ms(ctx, is_dense)
