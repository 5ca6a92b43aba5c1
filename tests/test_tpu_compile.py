"""The block-sparse SpMM kernels compile for a TPU v5e, without the chip.

Each test lowers one `gcn_spmm` entry point with `interpret=False` for a
described (not attached) v5e chip at the widths `chip_smoke.py` trains
(Reddit's 602 input features padded to 640, hidden 256, 41 classes padded
to 128) and asserts the Mosaic kernel is in the compiled program. The
chip's compiler refuses what interpret mode accepts — misaligned slices,
too much VMEM — so these guard every change to the kernels at no chip
time. Nothing runs: a compile says nothing about speed or results. The
precision tests read the Mosaic module inside each kernel's custom call and
check the contract precision of every MXU matmul in it.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and the tests' workers
import every test file.
"""
import base64
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import gcn_spmm as k

T = k.TILE
F_IN, HIDDEN, CLASSES = 640, 256, 128     # 602 -> 640, 41 -> 128 padded
N_TILES = 512
ROWS = 16 * T                              # one partition's inner rows
COLS = 64 * T                              # inner + halo columns
PARTS = 4


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, one_chip, *shapes):
    """Compile `fn` for the described chip from (shape, dtype) pairs and
    return the compiled program's text. 64-bit mode is off inside, as on
    the chip: other test files turn it on for the whole process, and Mosaic
    cannot legalize the kernels' 64-bit index arithmetic."""
    with jax.enable_x64(False):
        args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                for s, d in shapes]
        return jax.jit(fn).lower(*args).compile().as_text()


def _idx(*lead):
    return (lead + (N_TILES,), jnp.int32)


def _vals(*lead):
    return (lead + (N_TILES, T, T), jnp.float32)


@pytest.mark.parametrize("f", [F_IN, HIDDEN])
def test_forward_compiles(one_chip, f):
    text = _compile(
        lambda r, c, v, h: k.spmm_block_sparse(r, c, v, h, ROWS,
                                               interpret=False),
        one_chip, _idx(), _idx(), _vals(), ((COLS, f), jnp.float32))
    assert "tpu_custom_call" in text


def test_transpose_compiles(one_chip):
    text = _compile(
        lambda o, i, p, v, dz: k.spmm_block_sparse_t(o, i, p, v, dz, COLS,
                                                     interpret=False),
        one_chip, _idx(), _idx(), _idx(), _vals(),
        ((ROWS, F_IN), jnp.float32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("fin,fout", [(F_IN, HIDDEN), (HIDDEN, CLASSES)])
def test_fused_compiles(one_chip, fin, fout):
    text = _compile(
        lambda r, c, v, h, w, b: k.spmm_block_sparse_fused(
            r, c, v, h, w, b, ROWS, relu=True, with_z=True,
            interpret=False),
        one_chip, _idx(), _idx(), _vals(), ((COLS, fin), jnp.float32),
        ((fin, fout), jnp.float32), ((1, fout), jnp.float32))
    assert "tpu_custom_call" in text


def test_fused_transpose_compiles(one_chip):
    text = _compile(
        lambda o, i, p, v, du, w: k.spmm_block_sparse_fused_t(
            o, i, p, v, du, w, COLS, interpret=False),
        one_chip, _idx(), _idx(), _idx(), _vals(),
        ((ROWS, HIDDEN), jnp.float32), ((F_IN, HIDDEN), jnp.float32))
    assert "tpu_custom_call" in text


def test_vmapped_forward_compiles(one_chip):
    """The form the sim backend issues: one kernel call vmapped over the
    partition axis."""
    fwd = jax.vmap(lambda r, c, v, h: k.spmm_block_sparse(
        r, c, v, h, ROWS, interpret=False))
    text = _compile(fwd, one_chip, _idx(PARTS), _idx(PARTS), _vals(PARTS),
                    ((PARTS, COLS, F_IN), jnp.float32))
    assert "tpu_custom_call" in text


def _matmuls(fn, one_chip, *shapes):
    """Every `tpu.matmul` of the Mosaic kernels in `fn` lowered for the
    described chip, one line of the kernel module's text each. The kernel
    travels as MLIR bytecode, base64 in the custom call's config; its
    dialects are not registered in JAX's context, hence the generic form."""
    from jax._src.interpreters import mlir
    from jax._src.lib.mlir import ir
    with jax.enable_x64(False):
        args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                for s, d in shapes]
        text = jax.jit(fn).lower(*args).as_text()
    bodies = re.findall(r"body\\22: \\22([A-Za-z0-9+/=]+)", text)
    assert bodies, "no Mosaic kernel in the lowered program"
    lines = []
    for body in bodies:
        ctx = mlir.make_ir_context()
        ctx.allow_unregistered_dialects = True
        with ctx:
            module = ir.Module.parse(base64.b64decode(body))
        lines += [ln for ln in str(module).splitlines() if "tpu.matmul" in ln]
    return lines


def _kernels(dtype):
    """(name, fn, shapes) of every kernel entry point, operands in dtype."""
    tile = (N_TILES, T, T)
    return [
        ("forward", lambda r, c, v, h: k.spmm_block_sparse(
            r, c, v, h, ROWS, interpret=False),
         [_idx(), _idx(), (tile, dtype), ((COLS, F_IN), dtype)]),
        ("transpose", lambda o, i, p, v, dz: k.spmm_block_sparse_t(
            o, i, p, v, dz, COLS, interpret=False),
         [_idx(), _idx(), _idx(), (tile, dtype), ((ROWS, F_IN), dtype)]),
        ("forward_phased", lambda r, c, v, h: k.spmm_block_sparse_phased(
            r, c, v, h, ROWS, N_TILES // 4, "boundary", interpret=False),
         [_idx(), _idx(), (tile, dtype), ((COLS, HIDDEN), dtype)]),
        ("transpose_phased",
         lambda o, i, p, v, dz: k.spmm_block_sparse_t_phased(
             o, i, p, v, dz, COLS, N_TILES // 4, "interior",
             interpret=False),
         [_idx(), _idx(), _idx(), (tile, dtype), ((ROWS, HIDDEN), dtype)]),
        ("fused", lambda r, c, v, h, w, b: k.spmm_block_sparse_fused(
            r, c, v, h, w, b, ROWS, relu=True, with_z=True,
            interpret=False)[0],
         [_idx(), _idx(), (tile, dtype), ((COLS, F_IN), dtype),
          ((F_IN, HIDDEN), dtype), ((1, HIDDEN), dtype)]),
        ("fused_transpose",
         lambda o, i, p, v, du, w: k.spmm_block_sparse_fused_t(
             o, i, p, v, du, w, COLS, interpret=False),
         [_idx(), _idx(), _idx(), (tile, dtype), ((ROWS, HIDDEN), dtype),
          ((F_IN, HIDDEN), dtype)]),
    ]


@pytest.mark.parametrize("case", _kernels(jnp.float32),
                         ids=lambda c: c[0])
def test_f32_kernel_dots_contract_at_fp32(one_chip, case):
    """Every matmul of an f32 kernel, the fused kernels' epilogue and
    prologue included, lowers with `contract_precision<fp32>`: the tile
    engines sum at f32 accuracy, not in Mosaic's default single bf16
    pass."""
    _, fn, shapes = case
    lines = _matmuls(fn, one_chip, *shapes)
    assert lines
    assert all("#tpu.contract_precision<fp32>" in ln for ln in lines), lines


def test_bf16_forward_keeps_the_default_precision(one_chip):
    _, fn, shapes = _kernels(jnp.bfloat16)[0]
    lines = _matmuls(fn, one_chip, *shapes)
    assert lines
    assert not any("contract_precision" in ln for ln in lines), lines
