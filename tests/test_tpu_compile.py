"""The block-sparse SpMM kernels compile for a TPU v5e, without the chip.

Each test lowers one `gcn_spmm` entry point with `interpret=False` for a
described (not attached) v5e chip at the widths `chip_smoke.py` trains
(Reddit's 602 input features padded to 640, hidden 256, 41 classes padded
to 128) and asserts the Mosaic kernel is in the compiled program. The
chip's compiler refuses what interpret mode accepts — misaligned slices,
too much VMEM — so these guard every change to the kernels at no chip
time. Nothing runs: a compile says nothing about speed or results.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and the tests' workers
import every test file.
"""
import os

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
