"""Pallas kernels vs pure-jnp oracles: property sweeps over shapes, dtypes,
densities, and masking modes (interpret mode on CPU). Sweeps use hypothesis
when installed, else the deterministic fallback in _hypothesis_compat."""
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels import ops
from repro.kernels.gcn_spmm import TILE, build_tiles, spmm_block_sparse
from repro.kernels.flash_attention import flash_attention
from repro.kernels.ref import mha_ref, spmm_ref

ATOL = {jnp.float32: 2e-5, jnp.bfloat16: 5e-2}


# ------------------------------------------------------------------ SpMM

@settings(max_examples=12, deadline=None)
@given(rb=st.integers(1, 3), cb=st.integers(1, 3),
       fmul=st.integers(1, 2), density=st.floats(0.005, 0.08),
       seed=st.integers(0, 100))
def test_spmm_sweep(rb, cb, fmul, density, seed):
    rng = np.random.default_rng(seed)
    R, C, F = rb * TILE, cb * TILE, fmul * 128
    dense = ((rng.random((R, C)) < density)
             * rng.normal(size=(R, C))).astype(np.float32)
    h = rng.normal(size=(C, F)).astype(np.float32)
    tr, tc, tv = build_tiles(dense, R, C)
    got = spmm_block_sparse(jnp.asarray(tr), jnp.asarray(tc), jnp.asarray(tv),
                            jnp.asarray(h), R)
    np.testing.assert_allclose(np.asarray(got), dense @ h, atol=2e-4)
    ref = spmm_ref(jnp.asarray(tr), jnp.asarray(tc), jnp.asarray(tv),
                   jnp.asarray(h), R)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-5)


def test_spmm_empty_row_blocks():
    """Row blocks with no edges must produce zeros (filler-tile path)."""
    rng = np.random.default_rng(0)
    R, C, F = 3 * TILE, 2 * TILE, 128
    dense = np.zeros((R, C), np.float32)
    dense[:TILE] = (rng.random((TILE, C)) < 0.05) * 1.0   # only block-row 0
    h = rng.normal(size=(C, F)).astype(np.float32)
    tr, tc, tv = build_tiles(dense, R, C)
    got = np.asarray(spmm_block_sparse(jnp.asarray(tr), jnp.asarray(tc),
                                       jnp.asarray(tv), jnp.asarray(h), R))
    np.testing.assert_allclose(got, dense @ h, atol=2e-4)
    assert np.all(got[TILE:] == 0)


def test_spmm_real_graph_partition():
    """End to end: a real partition's local propagation as block-sparse."""
    from repro.graph import make_dataset, partition_graph, build_partitioned_graph
    from repro.graph.csr import sym_normalized
    ds = make_dataset("tiny")
    prop = sym_normalized(ds.graph)
    pg = build_partitioned_graph(prop, partition_graph(ds.graph, 2, seed=0), 2)
    i = 0
    row = pg.edge_row[i].astype(np.int64)
    col = pg.edge_col[i].astype(np.int64)
    w = pg.edge_w[i]
    combined = pg.max_inner + pg.num_parts * pg.slot
    rng = np.random.default_rng(1)
    h = rng.normal(size=(-(-combined // TILE) * TILE, 128)).astype(np.float32)
    tr, tc, tv = build_tiles((row, col, w), pg.max_inner, combined)
    rpad = -(-pg.max_inner // TILE) * TILE
    got = np.asarray(ops.spmm(jnp.asarray(tr), jnp.asarray(tc),
                              jnp.asarray(tv), jnp.asarray(h), rpad))
    want = np.zeros((rpad, 128), np.float32)
    np.add.at(want, row, w[:, None] * h[col])
    np.testing.assert_allclose(got, want, atol=2e-4)


# ------------------------------------------------------------ attention

@settings(max_examples=12, deadline=None)
@given(b=st.integers(1, 2), smul=st.integers(1, 3),
       h=st.sampled_from([2, 4]), g=st.sampled_from([1, 2]),
       d=st.sampled_from([32, 64]),
       causal=st.booleans(), windowed=st.booleans(),
       seed=st.integers(0, 100))
def test_flash_attention_sweep(b, smul, h, g, d, causal, windowed, seed):
    rng = np.random.default_rng(seed)
    S = smul * 256
    kh = h // g
    window = 192 if windowed else 0
    q = jnp.asarray(rng.normal(size=(b, S, h, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, S, kh, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, S, kh, d)), jnp.float32)
    got = flash_attention(q, k, v, causal=causal, window=window,
                          q_block=128, kv_block=128)
    want = mha_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_flash_attention_bf16():
    rng = np.random.default_rng(3)
    B, S, H, d = 1, 256, 4, 64
    q = jnp.asarray(rng.normal(size=(B, S, H, d)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(B, S, H, d)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(B, S, H, d)), jnp.bfloat16)
    got = flash_attention(q, k, v, causal=True, q_block=128, kv_block=128)
    want = mha_ref(q.astype(jnp.float32), k.astype(jnp.float32),
                   v.astype(jnp.float32), causal=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want), atol=5e-2)


def test_flash_matches_model_blockwise_path():
    """Kernel vs the model's jnp blockwise path (the serving oracle)."""
    from repro.models.attention import blockwise_attention
    rng = np.random.default_rng(4)
    B, S, H, K, d = 1, 512, 4, 2, 64
    q = jnp.asarray(rng.normal(size=(B, S, H, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, K, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, K, d)), jnp.float32)
    a = flash_attention(q, k, v, causal=True, window=100,
                        q_block=128, kv_block=128)
    b_ = blockwise_attention(q, k, v, jnp.arange(S), True, 100,
                             q_block=128, kv_block=128)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=2e-5)


def test_ops_wrappers_jit():
    rng = np.random.default_rng(5)
    q = jnp.asarray(rng.normal(size=(1, 256, 2, 32)), jnp.float32)
    out = ops.attention(q, q, q, causal=True, q_block=128, kv_block=128)
    assert out.shape == q.shape
    assert bool(jnp.all(jnp.isfinite(out)))


@pytest.mark.parametrize("backend,want", [("cpu", True), ("tpu", False)])
def test_resolve_interpret_by_backend(monkeypatch, backend, want):
    from repro.kernels import gcn_spmm
    monkeypatch.setattr(gcn_spmm.jax, "default_backend", lambda: backend)
    assert gcn_spmm.resolve_interpret(None) is want
    assert gcn_spmm.resolve_interpret(not want) is (not want)


def test_resolve_interpret_raises_on_other_backends(monkeypatch):
    """A GPU, or any backend but the TPU and the CPU, must not silently
    run the kernels in the Pallas interpreter."""
    from repro.kernels import gcn_spmm
    monkeypatch.setattr(gcn_spmm.jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="gpu"):
        gcn_spmm.resolve_interpret(None)
    assert gcn_spmm.resolve_interpret(True) is True
