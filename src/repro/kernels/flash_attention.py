"""Flash attention Pallas TPU kernel: blockwise online-softmax GQA attention
with causal and sliding-window masking — the prefill hot path.

Grid: (batch, q_heads, num_q_blocks, num_kv_blocks), kv innermost. The
running max / denominator / accumulator live in VMEM scratch across the kv
sweep; the output block is written on the last kv step. BlockSpec tiling
keeps one (Bq × d) query tile and one (Bk × d) kv tile resident per step —
VMEM working set = Bq·d + 2·Bk·d + Bq·Bk floats, MXU-aligned for d ≥ 128.

GQA is expressed in the index maps (kv head = q head // group) so no
repeated-KV materialization happens.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.gcn_spmm import resolve_interpret

DEFAULT_Q_BLOCK = 512
DEFAULT_KV_BLOCK = 512
NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale: float, causal: bool, window: int, q_block: int,
            kv_block: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[...]                                # (Bq, d)
    k = k_ref[...]                                # (Bk, d)
    v = v_ref[...]
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale

    qpos = qi * q_block + jax.lax.broadcasted_iota(jnp.int32,
                                                   (q_block, kv_block), 0)
    kpos = ki * kv_block + jax.lax.broadcasted_iota(jnp.int32,
                                                    (q_block, kv_block), 1)
    mask = jnp.ones((q_block, kv_block), jnp.bool_)
    if causal:
        mask &= kpos <= qpos
    if window:
        mask &= qpos - kpos < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=1)
    acc_ref[...] = (acc_ref[...] * alpha[:, None]
                    + jnp.dot(p.astype(v.dtype), v,
                              preferred_element_type=jnp.float32))
    m_ref[...] = m_new

    @pl.when(ki == pl.num_programs(3) - 1)
    def _():
        o_ref[...] = (acc_ref[...]
                    / jnp.maximum(l_ref[...], 1e-30)[:, None]
                    ).astype(o_ref.dtype)


def flash_attention(q, k, v, causal: bool = True, window: int = 0,
                    q_block: int = DEFAULT_Q_BLOCK,
                    kv_block: int = DEFAULT_KV_BLOCK,
                    interpret: bool | None = None):
    """q: (B, S, H, d), k/v: (B, T, K, d) with H % K == 0 -> (B, S, H, d)."""
    b, s, h, d = q.shape
    t, kh = k.shape[1], k.shape[2]
    assert s % q_block == 0 and t % kv_block == 0, (s, t, q_block, kv_block)
    g = h // kh
    scale = 1.0 / (d ** 0.5)
    nq, nk = s // q_block, t // kv_block

    qh = jnp.moveaxis(q, 2, 1)       # (B, H, S, d)
    kh_ = jnp.moveaxis(k, 2, 1)      # (B, K, T, d)
    vh = jnp.moveaxis(v, 2, 1)

    kernel = functools.partial(_kernel, scale=scale, causal=causal,
                               window=window, q_block=q_block,
                               kv_block=kv_block)
    out = pl.pallas_call(
        kernel,
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((None, None, q_block, d),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((None, None, kv_block, d),
                         lambda bi, hi, qi, ki, g=g: (bi, hi // g, ki, 0)),
            pl.BlockSpec((None, None, kv_block, d),
                         lambda bi, hi, qi, ki, g=g: (bi, hi // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, q_block, d),
                               lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, s, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((q_block,), jnp.float32),
            pltpu.VMEM((q_block,), jnp.float32),
            pltpu.VMEM((q_block, d), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(qh.reshape(b, h, nq * q_block, d),
      kh_.reshape(b, kh, nk * kv_block, d),
      vh.reshape(b, kh, nk * kv_block, d))
    return jnp.moveaxis(out, 1, 2)
