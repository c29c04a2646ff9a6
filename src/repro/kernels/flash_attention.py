"""Blockwise causal GQA flash attention (prefill hot-spot).

Canonical Pallas pattern: grid (batch, q-heads, Sq/BQ, T/BK); the KV axis is
the innermost *sequential* dim so the running (max, sum, acc) state lives in
VMEM scratch across KV blocks; at the last KV block the normalized output
tile is written. Causal blocks entirely above the diagonal are skipped via
pl.when (no MXU work issued). MXU-aligned tiles: BQ x BK x head_dim all
multiples of 128 at full scale (tests sweep smaller shapes in interpret).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_sc, l_sc, acc_sc,
            *, bq, bk, causal, scale):
    qi = pl.program_id(2)
    kj = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(kj == 0)
    def _():
        m_sc[...] = jnp.full(m_sc.shape, NEG_INF, jnp.float32)
        l_sc[...] = jnp.zeros(l_sc.shape, jnp.float32)
        acc_sc[...] = jnp.zeros(acc_sc.shape, jnp.float32)

    run = (not causal) or (kj * bk <= qi * bq + bq - 1)

    @pl.when(run)
    def _():
        q = q_ref[0, :, 0, :]                       # (BQ, D)
        k = k_ref[0, :, 0, :]                       # (BK, D)
        v = v_ref[0, :, 0, :]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (BQ, BK)
        if causal:
            qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kpos = kj * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(kpos <= qpos, s, NEG_INF)
        m_prev = m_sc[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_sc[...] = l_sc[...] * corr + p.sum(axis=1)
        acc_sc[...] = (acc_sc[...] * corr[:, None]
                       + jax.lax.dot_general(
                           p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                           preferred_element_type=jnp.float32))
        m_sc[...] = m_new

    @pl.when(kj == nk - 1)
    def _():
        l = jnp.maximum(l_sc[...], 1e-30)
        o_ref[0, :, 0, :] = (acc_sc[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True, block_q: int = 128,
                    block_k: int = 128, interpret: bool = True):
    """q: (B, S, H, D); k/v: (B, T, KH, D) with H % KH == 0.
    Returns (B, S, H, D). Head h reads kv head h // (H // KH)."""
    b, s, h, d = q.shape
    t, kh = k.shape[1], k.shape[2]
    assert s % block_q == 0 and t % block_k == 0, (s, t, block_q, block_k)
    g = h // kh
    grid = (b, h, s // block_q, t // block_k)
    return pl.pallas_call(
        functools.partial(_kernel, bq=block_q, bk=block_k, causal=causal,
                          scale=d ** -0.5),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, 1, d), lambda b_, h_, i, j: (b_, i, h_, 0)),
            pl.BlockSpec((1, block_k, 1, d),
                         lambda b_, h_, i, j: (b_, j, h_ // g, 0)),
            pl.BlockSpec((1, block_k, 1, d),
                         lambda b_, h_, i, j: (b_, j, h_ // g, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, 1, d),
                               lambda b_, h_, i, j: (b_, i, h_, 0)),
        out_shape=jax.ShapeDtypeStruct((b, s, h, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
