"""Mamba2 SSD chunk scan (state-space duality) — jamba/mamba2 hot loop.

Grid (batch, head-block, chunk): the chunk axis is sequential; the carried
recurrent state (HB, hd, N) lives in VMEM scratch across chunks. Per chunk:
intra-chunk quadratic term ((C B^T) o decay masked) plus inter-chunk term
C . state, then the state update with cumulative decay — all per-head-block
so the (L, L) decay tile and the state tile fit VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, b_ref, c_ref, dt_ref, a_ref, y_ref, st_sc, *, hb, l):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _():
        st_sc[...] = jnp.zeros(st_sc.shape, jnp.float32)

    x = x_ref[0].astype(jnp.float32)        # (L, HB, hd)
    bv = b_ref[0].astype(jnp.float32)       # (L, N)
    cv = c_ref[0].astype(jnp.float32)       # (L, N)
    dt = dt_ref[0].astype(jnp.float32)      # (L, HB)
    a = a_ref[0, :]                          # (HB,) negative

    dA = dt * a[None, :]                     # (L, HB)
    seg = jnp.cumsum(dA, axis=0)
    state = st_sc[...]                       # (HB, hd, N)

    # inter-chunk: y_i = C_i . state * exp(seg_i)
    y_inter = jnp.einsum("ln,hdn->lhd", cv, state) * jnp.exp(seg)[:, :, None]
    # intra-chunk
    cb = jnp.einsum("in,jn->ij", cv, bv)     # (L, L)
    decay = jnp.exp(seg[:, None, :] - seg[None, :, :])        # (i, j, HB)
    mask = (jax.lax.broadcasted_iota(jnp.int32, (l, l), 0)
            >= jax.lax.broadcasted_iota(jnp.int32, (l, l), 1))
    m = jnp.where(mask[:, :, None], decay * dt[None, :, :], 0.0)
    y_intra = jnp.einsum("ij,ijh,jhd->ihd", cb, m, x)
    y_ref[0] = (y_inter + y_intra).astype(y_ref.dtype)

    # state update
    w = jnp.exp(seg[-1][None, :] - seg) * dt                  # (L, HB)
    st_sc[...] = (state * jnp.exp(seg[-1])[:, None, None]
                  + jnp.einsum("lh,lhd,ln->hdn", w, x, bv))


def ssd_scan(xh, bv, cv, dt, a, *, chunk: int = 128, head_block: int = 8,
             interpret: bool = True):
    """xh: (B, S, H, hd); bv/cv: (B, S, N); dt: (B, S, H) f32; a: (H,) f32.
    Returns y: (B, S, H, hd)."""
    B, S, H, hd = xh.shape
    N = bv.shape[-1]
    assert S % chunk == 0 and H % head_block == 0
    grid = (B, H // head_block, S // chunk)
    return pl.pallas_call(
        functools.partial(_kernel, hb=head_block, l=chunk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, head_block, hd),
                         lambda b, h, c: (b, c, h, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, h, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, h, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, head_block), lambda b, h, c: (b, c, h)),
            pl.BlockSpec((1, head_block), lambda b, h, c: (0, h)),
        ],
        out_specs=pl.BlockSpec((1, chunk, head_block, hd),
                               lambda b, h, c: (b, c, h, 0)),
        out_shape=jax.ShapeDtypeStruct((B, S, H, hd), xh.dtype),
        scratch_shapes=[pltpu.VMEM((head_block, hd, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(xh, bv, cv, dt, a.reshape(1, H))
