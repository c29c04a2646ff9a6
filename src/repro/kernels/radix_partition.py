"""Radix partitioner: rows into software-managed buffers (paper §5.2, RRJ).

The router bins every request with its sort-free plan
(``repro.fabric.router.plan_route``) and inverts it into ``src``: for each
slot of the ``(S, w)`` wire buffer, the index of the request that lands
there, or ``A`` (one past the batch) for an empty slot.  This kernel moves
the rows: slot ``s`` receives row ``src[s]`` and a trailing valid lane (1
where a row landed, 0 in an empty slot) — the router's packed wire format
in one pass.

TPU layout.  A join shuffles 2-word rows, which would pad 64x in 128-lane
tiles, so rows travel transposed: words on sublanes, slots on lanes.  A
block of ``sb`` slots is filled on the MXU by a one-hot matmul,
``out[:, t] = sum_i rows[:, i] * (src[t] == i)``.  Each 32-bit word is
split into byte planes first: bf16 products and f32 sums of 0..255 are
exact, so the result is bit-exact.  The rows a slot block takes belong to
one bucket and arrive in order, so they lie in one contiguous input range;
the kernel DMAs only the input windows that range covers.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

SLOT_BLOCK = 512        # slots (lanes) per output block
WINDOW = 512            # input rows per DMA'd window
WORD_CHUNK = 256        # row words (sublanes) per grid step


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _kernel(src_ref, rows_hbm, out_ref, buf, bounds, sem, *, a, bw, hc,
            valid_row):
    c = pl.program_id(1)
    src = src_ref[...]                                  # (1, sb) int32
    sb = src.shape[1]
    filled = src < a
    bounds[0] = jnp.min(jnp.where(filled, src, a)) // bw
    bounds[1] = jnp.max(jnp.where(filled, src, -1)) // bw + 1
    out_ref[...] = jnp.zeros(out_ref.shape, out_ref.dtype)

    def window(wi, carry):
        cp = pltpu.make_async_copy(
            rows_hbm.at[pl.ds(pl.multiple_of(c * hc, 8), hc),
                        pl.ds(pl.multiple_of(wi * bw, 128), bw)], buf, sem)
        cp.start()
        cp.wait()
        rows = buf[...]                                 # (hc, bw) int32
        ids = wi * bw + jax.lax.broadcasted_iota(jnp.int32, (bw, sb), 0)
        onehot = (ids == src).astype(jnp.float32)       # (bw, sb)
        planes = jnp.concatenate(
            [jax.lax.shift_right_logical(rows, 8 * k) & 0xFF
             for k in range(4)], axis=0).astype(jnp.float32)
        part = jnp.dot(planes, onehot,
                       preferred_element_type=jnp.float32).astype(jnp.int32)
        word = part[:hc]
        for k in range(1, 4):
            word = word | (part[k * hc:(k + 1) * hc] << (8 * k))
        out_ref[...] |= word
        return carry

    jax.lax.fori_loop(bounds[0], bounds[1], window, 0)
    row = c * hc + jax.lax.broadcasted_iota(jnp.int32, (hc, sb), 0)
    out_ref[...] = jnp.where(row == valid_row, filled.astype(jnp.int32),
                             out_ref[...])


def radix_partition(rows, src, *, interpret: bool = True):
    """rows: (A, w) uint32 packed request rows; src: (S,) int32 — the row
    each wire slot takes, ``A`` for an empty slot.  Returns the ``(S, w+1)``
    uint32 wire buffer: ``out[s] = [rows[src[s]], 1]``, all zeros where
    ``src[s] == A``."""
    a, w = rows.shape
    s = src.shape[0]
    sb = min(SLOT_BLOCK, _round_up(s, 128))
    bw = min(WINDOW, _round_up(max(a, 1), 128))
    chunks = -(-(w + 1) // WORD_CHUNK)
    hc = _round_up(-(-(w + 1) // chunks), 8)
    h = chunks * hc
    sp, ap = _round_up(s, sb), _round_up(max(a, 1), bw)
    rows_t = jnp.pad(jax.lax.bitcast_convert_type(rows, jnp.int32).T,
                     ((0, h - w), (0, ap - a)))
    src = jnp.pad(src.astype(jnp.int32), (0, sp - s),
                  constant_values=a)[None, :]
    out = pl.pallas_call(
        functools.partial(_kernel, a=a, bw=bw, hc=hc, valid_row=w),
        grid=(sp // sb, h // hc),
        in_specs=[pl.BlockSpec((1, sb), lambda b, c: (0, b)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((hc, sb), lambda b, c: (c, b)),
        out_shape=jax.ShapeDtypeStruct((h, sp), jnp.int32),
        scratch_shapes=[pltpu.VMEM((hc, bw), jnp.int32),
                        pltpu.SMEM((2,), jnp.int32),
                        pltpu.SemaphoreType.DMA(())],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(src, rows_t)
    return jax.lax.bitcast_convert_type(out[:w + 1, :s].T, jnp.uint32)
