"""RSI validate+lock arbitration kernel (paper §4.2, Table 1).

The home-shard twin of the RNIC's atomic compare-and-swap: a batch of lock
requests (record row, expected word) is applied against the lock-word array
sequentially within the kernel (one grid step per request block, fori_loop
inside) — exactly the FIFO the paper gets from RDMA queue pairs. Words are
u32 here (1-bit lock | 31-bit CID) because TPU vector lanes are 32-bit; the
u64 protocol layout lives in ``repro.core.rsi``.

words is aliased in/out (input_output_aliases) — in-place memory semantics.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LOCK_BIT_32 = jnp.uint32(1 << 31)


def _kernel(idx_ref, exp_ref, words_ref, out_words_ref, ok_ref, *, bn, nwords):
    j = pl.program_id(0)

    @pl.when(j == 0)
    def _():
        out_words_ref[...] = words_ref[...]

    idx = idx_ref[...]
    exp = exp_ref[...]

    def body(i, _):
        r = idx[i]
        valid = (r >= 0) & (r < nwords)
        r_safe = jnp.where(valid, r, 0)
        cur = out_words_ref[pl.ds(r_safe, 1)][0]
        ok = valid & (cur == exp[i])

        @pl.when(ok)
        def _():
            locked = exp[i] | jnp.uint32(1 << 31)
            out_words_ref[pl.ds(r_safe, 1)] = locked[None]
        ok_ref[pl.ds(i, 1)] = ok[None]
        return 0

    jax.lax.fori_loop(0, bn, body, 0)


def cas_lock(words, idx, expected, *, block_n: int = 256,
             interpret: bool = True):
    """words: (R,) u32 lock|CID; idx: (A,) int32; expected: (A,) u32.
    Returns (ok (A,) bool, new_words (R,)). Requests apply in order."""
    a = idx.shape[0]
    r = words.shape[0]
    assert a % block_n == 0
    new_words, ok = pl.pallas_call(
        functools.partial(_kernel, bn=block_n, nwords=r),
        grid=(a // block_n,),
        in_specs=[
            pl.BlockSpec((block_n,), lambda j: (j,)),
            pl.BlockSpec((block_n,), lambda j: (j,)),
            pl.BlockSpec((r,), lambda j: (0,)),
        ],
        out_specs=[
            pl.BlockSpec((r,), lambda j: (0,)),
            pl.BlockSpec((block_n,), lambda j: (j,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((r,), jnp.uint32),
            jax.ShapeDtypeStruct((a,), jnp.bool_),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(idx, expected, words)
    return ok, new_words
