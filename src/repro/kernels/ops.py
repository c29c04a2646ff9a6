"""jit'd public wrappers for the Pallas kernels.

Each wrapper runs its kernel through Mosaic when the program is lowered
for a TPU and through the Pallas interpreter on any other platform (CPU
validation — kernel body executed as plain XLA).  The choice follows the
platform the program is compiled for, not the process's default backend,
so a compile for a described TPU takes the Mosaic kernel too."""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels import (cas_lock as _cas, flash_attention as _fa,
                           grouped_agg as _ga, radix_partition as _rp,
                           ssd_scan as _ssd)


def _by_platform(kernel, *args, **static):
    return jax.lax.platform_dependent(
        *args, tpu=partial(kernel, **static, interpret=False),
        default=partial(kernel, **static, interpret=True))


@jax.jit
def radix_partition(rows, src):
    return _by_platform(_rp.radix_partition, rows, src)


@partial(jax.jit, static_argnames=("causal", "block_q", "block_k"))
def flash_attention(q, k, v, causal=True, block_q=128, block_k=128):
    return _by_platform(_fa.flash_attention, q, k, v, causal=causal,
                        block_q=block_q, block_k=block_k)


@partial(jax.jit, static_argnames=("chunk", "head_block"))
def ssd_scan(xh, bv, cv, dt, a, chunk=128, head_block=8):
    return _by_platform(_ssd.ssd_scan, xh, bv, cv, dt, a, chunk=chunk,
                        head_block=head_block)


@partial(jax.jit, static_argnames=("num_slots", "block_n"))
def grouped_agg(slot, vals, num_slots, block_n=1024):
    return _by_platform(_ga.grouped_agg, slot, vals, num_slots=num_slots,
                        block_n=block_n)


@partial(jax.jit, static_argnames=("block_n",))
def cas_lock(words, idx, expected, block_n=256):
    return _by_platform(_cas.cas_lock, words, idx, expected, block_n=block_n)
