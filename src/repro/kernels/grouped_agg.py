"""Grouped pre-aggregation (RDMA-AGG phase 1, paper §5.3).

Scatter-add on TPU done the MXU way: each token block builds a one-hot
(BN, SLOTS) tile via iota-compare and accumulates table += one_hot^T @ vals
into a VMEM-resident (SLOTS,) table across sequential token blocks — the
cache-sized pre-aggregation hash table of the paper, kept in fast memory
while overflow streams out.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(slot_ref, val_ref, table_ref, acc_sc, *, slots, bn):
    j = pl.program_id(0)

    @pl.when(j == 0)
    def _():
        acc_sc[...] = jnp.zeros(acc_sc.shape, jnp.float32)

    s = slot_ref[...]                               # (BN,)
    v = val_ref[...].astype(jnp.float32)            # (BN,)
    onehot = (s[:, None] == jax.lax.broadcasted_iota(
        jnp.int32, (bn, slots), 1)).astype(jnp.float32)
    acc_sc[...] += jax.lax.dot_general(
        onehot, v[:, None], (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)[:, 0]

    @pl.when(j == pl.num_programs(0) - 1)
    def _():
        table_ref[...] = acc_sc[...]


def grouped_agg(slot, vals, num_slots: int, *, block_n: int = 1024,
                interpret: bool = True):
    """slot: (N,) int32 in [0, num_slots); vals: (N,).
    Returns dense table (num_slots,) f32 of per-slot sums.

    ``block_n`` is a multiple of 1024: XLA tiles a 1-D operand in 1024s
    on TPU v5e, and Mosaic refuses a block that splits that tile."""
    pad = (-slot.shape[0]) % block_n
    slot = jnp.pad(slot, (0, pad), constant_values=-1)   # matches no slot
    vals = jnp.pad(vals, (0, pad))
    n = slot.shape[0]
    return pl.pallas_call(
        functools.partial(_kernel, slots=num_slots, bn=block_n),
        grid=(n // block_n,),
        in_specs=[
            pl.BlockSpec((block_n,), lambda j: (j,)),
            pl.BlockSpec((block_n,), lambda j: (j,)),
        ],
        out_specs=pl.BlockSpec((num_slots,), lambda j: (0,)),
        out_shape=jax.ShapeDtypeStruct((num_slots,), jnp.float32),
        scratch_shapes=[pltpu.VMEM((num_slots,), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(slot, vals)
