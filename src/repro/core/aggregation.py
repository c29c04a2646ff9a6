"""Distributed aggregation (paper §5.3, Fig 8b).

Dist-AGG (classic hierarchical): local aggregate -> global union ->
post-aggregate. Cost grows with #distinct keys (the union re-aggregates
nodes x groups rows).

RDMA-AGG (paper): cache-sized local pre-aggregation tables; overflow is
*flushed in the background* to hash-partitioned owner shards — here each
chunk's pre-aggregated partition tables are requests routed through
``fabric.route()`` (dest = owner shard, chunked exchange = the background
flush; the router packs the tables into its single wire buffer and, on
TPU, bins them with the Pallas ``kernels/radix_partition`` kernel) — then
parallel per-owner post-aggregation.  More partitions than workers =>
robust to skew and high distinct counts.

Both builders take a fabric transport (``LocalTransport`` for one-shard
ground truth, ``MeshTransport(mesh, axis)`` for the real collectives).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def segment_sum_by_key(keys, vals, num_slots: int):
    """Exact grouped sum via sort (keys u32 < num_slots space assumed hashed).
    Returns (unique_slots dense array of sums (num_slots,))."""
    return jnp.zeros((num_slots,), jnp.uint64).at[
        (keys % jnp.uint32(num_slots)).astype(jnp.int32)].add(
            vals.astype(jnp.uint64))


def preagg_table(keys, vals, table_slots: int):
    """Cache-sized direct-mapped pre-aggregation: collisions are *merged*
    (hash-group semantics — benchmark aggregates by hashed group, matching
    how the paper sizes L3-resident tables). Returns (table (slots,),
    slot_keys)."""
    slot = (keys % jnp.uint32(table_slots)).astype(jnp.int32)
    table = jnp.zeros((table_slots,), jnp.uint64).at[slot].add(
        vals.astype(jnp.uint64))
    return table


def _program(name: str, transport, body):
    """f(keys, vals) running ``body`` over the transport, named ``name``
    (its jitted program is ``jit_<name>``)."""
    def f(keys, vals):
        return transport.run(body, (keys, vals), out_reps=True)

    f.__name__ = name
    return f


def dist_agg(transport, num_groups: int):
    """Classic hierarchical aggregation. Inputs sharded on axis 0.
    Returns f(keys, vals) -> dense (num_groups,) sums (group = key hash)."""

    def body(keys, vals):
        with jax.named_scope("agg.local"):
            local = segment_sum_by_key(keys, vals, num_groups)  # phase 1
        # global union + post-aggregation on every node (paper: the union
        # output is #nodes x #groups rows)
        with jax.named_scope("agg.merge"):
            return transport.psum(local)                        # phase 2

    return _program("agg_dist_agg", transport, body)


def rdma_agg(transport, num_groups: int, *, table_slots: int = 4096,
             chunks: int = 4):
    """RDMA-optimized aggregation. Groups are hash-partitioned across shards
    (owner = slot // (groups/n)); each chunk pre-aggregates into per-owner
    cache-sized tables which stream to their owners through the fabric
    router (background flush = chunked exchange), and each owner
    post-aggregates only its slice."""
    n = transport.n
    assert num_groups % n == 0 or num_groups < n

    def body(keys, vals):
        gsz = max(num_groups // n, 1)
        N = keys.shape[0]
        with jax.named_scope("agg.local"):
            # phase 1: per-chunk cache-sized pre-aggregation into the
            # owner layout — one (n, gsz) partition table per chunk
            ck = keys.reshape(chunks, N // chunks)
            cv = vals.reshape(chunks, N // chunks)
            slot = (ck % jnp.uint32(num_groups)).astype(jnp.int32)
            owner = jnp.minimum(slot // gsz, n - 1)
            ci = jnp.broadcast_to(
                jnp.arange(chunks, dtype=jnp.int32)[:, None], slot.shape)
            part = jnp.zeros((chunks, n, gsz), jnp.uint64).at[
                ci, owner, slot % gsz].add(cv.astype(jnp.uint64))
        with jax.named_scope("agg.route"):
            # background flush: route each chunk's n owner tables (dest =
            # owner, cap = chunks, chunked exchange pipelines the transfer)
            tabs = part.reshape(chunks * n, gsz)
            dest = jnp.tile(jnp.arange(n, dtype=jnp.int32), chunks)
            res = transport.route({"tab": tabs}, dest, cap=chunks,
                                  chunks=chunks)
        with jax.named_scope("agg.merge"):
            # phase 2: parallel post-aggregation of my slice only
            mine = jnp.sum(res.fields["tab"]
                           * (res.valid > 0).astype(jnp.uint64)[:, None],
                           axis=0)
            return transport.all_gather(mine)[:num_groups]

    return _program("agg_rdma_agg", transport, body)
