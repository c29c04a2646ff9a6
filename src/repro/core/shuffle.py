"""Distributed joins (paper §5.1–5.2): GHJ, GHJ+Bloom, RDMA-GHJ, RRJ.

All four share the same local building blocks (radix partition + sort-probe
join) so measured differences isolate the *shuffle strategy*, exactly like
the paper's Fig 8(a).  The shuffle itself is ``fabric.route()`` — the same
radix-into-fixed-buffers + paired all_to_all router RSI commits through —
driven by a pluggable transport: ``MeshTransport`` makes it a real
``all_to_all`` inside shard_map, ``LocalTransport`` is the one-shard ground
truth.  The RDMA variants set ``chunks > 1`` so XLA can overlap transfer
with partitioning compute (selective signaling).  The shuffle's
scatter-into-buffers step is the router's: packed single wire buffer,
sort-free rank-in-bucket binning, and on TPU the Pallas
``repro.kernels.radix_partition`` software-managed-buffer kernel
(jnp scatter elsewhere — see docs/fabric.md).  The *local* radix passes
and the build sort below sort each relation with its payload columns as
operands of one stable ``lax.sort`` — no permutation gathers; the only
gathers left are the probe's.  They never touch the wire, and the jaxpr
sort-free guarantee is scoped to the route/cas/fetch_add hot paths.

Relations are (keys, values) u32/u32; R is the (unique-key) build side.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import bloom as bloom_mod

MISS = jnp.uint32(0xFFFFFFFF)      # sentinel key: filtered / empty slot


def radix_partition(keys, *cols, num_parts: int, bits_from: int = 0):
    """A radix pass: ``keys`` and ``cols`` stably sorted by the partition
    id ``(keys >> bits_from) % num_parts``, the columns carried as
    operands of the sort.  Returns ``(keys, *cols)`` in partition order."""
    part = (keys >> bits_from) % jnp.uint32(num_parts)
    return jax.lax.sort((part, keys, *cols), num_keys=1, is_stable=True)[1:]


def local_join(rk, rv, sk, sv):
    """Join unique-key build side R with probe side S.
    Returns (matched mask (|S|,), r-values aligned to S (|S|,))."""
    with jax.named_scope("join.sort"):
        rks, rvs = jax.lax.sort((rk, rv), num_keys=1, is_stable=True)
    with jax.named_scope("join.probe"):
        pos = jnp.searchsorted(rks, sk)
        pos = jnp.clip(pos, 0, rks.shape[0] - 1)
        hit = rks[pos] == sk
        return hit, jnp.where(hit, rvs[pos], 0)


def join_agg(hit, rv, sv):
    """Benchmark payload: sum of matched value products (forces the join)."""
    return jnp.sum(jnp.where(hit, rv * sv, 0).astype(jnp.uint64))


# -------------------------------------------------------- single-node -----

def ghj_local(rk, rv, sk, sv, *, num_parts: int = 32,
              use_bloom: bool = False, bloom_bits: int = 1 << 20):
    """Grace hash join on one shard (partition -> per-partition join).
    With use_bloom, S is pre-filtered by a Bloom filter on R's keys
    (semi-join reduction; reduces shuffle volume, adds a scan + filter)."""
    if use_bloom:
        bits = bloom_mod.build(rk, bloom_bits)
        keep = bloom_mod.query(bits, sk)
        # fixed-shape filter: drop misses by pointing them at a sentinel key
        sk = jnp.where(keep, sk, MISS)
    with jax.named_scope("join.radix"):
        rk, rv = radix_partition(rk, rv, num_parts=num_parts)
        sk, sv = radix_partition(sk, sv, num_parts=num_parts)
        # pass 2: cache-sized blocks (software-managed buffers)
        rk2, rv2 = radix_partition(rk, rv, num_parts=num_parts, bits_from=16)
        sk2, sv2 = radix_partition(sk, sv, num_parts=num_parts, bits_from=16)
    hit, rvals = local_join(rk2, rv2, sk2, sv2)
    return join_agg(hit, rvals, sv2)


def rrj_local(rk, rv, sk, sv, *, num_blocks: int = 64):
    """RRJ collapses GHJ's network partition + radix pass into ONE radix pass
    straight into cache-sized remote buffers (paper §5.2)."""
    with jax.named_scope("join.radix"):
        rk, rv = radix_partition(rk, rv, num_parts=num_blocks)
        sk, sv = radix_partition(sk, sv, num_parts=num_blocks)
    hit, rvals = local_join(rk, rv, sk, sv)
    return join_agg(hit, rvals, sv)


# --------------------------------------------------------- distributed ----

def _route_by_key(transport, keys, vals, cap: int, chunks: int = 1):
    """Shuffle (keys, vals) to owner shard ``key % n`` through the fabric
    router; MISS keys are filtered, empty slots come back as MISS.
    Returns (keys, vals, dropped) — dropped = rows lost to cap overflow."""
    n = transport.n
    with jax.named_scope("join.route"):
        dest = (keys % jnp.uint32(n)).astype(jnp.int32)
        dest = jnp.where(keys == MISS, n, dest)    # filtered, not dropped
        res = transport.route({"k": keys, "v": vals}, dest, cap=cap,
                              chunks=chunks)
        k = jnp.where(res.valid > 0, res.fields["k"], MISS)
        return k, res.fields["v"], res.dropped


def make_distributed_join(transport, variant: str, *,
                          num_parts: int = 32, bloom_bits: int = 1 << 20,
                          capacity_factor: float = 2.0,
                          return_stats: bool = False):
    """variant in {ghj, ghj_bloom, rdma_ghj, rrj}. Returns f(rk, rv, sk, sv)
    -> u64 join aggregate, where inputs are sharded on axis 0 (under
    ``MeshTransport``) or whole (under ``LocalTransport``).

    Capacity is ``capacity_factor/n`` of each relation per destination
    shard; rows beyond it are dropped by the fixed buffers and the result
    undercounts.  Pass ``return_stats=True`` to get (agg, dropped_rows) and
    check the overflow counter — under heavy skew, raise capacity_factor.
    """
    n = transport.n

    def body(rk, rv, sk, sv):
        if variant == "ghj_bloom":
            # build local bloom over R keys, combine across shards (OR), then
            # filter S before shuffling (semi-join reduction §5.1.2)
            bits = bloom_mod.build(rk, bloom_bits)
            bits = transport.psum(bits.astype(jnp.int32)) > 0
            keep = bloom_mod.query(bits, sk)
            sk = jnp.where(keep, sk, MISS)
        chunks = 4 if variant in ("rdma_ghj", "rrj") else 1
        cap_r = int(rk.shape[0] * capacity_factor / n) // chunks * chunks
        cap_s = int(sk.shape[0] * capacity_factor / n) // chunks * chunks
        rk2, rv2, drop_r = _route_by_key(transport, rk, rv, cap_r,
                                         chunks=chunks)
        sk2, sv2, drop_s = _route_by_key(transport, sk, sv, cap_s,
                                         chunks=chunks)
        if variant == "rrj":
            agg = rrj_local(rk2, rv2, sk2, sv2, num_blocks=num_parts)
        else:
            agg = ghj_local(rk2, rv2, sk2, sv2, num_parts=num_parts)
        return transport.psum(agg), transport.psum(drop_r + drop_s)

    def f(rk, rv, sk, sv):
        agg, dropped = transport.run(body, (rk, rv, sk, sv),
                                     out_reps=(True, True))
        return (agg, dropped) if return_stats else agg

    f.__name__ = f"join_{variant}"     # the jitted program's name
    return f
