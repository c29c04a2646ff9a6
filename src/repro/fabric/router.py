"""The single request router (paper §5.2 software-managed buffers).

Every distributed protocol in the repo — RSI prepare/install, all four join
shuffles, RDMA-AGG's background flush — is the same motion: radix-partition
a batch of requests by destination shard into fixed ``(n, cap)`` buffers,
then exchange buffers with the paired ``all_to_all``.  :func:`route` is that
motion, written once:

  * **fields** is an arbitrary pytree of per-request arrays (leading dim A);
  * **dest** maps each request to a shard id; ``dest >= n`` (or negative)
    means *filtered* (the request is intentionally not sent — e.g. Bloom
    misses, unused txn write slots) and is **not** counted as a drop;
  * requests beyond a destination's ``cap`` are **dropped** and counted in
    ``RouteResult.dropped`` — fixed buffers are the paper's flow control, and
    silent truncation would corrupt protocols, so the counter is surfaced;
  * ``chunks > 1`` pipelines the exchange chunk-by-chunk (the paper's
    selective-signaling overlap) via an internal scan.

The exchange itself is injected by the transport (``None`` = stay local), so
the same router serves a single shard and a shard_mapped mesh unchanged.

Two hot-path properties realize the paper's per-message argument (§3.3):

**Packed wire format.**  All field leaves plus the occupancy mask travel in
ONE contiguous ``(n*cap, row_words)`` uint32 buffer: each leaf's row is
bitcast into 32-bit word lanes (sub-word dtypes padded up to a whole lane),
the last lane is the valid mask, and the receiver bitcasts the lanes back.
One ``route()`` is therefore exactly one ``all_to_all`` regardless of field
count — the doorbell-batching move: message count is per *routed batch*,
not per pytree leaf.  ``chunked_all_to_all`` pipelines the packed buffer.

**Sort-free binning.**  Slot assignment is a one-pass rank-in-bucket
scatter (:func:`bucket_ranks`: cumulative one-hot counts, O(A·n) fully
parallel work) instead of the former ``argsort`` + ``searchsorted`` — no
``sort`` primitive anywhere in a routed trace (guarded by tests).  Per-shard
A shrinks as n grows under a sharded mesh, so A·n stays ~the global batch.

On TPU the scatter-into-buffers step instead runs the Pallas
``repro.kernels.radix_partition`` kernel (``backend="pallas"``, the default
when the route is compiled for a TPU): the plan's slot map is inverted and
the kernel fills each wire slot from its source row, valid lane included.
The jnp scatter runs everywhere else and is the reference semantics; the
two are bit-identical.

A :class:`RoutePlan` (:func:`plan_route`) precomputes the slot assignment
for a given ``dest`` so protocols with identical routing across rounds —
RSI's prepare and install travel to the same home shards — bin once and
reuse; ``mask=`` filters requests out of a reused plan without re-ranking
(their slots stay reserved, which is exactly what keeps response slots
stable across the rounds).
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

WORD = jnp.uint32
WORD_BYTES = 4

#: scatter backends: "jnp" = pure-jnp reference scatter, "pallas" = the
#: kernels/radix_partition software-managed-buffer kernel; None = by the
#: platform the route is compiled for (pallas on TPU, jnp elsewhere).
ROUTE_BACKENDS = ("jnp", "pallas")


@dataclass
class RouteResult:
    """Outcome of one routed batch.

    fields:  pytree of (n*cap, ...) buffers *after* the exchange (receiver
             view: slots [p*cap:(p+1)*cap] came from peer p).
    valid:   (n*cap,) int32 occupancy mask (the packed buffer's valid lane).
    dropped: () int32 — local requests lost to capacity overflow (pre-
             exchange; filtered dest >= n requests are not counted).
    sent:        pytree of (n*cap, ...) buffers as *sent* (pre-exchange) —
                 the return-path key: a paired reverse exchange delivers
                 responses back to exactly these slots.
    sent_valid:  (n*cap,) int32 occupancy of the sent buffers.
    """
    fields: Any
    valid: jnp.ndarray
    dropped: jnp.ndarray
    sent: Any
    sent_valid: jnp.ndarray


# ---------------------------------------------------- packed wire format --

def _leaf_row_words(shape, dtype) -> int:
    """Words per request row of one leaf (row bytes padded to whole 32-bit
    lanes)."""
    row_bytes = math.prod(shape[1:]) * jnp.dtype(dtype).itemsize
    return -(-row_bytes // WORD_BYTES)


def packed_row_words(fields) -> int:
    """Static wire width of one packed request row, in uint32 lanes: every
    leaf's word lanes plus the trailing valid lane.  This is what one slot
    of the ``(n*cap, row_words)`` wire buffer costs, and what the transport
    bills ``route`` bytes from."""
    leaves = jax.tree_util.tree_leaves(fields)
    return sum(_leaf_row_words(l.shape, l.dtype) for l in leaves) + 1


def _pack_leaf(x) -> jnp.ndarray:
    """(A, ...) any dtype -> (A, w) uint32 word lanes (bit-exact)."""
    A = x.shape[0]
    flat = x.reshape(A, math.prod(x.shape[1:]))
    if flat.dtype == jnp.bool_:
        flat = flat.astype(jnp.uint8)
    if flat.dtype.itemsize < WORD_BYTES:          # sub-word: group lanes
        per = WORD_BYTES // flat.dtype.itemsize
        pad = (-flat.shape[1]) % per
        if pad:
            flat = jnp.pad(flat, ((0, 0), (0, pad)))
        flat = flat.reshape(A, flat.shape[1] // per, per)
    w = _leaf_row_words(x.shape, x.dtype)
    return jax.lax.bitcast_convert_type(flat, WORD).reshape(A, w)


def _unpack_leaf(words, shape, dtype) -> jnp.ndarray:
    """(B, w) uint32 -> (B,) + shape[1:] of dtype (inverse of _pack_leaf)."""
    B = words.shape[0]
    dt = jnp.dtype(dtype)
    carrier = jnp.dtype("uint8") if dt == jnp.bool_ else dt
    if carrier.itemsize > WORD_BYTES:             # wide: collapse word pairs
        per = carrier.itemsize // WORD_BYTES
        words = words.reshape(B, words.shape[1] // per, per)
    flat = jax.lax.bitcast_convert_type(words, carrier)
    flat = flat.reshape(B, math.prod(flat.shape[1:]))
    flat = flat[:, :math.prod(shape[1:])]
    if dt == jnp.bool_:
        flat = flat.astype(jnp.bool_)
    return flat.reshape((B,) + tuple(shape[1:]))


def pack_fields(fields, valid: bool = True):
    """Pack a request pytree into one (A, row_words) uint32 buffer whose
    last lane is the valid mask (all ones pre-scatter: empty buffer slots
    keep the zero lane, so occupancy travels inside the rows for free).
    Returns (packed, treedef, leaf_specs).

    ``valid=False`` omits the trailing valid lane — the Pallas scatter
    path (``kernels.radix_partition``) appends it inside the kernel as
    each row lands."""
    leaves, treedef = jax.tree_util.tree_flatten(fields)
    specs = [(l.shape, l.dtype) for l in leaves]
    A = leaves[0].shape[0] if leaves else 0
    cols = [_pack_leaf(l) for l in leaves]
    if valid:
        cols.append(jnp.ones((A, 1), WORD))
    elif not cols:
        cols.append(jnp.zeros((A, 0), WORD))
    return jnp.concatenate(cols, axis=1), treedef, specs


def unpack_fields(buf, treedef, specs):
    """Split a (B, row_words) wire buffer back into (fields pytree, valid).
    Empty slots unpack to zeros in every dtype (the all-zero bit pattern)."""
    out, col = [], 0
    for shape, dtype in specs:
        w = _leaf_row_words(shape, dtype)
        out.append(_unpack_leaf(buf[:, col:col + w], shape, dtype))
        col += w
    valid = buf[:, col].astype(jnp.int32)
    return jax.tree_util.tree_unflatten(treedef, out), valid


# -------------------------------------------------- sort-free bin ranks --

def bucket_ranks(dest, n: int) -> jnp.ndarray:
    """Stable arrival-order rank of each request within its destination
    bucket, sort-free: cumulative one-hot counts — O(A·n) fully parallel
    work instead of an O(A log A) sort (sorts are the TPU's weakest
    primitive; the one-hot cumsum is pure vector work).  Out-of-range dest
    (filtered) matches no bucket and consumes no rank; its returned rank is
    meaningless and must be masked by the caller."""
    dest = dest.astype(jnp.int32)
    onehot = dest[:, None] == jnp.arange(n, dtype=jnp.int32)[None, :]
    ranks = jnp.cumsum(onehot.astype(jnp.int32), axis=0) - 1
    safe = jnp.clip(dest, 0, n - 1)
    return jnp.take_along_axis(ranks, safe[:, None], axis=1)[:, 0]


@dataclass
class RoutePlan:
    """Precomputed slot assignment for one ``dest`` vector: everything
    :func:`route` needs except the payload.  Build once with
    :func:`plan_route`, reuse for every round that routes to the same
    destinations (RSI prepare+install); ``route(..., plan=p, mask=m)``
    drops masked requests from the wire without re-ranking, keeping slot
    positions identical across the rounds.

    slot:     (A,) int32 — wire slot (dest*cap + rank) for kept requests,
              n*cap (one past the buffer) otherwise, so a ``mode="drop"``
              scatter discards them.
    keep:     (A,) bool — deliverable and within capacity.
    overflow: (A,) bool — deliverable but beyond capacity (the drop set).
    window:   doorbell-batching contract: max in-flight messages per peer
              buffer when the exchange is replayed under contention
              (0 = post everything at once).  The fused collective's wire
              bits are identical at any window — this is a *pacing*
              declaration, priced by ``repro.fabric.sim`` (the transport
              records it in the event trace and the outstanding-request
              counters; see docs/netsim.md "netsim v2").
    """
    n: int
    cap: int
    slot: jnp.ndarray
    keep: jnp.ndarray
    overflow: jnp.ndarray
    window: int = 0

    @property
    def dropped(self) -> jnp.ndarray:
        return jnp.sum(self.overflow.astype(jnp.int32))


jax.tree_util.register_dataclass(
    RoutePlan, data_fields=["slot", "keep", "overflow"],
    meta_fields=["n", "cap", "window"])


def _check_window(window) -> int:
    window = int(window or 0)
    if window < 0:
        raise ValueError(f"window must be >= 0 (0 = unbounded), "
                         f"got {window}")
    return window


def plan_route(dest, *, n: int, cap: int, window: int = 0) -> RoutePlan:
    """One-pass rank-in-bucket slot assignment for ``dest`` (sort-free).
    ``window`` declares the plan's doorbell-batching cap (see
    :class:`RoutePlan`)."""
    dest = dest.astype(jnp.int32)
    deliverable = (dest >= 0) & (dest < n)
    rank = bucket_ranks(dest, n)
    keep = deliverable & (rank < cap)
    overflow = deliverable & (rank >= cap)
    slot = jnp.where(keep, dest * cap + rank, n * cap)
    return RoutePlan(n=n, cap=cap, slot=slot, keep=keep, overflow=overflow,
                     window=_check_window(window))


# ------------------------------------------------------------- scatter ---

def _scatter_rows(rows, plan: RoutePlan, mask):
    """Reference scatter of valid-less packed rows into the (n*cap, w+1)
    wire buffer, valid lane appended."""
    slot = plan.slot if mask is None else jnp.where(
        mask & plan.keep, plan.slot, plan.n * plan.cap)
    rows = jnp.concatenate([rows, jnp.ones((rows.shape[0], 1), WORD)],
                           axis=1)
    buf = jnp.zeros((plan.n * plan.cap, rows.shape[1]), WORD)
    return buf.at[slot].set(rows, mode="drop")


def _invert_plan(plan: RoutePlan, mask) -> jnp.ndarray:
    """Invert a plan's request->slot map into a slot->request gather index:
    ``inv[s]`` = index of the request occupying wire slot ``s``, or ``A``
    (one past the batch) for empty slots — so a gather from the rows padded
    with one zero row materializes any *slice* of the wire buffer without
    touching the rest.  This is what makes the double-buffered route a
    per-chunk pipeline: chunk k+1's pack is a gather over its own slot
    range only, independent of chunk k already on the wire.

    The scatter building ``inv`` is O(n*cap + A) scalar work; kept slots
    are unique by construction (dest*cap + rank-in-bucket), masked/overflow
    requests all carry the OOB sentinel slot and are dropped."""
    slot = plan.slot if mask is None else jnp.where(
        mask & plan.keep, plan.slot, plan.n * plan.cap)
    A = slot.shape[0]
    return jnp.full((plan.n * plan.cap,), A, jnp.int32).at[slot].set(
        jnp.arange(A, dtype=jnp.int32), mode="drop", unique_indices=True)


def _pallas_scatter_rows(rows, plan: RoutePlan, mask):
    """Scatter via the Pallas radix partitioner (TPU): the same slots as
    :func:`_scatter_rows`, filled from the inverted plan.  ``rows`` are the
    valid-less packed lanes (``pack_fields(valid=False)``); the kernel
    appends the valid lane itself, returning the full wire rows."""
    from repro.kernels import ops
    return ops.radix_partition(rows, _invert_plan(plan, mask))


def _scatter(rows, plan: RoutePlan, mask, backend: Optional[str]):
    """The wire buffer from valid-less rows through the chosen backend;
    ``None`` picks per platform when the route is lowered, so a program
    compiled for a TPU takes the kernel wherever it was traced."""
    if backend is None:
        return jax.lax.platform_dependent(rows, plan, mask,
                                          tpu=_pallas_scatter_rows,
                                          default=_scatter_rows)
    if backend not in ROUTE_BACKENDS:
        raise ValueError(f"backend {backend!r} not in {ROUTE_BACKENDS}")
    return (_pallas_scatter_rows if backend == "pallas"
            else _scatter_rows)(rows, plan, mask)


# --------------------------------------------------------------- route ---

def route(fields, dest=None, *, n: Optional[int] = None,
          cap: Optional[int] = None, chunks: int = 1,
          exchange: Optional[Callable] = None,
          plan: Optional[RoutePlan] = None, mask=None,
          backend: Optional[str] = None,
          window: Optional[int] = None,
          overlap: bool = False) -> RouteResult:
    """Radix-partition `fields` by `dest` into (n, cap) fixed buffers and
    (optionally) exchange them — as ONE packed wire buffer, one
    ``all_to_all``, any number of fields.  Pass ``plan=`` (from
    :func:`plan_route`) to reuse a slot assignment across rounds; ``mask=``
    (requires a plan) unsends requests without re-ranking.  ``window=``
    declares the doorbell-batching cap for contention pricing (defaults to
    the plan's; the exchanged bits are identical at any window — see
    :class:`RoutePlan`).  See the module docstring for semantics.

    ``overlap=True`` selects the **double-buffered** pipeline: the slot map
    is inverted once (:func:`_invert_plan`) and each chunk's wire buffer is
    then a *gather* over that chunk's slot range only, so chunk k+1 packs
    while chunk k's exchange is on the wire (with ``exchange=None`` the
    whole buffer is one gather).  Bit-for-bit identical to the synchronous
    scatter path — same slots, same drops, same wire bytes — the overlap
    changes the *schedule*, never the bits (guarded by
    ``tests/test_async.py``).  Legal whenever a plan-backed route is: the
    inversion needs the plan's slot ranks, so ``overlap`` forces the jnp
    plan path (no pallas scatter; the gathers replace it)."""
    if plan is not None:
        n, cap = plan.n, plan.cap
        if window is None:
            window = plan.window
    elif n is None or cap is None:
        raise ValueError("route needs n= and cap= (or a plan=)")
    _check_window(window)
    if mask is not None and plan is None:
        raise ValueError("mask= only applies to a reused plan=")
    if cap % chunks != 0:
        raise ValueError(f"cap={cap} not divisible by chunks={chunks}")
    if plan is None:
        plan = plan_route(dest, n=n, cap=cap)
        mask = None
    dropped = (plan.dropped if mask is None else
               jnp.sum((plan.overflow & mask).astype(jnp.int32)))
    if overlap:
        rows, treedef, specs = pack_fields(fields)
        inv = _invert_plan(plan, mask)
        padded = jnp.concatenate(
            [rows, jnp.zeros((1, rows.shape[1]), WORD)], axis=0)
        if exchange is None:
            buf = padded[inv]
            sent, sent_valid = unpack_fields(buf, treedef, specs)
            return RouteResult(sent, sent_valid, dropped, sent, sent_valid)
        c = cap // chunks
        w = rows.shape[1]
        inv_c = jnp.moveaxis(inv.reshape(n, chunks, c), 1, 0)

        def step(_, ic):
            sent_c = padded[ic.reshape(n * c)]     # pack chunk (gather)
            return None, (sent_c, exchange(sent_c))   # chunk on the wire

        _, (sent_s, recv_s) = jax.lax.scan(step, None, inv_c)

        def restripe(x):
            return jnp.moveaxis(x.reshape(chunks, n, c, w), 0, 1
                                ).reshape(n * cap, w)

        sent, sent_valid = unpack_fields(restripe(sent_s), treedef, specs)
        recv_fields, valid = unpack_fields(restripe(recv_s), treedef, specs)
        return RouteResult(recv_fields, valid, dropped, sent, sent_valid)
    rows, treedef, specs = pack_fields(fields, valid=False)
    buf = _scatter(rows, plan, mask, backend)
    sent, sent_valid = unpack_fields(buf, treedef, specs)
    if exchange is None:
        return RouteResult(sent, sent_valid, dropped, sent, sent_valid)
    recv_fields, valid = unpack_fields(exchange(buf), treedef, specs)
    return RouteResult(recv_fields, valid, dropped, sent, sent_valid)


def chunked_all_to_all(v, axis: str, n: int, cap: int, chunks: int = 1):
    """Paired all_to_all of a (n*cap, ...) buffer; chunks > 1 pipelines the
    transfer with a scan so chunk c's exchange overlaps chunk c+1's work."""
    with jax.named_scope("fabric.exchange"):
        rest = v.shape[1:]
        if chunks == 1:
            return jax.lax.all_to_all(
                v.reshape(n, cap, *rest), axis, 0, 0,
                tiled=False).reshape(n * cap, *rest)
        c = cap // chunks
        vc = jnp.moveaxis(v.reshape(n, chunks, c, *rest), 1, 0)

        def step(_, x):
            return None, jax.lax.all_to_all(x, axis, 0, 0, tiled=False)

        _, out = jax.lax.scan(step, None, vc)
        return jnp.moveaxis(out, 0, 1).reshape(n * cap, *rest)
