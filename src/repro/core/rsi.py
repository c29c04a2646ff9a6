"""RSI — RDMA-based Snapshot Isolation (paper §4.2), NAM-adapted to TPU.

Store layout (paper Table 1): per record a 64-bit word = 1-bit lock | 63-bit
CID, followed by n version slots (newest first). The client (= compute node)
drives commit entirely with one-sided verbs from ``repro.fabric``:

  msg 1: get CID from the client-partitioned timestamp bitvector (local slot)
  msg 2: validate+lock every write with a single CAS   (1 round trip)
  msg 3: install versions with WRITEs, release locks; flip the bitvector bit
         (unsignaled)

Abort path: losers release any locks they won (restore the old word).

There is ONE commit path: :func:`commit` routes prepare/install requests to
home shards through ``fabric.route()`` (radix into fixed software-managed
buffers + paired all_to_all) and arbitrates with the deterministic-priority
CAS — semantically a serial schedule in priority order, which is what
per-record atomic CAS gives the paper.  The transport decides the substrate:
``LocalTransport()`` (default) is the single-shard degenerate case where the
router never leaves the node; ``MeshTransport(mesh, axis)`` is the NAM
deployment (store sharded by home shard, clients sharded alongside, one
all_to_all per round trip).  Both count per-verb messages/bytes, which
``benchmarks/fig6_rsi.py`` reports next to the paper's analytic model.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from repro.fabric import LocalTransport

# JAX runs with x64 disabled, so the paper's 1+63-bit word is realized
# as 1-bit lock | 31-bit CID in uint32 (layout generalizes; the Pallas
# cas_lock kernel uses the same u32 word).
WORD = jnp.uint32
LOCK_BIT = jnp.uint32(1 << 31)
CID_MASK = ~LOCK_BIT


@dataclass(frozen=True)
class StoreCfg:
    num_records: int
    payload_words: int = 4        # m-bit record as u64 words
    version_slots: int = 1        # paper's current impl: n = 1
    num_timestamps: int = 60_000  # paper's bitvector size


def init_store(cfg: StoreCfg):
    """words[r] = lock|CID; payload (R, slots, m); cids (R, slots)."""
    return {
        "words": jnp.zeros((cfg.num_records,), WORD),
        "payload": jnp.zeros((cfg.num_records, cfg.version_slots,
                              cfg.payload_words), WORD),
        "cids": jnp.zeros((cfg.num_records, cfg.version_slots), WORD),
        "bitvec": jnp.zeros((cfg.num_timestamps,), bool),
    }


def highest_committed(bitvec) -> jnp.ndarray:
    """Highest consecutive set bit (paper's read-timestamp rule)."""
    consec = jnp.cumprod(bitvec.astype(jnp.int32))
    return jnp.sum(consec).astype(WORD)  # count of leading ones


@dataclass(frozen=True)
class TxnBatch:
    """W fixed write slots per txn (record -1 = unused).

    write_recs: (T, W) int32; read_cids: (T, W) uint32 (word) — the RID under which
    each record was read; new_payload: (T, W, m) uint32 (word); cid: (T,) uint32 (word)
    pre-assigned commit timestamps (bitvector slots).
    """
    write_recs: jnp.ndarray
    read_cids: jnp.ndarray
    new_payload: jnp.ndarray
    cid: jnp.ndarray


jax.tree_util.register_dataclass(
    TxnBatch, data_fields=["write_recs", "read_cids", "new_payload", "cid"],
    meta_fields=[])


def _cat(parts):
    """One table's (T, W, ...) array as it is; several tables' side by
    side along the write slots (no op for one table, so that a one-table
    wave traces to the program it always had)."""
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, 1)


def commit(store, txns, *, transport=None, priority=None,
           chunks: int = 1, exchange_chunks: int = 1, region_ns=""):
    """Commit a batch of concurrent transactions over a fabric transport.
    Returns (committed (T,) bool, new_store).

    Several tables: ``store``, ``txns`` and ``region_ns`` may be tuples
    with one entry per table the wave writes (every batch has the same T
    rows, transaction t is row t of each, with the same ``cid``; a table
    a transaction does not write holds -1 slots).  The prepare is ONE
    routed round for the writes of every table, each request carrying its
    table and routed to that table's home shard; a transaction commits
    iff it won every lock in every table; each table then installs and
    releases on its own routed round (payload widths differ by table).
    Returns (committed, tuple of new stores).

    transport: fabric transport (default ``LocalTransport()``). Under
      ``MeshTransport`` store leaves are sharded on axis 0 by home shard
      (record r lives on shard r // (R/n)) and txns/priority are sharded on
      axis 0 (each shard's clients); commit timestamps must be pre-assigned
      in shard-contiguous bitvector ranges.
    priority: (T,) int32 global arbitration order (lower wins; default =
      global row order). Values must be globally unique across shards —
      ties fall back to routed-buffer position, which favors lower peers.
    chunks: pipeline the routed prepare/install buffers (selective
      signaling); must divide T*W per shard.
    exchange_chunks: pipeline the grant exchange the same way (one
      doorbell per chunk) — :func:`commit_grouped` sets this to the group
      size so the coalesced wave's per-chunk message counts stay
      bit-identical to the solo commits it replaces.
    region_ns: region-name prefix (e.g. ``"acct/"``) for the schedule
      recorder when one is attached to the transport; a wave boundary is
      recorded so the race detector's lock-protocol rule can tie install
      WRITEs to this wave's CAS acquisitions.
    """
    multi = isinstance(store, (tuple, list))
    stores = tuple(store) if multi else (store,)
    batches = tuple(txns) if multi else (txns,)
    spaces = tuple(region_ns) if multi else (region_ns,)
    K = len(stores)
    if transport is None:
        transport = LocalTransport()
    T = batches[0].write_recs.shape[0]
    if any(b.write_recs.shape[0] != T for b in batches):
        raise ValueError("every table's batch holds the wave's T rows")
    if priority is None:
        priority = jnp.arange(T, dtype=jnp.int32)
    n = transport.n
    recorder = getattr(transport, "recorder", None)
    if recorder is not None:
        recorder.begin_wave("".join(spaces) + "commit")

    def body(*args):
        tabs = [list(args[4 * k:4 * k + 4]) for k in range(K)]
        reqs = [args[4 * K + 3 * k:4 * K + 3 * k + 3] for k in range(K)]
        cid, prio = args[-2:]
        Tl = cid.shape[0]
        Ws = [wr.shape[1] for wr, _, _ in reqs]
        W = sum(Ws)
        me = transport.shard_index()
        with jax.named_scope("rsi.prepare"):
            # ---- route prepares to home shards (radix by rec // r_local
            # of the request's table); unused write slots are filtered
            # (dest = n), not dropped.
            dest = _cat([jnp.where(wr >= 0, wr // t[0].shape[0], n)
                         for (wr, _, _), t in zip(reqs, tabs)])
            flat_dest = dest.reshape(-1)
            cap = Tl * W  # worst case: all my writes hit one shard
            gid = jnp.repeat(prio, W)      # globally unique txn priority
            wrecs = _cat([wr for wr, _, _ in reqs])
            recs_flat = wrecs.reshape(-1)
            exp_flat = _cat([rc & CID_MASK for _, rc, _ in reqs]).reshape(-1)
            cid_flat = jnp.repeat(cid & CID_MASK, W)
            # each table's payloads in its own columns of the wave's slots
            npay_flat, off = [], 0
            for (_, _, npay), w in zip(reqs, Ws):
                if K > 1:
                    npay = jnp.pad(npay, ((0, 0), (off, W - off - w), (0, 0)))
                npay_flat.append(npay.reshape(Tl * W, -1))
                off += w
            # the CAS prepare is payload-free (paper msg 2): new CIDs and
            # payloads stay client-side until the install round trip
            req = {"rec": recs_flat, "exp": exp_flat, "prio": gid,
                   "slot": jnp.arange(Tl * W, dtype=jnp.int32)}
            if K > 1:
                tab_flat = _cat([jnp.full((Tl, w), k, jnp.int32)
                                 for k, w in enumerate(Ws)]).reshape(-1)
                req["tab"] = tab_flat
            # both rounds travel to the same home shards, so the slot
            # assignment is binned ONCE and reused for the install (the act
            # filter is a mask over the same plan — slots stay put, which is
            # also what keeps the response path stable)
            plan = transport.plan_route(flat_dest, cap=cap)
            res = transport.route(req, plan=plan, chunks=chunks)
            r, rvalid = res.fields, res.valid
        with jax.named_scope("rsi.grant"):
            # ---- local CAS arbitration on my records of each table
            # (global prio = fair)
            ok = None
            for k, t in enumerate(tabs):
                mine = rvalid > 0 if K == 1 else (rvalid > 0) & (r["tab"] == k)
                lrec = jnp.where(mine, r["rec"] % t[0].shape[0], -1)
                ok_k, t[0] = transport.cas(t[0], lrec, r["exp"],
                                           LOCK_BIT | r["exp"],
                                           priority=r["prio"],
                                           region=spaces[k] + "words")
                ok = ok_k if ok is None else ok | ok_k
            # ---- grants return to requesters (paired reverse exchange lands
            # each response in the slot it was sent from); the grant bit
            # crosses the collective in the packed u32 wire width
            grant = transport.exchange(ok.astype(jnp.uint32),
                                       exchange_chunks).astype(jnp.int32)
            granted = jnp.zeros((Tl * W,), jnp.int32).at[res.sent["slot"]].add(
                grant * res.sent_valid)
            gmat = granted.reshape(Tl, W) > 0
            used = wrecs >= 0
            txn_ok = jnp.all(gmat | ~used, axis=1) & jnp.any(used, axis=1)
        with jax.named_scope("rsi.install"):
            # ---- phase 2: installs routed the same way (write + unlock),
            # one round per table; committed txns install their CID,
            # losers restore the old word.
            commit_req = jnp.repeat(txn_ok, W) & (granted > 0)
            release_req = (granted > 0) & ~commit_req
            val = jnp.where(commit_req, cid_flat, exp_flat)
            do_pay = commit_req.astype(jnp.int32)
            act = commit_req | release_req
            for k, t in enumerate(tabs):
                words, payload, cids, _ = t
                inst = {"rec": recs_flat, "val": val, "npay": npay_flat[k],
                        "do_pay": do_pay}
                res2 = transport.route(
                    inst, plan=plan, chunks=chunks,
                    mask=act if K == 1 else act & (tab_flat == k))
                r2, v2 = res2.fields, res2.valid
                lrec2 = jnp.where(v2 > 0, r2["rec"] % words.shape[0], -1)
                words = transport.write(words, lrec2, r2["val"],
                                        region=spaces[k] + "words")
                # version install: shift slots left, newest at 0.
                # NB: negative indices WRAP in jnp scatters — use an
                # explicit OOB sentinel (row N) so mode="drop" actually
                # drops skipped writes.
                oob = payload.shape[0]
                pay_idx = jnp.where((r2["do_pay"] > 0) & (v2 > 0), lrec2, -1)
                idx_pay = jnp.where(pay_idx >= 0, pay_idx, oob)
                if payload.shape[1] > 1:
                    shifted_pay = jnp.concatenate(
                        [payload[:, :1], payload[:, :-1]], axis=1)
                    shifted_cid = jnp.concatenate(
                        [cids[:, :1], cids[:, :-1]], axis=1)
                    has_commit = jnp.zeros((oob,), bool).at[idx_pay].set(
                        True, mode="drop")
                    payload = jnp.where(has_commit[:, None, None],
                                        shifted_pay, payload)
                    cids = jnp.where(has_commit[:, None], shifted_cid, cids)
                payload = payload.at[idx_pay, 0].set(r2["npay"], mode="drop")
                cids = cids.at[idx_pay, 0].set(r2["val"], mode="drop")
                # install bytes are already billed to the routed buffer;
                # the scatter itself is invisible to the verbs, so log it
                # record-only for the race detector's lock-protocol /
                # conflict rules
                transport.record_access("WRITE", spaces[k] + "payload",
                                        pay_idx, region_len=oob)
                transport.record_access("WRITE", spaces[k] + "cids",
                                        pay_idx, region_len=oob)
                t[:3] = words, payload, cids
        with jax.named_scope("rsi.bitvec"):
            # ---- timestamp bitvector [msg 3, unsignaled]: clients flip
            # their own (locally owned) bits in every table the wave
            # writes; aborted txns also burn their slot (the paper's
            # wrap/skip bookkeeping). cids are pre-assigned in shard-
            # contiguous ranges [me*bv_local, ...).
            for k, t in enumerate(tabs):
                bitvec = t[3]
                bv_local = bitvec.shape[0]
                cbit = cid.astype(jnp.int32) - me * bv_local
                cbit = jnp.where((cbit >= 0) & (cbit < bv_local), cbit,
                                 bv_local)
                t[3] = bitvec.at[cbit].set(True, mode="drop")
                transport.record_access(
                    "WRITE", spaces[k] + "bitvec",
                    jnp.where(cbit < bv_local, cbit, -1),
                    region_len=bv_local)
        return (txn_ok,) + tuple(a for t in tabs for a in t)

    out = transport.run(
        body,
        tuple(s[f] for s in stores
              for f in ("words", "payload", "cids", "bitvec"))
        + tuple(a for b in batches
                for a in (b.write_recs, b.read_cids, b.new_payload))
        + (batches[0].cid, priority),
        out_reps=(False,) * (1 + 4 * K))
    if recorder is not None:
        # the caller blocks on txn_ok, which rides the install round trip:
        # everything this wave installed happens-before whatever follows
        recorder.fence("commit-complete")
    new = tuple(dict(zip(("words", "payload", "cids", "bitvec"),
                         out[1 + 4 * k:5 + 4 * k])) for k in range(K))
    return out[0], (new if multi else new[0])


def commit_pipelined(store, waves, *, transport=None, priority=None,
                     chunks: int = 1, region_ns: str = ""):
    """Commit K *dependent* transaction waves with wave i's install round
    trip overlapping wave i+1's prepare round trip — the paper's motivation
    for one-sided verbs: the client issues the install WRITEs unsignaled
    and immediately posts the next wave's prepare, waiting on the install
    completion only when it must apply the results.

    Semantically identical to K sequential :func:`commit` calls (same CAS
    arbitration, same store mutations, same counters per wave — guarded by
    ``tests/test_async.py``): the prepare route of wave i+1 reads only the
    txn batch, never the store, so hoisting it over wave i's in-flight
    install changes the schedule, not the bits.  The ordering that *must*
    hold — wave i's lock-releasing words WRITE happens-before wave i+1's
    CAS — is carried by explicit ``Completion.wait()`` fences: install
    ``wait()`` (a route-roundtrip fence) precedes the next prepare
    ``wait()``, so the race detector records the pipeline clean; drop
    either wait and ``fabric.check`` names the racing verb pair (seeded
    fixtures in ``tests/test_check.py``).

    waves: list of :class:`TxnBatch` (per-wave T may differ).
    priority: optional list of (T,) int32, one per wave.
    Returns (txn_ok list — (T,) bool per wave — and the new store).
    """
    if transport is None:
        transport = LocalTransport()
    K = len(waves)
    if K == 0:
        return [], store
    if priority is None:
        priority = [jnp.arange(w.write_recs.shape[0], dtype=jnp.int32)
                    for w in waves]
    n = transport.n
    recorder = getattr(transport, "recorder", None)

    def body(words, payload, cids, bitvec, *flat):
        wv = [flat[5 * i:5 * (i + 1)] for i in range(K)]
        me = transport.shard_index()
        r_local = words.shape[0]
        bv_local = bitvec.shape[0]

        def issue_prepare(wrecs, rcids, prio):
            """Post wave's prepare on the wire (async — no fence until
            the caller waits).  Touches only the txn batch."""
            Tl, W = wrecs.shape
            dest = jnp.where(wrecs >= 0, wrecs // r_local, n).reshape(-1)
            req = {"rec": wrecs.reshape(-1),
                   "exp": (rcids & CID_MASK).reshape(-1),
                   "prio": jnp.repeat(prio, W),
                   "slot": jnp.arange(Tl * W, dtype=jnp.int32)}
            plan = transport.plan_route(dest, cap=Tl * W)
            return plan, transport.route_async(req, plan=plan, chunks=chunks)

        outs = []
        prep = issue_prepare(wv[0][0], wv[0][1], wv[0][4])
        for i in range(K):
            wrecs, rcids, npay, cid, prio = wv[i]
            Tl, W = wrecs.shape
            if recorder is not None:
                recorder.begin_wave(f"{region_ns}commit[{i}]")
            plan, prep_c = prep
            res = prep_c.wait()          # prepare round-trip fence, wave i
            r, rvalid = res.fields, res.valid
            lrec = jnp.where(rvalid > 0, r["rec"] % r_local, -1)
            ok, words = transport.cas(words, lrec, r["exp"],
                                      LOCK_BIT | r["exp"],
                                      priority=r["prio"],
                                      region=region_ns + "words")
            grant = transport.exchange(
                ok.astype(jnp.uint32)).astype(jnp.int32)
            granted = jnp.zeros((Tl * W,), jnp.int32).at[
                res.sent["slot"]].add(grant * res.sent_valid)
            gmat = granted.reshape(Tl, W) > 0
            used = wrecs >= 0
            txn_ok = jnp.all(gmat | ~used, axis=1) & jnp.any(used, axis=1)
            outs.append(txn_ok)
            commit_req = jnp.repeat(txn_ok, W) & (granted > 0)
            release_req = (granted > 0) & ~commit_req
            inst = {"rec": wrecs.reshape(-1),
                    "val": jnp.where(commit_req, jnp.repeat(
                        cid & CID_MASK, W), (rcids & CID_MASK).reshape(-1)),
                    "npay": npay.reshape(Tl * W, -1),
                    "do_pay": commit_req.astype(jnp.int32)}
            act = commit_req | release_req
            inst_c = transport.route_async(inst, plan=plan, mask=act,
                                           chunks=chunks)
            if i + 1 < K:
                # THE overlap: wave i+1's prepare goes on the wire while
                # wave i's install is still in flight.
                prep = issue_prepare(wv[i + 1][0], wv[i + 1][1],
                                     wv[i + 1][4])
            res2 = inst_c.wait()         # install round-trip fence, wave i
            r2, v2 = res2.fields, res2.valid
            lrec2 = jnp.where(v2 > 0, r2["rec"] % r_local, -1)
            words = transport.write(words, lrec2, r2["val"],
                                    region=region_ns + "words")
            oob = payload.shape[0]
            pay_idx = jnp.where((r2["do_pay"] > 0) & (v2 > 0), lrec2, -1)
            idx_pay = jnp.where(pay_idx >= 0, pay_idx, oob)
            if payload.shape[1] > 1:
                shifted_pay = jnp.concatenate(
                    [payload[:, :1], payload[:, :-1]], axis=1)
                shifted_cid = jnp.concatenate(
                    [cids[:, :1], cids[:, :-1]], axis=1)
                has_commit = jnp.zeros((oob,), bool).at[idx_pay].set(
                    True, mode="drop")
                payload = jnp.where(has_commit[:, None, None], shifted_pay,
                                    payload)
                cids = jnp.where(has_commit[:, None], shifted_cid, cids)
            payload = payload.at[idx_pay, 0].set(r2["npay"], mode="drop")
            cids = cids.at[idx_pay, 0].set(r2["val"], mode="drop")
            transport.record_access("WRITE", region_ns + "payload",
                                    pay_idx, region_len=oob)
            transport.record_access("WRITE", region_ns + "cids", pay_idx,
                                    region_len=oob)
            cbit = cid.astype(jnp.int32) - me * bv_local
            cbit = jnp.where((cbit >= 0) & (cbit < bv_local), cbit,
                             bv_local)
            bitvec = bitvec.at[cbit].set(True, mode="drop")
            transport.record_access(
                "WRITE", region_ns + "bitvec",
                jnp.where(cbit < bv_local, cbit, -1), region_len=bv_local)
        return tuple(outs) + (words, payload, cids, bitvec)

    flat_args = []
    for w, p in zip(waves, priority):
        flat_args += [w.write_recs, w.read_cids, w.new_payload, w.cid, p]
    out = transport.run(
        body,
        (store["words"], store["payload"], store["cids"], store["bitvec"],
         *flat_args),
        out_reps=(False,) * (K + 4))
    if recorder is not None:
        recorder.fence("commit-complete")
    txn_ok, (words, payload, cids, bitvec) = list(out[:K]), out[K:]
    return txn_ok, {"words": words, "payload": payload, "cids": cids,
                    "bitvec": bitvec}


def concat_group(groups, priority=None):
    """Coalesce K per-session :class:`TxnBatch`es into ONE batch.

    Write slots are padded to the group's widest W (record -1 = unused, so
    padding never reaches the wire's valid lanes), batches are stacked
    along T, and the default priority is the global row order — session
    order inside the group is arbitration order, exactly the order K solo
    commits would run in.  Returns (batch, priority (T,) int32, sizes) with
    ``sizes[i]`` = rows contributed by ``groups[i]`` (for splitting the
    grouped ``txn_ok`` back per session).
    """
    if not groups:
        raise ValueError("concat_group needs at least one TxnBatch")
    W = max(g.write_recs.shape[1] for g in groups)

    def pad(a, fill, width=W):
        t, w = a.shape[0], a.shape[1]
        if w == width:
            return a
        shape = (t, width - w) + a.shape[2:]
        return jnp.concatenate([a, jnp.full(shape, fill, a.dtype)], axis=1)

    batch = TxnBatch(
        write_recs=jnp.concatenate([pad(g.write_recs, -1) for g in groups]),
        read_cids=jnp.concatenate([pad(g.read_cids, 0) for g in groups]),
        new_payload=jnp.concatenate(
            [pad(g.new_payload, 0) for g in groups]),
        cid=jnp.concatenate([g.cid for g in groups]))
    sizes = [int(g.write_recs.shape[0]) for g in groups]
    if priority is None:
        priority = jnp.arange(sum(sizes), dtype=jnp.int32)
    else:
        priority = jnp.concatenate(
            [jnp.asarray(p, jnp.int32) for p in priority])
    return batch, priority, sizes


def _group_chunks(groups, chunks):
    """Doorbell count of a grouped round: one pipelined chunk per session
    (so the coalesced buffers post the same per-chunk wire messages K solo
    commits would), degrading to 1 when the group's slot count does not
    split evenly (unequal session sizes pad the capacity buffers)."""
    if chunks is not None:
        return int(chunks)
    K = len(groups)
    W = max(g.write_recs.shape[1] for g in groups)
    slots = sum(int(g.write_recs.shape[0]) for g in groups) * W
    return K if K and slots % K == 0 else 1


def commit_grouped(store, groups, *, transport=None, priority=None,
                   chunks=None, region_ns: str = ""):
    """Group commit (NAM-DB §4.2 at scale): coalesce K logical sessions'
    transaction batches into ONE routed prepare/install round trip.

    The group travels as a single :class:`TxnBatch` (:func:`concat_group`)
    through :func:`commit`: the write set is binned to home shards ONCE
    (one ``plan_route``, reused by the install round) and the prepare /
    grant / install rounds fire once for the whole group instead of once
    per session — 3 collective round trips and 1 plan build total, where K
    solo commits pay 3K and K.  The wire traffic itself is unchanged: the
    coalesced buffers pipeline in K chunks (one doorbell per session), so
    per-verb message and byte totals are bit-identical to the K solo
    commits (capacity counting is linear in slots — holds whenever the
    sessions share one W, e.g. a packed wave).

    Outcome parity (guarded by ``tests/test_scale.py``): for wave-consistent
    groups — every session snapshotted before the group commits, conflicts
    arbitrated by group order — the committed masks, store words, payload,
    cids and bitvector are bit-identical to committing each session alone
    in order.  The one divergence is deliberate: a session that loses a
    hot row to an *earlier* session that itself aborts stays aborted here
    (it conflicted with a concurrent writer — legal SI), where the solo
    schedule would have admitted it; the retry loop
    (``db.Database.commit(max_retries=)``), not intra-round cascade
    resolution, recovers those — cascades would cost extra grant rounds
    and break the 3-collective budget ``fabric.check`` enforces.

    groups: list of :class:`TxnBatch` (one per logical session, or one per
      worker's session stream).  priority: optional list of per-group
      priorities (default: global row order across the group).
    Returns (list of per-group txn_ok, new_store).
    """
    gch = _group_chunks(groups, chunks)
    batch, prio, sizes = concat_group(groups, priority)
    ok, store = commit(store, batch, transport=transport, priority=prio,
                       chunks=gch, exchange_chunks=gch,
                       region_ns=region_ns)
    return _split_sizes(ok, sizes), store


def commit_grouped_pipelined(store, grouped_waves, *, transport=None,
                             chunks=None, region_ns: str = ""):
    """Group commit composed with the async pipeline: each wave is a
    *group* of session batches (coalesced per :func:`concat_group`), and
    wave N+1's grouped prepare route goes on the wire while wave N's
    grouped install is still in flight (:func:`commit_pipelined`'s
    explicit ``Completion.wait()`` fences carry the ordering — the race
    detector records the composition clean, 3 collectives per wave).

    grouped_waves: list of lists of :class:`TxnBatch`.
    Returns (list of lists of per-group txn_ok, new_store).
    """
    if not grouped_waves:
        return [], store
    batches, prios, sizes = [], [], []
    for groups in grouped_waves:
        b, p, s = concat_group(groups)
        batches.append(b)
        prios.append(p)
        sizes.append(s)
    wave_chunks = ({_group_chunks(g, chunks) for g in grouped_waves}
                   or {1})
    # commit_pipelined shares one chunks= across waves; mixed group
    # shapes fall back to unpipelined buffers rather than mis-splitting
    ch = wave_chunks.pop() if len(wave_chunks) == 1 else 1
    oks, store = commit_pipelined(store, batches, transport=transport,
                                  priority=prios, chunks=ch,
                                  region_ns=region_ns)
    return [_split_sizes(ok, s) for ok, s in zip(oks, sizes)], store


def _split_sizes(arr, sizes):
    out, off = [], 0
    for s in sizes:
        out.append(arr[off:off + s])
        off += s
    return out


def read_snapshot(store, recs, rid, *, transport=None, region_ns: str = ""):
    """Read records at snapshot `rid`: newest version with CID <= rid.
    Returns (payload (..., m), cid, ok — False if no visible version).

    transport: when given, the version-array gathers go through the
    transport's READ verb so the snapshot traffic is counted (the paper's
    one-sided read path); None = plain local indexing.  region_ns prefixes
    the region names seen by an attached schedule recorder."""
    if transport is not None:
        def rd(region, idx, _name=None):
            return transport.read(
                region, idx,
                region=(region_ns + _name) if _name else None)
    else:
        def rd(region, idx, _name=None):
            return region[idx]
    cids = rd(store["cids"], recs, "cids")         # (..., slots)
    vis = (cids <= rid) & (cids > 0)
    slot = jnp.argmax(vis, axis=-1)
    ok = jnp.any(vis, axis=-1)
    pay = jnp.take_along_axis(
        rd(store["payload"], recs, "payload"),
        slot[..., None, None], axis=-2)[..., 0, :]
    cid = jnp.take_along_axis(cids, slot[..., None], axis=-1)[..., 0]
    return pay, cid, ok
