"""Tables: key/value relations bound to NamPool regions.

A :class:`Table` is the facade's unit of storage: an RSI version store
(lock|CID words, version slots, payload, timestamp bitvector — paper
Table 1) plus a join-key column, all allocated as named regions in the
database's :class:`~repro.fabric.NamPool` with a declared home-shard
partitioning.  Partitioning is *declarative*: under a ``MeshTransport`` the
RSI commit path homes record ``r`` on shard ``r // (R/n)`` (``"range"``)
while the OLAP shuffle homes key ``k`` on shard ``k % n`` (``"hash"``); the
planner and executor read the declaration instead of callers hand-wiring
destinations.

The lock-word column doubles as the facade's decentralized lock service:
:meth:`Table.claim_locks` / :meth:`Table.release_lock` run the RSI
validate+lock CAS through the database's transport (counted like every
other verb), which is how ``serving.engine`` claims decode slots.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import rsi
from repro.core.rsi import LOCK_BIT, WORD


@dataclass(frozen=True)
class TableSchema:
    name: str
    num_records: int
    payload_words: int = 4         # value width in u32 words
    version_slots: int = 1
    partitioning: str = "range"    # OLTP home-shard rule: "range" | "hash"
    key_bytes: int = 4             # join key width on the wire
    value_bytes: int = 4           # shuffled value width on the wire

    @property
    def tuple_bytes(self) -> int:
        """Wire width of one (key, value) tuple in an OLAP shuffle."""
        return self.key_bytes + self.value_bytes


class Table:
    """One relation: RSI version store + key column, regions in the pool."""

    def __init__(self, schema: TableSchema, pool, transport, *,
                 num_timestamps: int = 60_000):
        if schema.partitioning not in ("range", "hash"):
            raise ValueError(f"unknown partitioning {schema.partitioning!r}")
        self.schema = schema
        self._transport = transport
        self.cfg = rsi.StoreCfg(
            num_records=schema.num_records,
            payload_words=schema.payload_words,
            version_slots=schema.version_slots,
            num_timestamps=num_timestamps)
        R = schema.num_records
        pool.alloc(f"{schema.name}/words", (R,), WORD, ("record",))
        pool.alloc(f"{schema.name}/payload",
                   (R, schema.version_slots, schema.payload_words), WORD,
                   ("record", None, None))
        pool.alloc(f"{schema.name}/cids", (R, schema.version_slots), WORD,
                   ("record", None))
        pool.alloc(f"{schema.name}/bitvec", (num_timestamps,), bool,
                   ("record",))
        pool.alloc(f"{schema.name}/keys", (R,), jnp.uint32, ("record",))
        self.store = transport.place(rsi.init_store(self.cfg))
        # default join key = record id (OLTP tables); bulk loads replace it
        self.keys = transport.place(jnp.arange(R, dtype=jnp.uint32))
        self.rows = 0              # live rows, feeds the planner's stats

    # -------------------------------------------------------------- load --

    def load(self, keys, vals, *, cid: int = 1):
        """Bulk-load an OLAP relation: row i holds (keys[i], vals[i]) as a
        committed version at `cid` (load epoch).  vals fill payload word 0."""
        keys = jnp.asarray(keys, jnp.uint32)
        vals = jnp.asarray(vals, jnp.uint32)
        n = keys.shape[0]
        R = self.schema.num_records
        if n > R:
            raise ValueError(f"{n} rows > {R} records")
        self.keys = self._transport.place(
            jnp.zeros((R,), jnp.uint32).at[:n].set(keys))
        pay = jnp.zeros((R, self.schema.version_slots,
                         self.schema.payload_words), WORD)
        self.store["payload"] = pay.at[:n, 0, 0].set(vals)
        self.store["cids"] = jnp.zeros(
            (R, self.schema.version_slots), WORD).at[:n, 0].set(cid)
        self.store["words"] = jnp.zeros((R,), WORD).at[:n].set(cid)
        self.store = self._transport.place(self.store)
        self.rows = n
        return self

    def seed(self, recs, vals=None, *, cid: int = 1):
        """Mark records `recs` as existing at `cid` (OLTP base rows)."""
        recs = jnp.asarray(recs, jnp.int32)
        self.store["words"] = self.store["words"].at[recs].set(
            jnp.uint32(cid))
        self.store["cids"] = self.store["cids"].at[recs, 0].set(
            jnp.uint32(cid))
        if vals is not None:
            self.store["payload"] = self.store["payload"].at[recs, 0].set(
                jnp.asarray(vals, WORD))
        self.store = self._transport.place(self.store)
        with obs.span("namdb.sync.seed"):
            self.rows = max(self.rows, int(np.max(np.asarray(recs))) + 1)
        return self

    # ------------------------------------------------------- partitioning --

    def home_shard(self, recs, num_shards: int = None) -> np.ndarray:
        """Home shard of each record under this table's declared
        partitioning (default cluster size: the bound transport's).  The
        same rule the RSI commit router bins by — callers (fig_scale's
        locality axis) use it to place workers, not to route."""
        from repro.db import partition
        n = self._transport.n if num_shards is None else int(num_shards)
        return partition.home_shard(recs, self.schema.num_records, n,
                                    self.schema.partitioning)

    # ------------------------------------------------------------- stats --

    def stats(self) -> dict:
        """Planner inputs: live rows and their wire bytes in a shuffle."""
        rows = self.rows or self.schema.num_records
        return {"rows": rows, "bytes": rows * self.schema.tuple_bytes}

    def scan_arrays(self):
        """Materialize the (keys, vals) u32 columns an OLAP operator eats:
        vals = payload word 0 of the newest version, live rows only."""
        rows = self.rows or self.schema.num_records
        return self.keys[:rows], self.store["payload"][:rows, 0, 0]

    # -------------------------------------------------------- lock column --

    def claim_locks(self, n: int, *, tag: int = 0) -> list:
        """Claim up to `n` free rows of a DEDICATED lock/slot table with
        the RSI validate+lock CAS (one-sided, through the transport so the
        claim traffic is counted).  Returns the claimed row indices.

        Only valid on tables that were never seeded/loaded (e.g. serving's
        decode-slot table): there word 0 means 'free'.  On a data table
        words hold lock|CID, so 0 means *unborn record* — claiming those
        would poison future blind inserts, hence the guard.

        The client first peeks at the lock column for free candidates,
        then CASes only those n rows — each claim bills n CAS messages,
        not num_records, and the CAS still arbitrates races (a stale peek
        just loses the CAS)."""
        if self.rows:
            raise ValueError(
                f"claim_locks on data table {self.schema.name!r}: the lock "
                "column doubles as lock|CID words there; use a dedicated "
                "(never seeded/loaded) lock table")
        with obs.span("namdb.sync.lock"):
            cand = np.nonzero(np.array(self.store["words"]) == 0)[0][:n]
        if cand.size == 0:
            return []
        idx = jnp.asarray(cand, jnp.int32)
        expected = jnp.zeros((cand.size,), WORD)
        new = jnp.full((cand.size,), LOCK_BIT | jnp.uint32(tag), WORD)
        ok, words = self._transport.cas(self.store["words"], idx, expected,
                                        new,
                                        region=f"{self.schema.name}/words")
        self.store["words"] = words
        with obs.span("namdb.sync.lock"):
            return [int(i) for i in cand[np.array(ok)]]

    def release_lock(self, row: int, *, signaled: bool = False):
        """Unlock a claimed row (one-sided WRITE of the lock word).

        ``signaled=True`` posts the WRITE async and waits its completion —
        the completion fence orders the release before any later CAS
        re-claim of the same word.  A release that is immediately followed
        by a re-claim with no intervening global fence (the paged serving
        engine's swap-out -> swap-in of the same slot) needs this: the
        plain unsignaled WRITE vs the later CAS is exactly the
        lost-update shape ``fabric.check`` flags."""
        idx = jnp.array([row], jnp.int32)
        zero = jnp.zeros((1,), WORD)
        region = f"{self.schema.name}/words"
        if signaled:
            self.store["words"] = self._transport.write_async(
                self.store["words"], idx, zero, region=region).wait()
        else:
            self.store["words"] = self._transport.write(
                self.store["words"], idx, zero, region=region)

    def locked_rows(self) -> int:
        with obs.span("namdb.sync.lock"):
            return int(np.count_nonzero(np.array(self.store["words"]) &
                                        np.uint32(1 << 31)))
