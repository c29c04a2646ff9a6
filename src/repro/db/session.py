"""Sessions: the facade's transaction API.

A :class:`Session` is one NAM client connection: ``begin()`` takes a read
snapshot from the database's timestamp oracle, ``get`` runs snapshot reads
against a table's version store, ``put`` buffers writes, and ``commit()``
hands the transaction to the database, which batches every session
committing in the same wave into ONE fabric commit (the paper's compute
node drives many concurrent client transactions through one routed
prepare/install round trip).  A wave's two routed rounds share one
:class:`~repro.fabric.RoutePlan` — the prepare round bins the wave's
write set into per-home-shard buffers once and the install round reuses
the slots (``rsi.commit`` builds the plan, ``transport.plan_builds``
counts it) — so each wave pays the rank-in-bucket pass once, not twice.

A transaction may write several tables (the §4.3 checkout updates
products and inserts an order and its order lines): its writes buffer per
table, and they commit or abort together under one commit timestamp.

The isolation backend is selectable per session behind the same API:
``"rsi"`` (default) is the paper's RDMA snapshot-isolation protocol;
``"2pc"`` is the traditional coordinator baseline (``repro.core.twopc``) —
the data-plane outcome is identical, the *message economics* differ, which
is exactly what Fig 6 measures.
"""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp
import numpy as np

from repro.core import rsi

ISOLATION_LEVELS = ("rsi", "2pc")


class Session:
    """One client transaction at a time; writes buffer until commit."""

    def __init__(self, db, isolation: str = "rsi"):
        if isolation not in ISOLATION_LEVELS:
            raise ValueError(f"isolation {isolation!r} not in "
                             f"{ISOLATION_LEVELS}")
        self.db = db
        self.isolation = isolation
        self.seq = next(db._sessions)       # the id of its oracle reads
        self.rid: Optional[int] = None      # read snapshot timestamp
        self.cid: Optional[int] = None      # assigned at commit (latest)
        self.committed: Optional[bool] = None
        self.txn_id: Optional[int] = None   # first claimed cid — stable
                                            # across retries (backoff
                                            # jitter keys off it)
        self.attempts: int = 0              # fabric commit rounds run
        # buffered writes per table, in the order first written:
        # name -> [(recs (W,), payload (W, m), read_cids (W,)), ...]
        self._writes: dict = {}

    # ----------------------------------------------------------- txn API --

    def begin(self, rid: Optional[int] = None) -> "Session":
        """Start a transaction; rid defaults to the oracle's current read
        timestamp (highest consecutively committed cid)."""
        self.rid = (self.db.read_timestamp(session=self.seq) if rid is None
                    else int(rid))
        self.cid = None
        self.committed = None
        self.txn_id = None
        self.attempts = 0
        self._writes = {}
        return self

    def get(self, table, recs):
        """Snapshot-read records at this session's rid (one-sided READs
        through the database's counted transport).
        Returns (payload, read_cids, ok) — pass read_cids back into put()
        for validated updates."""
        self._check_open()
        t = self.db.table(table)
        return rsi.read_snapshot(t.store, jnp.asarray(recs, jnp.int32),
                                 jnp.uint32(self.rid),
                                 transport=self.db.transport,
                                 region_ns=f"{t.schema.name}/")

    def put(self, table, recs, payload, read_cids=None):
        """Buffer writes: recs (W,), payload (W, m); read_cids (W,) is the
        CID each record was read under (None = blind insert at CID 0).
        Writes to several tables buffer per table and commit together."""
        self._check_open()
        t = self.db.table(table)
        recs = np.asarray(recs, np.int32).reshape(-1)
        payload = np.asarray(payload, np.uint32).reshape(
            recs.shape[0], t.schema.payload_words)
        rcids = (np.zeros(recs.shape[0], np.uint32) if read_cids is None
                 else np.asarray(read_cids, np.uint32).reshape(-1))
        self._writes.setdefault(t.schema.name, []).append(
            (recs, payload, rcids))
        return self

    def commit(self, **kw) -> bool:
        """Commit this transaction alone (a one-session wave). Batch many
        concurrent sessions with ``db.commit([s1, s2, ...])`` instead."""
        return bool(self.db.commit([self], **kw)[0])

    def refresh_read_cids(self) -> "Session":
        """Retry path after an abort: re-read the *current* committed
        version of every buffered write record (ONE counted READ on each
        written table's word array — issued after the losing round's
        commit-complete fence, which is what makes the retry race-free)
        and revalidate the buffered writes against it.  The payload stays
        as buffered — fig_scale's increments are idempotent re-applies;
        an application would re-run its read-modify-write here."""
        if self._writes:
            self.db._refresh_losers([self])
        return self

    # ---------------------------------------------------------- internals --

    def _check_open(self):
        if self.rid is None:
            raise RuntimeError("call begin() first")

    @property
    def tables(self) -> tuple:
        """Names of the tables this transaction writes, in the order first
        written (empty for a read-only transaction)."""
        return tuple(self._writes)

    def writes(self, table: str):
        """(recs (W,), payload (W, m), read_cids (W,)) — the buffered
        write set in ``table``, concatenated."""
        return tuple(np.concatenate(x) for x in zip(*self._writes[table]))

    def revalidate(self, table: str, read_cids, rid: int):
        """Validate the writes buffered for ``table`` against
        ``read_cids`` (its records' words, re-read after an abort) from
        snapshot ``rid`` on."""
        recs, payload, _ = self.writes(table)
        self._writes[table] = [(recs, payload, read_cids)]
        self.rid = rid

    # -------------------------------------------------------- context mgr --

    def __enter__(self):
        return self.begin()

    def __exit__(self, exc_type, exc, tb):
        if exc_type is None and self.committed is None and self._writes:
            self.commit()
        return False
