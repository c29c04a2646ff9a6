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

from repro import obs
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
        self._table: Optional[str] = None   # single-table txn (v1)
        self._recs: list = []
        self._payload: list = []
        self._read_cids: list = []

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
        self._table, self._recs = None, []
        self._payload, self._read_cids = [], []
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
        CID each record was read under (None = blind insert at CID 0)."""
        self._check_open()
        t = self.db.table(table)
        name = t.schema.name
        if self._table is not None and self._table != name:
            raise NotImplementedError(
                f"multi-table transaction ({self._table} + {name}): one "
                "store per routed commit in v1")
        self._table = name
        recs = np.asarray(recs, np.int32).reshape(-1)
        payload = np.asarray(payload, np.uint32).reshape(
            recs.shape[0], t.schema.payload_words)
        rcids = (np.zeros(recs.shape[0], np.uint32) if read_cids is None
                 else np.asarray(read_cids, np.uint32).reshape(-1))
        self._recs.append(recs)
        self._payload.append(payload)
        self._read_cids.append(rcids)
        return self

    def commit(self, **kw) -> bool:
        """Commit this transaction alone (a one-session wave). Batch many
        concurrent sessions with ``db.commit([s1, s2, ...])`` instead."""
        return bool(self.db.commit([self], **kw)[0])

    def refresh_read_cids(self) -> "Session":
        """Retry path after an abort: re-read the *current* committed
        version of every buffered write record (ONE counted READ on the
        table's word array — issued after the losing round's
        commit-complete fence, which is what makes the retry race-free)
        and revalidate the buffered writes against it.  The payload stays
        as buffered — fig_scale's increments are idempotent re-applies;
        an application would re-run its read-modify-write here."""
        if self._table is None:
            return self
        t = self.db.table(self._table)
        recs = np.concatenate(self._recs)
        with obs.span("namdb.sync.refresh"):
            words = self.db.transport.read(t.store["words"],
                                           jnp.asarray(recs, jnp.int32),
                                           region=f"{t.schema.name}/words")
            fresh = (np.asarray(words, np.uint32)
                     & np.uint32(int(rsi.CID_MASK)))
        self._recs = [recs]
        self._payload = [np.concatenate(self._payload)]
        self._read_cids = [fresh]
        self.rid = self.db.read_timestamp()
        return self

    # ---------------------------------------------------------- internals --

    def _check_open(self):
        if self.rid is None:
            raise RuntimeError("call begin() first")

    @property
    def table_name(self) -> Optional[str]:
        return self._table

    def writes(self):
        """(recs (W,), payload (W, m), read_cids (W,)) — the buffered
        write set, concatenated."""
        if not self._recs:
            return (np.zeros((0,), np.int32),
                    np.zeros((0, 0), np.uint32),
                    np.zeros((0,), np.uint32))
        return (np.concatenate(self._recs),
                np.concatenate(self._payload),
                np.concatenate(self._read_cids))

    # -------------------------------------------------------- context mgr --

    def __enter__(self):
        return self.begin()

    def __exit__(self, exc_type, exc, tb):
        if exc_type is None and self.committed is None and self._recs:
            self.commit()
        return False
