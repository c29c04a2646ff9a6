"""Reference of §4.3 checkout waves under RSI (copied from the numpy
checks of ``chip_smoke.py``'s OLTP phase).

Within a wave every session's requests are taken in (session, slot)
order; the first request whose read CID equals the record's committed
CID locks the record, and a transaction commits iff it locked every
record it writes.  Committed transactions install their CID and payload;
the records an aborted transaction wrote keep what they held.
"""
from __future__ import annotations

import numpy as np

LOAD_CID = 1          # CID of the base products (the load epoch)
GOLDEN = np.uint32(0x9E3779B1)


def base_payload(recs, words: int) -> np.ndarray:
    """Load-epoch payload of product records: a hash of (record, word)."""
    r = np.asarray(recs).astype(np.uint32)[:, None] * GOLDEN
    return r ^ np.arange(words, dtype=np.uint32)[None, :]


def write_payload(write_seeds, words: int) -> np.ndarray:
    """Payload of each write from its 32-bit seed: (...,) -> (..., words)."""
    seeds = np.asarray(write_seeds, np.uint32)[..., None]
    return seeds ^ (np.arange(words, dtype=np.uint32) * GOLDEN)


def arbitrate(recs: np.ndarray, expect: np.ndarray,
              word: np.ndarray) -> np.ndarray:
    """The wave's committed set: recs and expect are (sessions, width)."""
    sessions, width = recs.shape
    won = np.zeros(recs.shape, bool)
    locked = set()
    for i in range(sessions):
        for k in range(width):
            r = int(recs[i, k])
            if r not in locked and expect[i, k] == word[r]:
                won[i, k] = True
                locked.add(r)
    return won.all(axis=1)


class Replay:
    """The reference store, replayed wave by wave in commit order."""

    def __init__(self, products: int, records: int):
        self.products = products
        self.word = np.zeros(records, np.uint32)   # committed CID per record
        self.word[:products] = LOAD_CID
        self.last = np.full(records, -1, np.int64)  # last committed write
        self.last_cid = np.zeros(records, np.uint32)
        self.max_cid = LOAD_CID
        self.counts = {"outcome_mismatch": 0, "read_cid_mismatch": 0,
                       "double_writes": 0, "cid_order": 0}

    def wave(self, recs, rids, ok, cids, write_ids):
        """Replay one wave.  recs (S, width) records written, updates first;
        rids (S, updates) the CIDs the program's snapshot read returned;
        ok (S,) and cids (S,) the program's outcomes; write_ids (S, width)
        a global id of each write, for :meth:`expected`."""
        sessions, width = recs.shape
        upd = rids.shape[1]
        c = self.counts
        c["read_cid_mismatch"] += int(np.sum(
            rids != self.word[recs[:, :upd]]))
        cids = np.asarray(cids, np.int64)
        if (np.unique(cids).size != cids.size
                or cids.min() <= self.max_cid):
            c["cid_order"] += 1
        self.max_cid = max(self.max_cid, int(cids.max()))
        expect = np.concatenate(
            [rids, np.zeros((sessions, width - upd), np.uint32)], axis=1)
        want = arbitrate(recs, expect, self.word)
        c["outcome_mismatch"] += int(np.sum(want != ok))
        mine = recs[ok].reshape(-1)
        c["double_writes"] += int(mine.size - np.unique(mine).size)
        for i in np.nonzero(ok)[0]:
            self.word[recs[i]] = cids[i]
            self.last[recs[i]] = write_ids[i]
            self.last_cid[recs[i]] = cids[i]

    def expected(self, recs, words: int, payload_of):
        """What a snapshot read of ``recs`` must return after the replay:
        (payload (n, words), cid (n,), visible (n,)).  ``payload_of``
        maps write ids to their payloads.  A record no committed
        transaction wrote holds its base payload at the load epoch if it
        is a product, and is invisible otherwise (an insert row that only
        aborted transactions wrote); an invisible record's payload and
        CID are not compared."""
        recs = np.asarray(recs, np.int64)
        last = self.last[recs]
        written = last >= 0
        visible = written | (recs < self.products)
        pay = base_payload(recs, words)
        if written.any():
            pay[written] = payload_of(last[written])
        cid = np.where(written, self.last_cid[recs], LOAD_CID)
        return pay, cid.astype(np.uint32), visible
