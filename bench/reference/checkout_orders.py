"""Reference of §4.3 checkouts over three tables under RSI: each
transaction updates products, inserts an order and inserts its order
lines.

The three tables are replayed as one record space (products, then orders,
then order lines), so that ``checkout.Replay`` arbitrates each wave in
(session, slot) order over all of a transaction's writes in all tables: a
lock won by a transaction that loses another still blocks within the
wave, and a transaction commits iff it won every lock in every table.
:func:`torn_txns` reads, from the state the program left, transactions
whose writes show in some of their tables and not in others.
"""
from __future__ import annotations

import numpy as np

from bench.reference import checkout

TABLES = ("products", "orders", "orderlines")


class Replay(checkout.Replay):
    """The three tables replayed wave by wave in commit order; ``rows``
    maps each table to its record count."""

    def __init__(self, rows: dict):
        sizes = [rows[t] for t in TABLES]
        self.offset = dict(zip(TABLES, np.cumsum([0] + sizes[:-1])))
        super().__init__(rows["products"], sum(sizes))

    def at(self, table: str, recs) -> np.ndarray:
        """Records of ``table`` in the replay's one record space."""
        return np.asarray(recs, np.int64) + self.offset[table]


def torn_txns(txn_cid, product_cid, insert_cid, insert_vis) -> int:
    """Transactions whose writes show in some of their tables and not in
    others.  ``txn_cid`` (n,): the CID each transaction committed or
    aborted under; ``product_cid`` (n, updates): the CID each of its
    products reads back with after the last wave; ``insert_cid``,
    ``insert_vis`` (n, inserts): its order and order-line rows as they
    read back.  An insert shows where its row is visible with the
    transaction's CID.  A product update shows where the product holds
    the transaction's CID and is missing where it holds an older one (a
    newer CID may be a later transaction's overwrite and decides
    nothing)."""
    c = np.asarray(txn_cid, np.int64)[:, None]
    ins = np.asarray(insert_vis) & (np.asarray(insert_cid, np.int64) == c)
    every, none = ins.all(axis=1), ~ins.any(axis=1)
    product_cid = np.asarray(product_cid, np.int64)
    shown = (product_cid == c).any(axis=1)
    missing = (product_cid < c).any(axis=1)
    torn = ~(every | none) | (every & missing) | (none & shown)
    return int(torn.sum())
