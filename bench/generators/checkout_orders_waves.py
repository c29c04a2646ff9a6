"""Closed loop of three-table checkout waves (paper §4.3 at its own shape)
through ``Database.commit``.

Each wave: one ``db.snapshot_read`` of every session's products, then
``sessions_per_wave`` sessions that each ``begin`` and ``put`` validated
updates of the products they read, a blind insert of its order into the
next fresh row of ``orders`` and blind inserts of its order lines into the
next fresh rows of ``orderlines``, and one ``db.commit`` of the whole
wave: each checkout is one RSI transaction over three tables.  The next
wave starts when the commit returns.  Product keys (uniform) and write
payloads are drawn from the seed at set-up for every wave the insert
tables can hold; a run that would need more fails (rows are never
reused).  After the window every record that any wave wrote, in each
table, is read back through ``db.snapshot_read``, ``READBACK_ROWS``
records per call.

Traffic parameters: ``sessions_per_wave``; ``warmup_waves`` (set-up
waves, checked like the others).
"""
from __future__ import annotations

import contextlib
import gc
import sys
import time
import traceback
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np

from bench.common import Check, host_rng, make_transport, span
from bench.generators import checkout_waves
from bench.generators.checkout_waves import READBACK_ROWS, _base_payload
from bench.reference import checkout as ref
from bench.reference import checkout_orders as ref3

TABLES = ref3.TABLES


class Cell(checkout_waves.Cell):
    """The window, its waves and its end-to-end metrics are
    ``checkout_waves``'; the tables, the waves' writes and the checks are
    the three tables'."""

    def __init__(self, cfg, traffic, seed):
        from repro.db import Database
        if cfg["reads_per_txn"] != cfg["updates_per_txn"]:
            raise ValueError("each checkout reads the products it updates")
        if cfg["orders_per_txn"] != 1:
            raise ValueError("each checkout inserts one order")
        if cfg.get("isolation", "rsi") != "rsi":
            raise ValueError("the reference replays RSI")
        self.seed = seed
        self.traffic = traffic
        self.products = cfg["num_products"]
        self.upd = cfg["updates_per_txn"]
        self.lines = cfg["orderlines_per_txn"]
        self.width = self.upd + 1 + self.lines
        # each write's slot in a transaction, by table
        self.slots = {"products": slice(0, self.upd),
                      "orders": slice(self.upd, self.upd + 1),
                      "orderlines": slice(self.upd + 1, self.width)}
        self.words = {"products": cfg["record_bytes"] // 4,
                      "orders": cfg["order_bytes"] // 4,
                      "orderlines": cfg["orderline_bytes"] // 4}
        self.rows = {"products": self.products,
                     "orders": cfg["orders_insert_rows"],
                     "orderlines": cfg["orderlines_insert_rows"]}
        self.sessions = traffic["sessions_per_wave"]
        self.max_waves = min(
            self.rows["orders"] // self.sessions,
            self.rows["orderlines"] // (self.sessions * self.lines))
        rng = host_rng(seed)
        self.keys = rng.integers(0, self.products, (
            self.max_waves, self.sessions, self.upd))
        self.write_seeds = rng.integers(
            0, 1 << 32, (self.max_waves, self.sessions, self.width),
            dtype=np.uint64).astype(np.uint32)
        self.db = Database(transport=make_transport(
            cfg["num_storage_nodes"]))
        self.tables = {t: self.db.create_table(
            t, self.rows[t], payload_words=self.words[t],
            version_slots=cfg["version_slots"]) for t in TABLES}
        self.tables["products"].seed(
            jnp.arange(self.products),
            _base_payload(self.products, self.words["products"]))
        jax.block_until_ready([t.store for t in self.tables.values()])
        self.waves = []
        self.first = 0             # first wave of the window
        self.window_s = None
        self.readback = None

    # ------------------------------------------------------------ loop --

    def _recs(self, w):
        """The records wave ``w`` writes: table -> (sessions, k)."""
        txn = w * self.sessions + np.arange(self.sessions)[:, None]
        return {"products": self.keys[w], "orders": txn,
                "orderlines": txn * self.lines + np.arange(self.lines)}

    def _wave(self):
        w = len(self.waves)
        if w >= self.max_waves:
            raise RuntimeError(
                f"insert rows exhausted after {w} waves: raise "
                f"orders_insert_rows in the configuration")
        db, t = self.db, self.tables
        recs = self._recs(w)
        pay = {n: ref.write_payload(self.write_seeds[w][:, sl],
                                    self.words[n])
               for n, sl in self.slots.items()}
        wave = checkout_waves._Wave()
        wave.t_begin = np.empty(self.sessions)
        wave.begin_s = 0.0
        wave.failed = False
        try:
            with span("bench.wave_build"):
                _, rids, _ = db.snapshot_read(t["products"],
                                              recs["products"])
                with span("bench.readback"):
                    wave.rids = np.asarray(rids, np.uint32)
                wave_sessions = []
                for i in range(self.sessions):
                    wave.t_begin[i] = time.perf_counter()
                    s = db.session().begin()
                    wave.begin_s += time.perf_counter() - wave.t_begin[i]
                    s.put(t["products"], recs["products"][i],
                          pay["products"][i], read_cids=wave.rids[i])
                    s.put(t["orders"], recs["orders"][i], pay["orders"][i])
                    s.put(t["orderlines"], recs["orderlines"][i],
                          pay["orderlines"][i])
                    wave_sessions.append(s)
            with span("bench.commit"):
                wave.ok = np.asarray(db.commit(wave_sessions), bool)
            wave.cids = np.asarray([s.cid for s in wave_sessions],
                                   np.int64)
        except Exception:          # noqa: BLE001 - a wave with no outcome
            traceback.print_exc(file=sys.stderr)
            wave.failed = True
        finally:
            wave.t_end = time.perf_counter()
            self.waves.append(wave)

    def warm(self):
        for _ in range(self.traffic["warmup_waves"]):
            self._wave()
            if self.waves[-1].failed:
                raise RuntimeError("a set-up wave raised")
        for n in TABLES:
            self._read(n, np.arange(READBACK_ROWS) % self.rows[n])

    # ----------------------------------------------------------- check --

    def collect(self):
        """Read back, through ``db.snapshot_read``, every record that any
        wave wrote, in each table, set-up waves included."""
        self.readback = {}
        for n in TABLES:
            recs = np.unique(np.concatenate(
                [self._recs(w)[n].reshape(-1)
                 for w in range(len(self.waves))]))
            parts = [self._read(n, recs[i:i + READBACK_ROWS])
                     for i in range(0, recs.size, READBACK_ROWS)]
            self.readback[n] = (recs,) + tuple(np.concatenate(x)
                                               for x in zip(*parts))

    def _read(self, table, recs):
        """(payload, cid, visible) of up to ``READBACK_ROWS`` records of
        ``table`` on the host, read in one call of that shape."""
        n = recs.size
        pay, cid, vis = self.db.snapshot_read(
            self.tables[table],
            np.resize(recs, READBACK_ROWS).astype(np.int32))
        return np.asarray(pay)[:n], np.asarray(cid)[:n], np.asarray(vis)[:n]

    def free(self):
        self.db = self.tables = None
        gc.collect()

    def _payload_in(self, table, write_ids):
        per_wave = self.sessions * self.width
        w, rest = np.divmod(write_ids, per_wave)
        i, k = np.divmod(rest, self.width)
        return ref.write_payload(self.write_seeds[w, i, k],
                                 self.words[table])

    def _read_back(self, table, recs):
        """(cid, visible) that the read-back gave ``recs`` of ``table``."""
        got, _, cid, vis = self.readback[table]
        at = np.searchsorted(got, recs)
        return cid[at], vis[at]

    def checks(self):
        replay = ref3.Replay(self.rows)
        done = [(w, wave) for w, wave in enumerate(self.waves)
                if not wave.failed]
        per_wave = self.sessions * self.width
        for w, wave in done:
            recs = self._recs(w)
            replay.wave(
                np.concatenate([replay.at(n, recs[n]) for n in TABLES],
                               axis=1),
                wave.rids, wave.ok, wave.cids,
                w * per_wave + np.arange(per_wave).reshape(
                    self.sessions, self.width))
        bad = 0
        for n in TABLES:
            recs, pay, cid, vis = self.readback[n]
            for i in range(0, recs.size, READBACK_ROWS):
                part = slice(i, i + READBACK_ROWS)
                want_pay, want_cid, want_vis = replay.expected(
                    replay.at(n, recs[part]), self.words[n],
                    lambda ids, n=n: self._payload_in(n, ids))
                bad += int(np.sum((vis[part] != want_vis) | (want_vis & (
                    (cid[part] != want_cid)
                    | np.any(pay[part] != want_pay, axis=1)))))
        torn = 0
        for w, wave in done:
            recs = self._recs(w)
            product_cid, _ = self._read_back("products", recs["products"])
            ins = [self._read_back(n, recs[n]) for n in TABLES[1:]]
            torn += ref3.torn_txns(
                wave.cids, product_cid,
                np.concatenate([c for c, _ in ins], axis=1),
                np.concatenate([v for _, v in ins], axis=1))
        committed = sum(int(w.ok.sum()) for _, w in done)
        c = replay.counts
        return [
            Check("outcome_mismatch", c["outcome_mismatch"], 0),
            Check("read_cid_mismatch", c["read_cid_mismatch"], 0),
            Check("double_writes", c["double_writes"], 0),
            Check("cid_order", c["cid_order"], 0),
            Check("readback_mismatch", bad, 0),
            Check("torn_txns", torn, 0),
            Check("no_commit", int(committed == 0), 0),
            Check("failed_txns", self.sessions * (len(self.waves)
                                                  - len(done)), 0),
        ]


def build(cfg, traffic, seed):
    return Cell(cfg, traffic, seed)


@contextlib.contextmanager
def control():
    """Commit each table's writes of a wave as a commit of its own: atomic
    in each table, torn across them.  A transaction's outcome is that of
    its products, so one whose product update loses still shows its order
    and order lines."""
    from repro.db import database
    real = database._BACKENDS["rsi"]

    def per_table(store, txns, *, region_ns, **kw):
        if not isinstance(store, tuple):
            return real(store, txns, region_ns=region_ns, **kw)
        out = [real(s, t, region_ns=r, **kw)
               for s, t, r in zip(store, txns, region_ns)]
        return (out[region_ns.index("products/")][0],
                tuple(new for _, new in out))

    with mock.patch.dict(database._BACKENDS, {"rsi": per_table}):
        yield
