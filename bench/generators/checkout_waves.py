"""Closed loop of checkout waves (paper §4.3) through ``Database.commit``.

Each wave: one ``db.snapshot_read`` of every session's products, then
``sessions_per_wave`` sessions that each ``begin``, ``put`` validated
updates of the products they read and blind inserts into fresh rows of
the insert region, and one ``db.commit`` of the whole wave.  The next
wave starts when the commit returns.  Product keys (uniform) and write
payloads are drawn from the seed at set-up for every wave the insert
region can hold; a run that would need more fails (the region never
wraps).  After the window every record that any wave wrote is read back
through ``db.snapshot_read``, ``READBACK_ROWS`` records per call.  Each
wave keeps the host time its sessions spent in ``begin`` (``begin_s``).

Traffic parameters: ``sessions_per_wave``; ``warmup_waves`` (set-up
waves, checked like the others).
"""
from __future__ import annotations

import contextlib
import functools
import gc
import sys
import time
import traceback
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np

from bench.common import Check, host_rng, make_transport, span
from bench.reference import checkout as ref


@functools.partial(jax.jit, static_argnames=("products", "words"))
def _base_payload(products: int, words: int):
    """``reference.checkout.base_payload`` of every product, on the
    device."""
    r = jnp.arange(products, dtype=jnp.uint32)[:, None] * jnp.uint32(
        0x9E3779B1)
    return r ^ jnp.arange(words, dtype=jnp.uint32)[None, :]


# records per read-back call after the window: one shape, warmed up in
# set-up, so reading back compiles nothing
READBACK_ROWS = 1 << 16


class _Wave:
    __slots__ = ("rids", "ok", "cids", "t_begin", "begin_s", "t_end",
                 "failed")


class Cell:
    def __init__(self, cfg, traffic, seed):
        from repro.db import Database
        if cfg["reads_per_txn"] != cfg["updates_per_txn"]:
            raise ValueError("each checkout reads the products it updates")
        if cfg.get("isolation", "rsi") != "rsi":
            raise ValueError("the reference replays RSI")
        self.seed = seed
        self.traffic = traffic
        self.products = cfg["num_products"]
        self.words = cfg["record_bytes"] // 4
        self.upd = cfg["updates_per_txn"]
        self.width = self.upd + cfg["inserts_per_txn"]
        self.sessions = traffic["sessions_per_wave"]
        per_wave = self.sessions * cfg["inserts_per_txn"]
        self.max_waves = cfg["insert_rows"] // per_wave
        self.records = self.products + self.max_waves * per_wave
        rng = host_rng(seed)
        self.keys = rng.integers(0, self.products, (
            self.max_waves, self.sessions, self.upd))
        self.write_seeds = rng.integers(
            0, 1 << 32, (self.max_waves, self.sessions, self.width),
            dtype=np.uint64).astype(np.uint32)
        self.db = Database(transport=make_transport(
            cfg["num_storage_nodes"]))
        self.table = self.db.create_table(
            "products", self.records, payload_words=self.words,
            version_slots=cfg["version_slots"])
        self.table.seed(jnp.arange(self.products),
                        _base_payload(self.products, self.words))
        jax.block_until_ready(self.table.store)
        self.waves = []
        self.first = 0             # first wave of the window
        self.window_s = None
        self.readback = None

    # ------------------------------------------------------------ loop --

    def _recs(self, w):
        ins = (self.products + w * self.sessions * (self.width - self.upd)
               + np.arange(self.sessions * (self.width - self.upd)))
        return np.concatenate(
            [self.keys[w], ins.reshape(self.sessions, -1)], axis=1)

    def _wave(self):
        w = len(self.waves)
        if w >= self.max_waves:
            raise RuntimeError(
                f"insert region exhausted after {w} waves: raise "
                f"insert_rows in the configuration")
        db, t, upd = self.db, self.table, self.upd
        recs = self._recs(w)
        pay = ref.write_payload(self.write_seeds[w], self.words)
        wave = _Wave()
        wave.t_begin = np.empty(self.sessions)
        wave.begin_s = 0.0
        wave.failed = False
        try:
            with span("bench.wave_build"):
                _, rids, _ = db.snapshot_read(t, recs[:, :upd])
                with span("bench.readback"):
                    wave.rids = np.asarray(rids, np.uint32)
                wave_sessions = []
                for i in range(self.sessions):
                    wave.t_begin[i] = time.perf_counter()
                    s = db.session().begin()
                    wave.begin_s += time.perf_counter() - wave.t_begin[i]
                    s.put(t, recs[i, :upd], pay[i, :upd],
                          read_cids=wave.rids[i])
                    s.put(t, recs[i, upd:], pay[i, upd:])
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
        self._read(np.arange(READBACK_ROWS) % self.records)

    def window(self, seconds):
        self.first = len(self.waves)
        start = time.perf_counter()
        while True:
            self._wave()
            last = self.waves[-1]
            if last.failed or last.t_end - start >= seconds:
                break
        self.window_s = self.waves[-1].t_end - start

    def _window_waves(self):
        return self.waves[self.first:]

    @property
    def attempted(self):
        return self.sessions * len(self._window_waves())

    @property
    def failed(self):
        return self.sessions * sum(w.failed for w in self._window_waves())

    def end_to_end(self):
        waves = [w for w in self._window_waves() if not w.failed]
        committed = sum(int(w.ok.sum()) for w in waves)
        lat = np.concatenate([w.t_end - w.t_begin for w in waves])
        return {"txn_per_s": committed / self.window_s,
                "txn_p95_ms": float(np.percentile(lat, 95)) * 1e3}

    # ----------------------------------------------------------- check --

    def collect(self):
        """Read back, through ``db.snapshot_read``, every record that any
        wave wrote, set-up waves included: each committed write, and each
        record that only aborted transactions wrote."""
        recs = np.unique(np.concatenate(
            [self._recs(w).reshape(-1) for w in range(len(self.waves))]))
        parts = [self._read(recs[i:i + READBACK_ROWS])
                 for i in range(0, recs.size, READBACK_ROWS)]
        self.readback = (recs,) + tuple(np.concatenate(x)
                                        for x in zip(*parts))

    def _read(self, recs):
        """(payload, cid, visible) of up to ``READBACK_ROWS`` records on
        the host, read in one call of that shape."""
        n = recs.size
        pay, cid, vis = self.db.snapshot_read(
            self.table, np.resize(recs, READBACK_ROWS).astype(np.int32))
        return np.asarray(pay)[:n], np.asarray(cid)[:n], np.asarray(vis)[:n]

    def free(self):
        self.db = self.table = None
        gc.collect()

    def _payload_of(self, write_ids):
        per_wave = self.sessions * self.width
        w, rest = np.divmod(write_ids, per_wave)
        i, k = np.divmod(rest, self.width)
        return ref.write_payload(self.write_seeds[w, i, k], self.words)

    def checks(self):
        replay = ref.Replay(self.products, self.records)
        done = [(w, wave) for w, wave in enumerate(self.waves)
                if not wave.failed]
        for w, wave in done:
            ids = (w * self.sessions * self.width
                   + np.arange(self.sessions * self.width).reshape(
                       self.sessions, self.width))
            replay.wave(self._recs(w), wave.rids, wave.ok, wave.cids, ids)
        recs, pay, cid, vis = self.readback
        bad = 0
        for i in range(0, recs.size, READBACK_ROWS):
            part = slice(i, i + READBACK_ROWS)
            want_pay, want_cid, want_vis = replay.expected(
                recs[part], self.words, self._payload_of)
            bad += int(np.sum((vis[part] != want_vis) | (want_vis & (
                (cid[part] != want_cid)
                | np.any(pay[part] != want_pay, axis=1)))))
        committed = sum(int(w.ok.sum()) for _, w in done)
        c = replay.counts
        return [
            Check("outcome_mismatch", c["outcome_mismatch"], 0),
            Check("read_cid_mismatch", c["read_cid_mismatch"], 0),
            Check("double_writes", c["double_writes"], 0),
            Check("cid_order", c["cid_order"], 0),
            Check("readback_mismatch", bad, 0),
            Check("no_commit", int(committed == 0), 0),
            Check("failed_txns", self.sessions * (len(self.waves)
                                                  - len(done)), 0),
        ]


def build(cfg, traffic, seed):
    return Cell(cfg, traffic, seed)


@contextlib.contextmanager
def control():
    """Commit without the validate+lock arbitration: every CAS is granted,
    so two transactions of one wave that write one record both commit
    (a lost update, which snapshot isolation forbids)."""
    from repro.fabric import verbs

    def cas_granting_all(words, idx, expected, new, priority=None):
        inside = (idx >= 0) & (idx < words.shape[0])
        safe = jnp.where(inside, idx, words.shape[0])
        return inside, words.at[safe].set(new, mode="drop")

    with mock.patch.object(verbs, "cas", cas_granting_all):
        yield
