"""Multi-table transactions (the §4.3 checkout's shape: validated product
updates, an order and its order lines in one transaction) through
``Session``/``Database.commit``, against a sequential numpy replay: the
same outcomes, CIDs and stores in every table, bit for bit, on
``LocalTransport`` and on four virtual devices (``MeshTransport``), with
seeded key collisions so that transactions abort, and with retries."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core import rsi
from repro.db import Database

SRC = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src")

# name -> (rows, payload words); rows divide by 4 for the sharded runs
TABLES = {"products": (64, 8), "orders": (64, 3), "orderlines": (192, 5)}
LOAD_CID = 1


def make_db(transport=None):
    db = Database(transport=transport)
    for name, (rows, m) in TABLES.items():
        db.create_table(name, rows, payload_words=m)
    rows, m = TABLES["products"]
    db.table("products").seed(np.arange(rows), _payload(
        np.arange(rows * m).reshape(rows, m) * 7 + 1))
    return db


def _payload(x):
    return np.asarray(x, np.uint64).astype(np.uint32)


def make_wave(rng, T, first_txn, hot=8, readonly=True):
    """T checkouts: 3 product updates over the ``hot`` first products (so
    that transactions collide), 1 order and 3 order lines at fresh rows.
    Every fifth transaction writes products only and, with ``readonly``,
    every seventh is read-only."""
    txns = []
    for i in range(T):
        n = first_txn + i
        if readonly and i % 7 == 6:
            txns.append({})
            continue
        w = {"products": rng.choice(hot, 3, replace=False)}
        if i % 5 != 4:
            w["orders"] = np.array([n])
            w["orderlines"] = 3 * n + np.arange(3)
        txns.append({t: (r, _payload(rng.integers(
            0, 1 << 32, (r.size, TABLES[t][1]), dtype=np.uint64)))
            for t, r in w.items()})
    return txns


def run_wave(db, txns, max_retries=0):
    """Begin every session, read its products, buffer its writes and
    commit the wave.  Returns (ok, cids, read cids of the products)."""
    sessions, read = [], []
    for w in txns:
        s = db.session().begin()
        rc = np.zeros(0, np.uint32)
        for name, (recs, pay) in w.items():
            if name == "products":
                _, rc, _ = s.get(name, recs)
                rc = np.asarray(rc, np.uint32)
                s.put(name, recs, pay, read_cids=rc)
            else:
                s.put(name, recs, pay)
        sessions.append(s)
        read.append(rc)
    ok = db.commit(sessions, max_retries=max_retries)
    return ok, [s.cid for s in sessions], read


class Replay:
    """The database replayed on the host: per table the lock|CID word,
    the newest payload and its CID; a wave's transactions take their
    writes' locks in (session, slot) order, a lock won by a transaction
    that loses another still blocks the rest of its round, and a
    transaction commits iff it won every lock in every table."""

    def __init__(self):
        self.word = {t: np.zeros(r, np.uint32) for t, (r, _) in TABLES.items()}
        self.pay = {t: np.zeros((r, m), np.uint32)
                    for t, (r, m) in TABLES.items()}
        rows, m = TABLES["products"]
        self.word["products"][:] = LOAD_CID
        self.pay["products"][:] = _payload(
            np.arange(rows * m).reshape(rows, m) * 7 + 1)
        self.cids = {t: w.copy() for t, w in self.word.items()}
        self.clock = 2

    def _round(self, txns, exp):
        locked, ok = set(), []
        for w, e in zip(txns, exp):
            won = True
            for name in sorted(w):
                for r, x in zip(w[name][0], e[name]):
                    if (name, r) not in locked and x == self.word[name][r]:
                        locked.add((name, r))
                    else:
                        won = False
            ok.append(won)
        cids = list(range(self.clock, self.clock + len(txns)))
        self.clock += len(txns)
        for w, good, c in zip(txns, ok, cids):
            for name, (recs, pay) in w.items():
                if good:
                    self.word[name][recs] = c
                    self.cids[name][recs] = c
                    self.pay[name][recs] = pay
        return ok, cids

    def wave(self, txns, max_retries=0):
        """(ok, cids, the product read cids) of a wave as the database
        must give them."""
        read = [self.word["products"][w["products"][0]] if w
                else np.zeros(0, np.uint32) for w in txns]
        writers = [i for i, w in enumerate(txns) if w]
        exp = {i: {t: (self.word[t][r] if t == "products"
                       else np.zeros(r.size, np.uint32))
                   for t, (r, _) in txns[i].items()} for i in writers}
        ok = [True] * len(txns)
        cids = [None] * len(txns)
        got, c = self._round([txns[i] for i in writers],
                             [exp[i] for i in writers])
        for i, g, ci in zip(writers, got, c):
            ok[i], cids[i] = g, ci
        losers = [i for i in writers if not ok[i]]
        for _ in range(max_retries):
            if not losers:
                break
            for i in losers:          # one refresh of every loser's words
                exp[i] = {t: self.word[t][r].copy()
                          for t, (r, _) in txns[i].items()}
            at = 0
            while at < len(losers):   # power-of-two retry waves
                size = 1 << ((len(losers) - at).bit_length() - 1)
                chunk = losers[at:at + size]
                got, c = self._round([txns[i] for i in chunk],
                                     [exp[i] for i in chunk])
                for i, g, ci in zip(chunk, got, c):
                    ok[i], cids[i] = g, ci
                at += size
            losers = [i for i in losers if not ok[i]]
        return np.asarray(ok), cids, read


def state(db):
    """Each table's words and newest payload and CID, on the host."""
    return {t: (np.asarray(db.table(t).store["words"]),
                np.asarray(db.table(t).store["payload"])[:, 0],
                np.asarray(db.table(t).store["cids"])[:, 0])
            for t in TABLES}


def compare(db, replay, got, want):
    """Mismatches between the program and the replay, by name (empty:
    bit for bit equal)."""
    bad = []
    if not np.array_equal(got[0], want[0]):
        bad.append("ok")
    if got[1] != want[1]:
        bad.append("cids")
    if any(not np.array_equal(a, b) for a, b in zip(got[2], want[2])):
        bad.append("read_cids")
    for t, (words, pay, cids) in state(db).items():
        if not np.array_equal(words, replay.word[t]):
            bad.append(f"{t}.words")
        if not np.array_equal(pay, replay.pay[t]):
            bad.append(f"{t}.payload")
        if not np.array_equal(cids, replay.cids[t]):
            bad.append(f"{t}.cids")
    return bad


def replay_run(transport, seed, waves, T, max_retries, readonly=True):
    """Run seeded waves on a database and the replay; the mismatches of
    each wave, and the aborts counted."""
    db, replay = make_db(transport), Replay()
    rng = np.random.default_rng(seed)
    out = []
    for w in range(waves):
        txns = make_wave(rng, T, w * T, readonly=readonly)
        want = replay.wave(txns, max_retries)
        got = run_wave(db, txns, max_retries)
        out.append(compare(db, replay, got, want))
    return out, db.txn_stats["aborts"]


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("max_retries", [0, 2])
def test_waves_match_the_numpy_replay(seed, max_retries):
    mismatches, aborts = replay_run(None, seed, waves=3, T=12,
                                    max_retries=max_retries)
    assert mismatches == [[], [], []]
    assert aborts > 0                 # the hot keys did collide


def test_a_product_lock_lost_hides_the_order_and_its_lines():
    db = make_db()
    pay = {t: np.full((k, TABLES[t][1]), 9, np.uint32)
           for t, k in (("products", 1), ("orders", 1), ("orderlines", 3))}
    sessions = []
    for n in range(2):                 # both update product 5
        s = db.session().begin()
        _, rc, _ = s.get("products", [5])
        s.put("products", [5], pay["products"] + n, read_cids=rc)
        s.put("orders", [n], pay["orders"])
        s.put("orderlines", 3 * n + np.arange(3), pay["orderlines"])
        sessions.append(s)
    np.testing.assert_array_equal(db.commit(sessions), [True, False])
    for t, rows in (("orders", [0, 1]), ("orderlines", np.arange(6))):
        _, cid, vis = db.snapshot_read(t, rows)
        np.testing.assert_array_equal(
            np.asarray(vis), np.repeat([True, False], len(rows) // 2))
        assert set(np.asarray(cid)[np.asarray(vis)]) == {sessions[0].cid}
    assert int(db.table("products").store["words"][5]) == sessions[0].cid
    assert db.table("products").locked_rows() == 0


def test_an_insert_table_leaves_the_other_tables_wave_unchanged():
    """A wave that also inserts order lines gives its products the same
    outcomes, CIDs and store as the products-only wave."""
    rng = np.random.default_rng(7)
    txns = make_wave(rng, 12, 0)
    alone = [{t: w[t] for t in w if t == "products"} for w in txns]
    runs = []
    for wave in (txns, alone):
        db = make_db()
        ok, cids, _ = run_wave(db, wave)
        runs.append((ok, cids, state(db)["products"]))
    (ok_a, cids_a, st_a), (ok_b, cids_b, st_b) = runs
    np.testing.assert_array_equal(ok_a, ok_b)
    assert cids_a == cids_b and not ok_a.all()
    for a, b in zip(st_a, st_b):
        np.testing.assert_array_equal(a, b)


def test_one_table_as_a_tuple_commits_as_the_table():
    """``rsi.commit`` over a one-tuple of tables gives the outcomes and
    store of the one-table call."""
    db = make_db()
    t = db.table("products")
    s = [db.session().begin() for _ in range(4)]
    for i, x in enumerate(s):
        x.put(t, [i % 2, 2 + i], np.ones((2, 8), np.uint32) * i,
              read_cids=np.ones(2, np.uint32))
    (txns,), _ = db._pack_txns([t], s)
    ok, store = rsi.commit(t.store, txns, region_ns="products/")
    ok1, (store1,) = rsi.commit((t.store,), (txns,),
                                region_ns=("products/",))
    np.testing.assert_array_equal(ok, ok1)
    np.testing.assert_array_equal(ok, [True, True, False, False])
    for k in store:
        np.testing.assert_array_equal(store[k], store1[k])


def test_one_cid_and_one_pack_span_per_wave():
    import time

    from repro import obs
    db = make_db()
    lo = time.perf_counter_ns()
    ok, cids, _ = run_wave(db, make_wave(np.random.default_rng(3), 8, 0))
    writers = [c for c in cids if c is not None]
    assert sorted(writers) == list(range(2, 2 + len(writers)))
    packs = [r for r in obs.spans(lo) if r.name == "namdb.commit.pack"]
    assert packs[-1].ids["tables"] == 3
    assert len([r for r in obs.spans(lo) if r.name == "namdb.sync.claim"
                and r.start_ns >= packs[-1].start_ns]) == 1


def test_refresh_reads_each_table_once():
    db = make_db()
    s = db.session().begin()
    s.put("products", [1, 2], np.ones((2, 8), np.uint32),
          read_cids=np.zeros(2, np.uint32))      # stale: aborts
    s.put("orders", [3], np.ones((1, 3), np.uint32))
    assert not db.commit([s])[0]
    before = db.transport.stats().get("read", {}).get("calls", 0)
    s.refresh_read_cids()
    assert db.transport.stats()["read"]["calls"] - before == 2
    np.testing.assert_array_equal(s.writes("products")[2], [1, 1])
    assert db.commit([s])[0]


def test_backends_and_entry_points_keep_one_table():
    db = make_db()
    s = db.session(isolation="2pc").begin()
    s.put("orders", [0], np.ones((1, 3), np.uint32))
    s.put("orderlines", [0], np.ones((1, 5), np.uint32))
    with pytest.raises(ValueError, match="one table per wave"):
        db.commit([s])
    s = db.session().begin()
    s.put("orders", [0], np.ones((1, 3), np.uint32))
    s.put("orderlines", [0], np.ones((1, 5), np.uint32))
    with pytest.raises(ValueError, match="Database.commit"):
        db.commit_pipelined([[s]])


_MESH = r"""
import os, sys, json
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, os.environ["TEST_DIR"])
from repro.fabric import MeshTransport
from repro.launch.mesh import make_mesh
import test_multitable as M
mesh = make_mesh((4,), ("data",))
out = {}
for seed in (0, 1):
    # every session writes: a wave's writers split evenly over 4 shards
    out[seed] = M.replay_run(MeshTransport(mesh, "data"), seed, waves=2,
                             T=16, max_retries=0, readonly=False)
out["retry"] = M.mesh_retry(MeshTransport(mesh, "data"))
print("RESULT " + json.dumps(out))
"""


def mesh_retry(transport):
    """Eight sessions, the last four each losing a product to one of the
    first four: the four losers retry as one wave of four, one per
    shard.  Mismatches with the replay, and the aborts counted."""
    db, replay = make_db(transport), Replay()
    txns = [{"products": (np.array([i % 4, 10 + i, 20 + i]),
                          np.full((3, 8), i, np.uint32)),
             "orders": (np.array([i]), np.full((1, 3), i, np.uint32))}
            for i in range(8)]
    want = replay.wave(txns, max_retries=1)
    got = run_wave(db, txns, max_retries=1)
    return compare(db, replay, got, want), db.txn_stats["aborts"]


@pytest.fixture(scope="module")
def mesh_runs():
    env = dict(os.environ, PYTHONPATH=SRC,
               TEST_DIR=os.path.dirname(os.path.abspath(__file__)))
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", _MESH], env=env,
                       capture_output=True, text=True, timeout=900)
    line = [x for x in r.stdout.splitlines() if x.startswith("RESULT ")]
    assert line, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-3000:]}"
    return json.loads(line[0][len("RESULT "):])


@pytest.mark.parametrize("seed", ["0", "1"])
def test_sharded_waves_match_the_numpy_replay(mesh_runs, seed):
    mismatches, aborts = mesh_runs[seed]
    assert mismatches == [[], []]
    assert aborts > 0


def test_sharded_retry_matches_the_numpy_replay(mesh_runs):
    mismatches, aborts = mesh_runs["retry"]
    assert mismatches == []
    assert aborts == 4
