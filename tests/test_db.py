"""The NAM-DB facade: planner argmin fidelity, explain() coverage, session
commit parity with the raw RSI protocol, the 2PC backend behind the same
API, cost-planned query execution, and the lock column serving uses."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import costmodel, rsi
from repro.db import (AGG_VARIANTS, JOIN_VARIANTS, Database, Planner,
                      Session, Table)
from repro.fabric import LocalTransport, MeshTransport
from repro.launch.mesh import make_mesh


# ------------------------------------------------------------- planner ----

FIG7_CONFIGS = [(8 * 1_000_000,) * 2 + (net, sel)
                for sel in (0.1, 0.25, 0.5, 0.75, 0.9, 1.0)
                for net in ("ipoeth", "ipoib", "rdma")]


@pytest.mark.parametrize("nr,ns,net,sel", FIG7_CONFIGS)
def test_planner_matches_costmodel_argmin(nr, ns, net, sel):
    """Acceptance: the planner's choice IS the §5.1 cost-model argmin over
    the feasible variants on every fig7 configuration."""
    manual = {"ghj": costmodel.t_ghj(nr, ns, net),
              "ghj_bloom": costmodel.t_ghj_bloom(nr, ns, net, sel)}
    if net == "rdma":
        manual["rdma_ghj"] = costmodel.t_rdma_ghj(nr, ns)
        manual["rrj"] = costmodel.t_rrj(nr, ns)
    want = min(manual, key=manual.get)
    alts = Planner(net=net).join_alternatives(nr, ns, sel)
    assert Planner.chosen(alts) == want
    # costs must be the model's, verbatim
    for a in alts:
        if a.feasible:
            assert a.cost_s == pytest.approx(manual[a.name])


def test_planner_explain_lists_all_four_join_variants():
    db = Database()
    db.load_table("R", jnp.arange(64, dtype=jnp.uint32),
                  jnp.ones((64,), jnp.uint32))
    db.load_table("S", jnp.arange(64, dtype=jnp.uint32),
                  jnp.ones((64,), jnp.uint32))
    ex = db.explain(db.scan("R").join(db.scan("S")).aggregate())
    assert {a.name for a in ex.alternatives} == set(JOIN_VARIANTS)
    assert sum(a.chosen for a in ex.alternatives) == 1
    assert all(a.cost_s > 0 for a in ex.alternatives)
    # argmin-first ordering among feasible alternatives
    feas = [a for a in ex.alternatives if a.feasible]
    assert feas[0].chosen and feas == sorted(feas, key=lambda a: a.cost_s)
    assert "join" in ex.plan and "scan(R)" in ex.plan


def test_planner_rdma_variants_infeasible_off_rdma():
    alts = Planner(net="ipoeth").join_alternatives(1 << 20, 1 << 20, 0.5)
    by = {a.name: a for a in alts}
    assert not by["rdma_ghj"].feasible and not by["rrj"].feasible
    assert Planner.chosen(alts) in ("ghj", "ghj_bloom")


def test_planner_agg_alternatives():
    p = Planner(net="rdma", nodes=4)
    alts = p.agg_alternatives(1 << 23, 1 << 18)
    assert {a.name for a in alts} == set(AGG_VARIANTS)
    # paper §5.3: the n x groups union makes Dist-AGG lose at high distinct
    assert Planner.chosen(alts) == "rdma_agg"
    # and at 1 group the union is negligible: Dist-AGG wins
    assert Planner.chosen(p.agg_alternatives(1 << 23, 1)) == "dist_agg"


def test_planner_calibration_from_fabric_counters():
    p = Planner(net="rdma")
    stats = {"route": {"calls": 1, "msgs": 4, "bytes": 1_000_000}}
    c = p.calibrate(stats, elapsed_s=0.01)       # 10 ms for 1 MB
    assert c == pytest.approx(1e-8)
    assert p.effective_net == pytest.approx(1e-8)
    # costs now price the measured wire, not the datasheet
    slow = p.join_alternatives(1 << 20, 1 << 20, 1.0)
    fast = Planner(net="rdma").join_alternatives(1 << 20, 1 << 20, 1.0)
    assert {a.name: a for a in slow}["ghj"].cost_s > \
        {a.name: a for a in fast}["ghj"].cost_s


# ---------------------------------------------------- session txn parity --

def _parity_fixture():
    rng = np.random.RandomState(0)
    nrec, T, W = 32, 16, 2
    recs = np.stack([rng.permutation(nrec)[:W] for _ in range(T)])
    pay = rng.randint(1, 99, (T, W, 2)).astype(np.uint32)
    cfg = rsi.StoreCfg(num_records=nrec, payload_words=2, version_slots=1,
                       num_timestamps=64)
    store = rsi.init_store(cfg)
    store["words"] = jnp.full((nrec,), 1, jnp.uint32)
    store["cids"] = store["cids"].at[:, 0].set(1)
    txns = rsi.TxnBatch(write_recs=jnp.asarray(recs, jnp.int32),
                        read_cids=jnp.full((T, W), 1, jnp.uint32),
                        new_payload=jnp.asarray(pay),
                        cid=jnp.asarray(2 + np.arange(T), jnp.uint32))
    ok_raw, st_raw = rsi.commit(store, txns)
    return nrec, recs, pay, np.array(ok_raw), st_raw


@pytest.mark.parametrize("transport_kind", ["local", "mesh"])
def test_session_commit_parity_with_raw_rsi(transport_kind):
    """A wave of facade sessions == raw rsi.commit of the same batch (the
    oracle assigns the same contiguous cids the raw batch uses)."""
    nrec, recs, pay, ok_raw, st_raw = _parity_fixture()
    tp = (LocalTransport() if transport_kind == "local" else
          MeshTransport(make_mesh((1,), ("data",)), "data"))
    db = Database(transport=tp)
    t = db.create_table("t", nrec, payload_words=2, num_timestamps=64)
    t.seed(np.arange(nrec))
    sessions = []
    for i in range(recs.shape[0]):
        s = db.session().begin()
        s.put("t", recs[i], pay[i], read_cids=np.ones(recs.shape[1],
                                                      np.uint32))
        sessions.append(s)
    ok = db.commit(sessions)
    np.testing.assert_array_equal(ok, ok_raw)
    for k in ("words", "cids", "payload", "bitvec"):
        np.testing.assert_array_equal(np.array(t.store[k]),
                                      np.array(st_raw[k]), err_msg=k)
    assert all(s.committed == bool(o) for s, o in zip(sessions, ok))


def test_2pc_backend_same_api_same_outcome():
    nrec, recs, pay, ok_raw, _ = _parity_fixture()
    db = Database()
    t = db.create_table("t", nrec, payload_words=2, num_timestamps=64)
    t.seed(np.arange(nrec))
    sessions = []
    for i in range(recs.shape[0]):
        s = db.session(isolation="2pc").begin()
        s.put("t", recs[i], pay[i],
              read_cids=np.ones(recs.shape[1], np.uint32))
        sessions.append(s)
    np.testing.assert_array_equal(db.commit(sessions), ok_raw)


def test_session_snapshot_read_and_single_commit():
    db = Database()
    t = db.create_table("acct", 16, payload_words=1)
    t.seed(np.arange(4), np.full((4, 1), 100))
    s = db.session().begin()
    pay, rids, ok = s.get("acct", [0, 1])
    assert np.array(ok).all() and (np.array(pay)[:, 0] == 100).all()
    s.put("acct", [0, 1], np.array(pay) + 11, read_cids=rids)
    assert s.commit()
    pay2, cid2, _ = db.session().begin().get("acct", [0])
    assert int(pay2[0, 0]) == 111 and int(cid2[0]) == s.cid
    # stale read_cids must abort
    s3 = db.session().begin()
    s3.put("acct", [0], np.array([[5]]), read_cids=np.asarray(rids)[:1])
    assert not s3.commit()


def test_session_guards():
    db = Database()
    db.create_table("a", 8, payload_words=1)
    db.create_table("b", 8, payload_words=1)
    s = db.session()
    with pytest.raises(RuntimeError, match="begin"):
        s.get("a", [0])
    s.begin()
    s.put("a", [0], np.ones((1, 1), np.uint32))
    s.put("b", [1, 2], np.ones((2, 1), np.uint32))    # two tables buffer
    assert s.tables == ("a", "b")
    np.testing.assert_array_equal(s.writes("b")[0], [1, 2])
    with pytest.raises(ValueError, match="one table per wave"):
        db.commit_grouped([[s]])
    with pytest.raises(ValueError, match="isolation"):
        db.session(isolation="3pc")


def test_readonly_sessions_commit_trivially():
    """Under SI a read-only txn validates nothing; a mixed wave's mask
    stays aligned with the caller's session order."""
    db = Database()
    t = db.create_table("t", 8, payload_words=1)
    t.seed(np.arange(8))
    ro = db.session().begin()
    ro.get("t", [0, 1])
    w1 = db.session().begin()
    w1.put("t", [2], np.array([[9]]), read_cids=np.ones(1, np.uint32))
    w2 = db.session().begin()            # conflicting write loses
    w2.put("t", [2], np.array([[8]]), read_cids=np.ones(1, np.uint32))
    ok = db.commit([w1, ro, w2])
    np.testing.assert_array_equal(ok, [True, True, False])
    assert ro.committed and ro.cid is None
    assert db.commit([db.session().begin()]).all()     # all-readonly wave


def test_oracle_claims_contiguous_cids():
    db = Database()
    a = db.claim_cids(4)
    b = db.claim_cids(2)
    np.testing.assert_array_equal(a, [2, 3, 4, 5])
    np.testing.assert_array_equal(b, [6, 7])
    assert db.read_timestamp() == 7


# ------------------------------------------------------------- queries ----

@pytest.fixture(scope="module")
def olap_db():
    db = Database()
    key = jax.random.PRNGKey(0)
    rk = jax.random.permutation(key, jnp.arange(1, 2049, dtype=jnp.uint32))
    db.load_table("R", rk, rk * 3)
    sk = jax.random.randint(jax.random.fold_in(key, 1), (4096,), 1, 4096
                            ).astype(jnp.uint32)
    db.load_table("S", sk, jnp.full((4096,), 2, jnp.uint32))
    hit = np.array(sk) <= 2048
    expect = int(np.sum(np.where(hit, np.array(sk) * 3 * 2, 0)))
    return db, expect


def test_query_all_forced_variants_agree(olap_db):
    db, expect = olap_db
    q = db.scan("R").join(db.scan("S").filter(sel=0.5)).aggregate()
    for variant in JOIN_VARIANTS:
        res = db.execute(q, force_variant=variant)
        assert int(res.value) == expect, variant
        assert res.variant == variant
    planned = db.execute(q)
    assert int(planned.value) == expect
    assert planned.variant == planned.planned


def test_query_group_aggregate_schemes_agree(olap_db):
    db, _ = olap_db
    q = db.scan("S").aggregate(groups=64)
    a = db.execute(q, force_variant="dist_agg").value
    b = db.execute(q, force_variant="rdma_agg").value
    np.testing.assert_array_equal(np.array(a), np.array(b))
    assert int(np.array(a).sum()) == 4096 * 2      # every S value is 2


def test_query_validation(olap_db):
    db, _ = olap_db
    with pytest.raises(ValueError, match="not in"):
        db.execute(db.scan("R").join(db.scan("S")).aggregate(),
                   force_variant="nested_loop")
    with pytest.raises(ValueError, match="aggregate"):
        db.explain(db.scan("R"))
    with pytest.raises(ValueError, match="groups"):
        db.explain(db.scan("R").aggregate())     # bare scan aggregate
    with pytest.raises(KeyError):
        db.scan("missing")
    with pytest.raises(ValueError, match="sel"):
        db.scan("R").filter(sel=0.0)
    with pytest.raises(ValueError, match="scalar"):
        db.scan("R").join(db.scan("S")).aggregate(groups=64)


def test_execute_calibrate_feeds_planner_measured_rate():
    db = Database()
    db.load_table("R", jnp.arange(1, 513, dtype=jnp.uint32),
                  jnp.ones((512,), jnp.uint32))
    db.load_table("S", jnp.arange(1, 1025, dtype=jnp.uint32),
                  jnp.ones((1024,), jnp.uint32))
    q = db.scan("R").join(db.scan("S")).aggregate()
    res = db.execute(q, calibrate=True)          # fresh shape: traced
    assert res.stats                             # counters captured
    assert db.planner.effective_net != "rdma"    # measured float installed
    assert db.planner.effective_net > 0


# ------------------------------------------------------------ lock column --

def test_table_lock_column_claim_release():
    db = Database()
    t = db.create_table("slots", 6, payload_words=1)
    got = t.claim_locks(4)
    assert got == [0, 1, 2, 3] and t.locked_rows() == 4
    # claimed rows are not re-claimable; remaining rows are
    more = t.claim_locks(4)
    assert more == [4, 5] and t.locked_rows() == 6
    t.release_lock(1)
    assert t.locked_rows() == 5 and t.claim_locks(1) == [1]
    # the claim traffic ran through the counted transport
    assert db.fabric_stats()["cas"]["msgs"] > 0
    # data tables refuse claim_locks: their words hold lock|CID, so word 0
    # means unborn record, not free
    data = db.create_table("data", 6, payload_words=1)
    data.seed(np.arange(3))
    with pytest.raises(ValueError, match="data table"):
        data.claim_locks(1)


def test_tables_are_nampool_regions():
    db = Database()
    db.create_table("t", 8, payload_words=2)
    names = set(db.pool.regions)
    assert {"t/words", "t/payload", "t/cids", "t/bitvec", "t/keys",
            "oracle/clock"} <= names
    with pytest.raises(KeyError):      # double registration is an error
        db.create_table("t", 8)
