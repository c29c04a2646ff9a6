"""fabric-check: the jaxpr lint engine + one-sided race detector (ISSUE 6).

Covers both passes and the CLI:

  * **lint engine** — the structural walker recurses into scan/cond/pjit
    sub-jaxprs with path attribution; each rule (sort-free, collective
    budget, no-host-transfer, packed-wire) fires on a seeded-bad trace and
    stays quiet on the real hot paths;
  * **race detector** — the four seeded-violation fixtures from ISSUE 6
    (unfenced WRITE/WRITE overlap, lost-update RMW next to a FETCH_ADD,
    install-without-lock wave, stale pull beyond k) are each flagged with
    the offending verb pair + region named, while the REAL protocols (RSI
    and 2PC session waves, lock-table claims, the PS trainer loop) record
    clean schedules;
  * **CLI** — ``python -m repro.fabric.check`` exits 0 on the figure gate
    and the summary carries the ``{rules_run, violations}`` block that
    ``benchmarks/run.py --check`` embeds.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.fabric import LocalTransport, check
from repro.launch.mesh import make_mesh

LOCK = 1 << 31


# ------------------------------------------------------- pass 1: lint ----

def test_walker_attributes_primitives_inside_scan():
    def f(x):
        def step(c, _):
            return jnp.sort(c), None
        y, _ = jax.lax.scan(step, x, None, length=3)
        return y

    jaxpr = jax.make_jaxpr(f)(jnp.arange(8.0))
    # one syntactic sort site, even though the scan runs it 3 times
    assert check.count_primitive(jaxpr, "sort") == 1
    rep = check.lint_jaxpr(jaxpr, [check.SortFree()], target="scan-sort")
    assert not rep.ok
    assert "scan" in rep.violations[0].where   # path names the enclosure


def test_collective_budget_exact_counts():
    import jax.numpy as _  # noqa: F401
    mesh = make_mesh((1,), ("ax",))
    from jax.sharding import PartitionSpec as P

    def body(v):
        return jax.lax.all_to_all(v.reshape(1, -1), "ax", 0, 0).reshape(-1)

    f = jax.shard_map(body, mesh=mesh, in_specs=P("ax"), out_specs=P("ax"),
                      check_vma=False)
    jaxpr = jax.make_jaxpr(f)(jnp.zeros((4,), jnp.uint32))
    ok = check.lint_jaxpr(jaxpr, [check.CollectiveBudget(
        {"all_to_all": 1})], target="one")
    assert ok.ok, ok.render()
    bad = check.lint_jaxpr(jaxpr, [check.CollectiveBudget(
        {"all_to_all": 2})], target="two")
    assert not bad.ok
    assert "1 all_to_all site(s) traced, budget is 2" in \
        bad.violations[0].detail


def test_no_host_transfer_flags_callbacks():
    def f(x):
        return jax.pure_callback(
            lambda v: np.asarray(v), jax.ShapeDtypeStruct(x.shape, x.dtype),
            x)

    rep = check.lint_fn(f, jnp.ones((4,)), rules=[check.NoHostTransfer()],
                        target="cb")
    assert not rep.ok
    assert "pure_callback" in rep.violations[0].detail


def test_packed_wire_flags_non_u32_collective():
    mesh = make_mesh((1,), ("ax",))
    from repro.fabric import MeshTransport
    tp = MeshTransport(mesh, "ax")
    # an f32 buffer on the exchange bypasses the packed u32 wire
    rep = check.lint_fn(
        lambda v: tp.run(lambda x: tp.exchange(x), (v,), False),
        jnp.zeros((4,), jnp.float32), rules=[check.PackedWire()],
        target="raw-f32")
    assert not rep.ok
    assert "float32" in rep.violations[0].detail
    # the same buffer as packed u32 passes
    rep = check.lint_fn(
        lambda v: tp.run(lambda x: tp.exchange(x), (v,), False),
        jnp.zeros((4,), jnp.uint32), rules=[check.PackedWire()],
        target="u32")
    assert rep.ok, rep.render()


# ------------------------------ pass 2: seeded-violation fixtures --------

def _rec_tp():
    rec = check.ScheduleRecorder()
    return rec, LocalTransport(recorder=rec)


def test_fixture_unfenced_write_write_overlap():
    rec, t = _rec_tp()
    arr = jnp.zeros((16,), jnp.uint32)
    t.write(arr, jnp.array([2, 3, 4], jnp.int32),
            jnp.ones((3,), jnp.uint32), region="buf")
    t.write(arr, jnp.array([4, 5], jnp.int32),
            jnp.ones((2,), jnp.uint32), region="buf")
    rep = check.check_schedule(rec, target="fixture-ww")
    assert [v.rule for v in rep.violations] == ["ww-race"]
    v = rep.violations[0]
    assert v.where == "buf"                       # region named
    assert "WRITE#0" in v.detail and "WRITE#1" in v.detail  # verb pair
    assert "rows {4}" in v.detail                 # exact overlap


def test_fence_orders_the_same_writes():
    rec, t = _rec_tp()
    arr = jnp.zeros((16,), jnp.uint32)
    t.write(arr, jnp.array([2, 3, 4], jnp.int32),
            jnp.ones((3,), jnp.uint32), region="buf")
    rec.fence("flush")                            # an explicit barrier
    t.write(arr, jnp.array([4, 5], jnp.int32),
            jnp.ones((2,), jnp.uint32), region="buf")
    assert check.check_schedule(rec).ok


def test_fixture_lost_update_rmw_next_to_fetch_add():
    rec, t = _rec_tp()
    words = jnp.zeros((8,), jnp.uint32)
    with rec.agent("w0"):
        v = t.read(words, jnp.array([1], jnp.int32), region="ctr")
        t.write(words, jnp.array([1], jnp.int32), v + 1, region="ctr")
    with rec.agent("w1"):
        t.fetch_add(words, jnp.array([1], jnp.int32),
                    jnp.ones((1,), jnp.uint32), region="ctr")
    rep = check.check_schedule(rec, target="fixture-lost-update")
    rules = {v.rule for v in rep.violations}
    assert rules == {"lost-update"}
    blob = " ".join(v.detail for v in rep.violations)
    assert "FETCH_ADD#2" in blob and "WRITE#1" in blob   # verb pair
    assert all(v.where == "ctr" for v in rep.violations)  # region named


def test_fixture_install_without_lock_wave():
    rec, t = _rec_tp()
    rec.declare_locks("T/words", ("T/payload",), lock_bit=LOCK)
    words = jnp.zeros((8,), jnp.uint32)
    pay = jnp.zeros((8, 2), jnp.uint32)
    rec.begin_wave()
    t.cas(words, jnp.array([1, 2], jnp.int32), jnp.zeros((2,), jnp.uint32),
          jnp.full((2,), LOCK | 5, jnp.uint32), region="T/words")
    # row 2 was CAS-acquired this wave; row 3 was not
    t.write(pay, jnp.array([2, 3], jnp.int32),
            jnp.ones((2, 2), jnp.uint32), region="T/payload")
    rep = check.check_schedule(rec, target="fixture-lock")
    assert [v.rule for v in rep.violations] == ["lock-protocol"]
    v = rep.violations[0]
    assert v.where == "T/payload"
    assert "WRITE#1" in v.detail and "rows {3}" in v.detail
    assert "T/words" in v.detail and "wave 1" in v.detail


def test_fixture_stale_pull_beyond_k():
    rec = check.ScheduleRecorder()
    rec.note_pull(region="ps/params", worker="w0", observed_epoch=1,
                  current_epoch=5, staleness=2)
    rec.note_pull(region="ps/params", worker="w1", observed_epoch=4,
                  current_epoch=5, staleness=2)   # within bound: clean
    rep = check.check_schedule(rec, target="fixture-stale")
    assert [v.rule for v in rep.violations] == ["staleness"]
    v = rep.violations[0]
    assert v.where == "ps/params" and "'w0'" in v.detail
    assert "lag 4" in v.detail and "k=2" in v.detail


def test_read_write_race_and_completion_fence():
    rec, t = _rec_tp()
    arr = jnp.zeros((8,), jnp.uint32)
    with rec.agent("reader"):
        t.read(arr, jnp.array([3], jnp.int32), region="r")
    with rec.agent("writer"):
        t.write(arr, jnp.array([3], jnp.int32),
                jnp.ones((1,), jnp.uint32), region="r")
    rep = check.check_schedule(rec)
    assert [v.rule for v in rep.violations] == ["rw-race"]
    # same-agent: the READ's completion fence orders the pair
    rec, t = _rec_tp()
    v = t.read(arr, jnp.array([3], jnp.int32), region="r")
    t.write(arr, jnp.array([3], jnp.int32), v + 1, region="r")
    assert check.check_schedule(rec).ok


# ------------------------- negatives: real protocols record clean --------

@pytest.mark.parametrize("isolation", ["rsi", "2pc"])
def test_real_session_waves_record_clean(isolation):
    rec = check.record_session_waves(isolation)
    assert rec.accesses, "schedule must not be trivially empty"
    assert {a.region for a in rec.accesses} >= {
        "acct/words", "acct/payload", "acct/cids", "oracle/clock"}
    rep = check.check_schedule(rec, target=f"sessions/{isolation}")
    assert rep.ok, rep.render()


def test_real_paramserver_trainer_records_clean():
    rec = check.record_paramserver(staleness=2, steps=3, workers=2)
    assert any(n["kind"] == "ps_pull" for n in rec.notes)
    assert any(a.verb == "FETCH_ADD" and a.region == "ps/epoch"
               for a in rec.accesses)
    rep = check.check_schedule(rec, target="paramserver/trainer")
    assert rep.ok, rep.render()


def test_lock_table_claims_record_clean():
    # claim_locks CAS + release WRITE on the same lock column must be
    # ordered by the CAS completion fence, not flagged as a lost update
    from repro.db import Database
    rec = check.ScheduleRecorder()
    db = Database(LocalTransport(recorder=rec))
    slots = db.create_table("slots", 8, payload_words=1, num_timestamps=16)
    claimed = slots.claim_locks(3, tag=7)
    assert len(claimed) == 3
    for row in claimed:
        slots.release_lock(row)
    rep = check.check_schedule(rec, target="lock-table")
    assert rep.ok, rep.render()


# ----------------------- async verbs + pipelined commit (ISSUE 8) --------
# Seeded-violation fixtures where an overlapped schedule omits a required
# ``Completion.wait()``, plus the shipped schedules recording clean and the
# collective-budget regression in both directions.


def test_fixture_unwaited_route_async_races():
    """Producer fills the route buffer (signaled write), issues the route
    async, and the consumer reads — with the route completion never
    waited, the roundtrip fence never fires and the pair races."""
    rec, t = _rec_tp()
    words = jnp.arange(16, dtype=jnp.uint32)
    buf = jnp.zeros((16,), jnp.uint32)
    with rec.agent("producer"):
        t.write_async(buf, jnp.arange(8, dtype=jnp.int32), words[:8],
                      region="async/buf").wait()
    c = t.route_async({"k": words[:8]}, jnp.zeros((8,), jnp.int32), cap=16)
    assert not c.done                       # MISSING: c.wait()
    with rec.agent("consumer"):
        t.read(buf, jnp.arange(8, dtype=jnp.int32), region="async/buf")
    rep = check.check_schedule(rec, target="fixture-unwaited-route")
    assert [v.rule for v in rep.violations] == ["rw-race"]
    v = rep.violations[0]
    assert v.where == "async/buf"                         # region named
    assert "WRITE#0" in v.detail and "READ#1" in v.detail  # verb pair
    # the SAME schedule with the completion waited records clean
    rec, t = _rec_tp()
    with rec.agent("producer"):
        t.write_async(buf, jnp.arange(8, dtype=jnp.int32), words[:8],
                      region="async/buf").wait()
    t.route_async({"k": words[:8]}, jnp.zeros((8,), jnp.int32),
                  cap=16).wait()
    with rec.agent("consumer"):
        t.read(buf, jnp.arange(8, dtype=jnp.int32), region="async/buf")
    assert check.check_schedule(rec).ok


def test_fixture_unwaited_write_async_pair_ww_races():
    """Two agents post unsignaled WRITEs into overlapping rows of the
    route buffer — the ww-race on the route buffer, verb pair + region
    named; a global flush fence between them orders the pair."""
    rec, t = _rec_tp()
    buf = jnp.zeros((16,), jnp.uint32)
    with rec.agent("a"):
        t.write_async(buf, jnp.array([1, 2], jnp.int32),
                      jnp.ones((2,), jnp.uint32), region="route/buf")
    with rec.agent("b"):
        t.write_async(buf, jnp.array([2, 3], jnp.int32),
                      jnp.ones((2,), jnp.uint32), region="route/buf")
    rep = check.check_schedule(rec, target="fixture-async-ww")
    assert [v.rule for v in rep.violations] == ["ww-race"]
    v = rep.violations[0]
    assert v.where == "route/buf"
    assert "WRITE#0" in v.detail and "WRITE#1" in v.detail
    assert "rows {2}" in v.detail
    # ordered by an explicit global fence between the posts: clean
    rec, t = _rec_tp()
    with rec.agent("a"):
        t.write_async(buf, jnp.array([1, 2], jnp.int32),
                      jnp.ones((2,), jnp.uint32), region="route/buf")
    rec.fence("flush")
    with rec.agent("b"):
        t.write_async(buf, jnp.array([2, 3], jnp.int32),
                      jnp.ones((2,), jnp.uint32), region="route/buf")
    assert check.check_schedule(rec).ok


def test_fixture_install_write_overlapping_next_prepare_read():
    """The pipelined-commit hazard: wave 0's install WRITE is still in
    flight when wave 1's prepare READs the same store rows.  Dropping the
    install completion (the route-roundtrip fence) makes it an rw-race;
    the fence — exactly what ``inst_c.wait()`` fires in
    ``rsi.commit_pipelined`` — restores the order."""
    rec, t = _rec_tp()
    words = jnp.zeros((16,), jnp.uint32)
    with rec.agent("wave0"):
        t.write_async(words, jnp.array([2, 3], jnp.int32),
                      jnp.full((2,), 9, jnp.uint32), region="acct/words")
    with rec.agent("wave1"):                # prepare reads the store rows
        t.read(words, jnp.array([3, 4], jnp.int32), region="acct/words")
    rep = check.check_schedule(rec, target="fixture-pipelined-unfenced")
    assert [v.rule for v in rep.violations] == ["rw-race"]
    v = rep.violations[0]
    assert v.where == "acct/words" and "rows {3}" in v.detail
    assert "WRITE#0" in v.detail and "READ#1" in v.detail
    # with the install completion fence between the waves: clean
    rec, t = _rec_tp()
    with rec.agent("wave0"):
        t.write_async(words, jnp.array([2, 3], jnp.int32),
                      jnp.full((2,), 9, jnp.uint32), region="acct/words")
    rec.fence("route-roundtrip")            # == install Completion.wait()
    with rec.agent("wave1"):
        t.read(words, jnp.array([3, 4], jnp.int32), region="acct/words")
    assert check.check_schedule(rec).ok


def test_shipped_async_schedules_record_clean():
    """Negatives: the double-buffered route and the pipelined RSI commit
    as shipped (all completions waited) record clean schedules."""
    rec = check.record_overlapped_route()
    assert rec.accesses, "schedule must not be trivially empty"
    rep = check.race_overlapped_route()
    assert rep.ok, rep.render()
    rec = check.record_pipelined_commit(waves=2)
    assert any(a.verb == "CAS" for a in rec.accesses)
    rep = check.race_pipelined_commit(waves=2)
    assert rep.ok, rep.render()


def test_overlap_route_lints_same_budget():
    # the double-buffered route's per-chunk exchange lives inside ONE scan
    # body: still one syntactic all_to_all site, same budget as sync
    rep = check.lint_route(3, chunks=4, overlap=True)
    assert rep.ok, rep.render()


def test_pipelined_commit_budget_scales_with_waves():
    """Regression, both directions: the per-wave budget passes the
    pipelined trace, and the former fixed budget of 3 rejects it."""
    assert check.commit_all_to_all_budget(1) == check.COMMIT_ALL_TO_ALL_BUDGET
    assert check.commit_all_to_all_budget(2) == \
        2 * check.COMMIT_ALL_TO_ALL_BUDGET
    rep = check.lint_commit_pipelined(waves=2)
    assert rep.ok, rep.render()
    # the old rule hard-coded 3 sequential sites on one RoutePlan; a
    # 2-wave pipelined trace has 6 and must FAIL under it
    from repro.core import rsi
    tp = check._mesh_transport()
    cfg = rsi.StoreCfg(num_records=16, payload_words=2, num_timestamps=32)
    store = rsi.init_store(cfg)
    wv = [rsi.TxnBatch(write_recs=jnp.zeros((4, 2), jnp.int32),
                       read_cids=jnp.zeros((4, 2), jnp.uint32),
                       new_payload=jnp.zeros((4, 2, 2), jnp.uint32),
                       cid=jnp.arange(4 * i, 4 * i + 4, dtype=jnp.uint32))
          for i in range(2)]
    bad = check.lint_fn(
        lambda s, w: rsi.commit_pipelined(s, w, transport=tp), store, wv,
        rules=[check.CollectiveBudget(
            {"all_to_all": check.COMMIT_ALL_TO_ALL_BUDGET})],
        target="pipelined-under-old-budget")
    assert not bad.ok
    assert "6 all_to_all site(s) traced, budget is 3" in \
        bad.violations[0].detail


def test_async_suite_registered():
    assert "async" in check.SUITES
    assert "async" in check.FIGURE_SUITES["fig8a"]


# --------------------------------------------------- CLI + summaries -----

def test_summarize_schema():
    reports = [check.lint_route(2), check.lint_route(2, response=True)]
    s = check.summarize(reports)
    assert s["ok"] and s["violations"] == []
    assert "collective-budget" in s["rules_run"]
    assert len(s["targets"]) == 2


def test_cli_figure_gate_passes(tmp_path, capsys):
    out = tmp_path / "check.json"
    rc = check.main(["--figure", "fig2", "-q", "--json", str(out)])
    assert rc == 0
    payload = json.loads(out.read_text())
    assert payload["ok"] and payload["violations"] == []
    assert set(payload) >= {"rules_run", "violations", "targets"}
    capsys.readouterr()


def test_cli_exit_codes_reflect_violations(monkeypatch, capsys):
    bad = check.Report("seeded", ("sort-free",),
                       [check.Violation("sort-free", "<top>", "seeded")])
    monkeypatch.setitem(check.SUITES, "verbs", lambda: [bad])
    assert check.main(["--suite", "verbs", "-q"]) == 1
    capsys.readouterr()


# ------------------- group commit + retry economics (ISSUE 9) ------------
# The retry loop's ordering obligation: a loser may re-read the hot row's
# lock|CID word ONLY after the wave that beat it has fully landed (the
# grant exchange / commit-complete global fence).  Plus the grouped-commit
# schedules and the 3K -> 3 collective collapse, pinned in both directions.

def test_fixture_unfenced_retry_reread_races():
    """A retrying session re-reads the hot row while the winner's install
    WRITE is still unsignaled in flight: rw-race, naming the verb pair
    and the region.  The clean twin waits for the commit-complete fence —
    exactly what ``db.Database._refresh_losers`` gets for free by running
    strictly after the grouped wave returns."""
    rec, t = _rec_tp()
    words = jnp.zeros((16,), jnp.uint32)
    with rec.agent("winner"):                # install still in flight
        t.write_async(words, jnp.array([0], jnp.int32),
                      jnp.full((1,), 7, jnp.uint32), region="acct/words")
    with rec.agent("retry"):                 # refresh re-read, no fence
        t.read(words, jnp.array([0], jnp.int32), region="acct/words")
    rep = check.check_schedule(rec, target="fixture-retry-unfenced")
    assert [v.rule for v in rep.violations] == ["rw-race"]
    v = rep.violations[0]
    assert v.where == "acct/words"
    assert "WRITE#0" in v.detail and "READ#1" in v.detail
    # fenced twin: the wave's commit-complete barrier orders the re-read
    rec, t = _rec_tp()
    with rec.agent("winner"):
        t.write_async(words, jnp.array([0], jnp.int32),
                      jnp.full((1,), 7, jnp.uint32), region="acct/words")
    rec.fence("commit-complete")             # the grant-exchange barrier
    with rec.agent("retry"):
        t.read(words, jnp.array([0], jnp.int32), region="acct/words")
    assert check.check_schedule(rec).ok


def test_own_cas_inside_rmw_window_is_not_lost_update():
    """The retry shape: refresh READ -> prepare CAS -> install WRITE, all
    one agent, program-ordered.  The agent's OWN atomic inside its
    READ->WRITE window is not a lost update (the writer holds the CAS
    result); another agent's atomic in the same window stays flagged even
    when fences order it — the read predates it, so the write-back still
    loses its value."""
    rec, t = _rec_tp()
    words = jnp.zeros((8,), jnp.uint32)
    v = t.read(words, jnp.array([0], jnp.int32), region="acct/words")
    t.cas(words, jnp.array([0], jnp.int32), v,
          jnp.full((1,), LOCK, jnp.uint32), region="acct/words")
    t.write(words, jnp.array([0], jnp.int32),
            jnp.full((1,), 5, jnp.uint32), region="acct/words")
    rep = check.check_schedule(rec, target="own-cas-rmw")
    assert rep.ok, rep.render()
    # other-agent atomic, globally fenced into the window: still lost
    rec, t = _rec_tp()
    with rec.agent("rmw"):
        t.read(words, jnp.array([0], jnp.int32), region="acct/words")
    rec.fence("round")
    with rec.agent("bumper"):
        t.fetch_add(words, jnp.array([0], jnp.int32),
                    jnp.ones((1,), jnp.uint32), region="acct/words")
    rec.fence("round")
    with rec.agent("rmw"):
        t.write(words, jnp.array([0], jnp.int32),
                jnp.full((1,), 5, jnp.uint32), region="acct/words")
    rep = check.check_schedule(rec, target="foreign-atomic-rmw")
    assert "lost-update" in [v.rule for v in rep.violations]


def test_grouped_commit_schedule_records_clean():
    rec = check.record_grouped_commit(max_retries=1)
    assert any(a.verb == "READ" and a.region == "acct/words"
               for a in rec.accesses), "retry refresh READ must appear"
    assert sum(a.verb == "CAS" and a.region == "acct/words"
               for a in rec.accesses) >= 2, "initial + retry prepare"
    rep = check.race_grouped_commit(max_retries=1)
    assert rep.ok, rep.render()


def test_grouped_commit_budget_collapse_both_directions():
    """K coalesced sessions stay inside ONE wave's 3-collective budget
    (the 3K -> 3 collapse fig_scale's economics panel measures), and a
    budget of 2 rejects the same trace — the lint is sharp, not vacuous."""
    rep = check.lint_commit_grouped(groups=3)
    assert rep.ok, rep.render()
    from repro.core import rsi
    tp = check._mesh_transport()
    cfg = rsi.StoreCfg(num_records=16, payload_words=2, num_timestamps=64)
    store = rsi.init_store(cfg)
    gs = [rsi.TxnBatch(write_recs=jnp.zeros((2, 2), jnp.int32),
                       read_cids=jnp.zeros((2, 2), jnp.uint32),
                       new_payload=jnp.zeros((2, 2, 2), jnp.uint32),
                       cid=jnp.arange(2 * g, 2 * g + 2, dtype=jnp.uint32))
          for g in range(3)]
    bad = check.lint_fn(
        lambda s, g: rsi.commit_grouped(s, g, transport=tp), store, gs,
        rules=[check.CollectiveBudget({"all_to_all": 2})],
        target="grouped-under-tight-budget")
    assert not bad.ok
    assert "3 all_to_all site(s) traced, budget is 2" in \
        bad.violations[0].detail


def test_scale_suite_registered():
    assert "scale" in check.SUITES
    assert check.FIGURE_SUITES["fig_scale"] == ("scale", "rsi")


# --------------------- two-tier KV paging fixtures (ISSUE 10) ------------
# Seeded-violation twins for the serving engine's two fence obligations:
# the evicted dirty block's write-back must be signaled before the same
# cold rows page back in, and a slot-lock release must be signaled before
# the slot is re-claimed.  Each bad fixture has a clean twin that differs
# ONLY in the fence.


def test_fixture_unfenced_writeback_races_page_in():
    """Evict-write-back vs page-in: a plain (unsignaled) WRITE of the
    evicted block's cold rows, then a READ paging the same block back in.
    Without the completion fence nothing orders the pair — one-sided
    READs bypass the remote CPU, so if the write-back is still in flight
    the page-in returns torn rows."""
    rec, t = _rec_tp()
    cold = jnp.zeros((32,), jnp.uint32)
    rows = jnp.array([8, 9, 10, 11], jnp.int32)     # block 2's rows
    t.write(cold, rows, jnp.ones((4,), jnp.uint32), region="serve_kv",
            tier="cold")                             # write-back, unfenced
    t.read(cold, rows, region="serve_kv", tier="cold")   # page-in
    rep = check.check_schedule(rec, target="fixture-writeback-pagein")
    assert [v.rule for v in rep.violations] == ["rw-race"]
    assert rep.violations[0].where == "serve_kv"


def test_signaled_writeback_fences_page_in():
    # the clean twin: write_async().wait() — the completion IS the fence
    # (this is TieredStore._flush_writebacks's shipped path)
    rec, t = _rec_tp()
    cold = jnp.zeros((32,), jnp.uint32)
    rows = jnp.array([8, 9, 10, 11], jnp.int32)
    t.write_async(cold, rows, jnp.ones((4,), jnp.uint32),
                  region="serve_kv", tier="cold").wait()
    t.read(cold, rows, region="serve_kv", tier="cold")
    assert check.check_schedule(rec).ok


def test_fixture_unsignaled_release_races_reclaim():
    """Slot release vs re-claim: an unsignaled release WRITE of a lock
    word followed by a CAS re-claiming the same word is the lost-update
    shape — the CAS may execute against the pre-release value.  The
    paged engine's swap-out -> swap-in of the same slot does exactly
    this sequence, so ``release_lock(signaled=True)`` exists."""
    from repro.db import Database
    rec = check.ScheduleRecorder()
    db = Database(LocalTransport(recorder=rec))
    slots = db.create_table("slots", 4, payload_words=1, num_timestamps=16)
    (row,) = slots.claim_locks(1, tag=3)
    slots.release_lock(row, signaled=False)          # plain WRITE
    assert slots.claim_locks(1, tag=4) == [row]      # CAS re-claim
    rep = check.check_schedule(rec, target="fixture-release-reclaim")
    assert any(v.rule == "lost-update" for v in rep.violations), \
        rep.render()


def test_signaled_release_fences_reclaim():
    from repro.db import Database
    rec = check.ScheduleRecorder()
    db = Database(LocalTransport(recorder=rec))
    slots = db.create_table("slots", 4, payload_words=1, num_timestamps=16)
    (row,) = slots.claim_locks(1, tag=3)
    slots.release_lock(row, signaled=True)           # async WRITE + wait
    assert slots.claim_locks(1, tag=4) == [row]
    rep = check.check_schedule(rec, target="release-reclaim-signaled")
    assert rep.ok, rep.render()


def test_paged_decode_lints_clean():
    # synthetic page-in/swap-out jaxprs: sort-free, collective-free,
    # fori-free — the pack/unpack path stays pure gather/scatter
    reps = check.lint_paged_decode(2)
    assert len(reps) == 2
    for rep in reps:
        assert rep.ok, rep.render()
    assert {r.target for r in reps} == {"serve/page_in[2b]",
                                        "serve/swap_out[2b]"}


def test_serve_suite_registered():
    assert "serve" in check.SUITES
    assert check.FIGURE_SUITES["fig_serve"] == ("serve", "sim")
