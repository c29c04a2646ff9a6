"""The program's span log (``repro.obs``): nesting, bounds, self time,
compile records, the spans on a profiler's host plane, the facade's sync
count per wave, and the names and scopes of the jitted programs."""
import gc
import glob
import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.db import Database

SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "src")


def _mine(lo, prefix="t."):
    return [r for r in obs.spans(lo) if r.name.startswith(prefix)]


def test_spans_nest_and_carry_parent_and_ids():
    lo = time.perf_counter_ns()
    with obs.span("t.outer", wave=7):
        with obs.span("t.inner"):
            pass
        with obs.span("t.other", attempt=1):
            pass
    inner, other, outer = _mine(lo)
    assert [r.name for r in (inner, other, outer)] == [
        "t.inner", "t.other", "t.outer"]
    assert outer.parent is None and outer.ids == {"wave": 7}
    assert inner.parent == other.parent == ("t.outer", outer.start_ns)
    assert inner.ids == {"wave": 7}                  # inherited
    assert other.ids == {"wave": 7, "attempt": 1}
    assert outer.start_ns <= inner.start_ns <= inner.end_ns \
        <= other.start_ns <= other.end_ns <= outer.end_ns


def test_a_span_is_logged_when_its_body_raises():
    lo = time.perf_counter_ns()
    with pytest.raises(KeyError):
        with obs.span("t.raises"):
            raise KeyError("x")
    with obs.span("t.after"):
        pass
    raised, after = _mine(lo)
    assert raised.name == "t.raises" and after.parent is None


def test_the_log_is_bounded():
    lo = time.perf_counter_ns()
    for i in range(obs.MAXLEN + 10):
        with obs.span("t.bound", i=i):
            pass
    kept = obs.spans()
    assert len(kept) == obs.MAXLEN
    mine = [r.ids["i"] for r in kept if r.name == "t.bound"
            and r.start_ns >= lo]
    assert mine == list(range(10, obs.MAXLEN + 10))   # oldest dropped


def test_logged_records_leave_the_garbage_collectors_tracking():
    """A full log must not lengthen full collections: each entry is untracked
    at the first young collection after it is logged."""
    with obs.span("t.gc", wave=3):
        with obs.span("t.gc.child"):
            pass
    gc.collect(0)
    assert not any(gc.is_tracked(e) for e in list(obs._log)[-2:])
    child, parent = _mine(0, "t.gc")
    assert child.ids == parent.ids == {"wave": 3}


def test_summary_self_time_is_duration_minus_children():
    lo = time.perf_counter_ns()
    with obs.span("t.parent"):
        time.sleep(0.002)
        for _ in range(2):
            with obs.span("t.child"):
                with obs.span("t.grandchild"):
                    time.sleep(0.001)
    hi = time.perf_counter_ns()
    recs = {n: [r for r in obs.spans(lo, hi) if r.name == n]
            for n in ("t.parent", "t.child", "t.grandchild")}
    dur = {n: sum(r.end_ns - r.start_ns for r in rs) / 1e6
           for n, rs in recs.items()}
    s = obs.summary(lo, hi)
    assert s["t.parent"]["count"] == 1 and s["t.child"]["count"] == 2
    assert s["t.parent"]["total_ms"] == pytest.approx(dur["t.parent"])
    assert s["t.parent"]["self_ms"] == pytest.approx(
        dur["t.parent"] - dur["t.child"])
    assert s["t.child"]["self_ms"] == pytest.approx(
        dur["t.child"] - dur["t.grandchild"])
    assert s["t.grandchild"]["self_ms"] == pytest.approx(dur["t.grandchild"])
    assert s["t.parent"]["self_ms"] >= 2.0


def test_a_fresh_jit_logs_compiles_on_its_first_call_only():
    f = jax.jit(lambda x: x * 3 + 1)
    x = jnp.arange(5.0)
    x.block_until_ready()
    lo = time.perf_counter_ns()
    with obs.span("t.call"):
        f(x).block_until_ready()
    mid = time.perf_counter_ns()
    f(x).block_until_ready()
    hi = time.perf_counter_ns()
    first = [r for r in obs.spans(lo, mid) if r.name == obs.COMPILE]
    assert {(r.ids["phase"], r.ids["fun"]) for r in first} >= {
        ("trace", "<lambda>"), ("compile", "jit(<lambda>)")}
    assert all(r.parent[0] == "t.call" for r in first)
    assert not [r for r in obs.spans(mid, hi) if r.name == obs.COMPILE]


# ------------------------------------------------------- checkout wave --

def _checkout_db(transport=None):
    db = Database(transport=transport)
    t = db.create_table("p", 4096, payload_words=4)
    t.seed(np.arange(1024))
    return db, t


def _wave(db, t, k, w):
    """One checkout wave as the benchmark's generator runs it: a batched
    snapshot read, k sessions of 3 validated updates and 4 inserts, one
    commit.  Returns the records logged while it ran."""
    lo = time.perf_counter_ns()
    _, rids, _ = db.snapshot_read(t, (np.arange(3 * k) % 1024).reshape(k, 3))
    rids = np.asarray(rids)
    sessions = []
    for i in range(k):
        s = db.session().begin()
        s.put(t, (np.arange(3) + 3 * i) % 1024, np.ones((3, 4), np.uint32),
              read_cids=rids[i])
        s.put(t, 1024 + (w * k + i) * 4 + np.arange(4),
              np.ones((4, 4), np.uint32))
        sessions.append(s)
    assert db.commit(sessions).all()
    return obs.spans(lo, time.perf_counter_ns())


def _syncs(records):
    return sum(r.name.startswith("namdb.sync.") for r in records)


def test_a_profile_holds_the_facade_spans_on_the_host_plane(tmp_path):
    db, t = _checkout_db()
    _wave(db, t, 4, 0)                        # compiles outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        _wave(db, t, 4, 1)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    names = {e.name for p in data.planes if p.name.startswith("/host:")
             for ln in p.lines for e in ln.events}
    assert {"namdb.snapshot_read", "namdb.sync.oracle", "namdb.commit",
            "namdb.commit.pack", "namdb.sync.claim",
            "namdb.sync.outcome"} <= names


@pytest.mark.parametrize("k", [1, 16, 64])
def test_a_wave_of_k_sessions_syncs_k_plus_3_times(k):
    """k oracle reads in ``Session.begin``, each tagged with its session,
    one for the snapshot read, the CID claim and the outcome wait: no
    other device-to-host sync."""
    db, t = _checkout_db()
    _wave(db, t, k, 0)
    recs = _wave(db, t, k, 1)
    assert _syncs(recs) == k + 3
    by = {n: sum(r.name == n for r in recs) for n in (
        "namdb.sync.oracle", "namdb.sync.claim", "namdb.sync.outcome",
        "namdb.snapshot_read", "namdb.commit", "namdb.commit.pack")}
    assert by == {"namdb.sync.oracle": k + 1, "namdb.sync.claim": 1,
                  "namdb.sync.outcome": 1, "namdb.snapshot_read": 1,
                  "namdb.commit": 1, "namdb.commit.pack": 1}
    begins = [r.ids["session"] for r in recs
              if r.name == "namdb.sync.oracle" and "session" in r.ids]
    assert len(set(begins)) == k              # one per session
    wave = {r.ids.get("wave") for r in recs
            if r.name in ("namdb.commit", "namdb.commit.pack",
                          "namdb.sync.claim", "namdb.sync.outcome")}
    assert wave == {1}                        # one wave, one id
    assert not [r for r in recs if r.name == obs.COMPILE]


_MESH = r"""
import os, sys, json
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, os.environ["TEST_DIR"])
import numpy as np
from repro.fabric import MeshTransport
from repro.launch.mesh import make_mesh
import test_obs as T
db, t = T._checkout_db(MeshTransport(make_mesh((4,), ("data",)), "data"))
out = {}
for w, k in enumerate((4, 16, 64)):
    T._wave(db, t, k, 2 * w)
    out[k] = T._syncs(T._wave(db, t, k, 2 * w + 1))
print("SYNCS", json.dumps(out))
"""


@pytest.fixture(scope="module")
def mesh_syncs():
    env = dict(os.environ, PYTHONPATH=SRC,
               TEST_DIR=os.path.dirname(os.path.abspath(__file__)))
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", _MESH], env=env,
                       capture_output=True, text=True, timeout=600)
    line = [x for x in r.stdout.splitlines() if x.startswith("SYNCS ")]
    assert line, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-3000:]}"
    return {int(k): v for k, v in json.loads(line[0][6:]).items()}


@pytest.mark.parametrize("k", [4, 16, 64])
def test_a_sharded_wave_syncs_k_plus_3_times(mesh_syncs, k):
    """On 4 shards the commit's bitvector completion and ``place`` add no
    sync (a wave there holds a multiple of 4 sessions, one share per
    shard)."""
    assert mesh_syncs[k] == k + 3


# --------------------------------------------------- program names --

def test_the_commit_program_keeps_its_name_and_carries_rsi_scopes():
    db, t = _checkout_db()
    s = db.session().begin()
    s.put(t, np.arange(3), np.ones((3, 4), np.uint32))
    (txns,), _ = db._pack_txns([t], [s])
    text = db._jit_commit("rsi", 1, "p/").lower(
        t.store, txns, None).as_text(debug_info=True)
    assert "module @jit__lambda" in text
    for scope in ("rsi.prepare", "rsi.grant", "rsi.install", "rsi.bitvec",
                  "fabric.route"):
        assert scope in text, scope


@pytest.mark.parametrize("kind,variant,scopes", [
    ("join", "rrj", ("join.radix", "join.route", "join.sort",
                     "join.probe", "fabric.route")),
    ("join", "ghj", ("join.radix", "join.route", "join.sort",
                     "join.probe")),
    ("agg", "dist_agg", ("agg.local",)),
    ("agg", "rdma_agg", ("agg.local", "agg.route", "agg.merge",
                         "fabric.route")),
])
def test_join_and_aggregation_programs_are_named_by_variant(kind, variant,
                                                            scopes):
    db = Database()
    keys = jnp.arange(1, 257, dtype=jnp.uint32)
    rel = (keys, keys)
    if kind == "join":
        f, args = db._jit_join(variant, 2.0), rel + rel
    else:
        f, args = db._jit_agg(variant, 64), rel
    text = f.lower(*args).as_text(debug_info=True)
    assert f"module @jit_{kind}_{variant}" in text
    for scope in scopes:
        assert scope in text, scope
