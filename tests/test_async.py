"""Async verbs + double-buffered routes (ISSUE 8 tentpole).

Contracts guarded here:

  * **bit-for-bit parity** — the double-buffered (inversion-gather) route
    reproduces the synchronous scatter route exactly: fields, valid,
    dropped, sent, sent_valid, for arbitrary mixed-dtype pytrees including
    drop / filter / overflow and masked-plan reuse (hypothesis property,
    mirroring ``test_router_packed``'s generators), and including the
    chunked per-chunk scan pipeline via a loopback exchange;
  * **determinism** — two identical async schedules on fresh transports
    produce identical buffers AND identical transport counters (async
    changes the *schedule*, never the bits or the accounting);
  * **Completion semantics** — values are eager, ``wait()`` is idempotent,
    ``done`` flips exactly once, and async verbs count like their sync
    twins;
  * **pipelined RSI commit** — ``rsi.commit_pipelined`` (wave i's install
    overlapping wave i+1's prepare) is bit-identical to K sequential
    ``rsi.commit`` calls, through both the core API and the ``repro.db``
    facade; counters match too;
  * **mesh parity** — sync == overlap == route_async across a 4-device
    mesh (subprocess, per the dry-run isolation rule; marked slow).
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import fabric
from repro.core import rsi
from repro.core.rsi import StoreCfg, TxnBatch
from repro.db import Database
from repro.fabric import Completion, LocalTransport, router


def _mixed_fields(rng, A):
    """Same mixed-dtype request pytree as test_router_packed."""
    return {
        "tag": jnp.asarray(rng.integers(0, 255, (A, 3)), jnp.uint8),
        "key": jnp.asarray(rng.integers(0, 2**31, (A,)), jnp.uint32),
        "val": jnp.asarray(rng.standard_normal((A, 2)), jnp.float32),
        "flag": jnp.asarray(rng.integers(0, 2, (A,)) > 0),
        "pay": jnp.asarray(rng.integers(0, 2**31, (A, 2, 3)), jnp.uint32),
    }


def _assert_results_equal(a, b):
    for name in ("fields", "sent"):
        jax.tree_util.tree_map(
            lambda x, y: np.testing.assert_array_equal(
                np.asarray(x), np.asarray(y)),
            getattr(a, name), getattr(b, name))
    np.testing.assert_array_equal(np.asarray(a.valid), np.asarray(b.valid))
    np.testing.assert_array_equal(np.asarray(a.sent_valid),
                                  np.asarray(b.sent_valid))
    assert int(a.dropped) == int(b.dropped)


def _assert_overlap_parity(fields, dest, n, cap, chunks):
    sync = router.route(fields, dest, n=n, cap=cap)
    over = router.route(fields, dest, n=n, cap=cap, overlap=True)
    _assert_results_equal(over, sync)
    # the chunked per-chunk scan pipeline, exercised without a mesh via a
    # loopback exchange (identity stands in for the paired all_to_all:
    # the restriped chunk reassembly must still be bit-exact)
    ident = lambda x: x                                       # noqa: E731
    sync_x = router.route(fields, dest, n=n, cap=cap, exchange=ident)
    over_x = router.route(fields, dest, n=n, cap=cap, chunks=chunks,
                          exchange=ident, overlap=True)
    _assert_results_equal(over_x, sync_x)


@pytest.mark.parametrize("seed,A,n,cap,chunks", [
    (0, 64, 4, 8, 2),     # overflow + filtered mix, 2-deep pipeline
    (1, 33, 3, 64, 4),    # roomy (no drops), odd sizes
    (2, 128, 1, 16, 4),   # single shard, heavy overflow
    (3, 0, 2, 4, 2),      # empty batch
])
def test_overlap_route_matches_sync(seed, A, n, cap, chunks):
    rng = np.random.default_rng(seed)
    fields = _mixed_fields(rng, A)
    dest = jnp.asarray(rng.integers(-2, n + 2, (A,)), jnp.int32)
    _assert_overlap_parity(fields, dest, n, cap, chunks)


def test_overlap_route_property():
    """Hypothesis: the double-buffered route is bit-for-bit the synchronous
    route for arbitrary mixed-dtype pytrees, drop / filter / overflow
    included, at any legal chunk depth."""
    hyp = pytest.importorskip("hypothesis")
    st = pytest.importorskip("hypothesis.strategies")

    @hyp.settings(max_examples=25, deadline=None)
    @hyp.given(seed=st.integers(0, 2**31 - 1), A=st.integers(0, 96),
               n=st.integers(1, 5), capm=st.integers(1, 8),
               chunks=st.integers(1, 4))
    def prop(seed, A, n, capm, chunks):
        rng = np.random.default_rng(seed)
        cap = capm * chunks                    # cap % chunks == 0 by build
        _assert_overlap_parity(
            _mixed_fields(rng, A),
            jnp.asarray(rng.integers(-2, n + 2, (A,)), jnp.int32),
            n, cap, chunks)

    prop()


def test_overlap_route_masked_plan_parity():
    """Plan reuse + mask under overlap: the inversion respects the masked
    slot map (masked requests leave their slots empty, overflow drops are
    recounted against the mask) exactly like the scatter path."""
    rng = np.random.default_rng(5)
    A, n, cap = 48, 3, 8                       # overflow guaranteed
    fields = _mixed_fields(rng, A)
    dest = jnp.asarray(rng.integers(0, n, (A,)), jnp.int32)
    mask = jnp.asarray(rng.integers(0, 2, (A,)) > 0)
    plan = fabric.plan_route(dest, n=n, cap=cap)
    sync = fabric.route(fields, plan=plan, mask=mask)
    over = router.route(fields, plan=plan, mask=mask, overlap=True)
    _assert_results_equal(over, sync)


# ------------------------------------------------------------ transport --


def test_route_async_counters_match_sync():
    """route_async counts exactly like route — same msgs, same packed
    bytes, same queue histogram; the only difference is *when* the
    roundtrip fence fires (at wait, not at issue)."""
    rng = np.random.default_rng(11)
    A = 32
    fields = {"k": jnp.asarray(rng.integers(0, 99, (A,)), jnp.uint32)}
    dest = jnp.asarray(rng.integers(0, 1, (A,)), jnp.int32)
    tp_s, tp_a = LocalTransport(), LocalTransport()
    sync = tp_s.route(fields, dest, cap=A)
    comp = tp_a.route_async(fields, dest, cap=A)
    assert isinstance(comp, Completion) and not comp.done
    _assert_results_equal(comp.wait(), sync)
    assert comp.done
    assert tp_a.stats() == tp_s.stats()


def test_async_schedule_is_deterministic():
    """Two identical async schedules on fresh transports -> identical
    buffers and identical counters."""
    def run_once():
        tp = LocalTransport()
        rng = np.random.default_rng(17)
        words = jnp.asarray(rng.integers(0, 2**31, (64,)), jnp.uint32)
        wc = tp.write_async(words, jnp.arange(8),
                            jnp.arange(100, 108, dtype=jnp.uint32))
        words = wc.wait()
        rc = tp.read_async(words, jnp.arange(16))
        fields = {"k": jnp.asarray(rng.integers(0, 99, (32,)), jnp.uint32),
                  "v": jnp.asarray(rng.standard_normal((32,)), jnp.float32)}
        dest = jnp.asarray(rng.integers(0, 1, (32,)), jnp.int32)
        route_c = tp.route_async(fields, dest, cap=32)
        got = rc.wait()
        res = route_c.wait()
        return words, got, res, tp.stats()

    w1, g1, r1, s1 = run_once()
    w2, g2, r2, s2 = run_once()
    np.testing.assert_array_equal(np.asarray(w1), np.asarray(w2))
    np.testing.assert_array_equal(np.asarray(g1), np.asarray(g2))
    _assert_results_equal(r1, r2)
    assert s1 == s2


def test_completion_semantics():
    """Value eager, wait idempotent, deferred edge fires exactly once."""
    fired = []
    c = Completion(42, on_wait=lambda: fired.append(1))
    assert not c.done and fired == []
    assert c.wait() == 42 and c.done
    assert c.wait() == 42                      # idempotent
    assert fired == [1]                        # the fence fired ONCE
    assert Completion("x").wait() == "x"       # no deferred edge is fine

    tp = LocalTransport()
    words = jnp.zeros((8,), jnp.uint32)
    wc = tp.write_async(words, jnp.array([3]), jnp.array([7], jnp.uint32))
    sync = LocalTransport().write(words, jnp.array([3]),
                                  jnp.array([7], jnp.uint32))
    np.testing.assert_array_equal(np.asarray(wc.wait()), np.asarray(sync))
    rc = tp.read_async(wc.wait(), jnp.array([3]))
    assert int(rc.wait()[0]) == 7


# ------------------------------------------------------- pipelined commit --


def _seed_store(nrec=32):
    cfg = StoreCfg(num_records=nrec, payload_words=2, num_timestamps=64)
    store = rsi.init_store(cfg)
    store["words"] = jnp.full((nrec,), 1, jnp.uint32)
    store["cids"] = store["cids"].at[:, 0].set(1)
    return store


def _mk_wave(rng, nrec, T, W, cid0):
    recs = np.stack([rng.permutation(nrec)[:W] for _ in range(T)])
    return TxnBatch(
        write_recs=jnp.asarray(recs, jnp.int32),
        read_cids=jnp.full((T, W), 1, jnp.uint32),
        new_payload=jnp.asarray(rng.randint(1, 99, (T, W, 2)), jnp.uint32),
        cid=jnp.asarray(cid0 + np.arange(T), jnp.uint32))


def test_commit_pipelined_matches_sequential_commits():
    """K dependent waves through the pipelined schedule == K sequential
    commits: same txn_ok, same store words/payload/cids/bitvec, same
    counters (the overlap moves the fences, not the traffic)."""
    nrec, T, W, K = 32, 6, 2, 3
    rng = np.random.RandomState(0)
    waves = [_mk_wave(rng, nrec, T, W, 10 + 20 * i) for i in range(K)]

    store_seq = _seed_store(nrec)
    tp_seq = LocalTransport()
    ok_seq = []
    for w in waves:
        ok_w, store_seq = rsi.commit(store_seq, w, transport=tp_seq)
        ok_seq.append(ok_w)

    tp_pipe = LocalTransport()
    ok_pipe, store_pipe = rsi.commit_pipelined(
        _seed_store(nrec), waves, transport=tp_pipe)

    assert len(ok_pipe) == K
    for i in range(K):
        np.testing.assert_array_equal(np.asarray(ok_pipe[i]),
                                      np.asarray(ok_seq[i]), err_msg=f"w{i}")
    for leaf in ("words", "payload", "cids", "bitvec"):
        np.testing.assert_array_equal(np.asarray(store_pipe[leaf]),
                                      np.asarray(store_seq[leaf]),
                                      err_msg=leaf)
    assert tp_pipe.stats() == tp_seq.stats()


def test_db_commit_pipelined_matches_sequential():
    """The facade: Database.commit_pipelined over session waves ==
    sequential db.commit per wave (masks + final store bit-identical)."""
    nrec, K = 24, 3

    def build(db):
        tab = db.create_table("acct", nrec, payload_words=2,
                              num_timestamps=64)
        tab.seed(np.arange(nrec))
        rng = np.random.RandomState(3)
        waves = []
        for _ in range(K):
            wave = []
            for _ in range(4):
                s = db.session().begin()
                recs = rng.permutation(nrec)[:2]
                pay = rng.randint(1, 99, (2, 2)).astype(np.uint32)
                s.put("acct", recs, pay, read_cids=np.ones(2, np.uint32))
                wave.append(s)
            waves.append(wave)
        return tab, waves

    db_a = Database()
    tab_a, waves_a = build(db_a)
    masks_a = db_a.commit_pipelined(waves_a)

    db_b = Database()
    tab_b, waves_b = build(db_b)
    masks_b = [db_b.commit(w) for w in waves_b]

    assert len(masks_a) == K
    for i in range(K):
        np.testing.assert_array_equal(np.asarray(masks_a[i]),
                                      np.asarray(masks_b[i]),
                                      err_msg=f"wave {i}")
    for leaf in ("words", "payload", "cids", "bitvec"):
        np.testing.assert_array_equal(np.asarray(tab_a.store[leaf]),
                                      np.asarray(tab_b.store[leaf]),
                                      err_msg=leaf)


# ------------------------------------------------------------ mesh parity --

_MESH_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from repro.fabric import MeshTransport
from repro.launch.mesh import make_mesh

mesh = make_mesh((4,), ("data",))
n, cap, A = 4, 12, 40
rng = np.random.default_rng(0)
fields = {"k": jnp.asarray(rng.integers(0, 99, (A,)), jnp.uint32),
          "v": jnp.asarray(rng.standard_normal((A, 2)), jnp.float32)}
dest = jnp.asarray(rng.integers(-1, n + 1, (A,)), jnp.int32)

def run(tp, mode):
    def body(k, v, d):
        f = {"k": k, "v": v}
        if mode == "sync":
            r = tp.route(f, d, cap=cap, chunks=3)
        elif mode == "overlap":
            r = tp.route(f, d, cap=cap, chunks=3, overlap=True)
        else:
            r = tp.route_async(f, d, cap=cap, chunks=3).wait()
        return (r.fields["k"], r.fields["v"], r.valid,
                r.dropped.reshape(1), r.sent["k"], r.sent_valid)
    out = jax.jit(lambda k, v, d: tp.run(
        body, (k, v, d),
        out_reps=(False, False, False, True, False, False)))(
            fields["k"], fields["v"], dest)
    return [np.asarray(x) for x in out]

outs, stats = [], []
for mode in ("sync", "overlap", "async"):
    tp = MeshTransport(mesh, "data")
    outs.append(run(tp, mode))
    stats.append(tp.stats())
for got in outs[1:]:
    for a, b in zip(got, outs[0]):
        np.testing.assert_array_equal(a, b)
assert stats[0] == stats[1] == stats[2], stats
print("ASYNC_MESH_PARITY_OK")
"""


@pytest.mark.slow
def test_mesh_route_async_parity():
    """sync == overlap == route_async on a 4-device mesh, buffers and
    counters both (subprocess so the main session keeps 1 device)."""
    env = dict(os.environ,
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", _MESH_SCRIPT], env=env,
                       capture_output=True, text=True, timeout=900)
    assert "ASYNC_MESH_PARITY_OK" in r.stdout, \
        f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-3000:]}"
