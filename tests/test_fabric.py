"""Fabric layer: router capacity/overflow/skew/chunking semantics, the
fetch_add verb, transport parity (Local vs 1-device Mesh RSI commit), verb
message/byte accounting, and the NamPool region factory."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import fabric
from repro.core import rsi, shuffle, workqueue
from repro.core.rsi import StoreCfg, TxnBatch
from repro.fabric import LocalTransport, MeshTransport
from repro.launch.mesh import make_mesh


# --------------------------------------------------------------- router ---

def test_route_overflow_drops_counted():
    # 6 requests for shard 0 but cap=2: 2 delivered, 4 dropped (and counted)
    dest = jnp.zeros((6,), jnp.int32)
    vals = jnp.arange(1, 7, dtype=jnp.int32)
    res = fabric.route({"v": vals}, dest, n=2, cap=2)
    assert int(res.dropped) == 4
    assert int(res.valid.sum()) == 2
    # first-in-order requests survive (stable radix)
    np.testing.assert_array_equal(np.array(res.fields["v"]), [1, 2, 0, 0])


def test_route_filtered_not_counted_as_dropped():
    # dest >= n means intentionally filtered — not an overflow drop
    dest = jnp.array([0, 2, 2, 2], jnp.int32)
    res = fabric.route({"v": jnp.arange(4)}, dest, n=2, cap=4)
    assert int(res.dropped) == 0
    assert int(res.valid.sum()) == 1


def test_route_negative_dest_filtered_not_wrapped():
    # negative dest must be filtered, not wrap into another shard's buffer
    dest = jnp.array([-1, 0], jnp.int32)
    vals = jnp.array([5, 6], jnp.int32)
    res = fabric.route({"v": vals}, dest, n=2, cap=4)
    np.testing.assert_array_equal(np.array(res.fields["v"]),
                                  [6, 0, 0, 0, 0, 0, 0, 0])
    assert int(res.valid.sum()) == 1 and int(res.dropped) == 0


def test_route_empty_batch():
    dest = jnp.zeros((0,), jnp.int32)
    res = fabric.route({"v": jnp.zeros((0,), jnp.int32)}, dest, n=2, cap=3)
    assert res.fields["v"].shape == (6,)
    assert int(res.valid.sum()) == 0 and int(res.dropped) == 0


def test_route_all_to_one_shard_skew():
    # all requests target shard 1; shard 0's buffer stays empty
    dest = jnp.ones((4,), jnp.int32)
    vals = jnp.array([7, 8, 9, 10], jnp.int32)
    res = fabric.route({"v": vals}, dest, n=2, cap=4)
    np.testing.assert_array_equal(np.array(res.fields["v"]),
                                  [0, 0, 0, 0, 7, 8, 9, 10])
    np.testing.assert_array_equal(np.array(res.valid),
                                  [0, 0, 0, 0, 1, 1, 1, 1])
    assert int(res.dropped) == 0


@pytest.mark.parametrize("transport_kind", ["local", "mesh"])
def test_route_chunks_equivalence(transport_kind):
    # chunks>1 must deliver exactly the same buffers as chunks=1
    if transport_kind == "local":
        tp = LocalTransport()
    else:
        tp = MeshTransport(make_mesh((1,), ("data",)), "data")
    key = jax.random.PRNGKey(0)
    vals = jax.random.randint(key, (64,), 0, 1000).astype(jnp.int32)
    dest = jax.random.randint(jax.random.fold_in(key, 1), (64,), 0,
                              tp.n + 1).astype(jnp.int32)  # incl. filtered

    def go(chunks):
        def body(v, d):
            res = tp.route({"v": v}, d, cap=128, chunks=chunks)
            return res.fields["v"], res.valid, res.dropped
        return tp.run(body, (vals, dest), out_reps=(False, False, True))

    v1, m1, d1 = go(1)
    v4, m4, d4 = go(4)
    np.testing.assert_array_equal(np.array(v1), np.array(v4))
    np.testing.assert_array_equal(np.array(m1), np.array(m4))
    assert int(d1) == int(d4) == 0


def test_join_surfaces_capacity_drops():
    # skew past capacity_factor must be visible via return_stats, and a
    # roomy capacity must report zero drops with the exact aggregate
    rk = jnp.arange(1, 257, dtype=jnp.uint32)
    rv = rk
    sk = jnp.arange(1, 257, dtype=jnp.uint32)
    sv = jnp.ones((256,), jnp.uint32)
    tp = LocalTransport()
    tight = shuffle.make_distributed_join(tp, "ghj", capacity_factor=0.5,
                                          return_stats=True)
    agg_t, dropped_t = tight(rk, rv, sk, sv)
    assert int(dropped_t) == 256  # half of each relation overflowed
    roomy = shuffle.make_distributed_join(tp, "ghj", return_stats=True)
    agg_r, dropped_r = roomy(rk, rv, sk, sv)
    assert int(dropped_r) == 0
    assert int(agg_r) == int(np.sum(np.arange(1, 257, dtype=np.uint64)))
    assert int(agg_t) < int(agg_r)  # silent undercount made loud


# ------------------------------------------------------------ fetch_add ---

def test_fetch_add_sequential_semantics():
    words = jnp.array([10, 100], jnp.uint32)
    idx = jnp.array([0, 0, 1, 0], jnp.int32)
    delta = jnp.array([1, 2, 5, 3], jnp.uint32)
    fetched, new = fabric.fetch_add(words, idx, delta)
    # word 0 sees 10, 10+1, 10+1+2 in request order; word 1 sees 100
    np.testing.assert_array_equal(np.array(fetched), [10, 11, 100, 13])
    np.testing.assert_array_equal(np.array(new), [16, 105])


def test_fetch_add_priority_reorders():
    words = jnp.array([10], jnp.uint32)
    idx = jnp.zeros((3,), jnp.int32)
    delta = jnp.array([1, 2, 3], jnp.uint32)
    prio = jnp.array([2, 1, 0], jnp.int32)     # request 2 goes first
    fetched, new = fabric.fetch_add(words, idx, delta, priority=prio)
    np.testing.assert_array_equal(np.array(fetched), [15, 13, 10])
    assert int(new[0]) == 16


def test_fetch_add_oob_is_noop():
    words = jnp.array([7], jnp.uint32)
    fetched, new = fabric.fetch_add(words, jnp.array([-1, 0], jnp.int32),
                                    jnp.array([5, 5], jnp.uint32))
    np.testing.assert_array_equal(np.array(fetched), [0, 7])
    assert int(new[0]) == 12


def test_workqueue_ticket_counter():
    head = jnp.zeros((1,), jnp.uint32)
    amounts = jnp.array([4, 2, 8], jnp.uint32)
    starts, head = workqueue.claim_ticket_ranges(head, amounts)
    # disjoint contiguous ranges in worker order
    np.testing.assert_array_equal(np.array(starts), [0, 4, 6])
    assert int(head[0]) == 14


# ------------------------------------------------------------ transport ---

def _mk_batch(seed=0, T=16, W=2, nrec=32):
    rng = np.random.RandomState(seed)
    recs = np.stack([rng.permutation(nrec)[:W] for _ in range(T)])
    return TxnBatch(
        write_recs=jnp.asarray(recs, jnp.int32),
        read_cids=jnp.full((T, W), 1, jnp.uint32),
        new_payload=jnp.asarray(rng.randint(1, 99, (T, W, 2)), jnp.uint32),
        cid=jnp.asarray(2 * np.arange(T) + 70, jnp.uint32))


def test_commit_local_vs_mesh_parity():
    """Satellite: LocalTransport and a 1-device MeshTransport must produce
    identical (txn_ok, store) for the same TxnBatch."""
    nrec = 32
    cfg = StoreCfg(num_records=nrec, payload_words=2, version_slots=1,
                   num_timestamps=64)
    store = rsi.init_store(cfg)
    store["words"] = jnp.full((nrec,), 1, jnp.uint32)
    store["cids"] = store["cids"].at[:, 0].set(1)
    txns = _mk_batch()
    ok_l, st_l = rsi.commit(store, txns, transport=LocalTransport())
    mesh = make_mesh((1,), ("data",))
    ok_m, st_m = rsi.commit(store, txns,
                            transport=MeshTransport(mesh, "data"))
    np.testing.assert_array_equal(np.array(ok_l), np.array(ok_m))
    for k in st_l:
        np.testing.assert_array_equal(np.array(st_l[k]), np.array(st_m[k]),
                                      err_msg=k)


def test_transport_counts_messages_and_bytes():
    nrec = 16
    cfg = StoreCfg(num_records=nrec, payload_words=2, num_timestamps=64)
    store = rsi.init_store(cfg)
    store["words"] = jnp.full((nrec,), 1, jnp.uint32)
    store["cids"] = store["cids"].at[:, 0].set(1)
    tp = LocalTransport()
    rsi.commit(store, _mk_batch(T=8, W=2, nrec=nrec), transport=tp)
    s = tp.stats()
    T, W = 8, 2
    assert s["cas"]["msgs"] == T * W and s["cas"]["bytes"] == T * W * 8
    assert s["write"]["msgs"] == T * W
    assert s["route"]["calls"] == 2 and s["route"]["bytes"] > 0
    tp.reset_stats()
    assert tp.stats() == {}


def test_verb_read_counts():
    tp = LocalTransport()
    region = jnp.zeros((8, 4), jnp.float32)
    out = tp.read(region, jnp.array([1, 2, -1], jnp.int32))
    assert out.shape == (3, 4) and float(out[2].sum()) == 0.0
    s = tp.stats()["read"]
    assert s["msgs"] == 3 and s["bytes"] == 3 * 16


# -------------------------------------------------------------- NamPool ---

def test_nampool_region_factory():
    pool = fabric.NamPool()
    r = pool.alloc("words", (64,), jnp.uint32)
    pool.alloc("payload", (64, 4), jnp.uint32, logical_axes=("record", None))
    assert r.name == "words"
    z = pool.zeros()
    assert z["payload"].shape == (64, 4)
    assert pool.specs()["words"].dtype == jnp.uint32
    with pytest.raises(KeyError):
        pool.alloc("words", (8,), jnp.uint32)
