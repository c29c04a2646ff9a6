"""Chip smoke test: the NAM-DB main path end to end on a TPU.

  python chip_smoke.py            # one chip: OLTP, OLAP and route phases
  python chip_smoke.py --chips 4  # four chips: the sharded NAM phase only

Every phase drives the entry points a user calls — ``Database``,
``Session``, ``db.commit``, ``db.execute`` — at the paper's own shapes and
checks what comes out against a plain numpy reference on the same seeded
data:

  oltp   §4.3 checkout: a 1M x 1 KB products table, waves of 512 sessions
         that each read and update 3 products and insert 4 rows, committed
         through RSI;
  olap   §5.4 join + aggregation over 16M-tuple relations (the paper's
         128M tuples per node cut 8x to fit the smoke's time), through the
         planner's choice and every forced variant;
  route  one 1M-row packed batch through the Pallas partitioner and the
         jnp scatter, which must agree bit for bit;
  mesh   (--chips 4) the same waves and one join + aggregate on a
         ``MeshTransport`` over four chips, equal bit for bit to a
         ``LocalTransport`` run, with every table region sharded across
         the four devices between calls.

Earlier lines report each phase; their wall times are smoke timing, not a
metric.  The last line is one JSON object naming the device.  Without a
TPU, or when any check fails, the script exits non-zero without it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
if os.path.join(ROOT, "src") not in sys.path:
    sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs.paper_nam import OLTP  # noqa: E402
from repro.db import AGG_VARIANTS, JOIN_VARIANTS, Database  # noqa: E402
from repro.fabric import MeshTransport, router  # noqa: E402
from repro.launch.cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402

PRODUCTS = OLTP.num_products                  # 1,000,000 records
PAYLOAD_WORDS = OLTP.record_bytes // 4        # 1 KB = 256 u32 words
WAVES, SESSIONS = 4, 512
OLAP_CUT = 8
TUPLES = (1 << 27) // OLAP_CUT                # §5.4's 128M per node, cut 8x
GROUPS = 4096
ROUTE_ROWS = 1 << 20
SEED = 0


def base_payload(xp, recs, words: int):
    """Load-epoch payload of product records: a hash of (record, word),
    computable the same way on the device (jnp) and in the reference
    (numpy)."""
    r = xp.asarray(recs).astype(xp.uint32)[:, None] * xp.uint32(0x9E3779B1)
    return r ^ xp.arange(words, dtype=xp.uint32)[None, :]


def oltp_phase(db, *, products=PRODUCTS, waves=WAVES, sessions=SESSIONS,
               payload_words=PAYLOAD_WORDS, seed=SEED, on_wave=None):
    """§4.3 checkout waves committed through RSI, checked against numpy:
    the committed set equals a sequential replay of the wave's lock
    arbitration, no two committed txns of a wave share a record, every
    acknowledged write reads back, and rows only aborted txns wrote are
    unchanged.  Returns the table, per-wave outcomes and commit ids."""
    upd, ins = OLTP.updates_per_txn, OLTP.inserts_per_txn
    width = upd + ins
    records = products + waves * sessions * ins   # headroom for inserts
    table = db.create_table("products", records,
                            payload_words=payload_words)
    table.seed(np.arange(products),
               base_payload(jnp, jnp.arange(products), payload_words))
    rng = np.random.default_rng(seed)
    word = np.zeros(records, np.uint32)   # reference lock|CID words
    word[:products] = 1                   # cid 1 = load epoch
    acked = {}                            # record -> (payload, cid)
    lost = set()                          # records aborted txns wrote
    oks, cids = [], []
    for wave in range(waves):
        prods = rng.integers(0, products, (sessions, upd))
        inserts = (products + wave * sessions * ins
                   + np.arange(sessions * ins).reshape(sessions, ins))
        _, rids, _ = db.snapshot_read(table, prods)
        rids = np.asarray(rids, np.uint32)
        pay = rng.integers(0, 1 << 32, (sessions, width, payload_words),
                           dtype=np.uint64).astype(np.uint32)
        wave_sessions = []
        for i in range(sessions):
            s = db.session().begin()
            s.put(table, prods[i], pay[i, :upd], read_cids=rids[i])
            s.put(table, inserts[i], pay[i, upd:])
            wave_sessions.append(s)
        ok = db.commit(wave_sessions)
        cid = np.asarray([s.cid for s in wave_sessions], np.uint32)
        recs = np.concatenate([prods, inserts], axis=1)
        expect = np.concatenate([rids, np.zeros((sessions, ins),
                                                np.uint32)], axis=1)
        # reference: requests in (session, slot) order; the first whose
        # read CID matches the record's word locks it, and a txn commits
        # iff it locked every record it writes
        won = np.zeros(recs.shape, bool)
        locked = set()
        for i in range(sessions):
            for k in range(width):
                r = int(recs[i, k])
                if r not in locked and expect[i, k] == word[r]:
                    won[i, k] = True
                    locked.add(r)
        want = won.all(axis=1)
        if not np.array_equal(ok, want):
            raise AssertionError(
                f"wave {wave}: committed set differs from the reference "
                f"({int(ok.sum())} vs {int(want.sum())} commits)")
        mine = recs[ok].reshape(-1)
        if np.unique(mine).size != mine.size:
            raise AssertionError(f"wave {wave}: two committed txns wrote "
                                 "one record")
        for i in np.nonzero(ok)[0]:
            for k in range(width):
                word[recs[i, k]] = cid[i]
                acked[int(recs[i, k])] = (pay[i, k], cid[i])
        for i in np.nonzero(~ok)[0]:
            lost.update(int(r) for r in recs[i])
        oks.append(ok)
        cids.append(cid)
        if on_wave is not None:
            on_wave(table)
    committed = int(sum(o.sum() for o in oks))
    if committed == 0:
        raise AssertionError("no transaction committed")
    recs = np.asarray(sorted(acked), np.int32)
    got, got_cid, vis = (np.asarray(x) for x in
                         db.snapshot_read(table, recs))
    if not (vis.all() and np.array_equal(
            got, np.stack([acked[r][0] for r in recs]))
            and np.array_equal(got_cid, [acked[r][1] for r in recs])):
        raise AssertionError("an acknowledged write does not read back")
    untouched = np.asarray(sorted(lost - set(acked)), np.int32)
    if untouched.size:
        got, got_cid, vis = (np.asarray(x) for x in
                             db.snapshot_read(table, untouched))
        base = untouched < products
        if not (np.array_equal(got[base], base_payload(
                np, untouched[base], payload_words))
                and (got_cid[base] == 1).all() and vis[base].all()
                and not vis[~base].any()):
            raise AssertionError("an aborted txn changed a record")
    return {"table": table, "ok": oks, "cid": cids,
            "txns": waves * sessions, "committed": committed,
            "aborted": waves * sessions - committed,
            "reads_checked": int(recs.size + untouched.size)}


def _relations(tuples: int, seed: int):
    """§5.4 relations, made on the device: R has unique keys 1..N; half of
    S's keys hit R and half miss.  Value columns are ones, so a join
    aggregate is the exact match count."""
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    rk = jax.random.permutation(k[0], jnp.arange(1, tuples + 1,
                                                 dtype=jnp.uint32))
    hit = jax.random.randint(k[1], (tuples,), 1, tuples + 1)
    miss = jax.random.randint(k[2], (tuples,), tuples + 1, 2 * tuples)
    take = jax.random.uniform(k[3], (tuples,)) < 0.5
    sk = jnp.where(take, hit, miss).astype(jnp.uint32)
    tk = jax.random.randint(k[4], (tuples,), 0, 1 << 30).astype(jnp.uint32)
    return rk, sk, tk, jnp.ones((tuples,), jnp.uint32)


def olap_phase(db, *, tuples=TUPLES, groups=GROUPS, seed=SEED,
               join_variants=JOIN_VARIANTS, agg_variants=AGG_VARIANTS):
    """§5.4 join + aggregate through the planner's choice and each forced
    variant, and a group aggregate under each scheme, each compared
    exactly with numpy on the same data.  Returns the results by name."""
    rk, sk, tk, ones = _relations(tuples, seed)
    db.load_table("R", rk, ones)
    db.load_table("S", sk, ones)
    want = int(np.count_nonzero(np.isin(np.asarray(sk), np.asarray(rk))))
    q = db.scan("R").join(db.scan("S").filter(sel=0.5)).aggregate()
    out = {}
    for variant in (None,) + tuple(join_variants):
        res = db.execute(q, force_variant=variant)
        got = int(res.value)
        name = f"join/{variant or 'planned:' + res.variant}"
        if got != want:
            raise AssertionError(f"{name}: {got} matches, numpy says {want}")
        out[name] = got
    if agg_variants:
        db.load_table("T", tk, ones)
        want_g = np.bincount(np.asarray(tk) % groups, minlength=groups)
        g = db.scan("T").aggregate(groups=groups)
        for variant in agg_variants:
            got = np.asarray(db.execute(g, force_variant=variant).value)
            if not np.array_equal(got, want_g):
                raise AssertionError(f"agg/{variant}: group sums differ "
                                     "from numpy")
            out[f"agg/{variant}"] = got
    return out


def route_phase(*, rows=ROUTE_ROWS, seed=SEED):
    """One packed batch (u32, f32 and sub-word fields; filtered requests;
    capacity drops) routed through the Pallas partitioner, the jnp
    scatter and the platform's default, all bit-identical and equal to a
    numpy binning.  Returns the default backend's lowered program text."""
    n = 4
    cap = rows // 5               # ~7/32 of the rows want each bucket
    k = jax.random.split(jax.random.PRNGKey(seed), 3)
    fields = {"key": jax.random.bits(k[0], (rows,), jnp.uint32),
              "val": jax.random.normal(k[1], (rows, 2), jnp.float32),
              "tag": jax.random.bits(k[2], (rows, 3), jnp.uint8)}
    key = np.asarray(fields["key"])
    dest = np.where(key % 8 == 7, -1, key % 4).astype(np.int32)

    def run(backend):
        def f(fields, dest):
            r = router.route(fields, dest, n=n, cap=cap, backend=backend)
            return r.sent, r.sent_valid, r.dropped
        return jax.jit(f)(fields, jnp.asarray(dest))

    res = {b: run(b) for b in ("pallas", "jnp", None)}
    flat = {b: jax.tree_util.tree_leaves(r) for b, r in res.items()}
    for b in ("jnp", None):
        if not all(np.asarray(x).tobytes() == np.asarray(y).tobytes()
                   for x, y in zip(flat["pallas"], flat[b])):
            raise AssertionError(f"pallas and {b} routes differ")
    sent, sent_valid, sent_dropped = res["pallas"]
    valid = np.asarray(sent_valid).reshape(n, cap)
    got = np.asarray(sent["key"]).reshape(n, cap)
    for b in range(n):
        src = np.nonzero(dest == b)[0][:cap]
        if not (np.array_equal(got[b, :src.size], key[src])
                and valid[b, :src.size].all()
                and not valid[b, src.size:].any()):
            raise AssertionError(f"bucket {b} differs from numpy binning")
    dropped = int(np.sum(np.maximum(np.bincount(
        dest[dest >= 0], minlength=n) - cap, 0)))
    if int(sent_dropped) != dropped:
        raise AssertionError("dropped count differs from numpy")
    return jax.jit(lambda f, d: router.route(f, d, n=n, cap=cap).sent
                   ).lower(fields, jnp.asarray(dest)).as_text()


def _home_sharded(table, n: int):
    for name, arr in {**table.store, "keys": table.keys}.items():
        shard = arr.sharding.shard_shape(arr.shape)
        if len(arr.sharding.device_set) != n or shard[0] * n != arr.shape[0]:
            raise AssertionError(f"{table.schema.name}/{name} is not "
                                 f"sharded over {n} devices: {arr.sharding}")


def mesh_phase(mesh, *, products=PRODUCTS, waves=WAVES, sessions=SESSIONS,
               payload_words=PAYLOAD_WORDS, tuples=TUPLES, seed=SEED):
    """The sharded NAM: the OLTP waves and one join + aggregate through
    ``MeshTransport`` over every device of ``mesh``, equal bit for bit to
    a ``LocalTransport`` run of the same seeded work (and to numpy)."""
    n = mesh.size
    kw = dict(products=products, waves=waves, sessions=sessions,
              payload_words=payload_words, seed=seed)
    local = oltp_phase(Database(), **kw)
    sharded = oltp_phase(Database(transport=MeshTransport(mesh, "data")),
                         on_wave=lambda t: _home_sharded(t, n), **kw)
    for key in ("ok", "cid"):
        if not all(np.array_equal(a, b)
                   for a, b in zip(local[key], sharded[key])):
            raise AssertionError(f"mesh and local {key} differ")
    for leaf, arr in sharded["table"].store.items():
        if not np.array_equal(np.asarray(arr),
                              np.asarray(local["table"].store[leaf])):
            raise AssertionError(f"mesh and local store {leaf!r} differ")
    del local
    olap = dict(tuples=tuples, seed=seed, join_variants=(),
                agg_variants=())
    want = olap_phase(Database(), **olap)
    db = Database(transport=MeshTransport(mesh, "data"))
    got = olap_phase(db, **olap)
    for name in ("R", "S"):
        _home_sharded(db.table(name), n)
    if list(got.values()) != list(want.values()):
        raise AssertionError(f"mesh join {got} != local join {want}")
    return {"txns": sharded["txns"], "committed": sharded["committed"],
            "aborted": sharded["aborted"], "join": got}


def _peak_gb(device) -> str:
    stats = device.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else f"{peak / 1e9:.3f} GB"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded phase over four chips")
    args = ap.parse_args(argv)
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU; JAX sees {dev.platform}",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1
    cache = enable_compile_cache(ROOT)
    print(f"device: {dev.device_kind} x{len(devices)}; compile cache "
          f"{cache}; route scatter on this platform: pallas kernel")

    def phase(name, fn, summary):
        t0 = time.perf_counter()
        out = fn()
        print(f"phase {name}: ok, {summary(out)}; smoke timing "
              f"{time.perf_counter() - t0:.1f} s (compile included, not a "
              f"metric); peak device memory {_peak_gb(dev)}", flush=True)

    if args.chips == 4:
        mesh = make_mesh((4,), ("data",), devices=devices[:4])
        phase("mesh", lambda: mesh_phase(mesh), lambda r: (
            f"MeshTransport over 4 chips == LocalTransport bit for bit; "
            f"{r['committed']}/{r['txns']} committed; join {r['join']}; "
            f"table regions sharded over 4 devices"))
    else:
        phase("oltp", lambda: oltp_phase(Database()), lambda r: (
            f"{r['committed']}/{r['txns']} committed, {r['aborted']} "
            f"aborted, {r['reads_checked']} records read back == numpy"))
        print(f"olap: {TUPLES:,} tuples per relation (the paper's 128M "
              f"= {1 << 27:,} per node, cut {OLAP_CUT}x)")
        phase("olap", lambda: olap_phase(Database()), lambda r: (
            "; ".join(f"{k}=={'numpy' if k.startswith('agg') else v}"
                      for k, v in r.items())))

        def route():
            text = route_phase()
            if "tpu_custom_call" not in text:
                raise AssertionError("the default route took no Pallas "
                                     "kernel on this TPU")
            return text
        phase("route", route, lambda _: (
            f"{ROUTE_ROWS:,}-row batch: pallas == jnp == default bit for "
            f"bit == numpy binning; default lowers to the Mosaic kernel"))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
