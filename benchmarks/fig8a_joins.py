"""Fig 8(a): measured join runtimes — GHJ / GHJ+Red / RDMA-GHJ / RRJ over
bloom selectivities {0.25, 0.5, 0.75, 1.0}, through the ``repro.db`` facade.

|R|=|S| scaled to 2^20/node for the CPU container (paper: 128M/node).  The
query is ONE logical plan — ``scan(R).join(scan(S).filter(sel)).aggregate``
— the network-aware planner picks a variant from the §5.1 cost model (one
row per selectivity AND per network profile reports its choice: sweeping
``--profile all`` reproduces the paper's crossover, e.g. GHJ+Red on 1GbE
vs RRJ on EDR), and the figure's grid then *forces* each of the four
variants so the measured deltas isolate the shuffle/partition strategy, as
in the paper.  Device work runs ONCE — the counted traffic is re-priced
per profile (``modeled_wire_s``), since counters are workload and profiles
are the axis (docs/netsim.md).
"""
import time

import jax
import jax.numpy as jnp

from benchmarks import timing
from repro.db import JOIN_VARIANTS, Database
from repro.fabric import MeshTransport, netsim, sim
from repro.launch.mesh import make_mesh

DEFAULT_PROFILES = ("rdma_fdr4x",)       # the paper's measured cluster
ROUTE_CHUNKS = 4                         # double-buffer depth for the A/B


def _shuffle_route_bench(transport, n_rows: int = 1 << 20, *,
                         overlap: bool = False, chunks: int = 1):
    """The shuffle microbench: ONE routed exchange of a (keys, vals)
    relation — the exact motion `_route_by_key` performs inside every
    distributed join, isolated from the local join work.  This is the
    packed-wire + sort-free hot path the PR's speedup acceptance pins;
    ``overlap=True`` takes the double-buffered path (chunk k+1 packs
    while chunk k is on the wire, docs/fabric.md)."""
    key = jax.random.PRNGKey(0)
    ks = jax.random.randint(key, (n_rows,), 0, 1 << 30).astype(jnp.uint32)
    vs = jnp.ones((n_rows,), jnp.uint32)
    n = transport.n
    cap = 2 * n_rows // n

    def body(k, v):
        dest = (k % jnp.uint32(n)).astype(jnp.int32)
        res = transport.route({"k": k, "v": v}, dest, cap=cap,
                              chunks=chunks, overlap=overlap)
        return res.fields["k"], res.fields["v"], res.dropped

    f = jax.jit(lambda k, v: transport.run(
        body, (k, v), out_reps=(False, False, True)))
    return timing.device_time_s(f, ks, vs)


def _route_replay_pricing(profile_name: str, n: int, cap: int,
                          chunks: int, row_words: int = 3):
    """Price the double-buffered route *schedule* on the netsim v2
    simulator: per chunk, a pack (compute) event then the chunk's wire
    event, with the pack sized to the chunk's wire time (the balanced
    point where double-buffering can hide it all).  ``window=1`` replays
    the synchronous schedule and lands exactly on the analytic serial
    sum; ``window=2`` is the double-buffered one — the gap is the modeled
    value of the overlap on this profile (docs/netsim.md)."""
    p = netsim.get_profile(profile_name)
    nbytes = n * cap * 4 * row_words / chunks
    wire_s = p.t_call(n, nbytes)
    tr = sim.EventTracer()
    for _ in range(chunks):
        tr.emit_compute(wire_s)
        tr.emit("route", n, nbytes, collective=True)
    serial = sim.analytic_time(tr.events, p)
    nodes = max(2, n)
    sync = sim.replay(tr.events, p, nodes=nodes, window=1).makespan
    over = sim.replay(tr.events, p, nodes=nodes, window=2).makespan
    return {"profile": p.name, "chunks": chunks, "serial_s": serial,
            "window1_s": sync, "window2_s": over,
            "overlap_speedup": serial / over if over else 0.0}


def _rel(sel: float, n: int = 1 << 20):
    key = jax.random.PRNGKey(int(sel * 100))
    rk = jax.random.permutation(key, jnp.arange(1, n + 1, dtype=jnp.uint32))
    rv = rk
    # S keys: a `sel` fraction has matches in R, rest miss (keys > n)
    hits = jax.random.randint(jax.random.fold_in(key, 1), (n,), 1, n + 1)
    miss = jax.random.randint(jax.random.fold_in(key, 2), (n,), n + 1, 2 * n)
    take = jax.random.uniform(jax.random.fold_in(key, 3), (n,)) < sel
    sk = jnp.where(take, hits, miss).astype(jnp.uint32)
    return rk, rv, sk, jnp.ones((n,), jnp.uint32)


def run(profiles=None, timed=False):
    profiles = tuple(profiles) if profiles else DEFAULT_PROFILES
    rows = []
    measured = {}
    n = 1 << 20
    mesh = make_mesh((jax.device_count(),)[:1], ("data",))
    db = Database(transport=MeshTransport(mesh, "data",
                                          profile=profiles[0]))
    db.create_table("R", n, payload_words=1, partitioning="hash")
    db.create_table("S", n, payload_words=1, partitioning="hash")
    crossover = {}
    for sel in (0.25, 0.5, 0.75, 1.0):
        rk, rv, sk, sv = _rel(sel)
        db.table("R").load(rk, rv)
        db.table("S").load(sk, sv)
        q = db.scan("R").join(db.scan("S").filter(sel=sel)).aggregate()
        winners = {}
        for pname in profiles:
            ex = db.explain(q, profile=pname)
            winners[pname] = ex.chosen
            costs = "|".join(f"{a.name}:{a.cost_s * 1e3:.1f}ms"
                             for a in ex.alternatives)
            rows.append((f"fig8a/sel{sel}_planner_{pname}", 0.0,
                         f"picked_{ex.chosen}_{costs}"))
        crossover[sel] = winners
        if len(profiles) > 1:
            rows.append((f"fig8a/sel{sel}_crossover", 0.0,
                         "|".join(f"{p}:{w}" for p, w in winners.items())))
        base = None
        for name in JOIN_VARIANTS:              # forced grid for the figure
            if timed:
                s = timing.device_time_s(
                    lambda v=name: db.execute(q, force_variant=v).value,
                    warmup=1, k=3)
                measured[f"fig8a/sel{sel}_{name}"] = s
                us = s * 1e6
            else:
                r = db.execute(q, force_variant=name)   # warm/compile
                t0 = time.perf_counter()
                for _ in range(3):
                    r = db.execute(q, force_variant=name)
                us = (time.perf_counter() - t0) / 3 * 1e6
            if name == "ghj":
                base = us
            rows.append((f"fig8a/sel{sel}_{name}", us,
                         f"{base/us:.2f}x_vs_GHJ" if base else ""))
    if len(profiles) > 1:
        # acceptance: the join-variant argmin must differ on >= 2 profiles
        assert any(len(set(w.values())) > 1 for w in crossover.values()), \
            f"no join-variant crossover across {profiles}"
    # the shuffle microbench: the routed exchange alone, A/B'd on the
    # async overlap axis (PR acceptance: overlap_on strictly beats
    # overlap_off).  "on" is the double-buffered inversion-gather route
    # (chunk k+1 packs while chunk k is on the wire), "off" the
    # synchronous monolithic route.  FRESH transports each, so the
    # figure's modeled_wire/fabric counters keep pricing only the join
    # queries' traffic
    route_s = _shuffle_route_bench(MeshTransport(mesh, "data"),
                                   overlap=True, chunks=ROUTE_CHUNKS)
    route_off_s = _shuffle_route_bench(MeshTransport(mesh, "data"))
    rows.append(("fig8a/shuffle_route_1M", route_s * 1e6,
                 f"overlap_on_chunks{ROUTE_CHUNKS}"))
    rows.append(("fig8a/shuffle_route_1M_overlap_off", route_off_s * 1e6,
                 f"{route_off_s / route_s:.2f}x_slower_sync"))
    measured["fig8a/shuffle_route_1M"] = route_s
    measured["fig8a/shuffle_route_1M_overlap_off"] = route_off_s
    extras_overlap = {
        "on_s": route_s, "off_s": route_off_s,
        "chunks": ROUTE_CHUNKS,
        "replay": _route_replay_pricing(
            profiles[0], max(2, mesh.size), 2 * n // max(2, mesh.size),
            ROUTE_CHUNKS),
    }
    if timed:
        assert route_s < route_off_s, (
            f"overlap_on ({route_s * 1e3:.2f} ms) not faster than "
            f"overlap_off ({route_off_s * 1e3:.2f} ms)")
    stats = db.fabric_stats()
    modeled = {p: netsim.get_profile(p).modeled_time(stats)
               for p in profiles}
    for pname, s in modeled.items():
        rows.append((f"fig8a/modeled_wire_{pname}", s * 1e6,
                     "all_counted_traffic"))
    extras = {"fabric": stats, "modeled_wire_s": modeled,
              "overlap": extras_overlap,
              "crossover": {str(s): w for s, w in crossover.items()}}
    if timed:
        extras["measured_s"] = measured
    return rows, extras
