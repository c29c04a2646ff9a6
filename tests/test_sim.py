"""netsim v2 — the discrete-event contention simulator (ISSUE 7).

Contracts guarded here:

  * **anchored to the analytic model** — a lone point call simulates to
    exactly ``profile.t_call``; a single agent at window=1 replays any
    point trace in exactly the analytic serial sum (the uncontended
    limit, load->0); as window->inf throughput converges to the binding
    resource's analytic rate;
  * **work conservation** — simulated makespan >= the analytic lower
    bound (per-port byte work, per-NIC message work, longest flow) for
    arbitrary traces under both link schedulers (hypothesis property);
  * **determinism** — identical trace + seed => bit-identical simulated
    timeline;
  * **contention physics** — fair share splits a port exactly,
    ``contended_profile`` derates bandwidth to bw/(1+load), WRITE
    out-rates SEND at saturation, the window sweep bends;
  * **planning under load** — ``Planner(load=)``/``db.explain(load=)``
    flip the join argmin on a fixed RDMA profile as load rises (the
    fig10 crossover);
  * **trace plumbing** — ``Transport(tracer=)`` records every counted
    verb, ``RoutePlan.window`` survives the pytree round trip, the new
    outstanding/queue-depth counters land in ``stats()``, and the
    windowed route stays clean under ``repro.fabric.check``.
"""
import jax.numpy as jnp
import pytest

from repro.db import Database
from repro.db.planner import Planner
from repro.fabric import LocalTransport, netsim, router, sim

EDR = netsim.get_profile("rdma_edr")
ALL_PROFILES = sorted(netsim.PROFILES)


# ------------------------------------------------- analytic anchoring ----

@pytest.mark.parametrize("pname", ALL_PROFILES)
def test_single_call_is_exactly_t_call(pname):
    p = netsim.get_profile(pname)
    ev = sim.SimEvent(seq=0, verb="write", msgs=4, nbytes=65536,
                      src=0, dst=1)
    res = sim.FabricSim(p, nodes=2).run([ev])
    assert res.makespan == pytest.approx(p.t_call(4, 65536), rel=1e-12)
    assert res.latency[0] == res.makespan


@pytest.mark.parametrize("pname", ALL_PROFILES)
def test_serial_window1_equals_analytic_sum(pname):
    """The uncontended limit: one agent, one call in flight — the
    simulator IS the analytic model, summed."""
    p = netsim.get_profile(pname)
    trace = [sim.SimEvent(seq=i, verb="write", msgs=1 + i % 3,
                          nbytes=1024 * (1 + i % 5), agent="a",
                          src=0, dst=1) for i in range(40)]
    res = sim.FabricSim(p, nodes=2, window=1).run(trace)
    assert res.makespan == pytest.approx(sim.analytic_time(trace, p),
                                         rel=1e-12)


def test_window_inf_converges_to_binding_resource_rate():
    """As window -> inf a point stream saturates at the analytic rate of
    the binding resource (the wire for 4KB WRITEs on EDR)."""
    curve = sim.window_sweep(EDR, verb="write", op_bytes=4096, n_ops=512,
                             windows=(64, 128))
    bound = 1.0 / max(EDR.per_message_s, 4096 / EDR.bandwidth)
    assert curve[128] == pytest.approx(bound, rel=0.05)
    assert curve[128] >= curve[64] * 0.999


# ------------------------------------------------- work conservation ----

def test_makespan_never_beats_lower_bound_property():
    hyp = pytest.importorskip("hypothesis")
    st = pytest.importorskip("hypothesis.strategies")

    @hyp.settings(max_examples=30, deadline=None)
    @hyp.given(seed=st.integers(0, 2**31 - 1), tenants=st.integers(1, 4),
               ops=st.integers(1, 6), window=st.integers(0, 4),
               nodes=st.integers(2, 5),
               op_bytes=st.integers(1, 1 << 16),
               scheduler=st.sampled_from(["fair", "fcfs"]),
               verb=st.sampled_from(["write", "send", "read"]))
    def prop(seed, tenants, ops, window, nodes, op_bytes, scheduler, verb):
        trace = sim.synthetic_load(tenants, ops_per_tenant=ops,
                                   op_bytes=op_bytes, verb=verb,
                                   spread_s=1e-5, seed=seed)
        res = sim.FabricSim(EDR, nodes=nodes, window=window,
                            scheduler=scheduler).run(trace)
        lb = sim.analytic_lower_bound(trace, EDR, nodes=nodes)
        assert res.makespan >= lb * (1 - 1e-9)
        assert len(res.completions) == len(trace)

    prop()


@pytest.mark.parametrize("seed", range(12))
def test_makespan_never_beats_lower_bound_seeded(seed):
    """Stdlib fallback for the hypothesis property above — always runs."""
    import random
    rng = random.Random(seed)
    trace = sim.synthetic_load(rng.randint(1, 4),
                               ops_per_tenant=rng.randint(1, 6),
                               op_bytes=rng.randint(1, 1 << 16),
                               verb=rng.choice(["write", "send", "read"]),
                               spread_s=1e-5, seed=seed)
    nodes = rng.randint(2, 5)
    for scheduler in ("fair", "fcfs"):
        res = sim.FabricSim(EDR, nodes=nodes, window=rng.randint(0, 4),
                            scheduler=scheduler).run(trace)
        lb = sim.analytic_lower_bound(trace, EDR, nodes=nodes)
        assert res.makespan >= lb * (1 - 1e-9)
        assert len(res.completions) == len(trace)


def test_collective_replay_respects_lower_bound():
    trace = [sim.SimEvent(seq=i, verb="route", msgs=16, nbytes=1 << 20,
                          dst=sim.ALL) for i in range(3)]
    res = sim.replay(trace, EDR, nodes=4)
    assert res.makespan >= sim.analytic_lower_bound(trace, EDR, nodes=4)
    # a collective occupies every node's ports: all 4 tx ports billed
    assert sum(1 for k in res.port_bytes if k.startswith("tx")) == 4


# ------------------------------------------------------ determinism ----

def test_identical_trace_and_seed_bit_identical_timeline():
    mk = lambda: sim.synthetic_load(4, ops_per_tenant=8, op_bytes=4096,
                                    spread_s=1e-4, seed=11)
    r1 = sim.FabricSim(EDR, nodes=4, window=2).run(mk())
    r2 = sim.FabricSim(EDR, nodes=4, window=2).run(mk())
    assert r1.timeline == r2.timeline           # bit-identical, not approx
    assert r1.completions == r2.completions
    other = sim.synthetic_load(4, ops_per_tenant=8, op_bytes=4096,
                               spread_s=1e-4, seed=12)
    assert other != mk()                        # the seed is the only RNG


# ------------------------------------------------- contention physics ----

def test_fair_share_splits_the_ingress_port_exactly():
    """Two equal flows into one ingress: each runs at bw/2, so the wire
    stage takes exactly 2B/bw — fluid processor sharing."""
    B = 1 << 20
    trace = [sim.SimEvent(seq=0, verb="write", msgs=1, nbytes=B,
                          agent="a", src=0, dst=2),
             sim.SimEvent(seq=1, verb="write", msgs=1, nbytes=B,
                          agent="b", src=1, dst=2)]
    res = sim.FabricSim(EDR, nodes=3).run(trace)
    expect = EDR.setup_s + EDR.per_message_s + 2 * B / EDR.bandwidth
    assert res.makespan == pytest.approx(expect, rel=1e-9)


def test_fcfs_serializes_where_fair_shares():
    """Same two flows under FCFS: the first-arrived transfer gets the
    full port, so it completes a full wire-time earlier; the total is
    unchanged (both schedulers are work-conserving)."""
    B = 1 << 20
    trace = [sim.SimEvent(seq=0, verb="write", msgs=1, nbytes=B,
                          agent="a", src=0, dst=2),
             sim.SimEvent(seq=1, verb="write", msgs=1, nbytes=B,
                          agent="b", src=1, dst=2)]
    fair = sim.FabricSim(EDR, nodes=3, scheduler="fair").run(trace)
    fcfs = sim.FabricSim(EDR, nodes=3, scheduler="fcfs").run(trace)
    assert fcfs.makespan == pytest.approx(fair.makespan, rel=1e-9)
    assert fcfs.completions[0] < fair.completions[0] * (1 - 1e-6)


def test_window_sweep_saturates_and_write_beats_send():
    write = sim.window_sweep(EDR, verb="write", op_bytes=4096, n_ops=256)
    send = sim.window_sweep(EDR, verb="send", op_bytes=4096, n_ops=256)
    assert max(write.values()) / write[1] > 1.5      # the window pays
    assert write[64] / write[16] < 1.2               # ... then saturates
    assert max(write.values()) > 1.25 * max(send.values())


def test_queue_depth_histogram_counts_waiting_calls():
    trace = [sim.SimEvent(seq=i, verb="write", msgs=1, nbytes=4096,
                          agent="a", src=0, dst=1) for i in range(8)]
    res = sim.FabricSim(EDR, nodes=2, window=1).run(trace)
    # 8 calls arrive at t=0 with one admitted: depths 0..7 each seen once
    assert res.queue_depth_hist == {d: 1 for d in range(8)}
    assert res.peak_outstanding == {"write": 1}


@pytest.mark.parametrize("load", [0, 8, 64])
def test_contended_profile_measures_fair_share_law(load):
    cp = sim.contended_profile(EDR, load)
    if load == 0:
        assert cp is EDR                      # identity, not a copy
    else:
        assert cp.bandwidth == pytest.approx(EDR.bandwidth / (1 + load),
                                             rel=1e-9)
        assert cp.per_message_s == EDR.per_message_s   # NICs are private
        assert cp.name == f"rdma_edr+load{load}"


def test_invalid_scheduler_rejected():
    with pytest.raises(ValueError, match="scheduler"):
        sim.FabricSim(EDR, scheduler="lifo")


# ------------------------------------------------- planning under load ----

def test_planner_argmin_flips_with_load_on_fixed_profile():
    """The fig10 acceptance: at a FIXED RDMA profile the join argmin is a
    function of load — rrj (ships everything through the fused pass) when
    idle, ghj_bloom (ships the reduced fraction) under contention."""
    nr = ns = int(8e6)
    chosen = {L: Planner.chosen(Planner(net="rdma_edr", load=L)
                                .join_alternatives(nr, ns, sel=0.25))
              for L in (0, 8, 64)}
    assert chosen[0] == "rrj"
    assert chosen[8] == "rrj"
    assert chosen[64] == "ghj_bloom"


def test_planner_load_zero_is_isolated_argmin():
    nr = ns = int(8e6)
    a0 = Planner(net="rdma_edr").join_alternatives(nr, ns, sel=0.5)
    al = Planner(net="rdma_edr", load=0).join_alternatives(nr, ns, sel=0.5)
    assert [(a.name, a.cost_s) for a in a0] == \
        [(a.name, a.cost_s) for a in al]


def test_database_explain_load_flip_and_inputs():
    db = Database(net="rdma_edr")
    keys = jnp.arange(1, 1025, dtype=jnp.uint32)
    db.load_table("R", keys, keys)
    db.load_table("S", keys, keys)
    q = db.scan("R").join(db.scan("S").filter(sel=0.25)).aggregate()
    e0 = db.explain(q)
    e64 = db.explain(q, load=64)
    assert e0.inputs["load"] == 0 and e64.inputs["load"] == 64
    assert e0.chosen == "rrj"
    assert e64.chosen == "ghj_bloom"
    # db state untouched by the load-sweep planner
    assert db.planner.load == 0


# ----------------------------------------------------- trace plumbing ----

def test_tracer_records_every_counted_verb_and_replays():
    tracer = sim.EventTracer()
    tp = LocalTransport(tracer=tracer)
    words = jnp.zeros((64,), jnp.uint32)
    idx = jnp.arange(8, dtype=jnp.int32)
    with tracer.agent("w0"):
        tp.write(words, idx, jnp.ones((8,), jnp.uint32))
    tp.read(words, idx)
    tp.route({"k": words[:8]}, jnp.zeros((8,), jnp.int32), cap=8, window=4)
    verbs = [e.verb for e in tracer.events]
    assert verbs == ["write", "read", "route"]
    assert tracer.events[0].agent == "w0"
    assert tracer.events[2].window == 4
    calls = sum(v["calls"] for v in tp.stats().values())
    assert calls == len(tracer.events)          # one event per counted call
    res = sim.replay(tracer.events, "rdma_edr", nodes=4, window=2)
    assert res.makespan >= sim.analytic_lower_bound(tracer.events,
                                                    "rdma_edr", nodes=4)


def test_transport_counters_peak_outstanding_and_queue_hist():
    tp = LocalTransport()
    dest = jnp.zeros((64,), jnp.int32)
    plan = tp.plan_route(dest, cap=64, window=4)
    tp.route({"k": jnp.arange(64, dtype=jnp.uint32)}, plan=plan, chunks=8)
    s = tp.stats()["route"]
    assert s["peak_outstanding"] == 4           # capped by the window
    assert s["queue_hist"] == {"4-7": 1}        # 8 msgs - 4 in flight
    tp.route({"k": jnp.arange(64, dtype=jnp.uint32)}, dest, cap=64)
    s = tp.stats()["route"]
    assert s["peak_outstanding"] == 4           # high-water mark sticks
    assert s["queue_hist"] == {"4-7": 1, "0": 1}


def test_routeplan_window_survives_pytree_and_validates():
    import jax
    plan = router.plan_route(jnp.zeros((8,), jnp.int32), n=1, cap=8,
                             window=5)
    assert plan.window == 5
    leaves, treedef = jax.tree_util.tree_flatten(plan)
    assert jax.tree_util.tree_unflatten(treedef, leaves).window == 5
    with pytest.raises(ValueError, match="window"):
        router.plan_route(jnp.zeros((8,), jnp.int32), n=1, cap=8,
                          window=-2)
    # route() inherits the plan's window; explicit window overrides
    tp = LocalTransport()
    tp.route({"k": jnp.zeros((8,), jnp.uint32)}, plan=plan)
    assert tp.stats()["route"]["peak_outstanding"] == 1   # 1 msg, w=5


def test_check_sim_suite_records_clean():
    from repro.fabric import check
    reports = check.run_suite("sim")
    assert len(reports) == 3
    assert all(r.ok for r in reports), [r.violations for r in reports]


# ------------------------------- async overlap pricing (ISSUE 8) ---------
# Compute events price what the async schedule buys: window=1 serializes
# compute behind the wire (== the synchronous analytic serial sum,
# exactly); window>=2 overlaps them (strictly below the sum iff there is
# compute to hide).


def _verbs_plus_compute_trace(k=6, nbytes=1 << 16, compute_s=2e-5):
    """Alternating wire + pack-compute, one agent — the double-buffered
    route's schedule shape (chunk k+1 packs while chunk k is on the
    wire)."""
    ev = []
    for i in range(k):
        ev.append(sim.SimEvent(seq=len(ev), verb="compute", msgs=0.0,
                               nbytes=0.0, agent="a", src=0,
                               compute_s=compute_s))
        ev.append(sim.SimEvent(seq=len(ev), verb="write", msgs=1,
                               nbytes=nbytes, agent="a", src=0, dst=1))
    return ev


def test_compute_trace_window1_equals_analytic_serial_sum():
    trace = _verbs_plus_compute_trace()
    serial = sim.analytic_time(trace, EDR)
    assert serial > 6 * 2e-5                     # compute IS in the sum
    res = sim.FabricSim(EDR, nodes=2, window=1).run(trace)
    assert res.makespan == pytest.approx(serial, rel=1e-12)
    assert len(res.completions) == len(trace)


def test_compute_trace_window2_strictly_below_serial_sum():
    trace = _verbs_plus_compute_trace()
    serial = sim.analytic_time(trace, EDR)
    res = sim.FabricSim(EDR, nodes=2, window=2).run(trace)
    assert res.makespan < serial * (1 - 1e-6)    # the overlap pays
    assert res.makespan >= sim.analytic_lower_bound(trace, EDR, nodes=2)
    # overlap disabled (window=1) stays exactly the serial sum even
    # without compute; window=2 can only help (work conservation)
    wire_only = [e for e in trace if e.verb != "compute"]
    w1 = sim.FabricSim(EDR, nodes=2, window=1).run(wire_only).makespan
    w2 = sim.FabricSim(EDR, nodes=2, window=2).run(wire_only).makespan
    assert w1 == pytest.approx(sim.analytic_time(wire_only, EDR),
                               rel=1e-12)
    assert w2 <= w1 * (1 + 1e-12)
    # and the compute-bearing trace wins MORE from the window than the
    # wire-only one: the overlap hides the declared compute on top of
    # the setup/wire pipelining
    assert serial - res.makespan > w1 - w2


def test_compute_trace_replay_deterministic():
    trace = _verbs_plus_compute_trace()
    r1 = sim.FabricSim(EDR, nodes=2, window=2).run(trace)
    r2 = sim.FabricSim(EDR, nodes=2, window=2).run(trace)
    assert r1.timeline == r2.timeline
    assert r1.completions == r2.completions


def test_emit_compute_plumbs_through_tracer_and_replay():
    """The recorded-async-trace workflow end to end: a traced transport
    route with pack compute emitted between the verbs replays below the
    synchronous serial sum at window>=2, equal at window=1."""
    tracer = sim.EventTracer()
    tp = LocalTransport(tracer=tracer)
    words = jnp.arange(64, dtype=jnp.uint32)
    with tracer.agent("router"):
        for _ in range(4):
            tracer.emit_compute(3e-5)            # the chunk's pack gather
            tp.route({"k": words}, jnp.zeros((64,), jnp.int32), cap=64)
    ev = tracer.events
    assert [e.verb for e in ev[:2]] == ["compute", "route"]
    assert ev[0].agent == "router" and ev[0].compute_s == 3e-5
    assert ev[0].msgs == 0.0 and ev[0].nbytes == 0.0
    serial = sim.analytic_time(ev, EDR)
    sync = sim.replay(ev, EDR, nodes=2, window=1)
    over = sim.replay(ev, EDR, nodes=2, window=2)
    assert sync.makespan == pytest.approx(serial, rel=1e-12)
    assert over.makespan < sync.makespan * (1 - 1e-6)
    assert len(over.completions) == len(ev)


def test_database_stats_delta_survives_new_counters():
    db = Database(net="rdma_edr")
    keys = jnp.arange(1, 257, dtype=jnp.uint32)
    db.load_table("R", keys, keys)
    db.load_table("S", keys, keys)
    q = db.scan("R").join(db.scan("S").filter(sel=0.5)).aggregate()
    r = db.execute(q, calibrate=True)            # the delta's one reader
    assert r.stats                               # delta computed, no crash
    for verb, s in r.stats.items():
        assert isinstance(s.get("queue_hist", {}), dict)


# --------------------- fig_scale grouped-commit anchors (ISSUE 9) --------
# The synthesized grouped-commit trace (real economics from a counted
# grouped Database commit, re-priced by the simulator) obeys the same two
# laws every hand-built trace does: strictly serialized it IS the analytic
# serial sum, and doubling the workers that split a fixed uncontended
# workload ~halves the simulated wall-clock.


def _econ_trace_serial(workers=2):
    """A real zipf(1.2) grouped-commit trace (retries, backoff computes,
    grant rounds) re-attributed to ONE agent on a node OFF every home
    shard: no loopback events (a loopback skips the wire, which the
    serial analytic sum does not model), one strictly serial issuer."""
    import dataclasses as dc

    from benchmarks import fig_scale
    st, sets, att, tids = fig_scale._run_economics(workers, 1.2, seed=3)
    shards = 2
    off_node = shards                      # one node past the home shards
    trace = fig_scale._commit_trace(sets, att, tids, shards,
                                    [off_node] * workers)
    assert any(e.verb == "compute" for e in trace), "retry backoff missing"
    assert any(e.verb == "read" for e in trace), "refresh READ missing"
    return [dc.replace(e, agent="a") for e in trace], shards + 1


def test_grouped_commit_trace_window1_equals_analytic_serial_sum():
    trace, nodes = _econ_trace_serial()
    serial = sim.analytic_time(trace, EDR)
    res = sim.FabricSim(EDR, nodes=nodes, window=1).run(trace)
    assert res.makespan == pytest.approx(serial, rel=1e-12)
    assert len(res.completions) == len(trace)


def _uncontended_trace(workers, txns_per_worker, shards=8):
    from benchmarks import fig_scale, workloads
    sets = workloads.worker_write_sets(workers, txns_per_worker, 2, 4096,
                                       skew=0.0, seed=11)
    attempts = [[1] * txns_per_worker] * workers
    txn_ids = [list(range(w * txns_per_worker, (w + 1) * txns_per_worker))
               for w in range(workers)]
    placement = [w % shards for w in range(workers)]
    return fig_scale._commit_trace(sets, attempts, txn_ids, shards,
                                   placement)


@pytest.mark.parametrize("pname", ["rdma_edr", "ethernet_1g"])
def test_doubling_workers_halves_uncontended_wallclock(pname):
    # the same 256-txn uniform workload split over 4 vs 8 worker agents
    # on one 8-shard fabric: no contention, so the per-agent verb work
    # halves and the simulated makespan follows
    prof = netsim.get_profile(pname)
    m4 = sim.FabricSim(prof, nodes=8, window=2,
                       windows={"grant": 0}).run(
        _uncontended_trace(4, 64)).makespan
    m8 = sim.FabricSim(prof, nodes=8, window=2,
                       windows={"grant": 0}).run(
        _uncontended_trace(8, 32)).makespan
    assert m8 <= 0.55 * m4, f"{pname}: {m8:.2e} vs {m4:.2e}"
    assert m8 >= 0.25 * m4                 # and not absurdly better


# --------------------------- paged-serving read-storm anchors (ISSUE 10) --
# fig_serve prices KV page-ins by replaying serve traces through this
# simulator.  Two anchors pin that pricing: (1) N concurrent page-in
# READs at window=1 cost exactly the analytic serial sum — the blocking
# (no-prefetch) baseline IS the uncontended analytic limit; (2) at
# KV-block sizes the NIC message pipeline, not bandwidth, is what binds
# on EDR — the paper's Fig 4 small-message regime reopened for serving.


@pytest.mark.parametrize("pname", ["rdma_fdr4x", "rdma_edr"])
def test_read_storm_window1_equals_analytic_serial_sum(pname):
    r = sim.read_storm(pname, n_reads=64, block_bytes=2048, window=1)
    assert r["makespan_s"] == pytest.approx(r["analytic_serial_s"],
                                            rel=1e-12)
    assert r["makespan_s"] >= r["lower_bound_s"] - 1e-15
    assert r["peak_outstanding"] == {"read": 1}


def test_read_storm_msg_rate_binds_on_edr_at_kv_block_sizes():
    # 1 KiB blocks sit below EDR's per_msg*bw crossover (~2017 bytes):
    # per-READ NIC time exceeds wire time, so the storm is message-rate
    # bound and the makespan can never beat the NIC pipeline floor
    r = sim.read_storm("rdma_edr", n_reads=128, block_bytes=1024, window=0)
    assert r["binding"] == "msg_rate"
    assert r["nic_s"] > r["wire_s"]
    assert r["makespan_s"] >= r["nic_s"] - 1e-15


def test_read_storm_window_relaxation_monotone():
    # opening the in-flight window can only help: unbounded <= w=4 <= w=1
    mk = {w: sim.read_storm("rdma_edr", n_reads=64, block_bytes=1024,
                            window=w)["makespan_s"] for w in (1, 4, 0)}
    assert mk[0] <= mk[4] + 1e-15 <= mk[1] + 1e-15


def test_percentile_and_completion_gaps():
    vals = [5.0, 1.0, 3.0, 2.0, 4.0]
    assert sim.percentile(vals, 0.0) == 1.0
    assert sim.percentile(vals, 0.5) == 3.0
    assert sim.percentile(vals, 0.99) == 5.0
    assert sim.percentile(vals, 1.0) == 5.0
    with pytest.raises(ValueError):
        sim.percentile([], 0.5)
    with pytest.raises(ValueError):
        sim.percentile(vals, 1.5)
    # gaps reconstruct the sorted completion times, first gap from t=0
    trace = [sim.SimEvent(seq=i, verb="read", msgs=1, nbytes=4096,
                          agent="a", src=0, dst=1) for i in range(4)]
    res = sim.FabricSim(EDR, nodes=2, window=1).run(trace)
    gaps = sim.completion_gaps(res, range(4))
    assert len(gaps) == 4
    assert all(g > 0 for g in gaps)
    assert sum(gaps) == pytest.approx(res.makespan, rel=1e-12)
