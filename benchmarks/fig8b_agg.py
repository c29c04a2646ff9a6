"""Fig 8(b): aggregation — hierarchical Dist-AGG vs RDMA-AGG over distinct
group counts (paper sweeps 1 -> 64M; scaled to the CPU container), through
the ``repro.db`` facade.

Claim reproduced: Dist-AGG cost grows with #groups (the global union is
#nodes x #groups rows); RDMA-AGG stays flat-ish (owner-partitioned
post-aggregation).  The query is ONE logical plan —
``scan(T).aggregate(groups=G)`` — the planner reports its §5.3 cost-model
choice per group count and per network profile (``--profile all`` sweeps
the axis: Dist-AGG is the only feasible scheme off-RDMA, RDMA-AGG takes
over on the one-sided profiles as the distinct count grows), then the
figure's grid forces both schemes.  Also times the Pallas grouped_agg
pre-aggregation kernel.  Device work runs once; counted traffic is
re-priced per profile (docs/netsim.md).
"""
import time

import jax
import jax.numpy as jnp

from benchmarks import timing
from repro.db import AGG_VARIANTS, Database
from repro.fabric import MeshTransport, netsim
from repro.kernels import ops
from repro.launch.mesh import make_mesh

DEFAULT_PROFILES = ("rdma_fdr4x",)


def run(profiles=None, timed=False):
    profiles = tuple(profiles) if profiles else DEFAULT_PROFILES
    rows = []
    measured = {}
    n = 1 << 20
    mesh = make_mesh((jax.device_count(),)[:1], ("data",))
    db = Database(transport=MeshTransport(mesh, "data",
                                          profile=profiles[0]))
    key = jax.random.PRNGKey(0)
    keys = jax.random.randint(key, (n,), 0, 1 << 30).astype(jnp.uint32)
    vals = jnp.ones((n,), jnp.uint32)
    db.load_table("T", keys, vals)
    crossover = {}
    for groups in (1, 64, 4096, 262_144):
        q = db.scan("T").aggregate(groups=groups)
        winners = {}
        for pname in profiles:
            ex = db.explain(q, profile=pname)
            winners[pname] = ex.chosen
            costs = "|".join(f"{a.name}:{a.cost_s * 1e3:.1f}ms"
                             for a in ex.alternatives)
            rows.append((f"fig8b/groups{groups}_planner_{pname}", 0.0,
                         f"picked_{ex.chosen}_{costs}"))
        crossover[groups] = winners
        if len(profiles) > 1:
            rows.append((f"fig8b/groups{groups}_crossover", 0.0,
                         "|".join(f"{p}:{w}" for p, w in winners.items())))
        for name in AGG_VARIANTS:               # forced grid for the figure
            if timed:
                s = timing.device_time_s(
                    lambda v=name: db.execute(q, force_variant=v).value,
                    warmup=1, k=3)
                measured[f"fig8b/groups{groups}_{name}"] = s
                us = s * 1e6
            else:
                r = db.execute(q, force_variant=name)   # warm/compile
                t0 = time.perf_counter()
                for _ in range(3):
                    r = db.execute(q, force_variant=name)
                us = (time.perf_counter() - t0) / 3 * 1e6
            rows.append((f"fig8b/groups{groups}_{name}", us, ""))
    if len(profiles) > 1:
        # the agg-scheme argmin must differ somewhere along the axis
        assert any(len(set(w.values())) > 1 for w in crossover.values()), \
            f"no agg-scheme crossover across {profiles}"
    # kernel-level pre-aggregation (phase 1 hot loop)
    slot = (keys % jnp.uint32(2048)).astype(jnp.int32)
    fv = vals.astype(jnp.float32)
    r = ops.grouped_agg(slot, fv, 2048)
    t0 = time.perf_counter()
    r = ops.grouped_agg(slot, fv, 2048)
    jax.block_until_ready(r)
    rows.append(("fig8b/kernel_grouped_agg_1M_2048slots",
                 (time.perf_counter() - t0) * 1e6, ""))
    if timed:
        measured["fig8b/kernel_grouped_agg_1M_2048slots"] = \
            timing.device_time_s(lambda: ops.grouped_agg(slot, fv, 2048))
    stats = db.fabric_stats()
    modeled = {p: netsim.get_profile(p).modeled_time(stats)
               for p in profiles}
    extras = {"fabric": stats, "modeled_wire_s": modeled,
              "crossover": {str(g): w for g, w in crossover.items()}}
    if timed:
        extras["measured_s"] = measured
    return rows, extras
