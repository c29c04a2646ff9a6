"""Multi-device parity tests (8 virtual CPU devices via subprocess so the
main test session keeps 1 device, per the dry-run isolation rule):

  - MoE RRJ shard_map dispatch == reference loop-over-experts
  - RSI commit over MeshTransport == local commit
  - distributed joins/aggregation across 4 shards == 1-shard ground truth
  - reduced-config train_step lowers+compiles on a (2, 4) mesh
"""
import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.slow

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_mesh

mode = os.environ["MD_MODE"]

if mode == "moe":
    from repro.configs import get_config, reduce_config
    from repro.models import moe as M
    from repro.sharding import make_policy, set_policy
    import dataclasses
    cfg = reduce_config(get_config("deepseek-v2-236b"))
    mcfg = dataclasses.replace(cfg.moe, capacity_factor=8.0)  # no drops
    mesh = make_mesh((2, 4), ("data", "model"))
    key = jax.random.PRNGKey(0)
    mk_key = jax.random.fold_in(key, 1)
    E, D, F = mcfg.num_experts, cfg.d_model, mcfg.d_ff
    p = {"router": jax.random.normal(key, (D, E)) * 0.1,
         "wi": jax.random.normal(mk_key, (E, D, 2 * F)) * 0.05,
         "wo": jax.random.normal(jax.random.fold_in(key, 2), (E, F, D)) * 0.05}
    x = jax.random.normal(jax.random.fold_in(key, 3), (4, 8, D),
                          jnp.float32)
    want = M._moe_reference(cfg, mcfg, p, x)
    pol = make_policy(mesh, shape_kind="train")
    with mesh, set_policy(pol):
        got = jax.jit(lambda x, r, wi, wo: M._moe_rrj(
            cfg, mcfg, {"router": r, "wi": wi, "wo": wo}, x))(
            x, p["router"], p["wi"], p["wo"])
        got_dec = jax.jit(lambda x, r, wi, wo: M._moe_replicated(
            cfg, mcfg, {"router": r, "wi": wi, "wo": wo}, x))(
            x, p["router"], p["wi"], p["wo"])
    np.testing.assert_allclose(np.array(got), np.array(want), atol=2e-2,
                               rtol=2e-2)
    np.testing.assert_allclose(np.array(got_dec), np.array(want), atol=2e-2,
                               rtol=2e-2)
    print("MOE_PARITY_OK")

elif mode == "rsi":
    from repro.core import rsi
    from repro.core.rsi import StoreCfg, TxnBatch
    from repro.db import Database
    from repro.fabric import MeshTransport
    nrec, nsh = 32, 8
    mesh = make_mesh((nsh,), ("data",))
    cfg = StoreCfg(num_records=nrec, payload_words=2, version_slots=1,
                   num_timestamps=64)
    store = rsi.init_store(cfg)
    store["words"] = jnp.full((nrec,), 1, jnp.uint32)
    store["cids"] = store["cids"].at[:, 0].set(1)
    rng = np.random.RandomState(0)
    T = 16  # txns (2 clients per shard)
    recs = np.stack([rng.permutation(nrec)[:2] for _ in range(T)])
    pay = rng.randint(1, 99, (T, 2, 2)).astype(np.uint32)
    txns = TxnBatch(
        write_recs=jnp.asarray(recs, jnp.int32),
        read_cids=jnp.full((T, 2), 1, jnp.uint32),
        new_payload=jnp.asarray(pay),
        cid=jnp.asarray(2 + np.arange(T), jnp.uint32))
    ok_local, st_local = rsi.commit(store, txns)
    # sharded NAM deployment through the repro.db facade: a wave of
    # sessions is one routed commit; the oracle assigns the same cids
    with mesh:
        db = Database(transport=MeshTransport(mesh, "data"))
        tab = db.create_table("t", nrec, payload_words=2, num_timestamps=64)
        tab.seed(np.arange(nrec))
        sessions = []
        for i in range(T):
            s = db.session().begin()
            s.put("t", recs[i], pay[i], read_cids=np.ones(2, np.uint32))
            sessions.append(s)
        ok_sh = db.commit(sessions)
    np.testing.assert_array_equal(np.array(ok_sh), np.array(ok_local))
    for leaf in ("words", "payload", "cids", "bitvec"):
        np.testing.assert_array_equal(np.array(tab.store[leaf]),
                                      np.array(st_local[leaf]),
                                      err_msg=leaf)
    print("RSI_PARITY_OK")

elif mode == "olap":
    from repro.core import shuffle, aggregation
    from repro.fabric import MeshTransport
    mesh4 = make_mesh((4,), ("data",))
    tp4 = MeshTransport(mesh4, "data")
    key = jax.random.PRNGKey(0)
    rk = jax.random.permutation(key, jnp.arange(1, 2049, dtype=jnp.uint32))
    rv = rk * 3
    sk = jax.random.randint(jax.random.fold_in(key, 1), (4096,), 1, 4096
                            ).astype(jnp.uint32)
    sv = jnp.full((4096,), 2, jnp.uint32)
    hit = np.array(sk) <= 2048
    expect = int(np.sum(np.where(hit, np.array(sk) * 3 * 2, 0)))
    for variant in ("ghj", "ghj_bloom", "rdma_ghj", "rrj"):
        f = shuffle.make_distributed_join(tp4, variant)
        got = int(f(rk, rv, sk, sv))
        assert got == expect, (variant, got, expect)
    keys = jax.random.randint(key, (4096,), 0, 10_000).astype(jnp.uint32)
    vals = jnp.ones((4096,), jnp.uint32)
    a = aggregation.dist_agg(tp4, 64)(keys, vals)
    b = aggregation.rdma_agg(tp4, 64)(keys, vals)
    np.testing.assert_array_equal(np.array(a), np.array(b))
    print("OLAP_PARITY_OK")

elif mode == "dryrun":
    from repro.configs import get_config, reduce_config
    from repro.models import api
    from repro.sharding import make_policy, set_policy
    from repro.train import train_step as ts
    from repro.train.optimizer import make_optimizer
    cfg = reduce_config(get_config("glm4-9b"))
    mesh = make_mesh((2, 4), ("data", "model"))
    pol = make_policy(mesh, shape_kind="train")
    with mesh, set_policy(pol):
        pshapes = jax.eval_shape(
            lambda: api.init_params(cfg, jax.random.PRNGKey(0)))
        opt = make_optimizer("adamw")
        oshapes = jax.eval_shape(opt.init, pshapes)
        batch = {"tokens": jax.ShapeDtypeStruct((8, 64), jnp.int32),
                 "labels": jax.ShapeDtypeStruct((8, 64), jnp.int32)}
        step = ts.build_train_step(cfg, opt)
        jitted = jax.jit(step,
                         in_shardings=(ts.param_shardings(cfg, pol, pshapes),
                                       ts.opt_state_shardings(cfg, pol, opt,
                                                              oshapes),
                                       ts.batch_shardings(cfg, pol, batch)))
        compiled = jitted.lower(pshapes, oshapes, batch).compile()
        assert compiled.memory_analysis() is not None
    print("SMALLMESH_DRYRUN_OK")
"""


@pytest.mark.parametrize("mode,token", [
    ("moe", "MOE_PARITY_OK"),
    ("rsi", "RSI_PARITY_OK"),
    ("olap", "OLAP_PARITY_OK"),
    ("dryrun", "SMALLMESH_DRYRUN_OK"),
])
def test_multidevice(mode, token):
    env = dict(os.environ, MD_MODE=mode,
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                       capture_output=True, text=True, timeout=900)
    assert token in r.stdout, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-3000:]}"
