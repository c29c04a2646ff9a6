"""Substrate tests: optimizer, checkpoint manager, data pipeline, work
queue, sharding policy."""
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager
from repro.core.workqueue import WorkQueue, run_workers
from repro.data import SyntheticLM, Prefetcher
from repro.launch.mesh import make_mesh
from repro.sharding import make_policy, param_pspec
from repro.train.optimizer import (clip_by_global_norm, make_adafactor,
                                   make_adamw)


# ------------------------------------------------------------ optimizer ---

@pytest.mark.parametrize("mk,steps,frac", [
    (make_adamw, 60, 0.1),
    (make_adafactor, 150, 0.2),   # RMS-clipped unit-scale updates: slower
])
def test_optimizer_descends_quadratic(mk, steps, frac):
    opt = mk(lr=0.05, schedule=lambda step, lr: lr)
    params = {"w": jnp.array([3.0, -2.0]), "b": jnp.array(5.0)}
    st = opt.init(params)
    loss = lambda p: jnp.sum(p["w"] ** 2) + p["b"] ** 2
    l0 = float(loss(params))
    step = jax.jit(lambda p, s: opt.update(jax.grad(loss)(p), s, p))
    for _ in range(steps):
        params, st = step(params, st)
    assert float(loss(params)) < frac * l0


def test_optimizer_state_axes_structure():
    opt = make_adafactor()
    params = {"w": jnp.zeros((4, 8, 16)), "b": jnp.zeros((5,))}
    axes = {"w": ("stack", "embed", "ff"), "b": (None,)}
    st_axes = opt.state_logical_axes(axes)
    assert st_axes["s"]["w"] == {"vr": ("stack", "embed"),
                                 "vc": ("stack", "ff")}
    assert st_axes["s"]["b"] == {"v": (None,)}
    st = opt.init(params)
    assert st["s"]["w"]["vr"].shape == (4, 8)
    assert st["s"]["w"]["vc"].shape == (4, 16)


def test_grad_clip():
    g = {"a": jnp.full((4,), 10.0)}
    clipped, norm = clip_by_global_norm(g, 1.0)
    assert abs(float(norm) - 20.0) < 1e-4
    assert abs(float(jnp.linalg.norm(clipped["a"])) - 1.0) < 1e-4


# ----------------------------------------------------------- checkpoint ---

def test_checkpoint_roundtrip_and_gc(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    tree = {"a": jnp.arange(6.0).reshape(2, 3), "n": {"b": jnp.ones(4)}}
    for s in (1, 2, 3):
        mgr.save(s, jax.tree.map(lambda x: x * s, tree))
    assert mgr.all_steps() == [2, 3]            # GC keeps 2
    got, man = mgr.restore(tree)
    assert man["step"] == 3
    np.testing.assert_allclose(got["a"], np.arange(6.0).reshape(2, 3) * 3)


def test_checkpoint_async_and_cas_commit(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    tree = {"x": jnp.ones((128, 128))}
    mgr.save(7, tree, async_=True)
    mgr.wait()
    assert mgr.latest_step() == 7
    # concurrent committers race on the rename: exactly one wins, no error
    mgr2 = CheckpointManager(str(tmp_path))
    mgr.save(9, tree)
    mgr2.save(9, tree)
    got, man = mgr.restore(tree)
    assert man["step"] == 9


def test_checkpoint_elastic_resharding(tmp_path):
    """Save from one layout, restore onto explicit shardings (new mesh)."""
    mgr = CheckpointManager(str(tmp_path))
    tree = {"w": jnp.arange(64.0).reshape(8, 8)}
    mgr.save(1, tree)
    mesh = make_mesh((1,), ("data",))
    from jax.sharding import NamedSharding, PartitionSpec as P
    sh = {"w": NamedSharding(mesh, P("data", None))}
    got, _ = mgr.restore(tree, shardings=sh)
    assert got["w"].sharding == sh["w"]
    np.testing.assert_allclose(got["w"], tree["w"])


# ------------------------------------------------------------- pipeline ---

def test_pipeline_determinism_and_resume():
    a = SyntheticLM(vocab_size=100, seq_len=16, global_batch=8)
    b1 = a.next_batch()
    b2 = a.next_batch()
    b = SyntheticLM(vocab_size=100, seq_len=16, global_batch=8)
    b.load_state_dict({"step": 1, "seed": 0})     # resume after batch 1
    r2 = b.next_batch()
    np.testing.assert_array_equal(b2["tokens"], r2["tokens"])
    assert not np.array_equal(b1["tokens"], b2["tokens"])
    assert b1["tokens"].min() >= 0 and b1["tokens"].max() < 100


def test_pipeline_straggler_work_stealing():
    d = SyntheticLM(vocab_size=50, seq_len=8, global_batch=16,
                    num_workers=4)
    ref = d.next_batch()
    d2 = SyntheticLM(vocab_size=50, seq_len=8, global_batch=16,
                     num_workers=4)
    slow = d2.next_batch(slow_worker=0)          # worker 0 is 5x slower
    np.testing.assert_array_equal(ref["tokens"], slow["tokens"])


def test_workqueue_steals_from_straggler():
    wq = WorkQueue(4)
    for i in range(64):
        wq.push(0, i)                            # all work on one worker
    done = run_workers(wq, lambda x: time.sleep(0.001))
    assert sum(len(d) for d in done) == 64
    stolen = sum(s.steals for s in wq.stats)
    assert stolen > 0                            # other workers stole
    assert wq.pending() == 0


def test_prefetcher():
    calls = []
    pf = Prefetcher(lambda: calls.append(1) or len(calls), depth=2)
    assert pf.next() >= 1
    pf.close()


# ------------------------------------------------------------- sharding ---

def test_policy_resolution():
    mesh = make_mesh((1, 1), ("data", "model"))
    pol = make_policy(mesh, shape_kind="train")
    assert pol.resolve(("batch", "seq_sharded", None))[1] == "model"
    dec = make_policy(mesh, shape_kind="decode")
    assert dec.rules["kv_seq"] == "model"
    long = make_policy(mesh, shape_kind="long_decode")
    assert long.rules["batch"] is None
    assert long.rules["kv_seq"] == ("data",)


def test_param_pspec():
    spec = param_pspec(("vocab", None))
    assert spec[0] == "model" and spec[1] is None
