"""repro.analytics — the §6 NAM parameter server: bounded-staleness
semantics (reads never observe an epoch older than current - k),
grad_compress round-trip parity through the routed push path, wire-byte
accounting, 1-device mesh parity, and the trainer's
``paramserver(staleness=k)`` sync mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.flatten_util import ravel_pytree

from repro.analytics import ParameterServer, sgd_apply
from repro.fabric import LocalTransport, MeshTransport
from repro.launch.mesh import make_mesh
from repro.train import grad_compress as gc

PARAMS = {"w": jnp.ones((33, 9)), "b": jnp.zeros((11,))}


def _grad(i, scale=1.0):
    key = jax.random.fold_in(jax.random.PRNGKey(42), i)
    return jax.tree.map(
        lambda p: scale * jax.random.normal(key, p.shape), PARAMS)


# ------------------------------------------------- bounded staleness -----

def test_pull_never_observes_epoch_older_than_bound():
    """The staleness invariant: for every pull, returned epoch >= current
    epoch - k — even for a worker that never pushes (pure lagger)."""
    k = 3
    ps = ParameterServer(PARAMS, staleness=k, block=64)
    for i in range(12):
        ps.push(_grad(i), worker=0)
        _, epoch = ps.pull(worker=1)          # the lagging reader
        assert epoch >= ps.epoch - k
        assert epoch <= ps.epoch


def test_staleness_zero_is_always_fresh():
    ps = ParameterServer(PARAMS, staleness=0, block=64)
    for i in range(5):
        ps.push(_grad(i))
        _, epoch = ps.pull(worker=1)
        assert epoch == ps.epoch == i + 1


def test_stale_pulls_serve_the_cache_without_shard_reads():
    """Within the bound the worker's cached view is served — only the
    1-word epoch READ hits the fabric, not the parameter shards."""
    ps = ParameterServer(PARAMS, staleness=5, block=64)
    ps.pull(worker=1)                          # prime the cache
    shard_bytes = ps.num_shards * ps.shard_len * 4
    before = ps.fabric_stats()["read"]["bytes"]
    for i in range(3):                         # 3 pushes, all within k=5
        ps.push(_grad(i))
        ps.pull(worker=1)
    delta = ps.fabric_stats()["read"]["bytes"] - before
    assert delta < shard_bytes                 # epoch words only, no shards
    ps.push(_grad(3))
    ps.push(_grad(4))
    ps.push(_grad(5))                          # now 6 behind: must refresh
    _, epoch = ps.pull(worker=1)
    assert epoch == ps.epoch
    after = ps.fabric_stats()["read"]["bytes"] - before
    assert after >= shard_bytes                # the refresh READ the shards


def test_stale_view_converges_after_refresh():
    """A stale pull returns old parameter values; once forced past the
    bound the worker sees the server's current state."""
    ps = ParameterServer(PARAMS, staleness=2, block=64,
                         apply_fn=sgd_apply(lr=1.0))
    stale_view, e0 = ps.pull(worker=1)
    ps.push(_grad(0))
    within, e1 = ps.pull(worker=1)
    assert e1 == e0                            # cache: same (old) view
    np.testing.assert_array_equal(np.asarray(within["w"]),
                                  np.asarray(stale_view["w"]))
    for i in range(1, 4):
        ps.push(_grad(i))
    fresh, e2 = ps.pull(worker=1)              # 4 behind > k=2: refresh
    assert e2 == ps.epoch
    assert not np.array_equal(np.asarray(fresh["w"]),
                              np.asarray(stale_view["w"]))
    np.testing.assert_allclose(np.asarray(fresh["w"]),
                               np.asarray(ps.current_params()["w"]),
                               atol=1e-6)


# -------------------------------- compression through the push path ------

def test_push_path_equals_grad_compress_roundtrip():
    """The gradient the server applies is bit-for-bit the grad_compress
    int8+EF round trip of the pushed gradient — routing through the fabric
    loses nothing."""
    applied = []

    def spy(params, grads):
        applied.append(grads)
        return params                          # no update: isolate the wire

    block = 64
    ps = ParameterServer(PARAMS, staleness=0, block=block, apply_fn=spy)
    residual = jnp.zeros((ps.num_shards, ps.shard_len), jnp.float32)
    for i in range(4):
        g = _grad(i, scale=3.0)
        ps.push(g)
        flat = ravel_pytree(g)[0].astype(jnp.float32)
        padded = jnp.pad(flat, (0, ps.num_shards * ps.shard_len - flat.size)
                         ).reshape(ps.num_shards, ps.shard_len)
        codes, scale, residual = gc.compress_with_feedback(
            padded, residual, block=block)
        want = gc.decompress(codes, scale, padded.shape, block=block)
        got = ravel_pytree(applied[-1])[0]
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(want.reshape(-1)
                                                 [:flat.size]))


def test_error_feedback_telescopes_through_push_path():
    """Sum of server-applied gradients + the worker residual == sum of the
    true gradients (EF is lossless in the telescoping sum) — the same
    guarantee test_grad_compress proves locally, here through route()."""
    applied = []
    ps = ParameterServer(PARAMS, staleness=0, block=64,
                         apply_fn=lambda p, g: (applied.append(g), p)[1])
    total_true = jnp.zeros(ravel_pytree(PARAMS)[0].size)
    for i in range(10):
        g = _grad(i)
        total_true += ravel_pytree(g)[0]
        ps.push(g)
    total_applied = sum(ravel_pytree(g)[0] for g in applied)
    resid = ps._residuals[0].reshape(-1)[:total_true.size]
    np.testing.assert_allclose(np.asarray(total_applied + resid),
                               np.asarray(total_true), atol=1e-4)


def test_push_pays_compressed_bytes_on_the_wire():
    """The routed push moves ~x4 fewer bytes than a raw f32 push — the
    cross-pod axis pays int8 codes + per-block scales."""
    ps = ParameterServer(PARAMS, staleness=0, block=256)
    ps.push(_grad(0))
    comp_route = ps.fabric_stats()["route"]["bytes"]
    ps_raw = ParameterServer(PARAMS, staleness=0, compress=False)
    ps_raw.push(_grad(0))
    raw_route = ps_raw.fabric_stats()["route"]["bytes"]
    assert comp_route < 0.35 * raw_route
    comp, raw = ps.wire_bytes_per_push()
    assert comp < 0.3 * raw


def test_uncompressed_push_applies_exact_gradient():
    ps = ParameterServer(PARAMS, staleness=0, compress=False,
                         apply_fn=sgd_apply(lr=1.0))
    g = _grad(0)
    ps.push(g)
    got = ps.current_params()
    want = jax.tree.map(lambda p, d: p - d, PARAMS, g)
    np.testing.assert_allclose(np.asarray(got["w"]),
                               np.asarray(want["w"]), atol=1e-6)


# ----------------------------------------------------- substrate parity --

def test_mesh_1device_parity_with_local():
    local = ParameterServer(PARAMS, staleness=0, block=64)
    mesh = make_mesh((1,), ("data",))
    dist = ParameterServer(PARAMS, staleness=0, block=64,
                           transport=MeshTransport(mesh, "data"))
    for i in range(3):
        local.push(_grad(i))
        dist.push(_grad(i))
    lw = np.asarray(local.current_params()["w"])
    dw = np.asarray(dist.current_params()["w"])
    np.testing.assert_allclose(lw, dw, atol=1e-6)


def test_num_shards_must_divide_transport():
    with pytest.raises(ValueError):
        ParameterServer(PARAMS, num_shards=3,
                        transport=_FakeWideTransport())


def test_default_num_shards_rounds_up_to_transport_multiple():
    """The default shard count must satisfy the constructor's own divider
    check on any transport width (e.g. a 3-shard mesh -> 6 shards)."""
    ps = ParameterServer(PARAMS, transport=_FakeTripleTransport())
    assert ps.num_shards == 6


class _FakeWideTransport(LocalTransport):
    @property
    def n(self):
        return 2


class _FakeTripleTransport(LocalTransport):
    @property
    def n(self):
        return 3


# ------------------------------------------------------------- trainer --

def _tiny_cfg():
    from repro.configs.base import ModelConfig
    return ModelConfig(name="tiny-ps", family="dense", num_layers=1,
                       d_model=32, num_heads=2, num_kv_heads=2, d_ff=64,
                       vocab_size=128, head_dim=16, tie_embeddings=True)


def test_sync_mode_parsing():
    from repro.train.trainer import parse_sync_mode
    assert parse_sync_mode("allreduce") == ("allreduce", None)
    assert parse_sync_mode("paramserver") == ("paramserver", None)
    assert parse_sync_mode("paramserver(staleness=4)") == ("paramserver", 4)
    with pytest.raises(ValueError):
        parse_sync_mode("paramserver(staleness=-1)")
    with pytest.raises(ValueError):
        parse_sync_mode("ring")


def test_trainer_paramserver_matches_allreduce(tmp_path):
    """staleness=0 + raw push + the same optimizer == the fused allreduce
    step: the PS sync mode is a faithful re-wiring of the update, not a
    different algorithm."""
    from repro.train.optimizer import make_adamw
    from repro.train.trainer import Trainer, TrainerConfig
    cfg = _tiny_cfg()
    logs = {}
    for mode in ("allreduce", "paramserver(staleness=0)"):
        tcfg = TrainerConfig(steps=6, global_batch=2, seq_len=16,
                             checkpoint_dir=str(tmp_path / mode[:6]),
                             log_every=2, checkpoint_every=100,
                             sync_mode=mode, ps_compress=False)
        tr = Trainer(cfg, tcfg,
                     optimizer=make_adamw(lr=1e-3,
                                          schedule=lambda s, lr: lr))
        logs[mode] = tr.run()
        if mode.startswith("paramserver"):
            assert tr.ps is not None and tr.ps.epoch == 6
            assert tr.comm_log, "ps mode must log comm-cost entries"
            entry = tr.comm_log[-1]
            assert entry["fabric"]["route"]["bytes"] > 0
            assert entry["t_ps_step_model_s"] > 0
    a = np.array([l for _, l in logs["allreduce"]])
    b = np.array([l for _, l in logs["paramserver(staleness=0)"]])
    np.testing.assert_allclose(a, b, rtol=1e-4)


def test_trainer_paramserver_stale_compressed_still_trains(tmp_path):
    """The production point of §6: bounded staleness + compressed push
    still descends on a tiny LM.

    The LM learns one repeated batch.  SyntheticLM's tokens are i.i.d.
    uniform, so no model gets below ln(vocab) on them, and a 12-step loss
    comparison there only reads the noise of the random init."""
    from repro.data import SyntheticLM
    from repro.train.optimizer import make_adamw
    from repro.train.trainer import Trainer, TrainerConfig

    class RepeatedBatch(SyntheticLM):
        def next_batch(self, **kw):
            self.step = 0
            return super().next_batch(**kw)

    cfg = _tiny_cfg()
    tcfg = TrainerConfig(steps=12, global_batch=2, seq_len=16,
                         checkpoint_dir=str(tmp_path / "ck"),
                         log_every=3, checkpoint_every=100,
                         sync_mode="paramserver(staleness=3)")
    tr = Trainer(cfg, tcfg,
                 optimizer=make_adamw(lr=5e-3, schedule=lambda s, lr: lr),
                 data=RepeatedBatch(vocab_size=cfg.vocab_size,
                                    seq_len=tcfg.seq_len,
                                    global_batch=tcfg.global_batch))
    log = tr.run()
    assert log[-1][1] < log[0][1]              # loss descended
    comp, raw = tr.ps.wire_bytes_per_push()
    assert comp < 0.3 * raw                    # wire paid compressed bytes
