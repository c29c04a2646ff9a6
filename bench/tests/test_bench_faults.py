"""The comparison that decides ``correct`` fails where it must: the
timed path broken underneath by each fault a cell can have, and each
cell's control, at a tiny size on the CPU."""
import jax
import jax.numpy as jnp
import pytest

from bench import harness
from bench.tests.tiny import tiny_root

SEED = 4242


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root(tmp_path_factory.mktemp("faults"))


@pytest.fixture(autouse=True)
def no_compile_cache(monkeypatch):
    monkeypatch.setattr(harness, "compile_cache", lambda root: None)


def _run(root, workload, **kw):
    return harness.run(workload, SEED, 0.3, False, root=root,
                       require_tpu=False, **kw)


# ---------------------------------------------------------- checkout --

def _unchanged_state(commit):
    def f(store, txns, **kw):
        ok, _ = commit(store, txns, **kw)
        return ok, store
    return f


def _half_the_wave(commit):
    def f(store, txns, **kw):
        half = txns.write_recs.shape[0] // 2
        recs = txns.write_recs.at[half:].set(-1)
        return commit(store, type(txns)(recs, txns.read_cids,
                                        txns.new_payload, txns.cid), **kw)
    return f


def _altered_outcome(commit):
    def f(store, txns, **kw):
        ok, new = commit(store, txns, **kw)
        return ok.at[0].set(~ok[0]), new
    return f


@pytest.mark.parametrize("fault", [_unchanged_state, _half_the_wave,
                                   _altered_outcome])
def test_checkout_fault_is_not_correct(root, monkeypatch, fault):
    from repro.db import database
    monkeypatch.setitem(database._BACKENDS, "rsi",
                        fault(database._BACKENDS["rsi"]))
    out = _run(root, "checkout.uniform")
    assert not out["correct"]


def _every_other_wave_lost(commit):
    """The installs of every other wave are lost (the store comes back as
    it went in), by the parity of the wave's number, taken from its first
    CID (each wave takes one CID per session); outcomes stay."""
    def f(store, txns, **kw):
        ok, new = commit(store, txns, **kw)
        lose = txns.cid[0] // txns.cid.shape[0] % 2 == 1
        return ok, jax.tree.map(lambda a, b: jnp.where(lose, a, b),
                                store, new)
    return f


def test_checkout_lost_waves_fail_the_read_back(root, monkeypatch):
    """Insert rows, which no later wave reads, of waves whose installs
    were lost: only the read-back of every written record sees them."""
    from repro.db import database
    monkeypatch.setitem(database._BACKENDS, "rsi",
                        _every_other_wave_lost(database._BACKENDS["rsi"]))
    out = _run(root, "checkout.uniform")
    assert out["checks"]["readback_mismatch"]["value"] > 0
    assert not out["correct"]


# -------------------------------------------------------------- join --

def test_join_altered_answer_is_not_correct(root, monkeypatch):
    from repro.core import shuffle
    real = shuffle.join_agg
    monkeypatch.setattr(shuffle, "join_agg",
                        lambda hit, rv, sv: real(hit, rv, sv) + 1)
    assert not _run(root, "join.1node")["correct"]


def test_join_half_the_probe_side_is_not_correct(root, monkeypatch):
    from repro.core import shuffle
    real = shuffle.local_join

    def half(rk, rv, sk, sv):
        hit, vals = real(rk, rv, sk, sv)
        keep = jnp.arange(sk.shape[0]) % 2 == 0   # every other probe
        return hit & keep, vals
    monkeypatch.setattr(shuffle, "local_join", half)
    assert not _run(root, "join.1node")["correct"]


# ---------------------------------------------------------- controls --

@pytest.mark.parametrize("workload", ["checkout.uniform", "join.1node"])
def test_control_is_not_correct(root, workload):
    out = _run(root, workload, control=True)
    assert not out["correct"]
    assert any(c["value"] > c["limit"] for c in out["checks"].values())
