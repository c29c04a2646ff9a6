"""Kernel validation: interpret-mode Pallas vs pure-jnp oracles, swept over
shapes/dtypes (+ hypothesis property sweeps for partitioner and CAS)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref


@pytest.mark.parametrize("a,w,s", [
    (256, 2, 512),       # join shuffle rows, half-empty wire buffer
    (512, 16, 384),      # more rows than slots
    (300, 259, 256),     # RSI install rows: 256 payload words + 3
    (4, 600, 4),         # aggregation flush: few wide rows
])
def test_radix_partition_sweep(a, w, s):
    """Kernel == gather oracle, bit for bit: every slot takes its source
    row plus a valid lane, empty slots stay all-zero."""
    rng = np.random.default_rng(a + w + s)
    rows = jnp.asarray(rng.integers(0, 1 << 32, (a, w), dtype=np.uint64)
                       .astype(np.uint32))
    src = np.full(s, a, np.int32)
    k = min(a, s) * 3 // 4
    src[np.sort(rng.choice(s, k, replace=False))] = np.sort(
        rng.choice(a, k, replace=False))
    got = ops.radix_partition(rows, jnp.asarray(src))
    want = ref.radix_partition(rows, jnp.asarray(src))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@settings(max_examples=10, deadline=None)
@given(st.integers(2, 7), st.integers(1, 3))
def test_radix_partition_property(num_buckets, seed):
    """Through the router's Pallas backend, every kept row appears in its
    bucket, in stable order, up to cap."""
    from repro.fabric import router
    key = jax.random.PRNGKey(seed)
    n, cap = 128, 32
    vals = jnp.arange(n, dtype=jnp.float32)[:, None] * jnp.ones((1, 4))
    bucket = jax.random.randint(key, (n,), 0, num_buckets)
    res = router.route({"v": vals}, bucket, n=num_buckets, cap=cap,
                       backend="pallas")
    out = np.array(res.fields["v"]).reshape(num_buckets, cap, 4)
    valid = np.array(res.valid).reshape(num_buckets, cap)
    bucket = np.array(bucket)
    for b in range(num_buckets):
        rows = np.nonzero(bucket == b)[0][:cap]
        np.testing.assert_array_equal(out[b, :len(rows), 0],
                                      rows.astype(np.float32))
        assert valid[b, :len(rows)].all() and not valid[b, len(rows):].any()


@pytest.mark.parametrize("s,t,h,kh,d,causal,dtype", [
    (128, 128, 4, 4, 32, True, jnp.float32),
    (256, 256, 4, 2, 32, True, jnp.float32),
    (128, 256, 8, 1, 64, False, jnp.float32),
    (128, 128, 4, 4, 32, True, jnp.bfloat16),
])
def test_flash_attention_sweep(s, t, h, kh, d, causal, dtype):
    key = jax.random.PRNGKey(s + t + h)
    q = jax.random.normal(key, (2, s, h, d), jnp.float32).astype(dtype)
    k = jax.random.normal(jax.random.fold_in(key, 1), (2, t, kh, d),
                          jnp.float32).astype(dtype)
    v = jax.random.normal(jax.random.fold_in(key, 2), (2, t, kh, d),
                          jnp.float32).astype(dtype)
    got = ops.flash_attention(q, k, v, causal=causal, block_q=64, block_k=64)
    want = ref.flash_attention(q, k, v, causal=causal)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("s,h,hd,n,chunk", [
    (64, 8, 16, 16, 32),
    (128, 4, 32, 8, 64),
    (256, 16, 16, 32, 128),
])
def test_ssd_scan_sweep(s, h, hd, n, chunk):
    key = jax.random.PRNGKey(s + h)
    B = 2
    xh = jax.random.normal(key, (B, s, h, hd)) * 0.5
    bv = jax.random.normal(jax.random.fold_in(key, 1), (B, s, n)) * 0.5
    cv = jax.random.normal(jax.random.fold_in(key, 2), (B, s, n)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(key, 3),
                                           (B, s, h)))
    a = -jnp.exp(jax.random.normal(jax.random.fold_in(key, 4), (h,)) * 0.3)
    got = ops.ssd_scan(xh, bv, cv, dt, a, chunk=chunk, head_block=min(h, 4))
    want = ref.ssd_scan(xh, bv, cv, dt, a)
    np.testing.assert_allclose(got, want, atol=2e-3, rtol=2e-3)


def test_ssd_scan_matches_model_chunked():
    """Kernel == the model's chunked SSD (two independent implementations)."""
    from repro.models.ssm import ssd_chunked
    key = jax.random.PRNGKey(0)
    B, S, H, hd, N = 1, 128, 4, 16, 16
    xh = jax.random.normal(key, (B, S, H, hd)) * 0.5
    bv = jax.random.normal(jax.random.fold_in(key, 1), (B, S, N)) * 0.5
    cv = jax.random.normal(jax.random.fold_in(key, 2), (B, S, N)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(key, 3),
                                           (B, S, H)))
    a = -jnp.exp(jax.random.normal(jax.random.fold_in(key, 4), (H,)) * 0.3)
    y_model, _ = ssd_chunked(xh, bv, cv, dt, a, chunk=32)
    y_kernel = ops.ssd_scan(xh, bv, cv, dt, a, chunk=32, head_block=4)
    np.testing.assert_allclose(y_model, y_kernel, atol=2e-3, rtol=2e-3)


@pytest.mark.parametrize("n,slots", [(1024, 64), (2048, 128), (512, 16)])
def test_grouped_agg_sweep(n, slots):
    key = jax.random.PRNGKey(n)
    slot = jax.random.randint(key, (n,), 0, slots)
    vals = jax.random.normal(key, (n,))
    got = ops.grouped_agg(slot, vals, slots)
    want = ref.grouped_agg(slot, vals, slots)
    np.testing.assert_allclose(got, want, atol=1e-3, rtol=1e-4)


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 1000))
def test_cas_lock_property(seed):
    """Kernel CAS == sequential-application oracle; at most one success per
    word; successful words get the lock bit."""
    key = jax.random.PRNGKey(seed)
    words = jax.random.randint(key, (32,), 0, 4).astype(jnp.uint32)
    idx = jax.random.randint(jax.random.fold_in(key, 1), (256,), 0, 32)
    exp = jax.random.randint(jax.random.fold_in(key, 2), (256,), 0, 4
                             ).astype(jnp.uint32)
    ok1, w1 = ops.cas_lock(words, idx, exp)
    ok2, w2 = ref.cas_lock(words, idx, exp)
    np.testing.assert_array_equal(ok1, ok2)
    np.testing.assert_array_equal(w1, w2)
    ok, w = np.array(ok1), np.array(w1)
    for r in np.nonzero(np.bincount(np.array(idx)[ok], minlength=32) > 1)[0]:
        raise AssertionError(f"word {r} locked twice")
    assert (w[np.unique(np.array(idx)[ok])] >> 31).all()
