"""Pure-jnp oracles for every kernel (the correctness ground truth)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.cas_lock import LOCK_BIT_32


def radix_partition(rows, src):
    """Wire slot s takes row src[s] plus a valid lane; src == A is empty."""
    a, w = rows.shape
    padded = jnp.concatenate([rows, jnp.zeros((1, w), rows.dtype)])
    return jnp.concatenate(
        [padded[src], (src < a).astype(rows.dtype)[:, None]], axis=1)


def flash_attention(q, k, v, *, causal: bool = True):
    b, s, h, d = q.shape
    t, kh = k.shape[1], k.shape[2]
    g = h // kh
    kk = jnp.repeat(k, g, axis=2)
    vv = jnp.repeat(v, g, axis=2)
    sc = jnp.einsum("bshd,bthd->bhst", q.astype(jnp.float32),
                    kk.astype(jnp.float32)) * d ** -0.5
    if causal:
        mask = (jnp.arange(t)[None, :] <= jnp.arange(s)[:, None])
        sc = jnp.where(mask[None, None], sc, -1e30)
    p = jax.nn.softmax(sc, axis=-1)
    return jnp.einsum("bhst,bthd->bshd", p,
                      vv.astype(jnp.float32)).astype(q.dtype)


def ssd_scan(xh, bv, cv, dt, a, *, chunk: int = 128):
    """Sequential-recurrence oracle (exact SSD semantics)."""
    B, S, H, hd = xh.shape
    N = bv.shape[-1]

    def step(state, inp):
        x_t, b_t, c_t, dt_t = inp
        dA = jnp.exp(dt_t * a)                        # (B, H)
        state = state * dA[..., None, None] + jnp.einsum(
            "bh,bhd,bn->bhdn", dt_t, x_t.astype(jnp.float32),
            b_t.astype(jnp.float32))
        y = jnp.einsum("bn,bhdn->bhd", c_t.astype(jnp.float32), state)
        return state, y

    state0 = jnp.zeros((B, H, hd, N), jnp.float32)
    xs = (jnp.moveaxis(xh.astype(jnp.float32), 1, 0),
          jnp.moveaxis(bv, 1, 0), jnp.moveaxis(cv, 1, 0),
          jnp.moveaxis(dt, 1, 0))
    _, ys = jax.lax.scan(step, state0, xs)
    return jnp.moveaxis(ys, 0, 1).astype(xh.dtype)    # (B, S, H, hd)


def grouped_agg(slot, vals, num_slots: int):
    return jnp.zeros((num_slots,), jnp.float32).at[slot].add(
        vals.astype(jnp.float32))


def cas_lock(words, idx, expected):
    """Sequential CAS application in request order (numpy-style loop via
    scan — exact FIFO semantics)."""
    def step(w, inp):
        r, e = inp
        valid = (r >= 0) & (r < w.shape[0])
        r_safe = jnp.where(valid, r, 0)
        cur = w[r_safe]
        ok = valid & (cur == e)
        w = jnp.where(ok, w.at[r_safe].set(e | LOCK_BIT_32), w)
        return w, ok

    new_words, ok = jax.lax.scan(step, words, (idx, expected))
    return ok, new_words
