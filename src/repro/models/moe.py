"""Mixture-of-Experts with NAM/RRJ dispatch.

The expert-parallel dispatch is the paper's RDMA Radix Join mapped to LM
workloads: each shard radix-partitions its local tokens into per-destination
*software-managed buffers* (fixed-capacity, like the paper's remote buffer
reservations), a single ``all_to_all`` over the 'model' axis performs the
network shuffle (the one-sided WRITE phase), experts compute locally, and the
paired ``all_to_all`` returns results to their source slots. Expert weights
live FSDP-sharded in the NAM pool and are fetched with an ``all_gather``
(one-sided READ) inside the shard_map body.

Three paths:
  - ``_moe_reference``      : maskless loop over experts (single-device smoke,
                              also the oracle for tests).
  - ``_moe_rrj``            : shard_map RRJ dispatch (train/prefill).
  - ``_moe_replicated``     : decode path — few tokens; dispatch is replicated
                              and combined with a psum (avoids the shuffle).
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.sharding import current_policy


def build_moe(cfg, mcfg, mk):
    d = cfg.d_model
    f = mcfg.d_ff
    e = mcfg.num_experts
    p = {
        "router": mk((d, e), ("embed", None)),
        "wi": mk((e, d, 2 * f), ("experts", "embed", None)),
        "wo": mk((e, f, d), ("experts", None, "embed")),
    }
    if mcfg.num_shared:
        sf = mcfg.shared_d_ff or f
        p["shared_wi"] = mk((d, 2 * sf * mcfg.num_shared), ("embed", "ff"))
        p["shared_wo"] = mk((sf * mcfg.num_shared, d), ("ff", "embed"))
    return p


def _gates(mcfg, xt, router_w):
    """xt: (T, D) -> (top-k values (T,k) renormalized, indices (T,k))."""
    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32),
                        router_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    vals, idx = jax.lax.top_k(probs, mcfg.top_k)
    vals = vals / jnp.maximum(vals.sum(-1, keepdims=True), 1e-9)
    return vals, idx, probs


def _expert_ffn(h_in, wi, wo):
    """h_in: (E, C, D); wi: (E, D, 2F); wo: (E, F, D) — grouped SwiGLU."""
    h = jnp.einsum("ecd,edf->ecf", h_in, wi)
    g, u = jnp.split(h, 2, axis=-1)
    h = jax.nn.silu(g) * u
    return jnp.einsum("ecf,efd->ecd", h, wo)


def aux_load_balance(mcfg, xt, router_w):
    """Switch-style load-balancing loss (computed in the GSPMD region)."""
    vals, idx, probs = _gates(mcfg, xt, router_w)
    e = mcfg.num_experts
    onehot = jax.nn.one_hot(idx, e, dtype=jnp.float32).sum(1)  # (T, E)
    f = onehot.mean(0)
    pm = probs.mean(0)
    return e * jnp.sum(f * pm)


# ------------------------------------------------------------- reference --

def _moe_reference(cfg, mcfg, p, x):
    """Loop-over-experts oracle; exact (no token dropping)."""
    B, S, D = x.shape
    xt = x.reshape(-1, D)
    vals, idx, _ = _gates(mcfg, xt, p["router"])
    out = jnp.zeros_like(xt)
    for e in range(mcfg.num_experts):
        w = (jnp.where(idx == e, vals, 0.0)).sum(-1)        # (T,)
        y = _expert_ffn(xt[None], p["wi"][e:e + 1].astype(x.dtype),
                        p["wo"][e:e + 1].astype(x.dtype))[0]
        out = out + y * w[:, None].astype(x.dtype)
    return out.reshape(B, S, D)


# ------------------------------------------------------------------- RRJ --

def _round8(n: int) -> int:
    return max(8, int(math.ceil(n / 8)) * 8)


def _radix_to_buffers(xt, dest, src_slot, meta, num_dest: int, cap: int):
    """Software-managed buffer fill (paper §5.2): stable-sort assignments by
    destination, drop overflow beyond each destination's capacity, scatter
    into the (num_dest, cap) send buffers.

    xt: (T, D) tokens; dest: (A,) destination ids; src_slot: (A,) source token
    index of each assignment; meta: dict of (A,) payload scalars.
    Returns (buf (num_dest*cap, D), meta_buf, valid (num_dest*cap,)).
    """
    A = dest.shape[0]
    order = jnp.argsort(dest, stable=True)
    d_sorted = dest[order]
    # position of each sorted assignment within its destination run
    first = jnp.searchsorted(d_sorted, d_sorted, side="left")
    pos = jnp.arange(A, dtype=jnp.int32) - first.astype(jnp.int32)
    keep = pos < cap
    slot = jnp.where(keep, d_sorted * cap + pos, num_dest * cap)  # drop -> OOB
    buf = jnp.zeros((num_dest * cap + 1, xt.shape[1]), xt.dtype)
    buf = buf.at[slot].set(xt[src_slot[order]])
    valid = jnp.zeros((num_dest * cap + 1,), jnp.float32).at[slot].set(
        keep.astype(jnp.float32))
    meta_out = {}
    for k, v in meta.items():
        mb = jnp.zeros((num_dest * cap + 1,), v.dtype).at[slot].set(v[order])
        meta_out[k] = mb[:-1]
    return buf[:-1], meta_out, valid[:-1]


def _moe_rrj_body(cfg, mcfg, tp: int, cap: int, ecap: int,
                  x, router_w, wi, wo):
    """shard_map body. x: (B_l, S_l, D); wi: (E_l, D/dp, 2F); wo likewise."""
    local_e = wi.shape[0]
    B_l, S_l, D = x.shape
    # NAM one-sided READ: fetch the FSDP-sharded expert weights for this
    # shard — cast to the compute dtype BEFORE the gather (half the wire
    # bytes; the paper's "ship the working representation")
    wi = jax.lax.all_gather(wi.astype(x.dtype), "data", axis=1, tiled=True)
    wo = jax.lax.all_gather(wo.astype(x.dtype), "data", axis=2, tiled=True)

    xt = x.reshape(-1, D)
    T = xt.shape[0]
    vals, idx, _ = _gates(mcfg, xt, router_w)
    A = T * mcfg.top_k
    e_flat = idx.reshape(-1).astype(jnp.int32)
    g_flat = vals.reshape(-1)
    src = jnp.repeat(jnp.arange(T, dtype=jnp.int32), mcfg.top_k)
    dest = e_flat // local_e                                   # owner shard

    meta = {"gate": g_flat.astype(jnp.float32),
            "local_e": (e_flat % local_e).astype(jnp.int32),
            "src": src}
    buf, mbuf, valid = _radix_to_buffers(xt, dest, src, meta, tp, cap)

    # network shuffle (paired one-sided WRITEs)
    def a2a(v):
        return jax.lax.all_to_all(v.reshape(tp, cap, *v.shape[1:]),
                                  "model", 0, 0, tiled=False).reshape(
                                      tp * cap, *v.shape[1:])

    rbuf = a2a(buf)
    rvalid = a2a(valid)
    rle = a2a(mbuf["local_e"])
    rgate = a2a(mbuf["gate"])

    # second radix pass: bin received tokens by local expert
    rle_k = jnp.where(rvalid > 0, rle, local_e)  # invalid -> overflow bin
    order2 = jnp.argsort(rle_k, stable=True)
    le_sorted = rle_k[order2]
    first2 = jnp.searchsorted(le_sorted, le_sorted, side="left")
    pos2 = jnp.arange(tp * cap, dtype=jnp.int32) - first2.astype(jnp.int32)
    keep2 = (pos2 < ecap) & (le_sorted < local_e)
    slot2 = jnp.where(keep2, le_sorted * ecap + pos2, local_e * ecap)
    ebuf = jnp.zeros((local_e * ecap + 1, D), x.dtype).at[slot2].set(
        rbuf[order2])
    y = _expert_ffn(ebuf[:-1].reshape(local_e, ecap, D), wi, wo)
    y = y.reshape(local_e * ecap, D)
    # un-bin back to the received-buffer layout (invert the radix sort)
    y_rows = jnp.concatenate([y, jnp.zeros((1, D), x.dtype)], 0)
    back = y_rows[slot2][jnp.argsort(order2, stable=True)]
    # reverse shuffle: results return to their source shards
    sbuf = a2a(back)
    # combine into source slots, gate-weighted
    w = (valid * mbuf["gate"]).astype(x.dtype)[:, None]
    out = jnp.zeros((T, D), x.dtype).at[mbuf["src"]].add(sbuf * w)
    return out.reshape(B_l, S_l, D)


def _moe_rrj(cfg, mcfg, p, x):
    pol = current_policy()
    mesh = pol.mesh
    tp = mesh.shape["model"]
    batch_axes = pol.rules.get("batch") or ()
    B, S, D = x.shape
    bsh = math.prod(mesh.shape[a] for a in batch_axes) if batch_axes else 1
    T_local = (B // max(bsh, 1)) * (S // tp)
    local_e = mcfg.num_experts // tp
    # software-managed buffer capacities (paper: reserve remote buffers)
    cap = _round8(int(T_local * mcfg.top_k / tp * mcfg.capacity_factor))
    ecap = min(_round8(int(tp * cap / local_e * mcfg.capacity_factor)),
               _round8(tp * cap))

    body = partial(_moe_rrj_body, cfg, mcfg, tp, cap, ecap)
    xspec = P(batch_axes or None, "model", None)
    f = jax.shard_map(body, mesh=mesh,
                      in_specs=(xspec, P(None, None),
                                P("model", "data", None),
                                P("model", None, "data")),
                      out_specs=xspec, check_vma=False)
    return f(x, p["router"], p["wi"], p["wo"])


# ---------------------------------------------------------------- decode --

def _moe_replicated_body(cfg, mcfg, tp: int, do_gather: bool,
                         x, router_w, wi, wo):
    """Decode dispatch — weights STAY PUT (the NAM principle at its purest):

    Expert weights remain (E/tp, D/dp, F)-sharded; every chip sees all (few)
    decode tokens (one tiny all_gather over 'data'), radix-bins the ones
    routed to ITS experts into capacity buffers (the RRJ software-managed
    buffers, local — no shuffle needed at decode), computes partial matmuls
    against its D-slice of the weights, and two small activation psums
    (data: hidden partials; model: expert combine) assemble the result.
    Replaces a per-layer 2.7 GB weight all-gather with ~10 MB of activation
    traffic (see EXPERIMENTS.md §Perf)."""
    local_e = wi.shape[0]
    B_l, S_l, D = x.shape
    d_l = wi.shape[1]                                  # D / dp
    dp = D // d_l                                      # static 'data' size
    me_m = jax.lax.axis_index("model")
    me_d = jax.lax.axis_index("data")

    # every chip sees the full (small) token wave
    xt = x.reshape(-1, D)
    xt_all = (jax.lax.all_gather(xt, "data", axis=0, tiled=True)
              if do_gather and dp > 1 else xt)
    T = xt_all.shape[0]
    vals, idx, _ = _gates(mcfg, xt_all, router_w)
    a_flat = idx.reshape(-1)
    g_flat = vals.reshape(-1)
    src = jnp.repeat(jnp.arange(T, dtype=jnp.int32), mcfg.top_k)
    # assignments owned by my model shard -> local expert bins
    mine = (a_flat // local_e) == me_m
    dest = jnp.where(mine, a_flat % local_e, local_e)
    cap = _round8(int(T * mcfg.top_k / max(local_e, 1)
                      * mcfg.capacity_factor))
    cap = min(cap, _round8(T * mcfg.top_k))
    # bin MY D-slice of the tokens (weights' D shard) into expert buffers
    xt_slice = jax.lax.dynamic_slice_in_dim(xt_all, me_d * d_l, d_l, axis=1)
    ebuf, meta, valid = _radix_to_buffers(
        xt_slice, dest, src, {"gate": g_flat.astype(jnp.float32),
                              "src": src}, local_e, cap)
    ebuf = ebuf.reshape(local_e, cap, d_l)
    # partial matmul over my D-slice, then assemble hidden over 'data'
    h = jnp.einsum("ecd,edf->ecf", ebuf, wi.astype(x.dtype))
    h = jax.lax.psum(h, "data")                        # (E_l, cap, 2F)
    g, u = jnp.split(h, 2, axis=-1)
    h = jax.nn.silu(g) * u
    y = jnp.einsum("ecf,efd->ecd", h, wo.astype(x.dtype))  # (E_l, cap, D/dp)
    # combine back to tokens, gate-weighted; experts merge over 'model'
    w = (valid * meta["gate"]).astype(x.dtype)[:, None]
    out = jnp.zeros((T, d_l), x.dtype).at[meta["src"]].add(
        y.reshape(local_e * cap, d_l) * w)
    out = jax.lax.psum(out, "model")
    out = jax.lax.all_gather(out, "data", axis=1, tiled=True)  # (T, D)
    if xt_all.shape[0] != xt.shape[0]:
        out = jax.lax.dynamic_slice_in_dim(out, me_d * B_l * S_l,
                                           B_l * S_l, axis=0)
    return out.reshape(B_l, S_l, D)


def _moe_replicated(cfg, mcfg, p, x):
    pol = current_policy()
    mesh = pol.mesh
    tp = mesh.shape["model"]
    batch_axes = pol.rules.get("batch") or ()
    xspec = P(batch_axes or None, None, None)
    body = partial(_moe_replicated_body, cfg, mcfg, tp, bool(batch_axes))
    f = jax.shard_map(body, mesh=mesh,
                      in_specs=(xspec, P(None, None),
                                P("model", "data", None),
                                P("model", None, "data")),
                      out_specs=xspec, check_vma=False)
    return f(x, p["router"], p["wi"], p["wo"])


# ------------------------------------------------------------------ api ---

def apply_moe(cfg, mcfg, p, x, *, decode: bool = False):
    """x: (B, S, D) (sequence-sharded over 'model' for train/prefill).
    Returns (y, aux_loss)."""
    pol = current_policy()
    xt = x.reshape(-1, x.shape[-1])
    aux = aux_load_balance(mcfg, xt, p["router"])
    if pol is None or pol.mesh.shape.get("model", 1) == 1 \
            or mcfg.num_experts % pol.mesh.shape["model"] != 0:
        y = _moe_reference(cfg, mcfg, p, x)
    elif decode or x.shape[1] == 1:
        y = _moe_replicated(cfg, mcfg, p, x)
    else:
        y = _moe_rrj(cfg, mcfg, p, x)
    if mcfg.num_shared:
        h = jnp.einsum("bsd,df->bsf", x, p["shared_wi"].astype(x.dtype))
        g, u = jnp.split(h, 2, axis=-1)
        y = y + jnp.einsum("bsf,fd->bsd", jax.nn.silu(g) * u,
                           p["shared_wo"].astype(x.dtype))
    return y, aux
