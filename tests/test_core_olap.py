"""OLAP operators: joins (all four variants agree with ground truth; the
local passes match a numpy argsort + take pipeline bit for bit and trace
to no permutation gather) and aggregation (both schemes agree); cost-model
sanity (Fig 7 crossovers)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import aggregation, bloom, costmodel, shuffle
from repro.fabric import LocalTransport, MeshTransport
from repro.fabric.check import iter_eqns
from repro.launch.mesh import make_mesh


@pytest.fixture(scope="module")
def rel():
    key = jax.random.PRNGKey(0)
    rk = jax.random.permutation(key, jnp.arange(1, 2049, dtype=jnp.uint32))
    rv = rk * 3
    sk = jax.random.randint(jax.random.fold_in(key, 1), (4096,), 1, 4096
                            ).astype(jnp.uint32)
    sv = jnp.full((4096,), 2, jnp.uint32)
    hit = np.array(sk) <= 2048
    expect = int(np.sum(np.where(hit, np.array(sk) * 3 * 2, 0)))
    return rk, rv, sk, sv, expect


def test_local_join_variants_agree(rel):
    rk, rv, sk, sv, expect = rel
    assert int(shuffle.ghj_local(rk, rv, sk, sv)) == expect
    assert int(shuffle.ghj_local(rk, rv, sk, sv, use_bloom=True)) == expect
    assert int(shuffle.rrj_local(rk, rv, sk, sv)) == expect


MISS = 0xFFFFFFFF


def _join_inputs(case):
    """(rk, rv, sk, sv) u32 numpy columns; R's keys are unique but for
    duplicate MISS slots, which carry distinct nonzero values."""
    rng = np.random.default_rng(7)
    rk = rng.permutation(np.arange(1, 513, dtype=np.uint32))
    sk = rng.integers(1, 1024, 1024, dtype=np.uint32)
    if case == "miss_and_empty_blocks":
        # keys on every 4th residue mod 64, a quarter of R's above 2^18:
        # most blocks of each radix pass stay empty
        rk = (rk * 4 + (rk % 4 == 0) * (1 << 18)).astype(np.uint32)
        sk = (sk * 4).astype(np.uint32)
        rk[::7] = MISS
        sk[::5] = MISS
    elif case == "all_probes_miss":
        rk[::3] = MISS
        sk[:] = MISS
    rv = rng.integers(1, 1 << 32, rk.shape[0], dtype=np.uint32)
    sv = rng.integers(1, 1 << 32, sk.shape[0], dtype=np.uint32)
    return rk, rv, sk, sv


def _np_radix(keys, vals, num_parts, bits_from=0):
    order = np.argsort((keys >> bits_from) % num_parts, kind="stable")
    return np.take(keys, order), np.take(vals, order)


@pytest.mark.parametrize("case", ["random", "miss_and_empty_blocks",
                                  "all_probes_miss"])
@pytest.mark.parametrize("variant", ["rrj", "ghj", "ghj_bloom"])
def test_local_join_passes_match_numpy_argsort_take(variant, case,
                                                    monkeypatch):
    """Every payload sort of the local join (each radix pass of R and S,
    then the build sort) yields the columns a stable numpy argsort + take
    yields, bit for bit, and the aggregate is the numpy pipeline's sum."""
    rk, rv, sk, sv = _join_inputs(case)
    if variant == "ghj_bloom":     # the filter ghj_local applies to S
        bits = bloom.build(jnp.asarray(rk), 1 << 20)
        keep = np.array(bloom.query(bits, jnp.asarray(sk)))
        sk_in = np.where(keep, sk, np.uint32(MISS))
    else:
        sk_in = sk
    if variant == "rrj":
        passes = [(64, 0)]
    else:
        passes = [(32, 0), (32, 16)]
    want, r, s = [], (rk, rv), (sk_in, sv)
    for num_parts, bits_from in passes:
        r = _np_radix(*r, num_parts, bits_from)
        s = _np_radix(*s, num_parts, bits_from)
        want += [r, s]
    order = np.argsort(r[0], kind="stable")
    rks, rvs = np.take(r[0], order), np.take(r[1], order)
    want.append((rks, rvs))
    pos = np.clip(np.searchsorted(rks, s[0]), 0, rks.shape[0] - 1)
    hit = rks[pos] == s[0]
    prod = rvs[pos].astype(np.uint64) * s[1] & 0xFFFFFFFF
    want_agg = int(np.where(hit, prod, 0).sum()) & 0xFFFFFFFF

    got, real_sort = [], jax.lax.sort

    def spy(operands, *args, **kw):
        out = real_sort(operands, *args, **kw)
        got.append(tuple(np.array(o) for o in out[-2:]))
        return out
    monkeypatch.setattr(jax.lax, "sort", spy)
    args = [jnp.asarray(c) for c in (rk, rv, sk, sv)]
    if variant == "rrj":
        agg = shuffle.rrj_local(*args)
    else:
        agg = shuffle.ghj_local(*args, use_bloom=variant == "ghj_bloom")
    assert len(got) == len(want)
    for g, w in zip(got, want):
        for gc, wc in zip(g, w):
            assert gc.dtype == np.uint32
            np.testing.assert_array_equal(gc, wc)
    assert int(agg) == want_agg


@pytest.mark.parametrize("variant,sorts", [("rrj", 3), ("ghj", 5),
                                           ("ghj_bloom", 5)])
def test_local_join_gathers_only_in_the_probe(variant, sorts):
    """The local passes carry their payloads through the sorts: outside
    the searchsorted loop the only gathers are the probe's two ``[pos]``
    gathers (plus the Bloom filter's bit lookups), one loop, and as many
    sorts as relations sorted, each keyed on one operand, stable."""
    col = jnp.zeros((256,), jnp.uint32)
    if variant == "rrj":
        fn = shuffle.rrj_local
    else:
        def fn(*a):
            return shuffle.ghj_local(*a, use_bloom=variant == "ghj_bloom")
    jaxpr = jax.make_jaxpr(fn)(col, col, col, col)
    loops = {"scan", "while"}
    eqns = list(iter_eqns(jaxpr))
    assert sum(e.primitive.name in loops for _, e in eqns) == 1
    gathers = [e for p, e in eqns
               if e.primitive.name == "gather" and not loops & set(p)
               and e.invars[0].aval.dtype != jnp.bool_]   # Bloom bits
    assert len(gathers) == 2
    assert all(str(e.source_info.name_stack) == "join.probe"
               for e in gathers)
    sort_eqns = [e for _, e in eqns if e.primitive.name == "sort"]
    assert len(sort_eqns) == sorts
    assert all(e.params["num_keys"] == 1 and e.params["is_stable"]
               for e in sort_eqns)


def test_distributed_join_one_shard(rel):
    rk, rv, sk, sv, expect = rel
    mesh = make_mesh((1,), ("data",))
    for transport in (LocalTransport(), MeshTransport(mesh, "data")):
        for variant in ("ghj", "ghj_bloom", "rdma_ghj", "rrj"):
            f = shuffle.make_distributed_join(transport, variant)
            assert int(f(rk, rv, sk, sv)) == expect, (transport, variant)


def test_bloom_no_false_negatives():
    keys = jnp.arange(100, 1100, dtype=jnp.uint32)
    bits = bloom.build(keys, 1 << 14)
    assert bool(bloom.query(bits, keys).all())
    probe = jnp.arange(5000, 9000, dtype=jnp.uint32)
    fp = float(bloom.query(bits, probe).mean())
    assert fp < 0.2, fp


def test_aggregation_schemes_agree():
    key = jax.random.PRNGKey(1)
    mesh = make_mesh((1,), ("data",))
    for transport in (LocalTransport(), MeshTransport(mesh, "data")):
        for groups in (4, 64, 512):
            keys = jax.random.randint(key, (4096,), 0, 100_000
                                      ).astype(jnp.uint32)
            vals = jnp.ones((4096,), jnp.uint32)
            a = aggregation.dist_agg(transport, groups)(keys, vals)
            b = aggregation.rdma_agg(transport, groups)(keys, vals)
            np.testing.assert_array_equal(np.array(a), np.array(b))
            assert int(np.array(a).sum()) == 4096


def test_fig7_crossovers():
    """The paper's core cost-model claims (§5.1.3): on slow networks the
    semi-join reduction pays off; on RDMA it only pays for tiny
    selectivities; RRJ beats everything at sel=1."""
    nr = ns = 8 * 1_000_000  # bytes
    # Ethernet: bloom wins broadly
    assert costmodel.t_ghj_bloom(nr, ns, "ipoeth", 0.5) \
        < costmodel.t_ghj(nr, ns, "ipoeth")
    # RDMA: at high selectivity the reduction does NOT pay off
    assert costmodel.t_ghj_bloom(nr, ns, "rdma", 0.9) \
        > costmodel.t_rdma_ghj(nr, ns)
    # RRJ <= RDMA GHJ <= GHJ (on rdma)
    assert costmodel.t_rrj(nr, ns) <= costmodel.t_rdma_ghj(nr, ns) \
        <= costmodel.t_ghj(nr, ns, "rdma")


def test_oltp_model_matches_paper_numbers():
    """§4.1.3: ~647K txn/s upper bound for 3 nodes at 3750 cycles/msg; 4
    nodes is LOWER (the unscalability argument). §4.3: RSI bandwidth cap
    ~2.4M txn/s on 3 storage nodes with dual-port FDR."""
    m = costmodel.OltpModel()
    t3 = m.trx_upper_bound_cpu(3, "ipoeth", cycles_per_msg=3750)
    t4 = m.trx_upper_bound_cpu(4, "ipoeth", cycles_per_msg=3750)
    assert abs(t3 - 647_000) / 647_000 < 0.01, t3     # paper: ~647,000
    assert abs(t4 - 634_000) / 634_000 < 0.01, t4     # paper: ~634,000
    assert t4 < t3                                    # adding a node LOWERS it
    rsi_cap = m.rsi_bound()
    assert 2.0e6 < rsi_cap < 2.5e6, rsi_cap           # paper: ~2.4M
