"""Compiles for a described TPU v5e (nothing runs): the main path's
kernels and its RSI commit at deployment sizes.  The TPU compiler refuses
here what the chip would refuse — misaligned blocks, VMEM overuse, a
program that does not fit HBM — at no chip time.  The topology is
described in a fixture, never at import, so only the worker that runs
these tests loads the TPU library."""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import rsi
from repro.fabric import LocalTransport
from repro.kernels import ops

HBM_BYTES = 16 << 30          # one v5e chip
ROWS = 1 << 20


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no topology
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_in(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("n", [1, 4])
@pytest.mark.parametrize("words,cap_per_row", [
    (2, 2.0),     # join shuffle: (key, value), capacity_factor 2
    (259, 1.0),   # RSI install: record, cid, 1 KB payload, flag
])
def test_radix_partition_compiles(one_chip, words, cap_per_row, n):
    """The radix partitioner as the router calls it for a 1M-row batch:
    rows plus the inverted plan's source index for each of n*cap slots."""
    cap = int(ROWS * cap_per_row) // n if words == 2 else ROWS
    compiled = jax.jit(ops.radix_partition).lower(
        _spec((ROWS, words), jnp.uint32, one_chip),
        _spec((n * cap,), jnp.int32, one_chip)).compile()
    assert _kernel_in(compiled)


def test_grouped_agg_compiles(one_chip):
    compiled = jax.jit(lambda s, v: ops.grouped_agg(s, v, 2048)).lower(
        _spec((ROWS,), jnp.int32, one_chip),
        _spec((ROWS,), jnp.float32, one_chip)).compile()
    assert _kernel_in(compiled)


def test_rsi_commit_fits_one_chip(one_chip):
    """One §4.3 wave (512 checkouts x 7 writes) against the 1M x 1 KB
    products table: the routed commit takes the kernel and fits HBM."""
    cfg = rsi.StoreCfg(num_records=1_008_192, payload_words=256)
    store = jax.tree.map(lambda a: _spec(a.shape, a.dtype, one_chip),
                         jax.eval_shape(lambda: rsi.init_store(cfg)))
    T, W = 512, 7
    txns = rsi.TxnBatch(
        write_recs=_spec((T, W), jnp.int32, one_chip),
        read_cids=_spec((T, W), jnp.uint32, one_chip),
        new_payload=_spec((T, W, 256), jnp.uint32, one_chip),
        cid=_spec((T,), jnp.uint32, one_chip))
    compiled = jax.jit(lambda s, t: rsi.commit(
        s, t, transport=LocalTransport())).lower(store, txns).compile()
    m = compiled.memory_analysis()
    used = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert used < HBM_BYTES, used
    assert _kernel_in(compiled)


def test_rsi_commit_of_three_tables_compiles_on_four_chips(topo):
    """One wave of the three-table checkout (512 transactions: 3 product
    updates, 1 order, 3 order lines) against 1M x 1 KB products, 2^20
    orders and 2^22 order lines, every table sharded over a 2x2 v5e:
    one prepare all-to-all for every table, one grant exchange, one
    install per table, and it fits HBM."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.fabric import MeshTransport
    mesh = Mesh(np.array(topo.devices), ("data",))
    sharded = NamedSharding(mesh, P("data"))
    T, stores, txns = 512, [], []
    for rows, m, W in ((1_000_000, 256, 3), (1 << 20, 21, 1),
                       (1 << 22, 31, 3)):
        cfg = rsi.StoreCfg(num_records=rows, payload_words=m)
        stores.append(jax.tree.map(
            lambda a: _spec(a.shape, a.dtype, sharded),
            jax.eval_shape(lambda: rsi.init_store(cfg))))
        txns.append(rsi.TxnBatch(
            write_recs=_spec((T, W), jnp.int32, sharded),
            read_cids=_spec((T, W), jnp.uint32, sharded),
            new_payload=_spec((T, W, m), jnp.uint32, sharded),
            cid=_spec((T,), jnp.uint32, sharded)))
    transport = MeshTransport(mesh, "data")
    compiled = jax.jit(lambda s, t: rsi.commit(
        s, t, transport=transport,
        region_ns=("products/", "orders/", "orderlines/"))).lower(
        tuple(stores), tuple(txns)).compile()
    m = compiled.memory_analysis()
    used = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert used < HBM_BYTES, used
    assert compiled.as_text().count(" all-to-all(") == 1 + 1 + 3
    assert _kernel_in(compiled)
