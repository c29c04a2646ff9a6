"""Mesh builders. Functions, not constants — importing this module never
touches jax device state."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, *, devices=None):
    """``jax.make_mesh`` with every axis ``Auto``: the repo's shard_map
    bodies and sharding constraints leave propagation to the compiler,
    while JAX 0.9 makes ``Explicit`` axes the default."""
    axes = tuple(axes)
    return jax.make_mesh(tuple(shape), axes, devices=devices,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 256 chips as (data=16, model=16). Multi-pod: 2 pods with a
    leading 'pod' (pure DP) axis. The 'pod' axis maps onto the inter-pod DCI;
    'data'/'model' map onto intra-pod ICI."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many (host) devices exist — for tests."""
    return make_mesh((data, model), ("data", "model"))
