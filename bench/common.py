"""What every traffic generator shares: seeds, the transport a
configuration names, host spans and the numbers a check compares."""
from __future__ import annotations

from dataclasses import dataclass

import jax
import numpy as np


def prng_key(seed: int, stream: int = 0):
    """A JAX key from a seed of any size: the seed's low and high 32-bit
    halves and the stream are folded into one key, so seeds above 2**31
    work and the same seed always gives the same key."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(0)
    for part in (seed & 0xFFFFFFFF, seed >> 32, stream):
        key = jax.random.fold_in(key, np.uint32(part & 0xFFFFFFFF))
    return key


def host_rng(seed: int, stream: int = 0) -> np.random.Generator:
    """A numpy generator from a seed of any size and a stream number."""
    return np.random.default_rng([int(seed), int(stream)])


def make_transport(nodes: int):
    """``LocalTransport`` for one node; a ``MeshTransport`` over the first
    ``nodes`` devices otherwise, one storage node per chip."""
    from repro.fabric import LocalTransport, MeshTransport
    from repro.launch.mesh import make_mesh
    if nodes == 1:
        return LocalTransport()
    devices = jax.devices()
    if len(devices) < nodes:
        raise RuntimeError(f"{nodes} nodes need {nodes} devices, JAX sees "
                           f"{len(devices)}")
    mesh = make_mesh((nodes,), ("data",), devices=devices[:nodes])
    return MeshTransport(mesh, "data")


def span(name: str):
    """A host span in the profiler's trace (``bench.*`` names are what the
    trace reduction attributes idle gaps to); costs next to nothing when
    no trace is being taken."""
    return jax.profiler.TraceAnnotation(name)


@dataclass(frozen=True)
class Check:
    """One number the correctness comparison reads, and its limit: the run
    is correct only where ``value <= limit`` for every check."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit
