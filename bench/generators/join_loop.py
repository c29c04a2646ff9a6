"""Closed loop of one planned join aggregate at a time (paper §5.4)
through ``Database.execute``: R ⋈ S → sum of R.value × S.value, the
planner's choice of variant included in every query.

R holds unique keys 1..N in a seeded order; a seeded share ``hit_share``
of S's keys hit R and the rest miss it.  Value columns are seeded
integers in [1, value_max].  N is the configuration's tuples per node
times its nodes; under several nodes the relations are sharded over the
chips by the program.

Traffic parameters: ``hit_share``; ``declared_sel`` (the selectivity the
plan declares to the planner); ``warmup_queries``.
"""
from __future__ import annotations

import contextlib
import functools
import gc
import sys
import time
import traceback
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np

from bench.common import Check, make_transport, prng_key, span
from bench.reference.join import join_aggregate


@functools.partial(jax.jit, static_argnames=("n", "hit_share", "vmax"))
def relations(key, *, n: int, hit_share: float, vmax: int):
    """(rk, rv, sk, sv) of the cell, made on the device in one call."""
    k = jax.random.split(key, 6)
    rk = jax.random.permutation(k[0], jnp.arange(1, n + 1,
                                                 dtype=jnp.uint32))
    hit = jax.random.randint(k[1], (n,), 1, n + 1)
    miss = jax.random.randint(k[2], (n,), n + 1, 2 * n)
    take = jax.random.uniform(k[3], (n,)) < hit_share
    sk = jnp.where(take, hit, miss).astype(jnp.uint32)
    rv = jax.random.randint(k[4], (n,), 1, vmax + 1).astype(jnp.uint32)
    sv = jax.random.randint(k[5], (n,), 1, vmax + 1).astype(jnp.uint32)
    return rk, rv, sk, sv


class Cell:
    def __init__(self, cfg, traffic, seed):
        from repro.db import Database
        self.nodes = cfg["num_nodes"]
        self.per_node = cfg["tuples_per_node"]
        self.n = self.per_node * self.nodes
        self.vmax = cfg["value_max"]
        self.traffic = traffic
        self.seed = seed
        self.db = Database(transport=make_transport(self.nodes))
        rk, rv, sk, sv = self._relations()
        self.db.load_table("R", rk, rv)
        self.db.load_table("S", sk, sv)
        del rk, rv, sk, sv
        self.plan = self.db.scan("R").join(
            self.db.scan("S").filter(sel=traffic["declared_sel"])
        ).aggregate()
        self.answers = []          # (value or None, t_end)
        self.first = 0
        self.window_s = None

    def _relations(self):
        return relations(prng_key(self.seed), n=self.n,
                         hit_share=float(self.traffic["hit_share"]),
                         vmax=self.vmax)

    def _query(self):
        value = None
        try:
            with span("bench.execute"):
                res = self.db.execute(self.plan)
                value = int(res.value)
        except Exception:          # noqa: BLE001 - a query with no answer
            traceback.print_exc(file=sys.stderr)
        self.answers.append((value, time.perf_counter()))

    def warm(self):
        for _ in range(self.traffic["warmup_queries"]):
            self._query()
            if self.answers[-1][0] is None:
                raise RuntimeError("a set-up query raised")

    def window(self, seconds):
        self.first = len(self.answers)
        start = time.perf_counter()
        while True:
            self._query()
            value, t_end = self.answers[-1]
            if value is None or t_end - start >= seconds:
                break
        self.window_s = self.answers[-1][1] - start

    def _window_answers(self):
        return self.answers[self.first:]

    @property
    def attempted(self):
        return len(self._window_answers())

    @property
    def failed(self):
        return sum(v is None for v, _ in self._window_answers())

    def end_to_end(self):
        done = self.attempted - self.failed
        return {"join_tuples_per_s": done * 2 * self.n / self.window_s}

    def route_shapes(self):
        """Each query routes R and S once: per chip ``per_node`` rows of a
        key and a value word into ``nodes`` buckets of ``capacity_factor
        / nodes`` of the relation (the program's default factor, 2)."""
        cap = int(self.per_node * 2.0 / self.nodes)
        return [dict(rows=self.per_node, field_words=(1, 1), n=self.nodes,
                     cap=cap)] * 2

    def collect(self):
        pass

    def free(self):
        self.db = None
        gc.collect()

    def checks(self):
        want = join_aggregate(*(np.asarray(a) for a in self._relations()))
        if want >= 1 << 32:
            raise ValueError("the exact aggregate does not fit 32 bits: "
                             "lower value_max in the configuration")
        got = [v for v, _ in self.answers]
        return [
            Check("wrong_answers",
                  sum(v is not None and v != want for v in got), 0),
            Check("failed_queries", sum(v is None for v in got), 0),
        ]


def build(cfg, traffic, seed):
    return Cell(cfg, traffic, seed)


@contextlib.contextmanager
def control():
    """The join aggregate accumulated in 16-bit integers, the width below
    the exact 32-bit integer sum the configuration states."""
    from repro.core import shuffle

    def join_agg_u16(hit, rv, sv):
        prod = jnp.where(hit, rv * sv, 0).astype(jnp.uint16)
        return jnp.sum(prod, dtype=jnp.uint16).astype(jnp.uint32)

    with mock.patch.object(shuffle, "join_agg", join_agg_u16):
        yield
