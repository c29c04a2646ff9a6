"""The Database: one NAM-DB facade over the verb fabric.

A :class:`Database` owns the pieces every workload was previously
hand-wiring:

  * a :class:`~repro.fabric.NamPool` of named regions (tables allocate
    their stores here; compute/storage co-location stays a sharding choice),
  * ONE fabric transport (``LocalTransport`` default, ``MeshTransport`` for
    the sharded NAM deployment) that every verb of every protocol runs —
    and is counted — through,
  * a timestamp oracle (a counter word bumped with the FETCH_ADD verb —
    NAM-DB's commit-timestamp service as a region, not a server),
  * the network-aware :class:`~repro.db.planner.Planner` that picks shuffle
    and aggregation strategies from the §5.1/§5.3 cost models.

OLTP: ``db.session()`` transactions commit through RSI (or the 2PC
baseline) in batched waves — ``db.commit([s1, s2, ...])`` is one routed
prepare/install round trip for the whole wave.  OLAP:
``db.scan("R").join(db.scan("S")).aggregate()`` builds a logical plan;
``db.execute(plan)`` runs the planner's argmin choice (or a forced variant
for benchmark grids) and ``db.explain(plan)`` returns every costed
alternative.
"""
from __future__ import annotations

import itertools
import time
from dataclasses import dataclass, field
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import fabric, obs
from repro.core import aggregation, rsi, shuffle, twopc
from repro.db.plan import Plan
from repro.db.planner import Planner
from repro.db.session import Session
from repro.db.table import Table, TableSchema

# modeled cluster size when running the single-shard degenerate case: the
# paper's §5.4 deployment, so planner choices match the target NAM cluster
DEFAULT_MODEL_NODES = 4

_BACKENDS = {"rsi": rsi.commit, "2pc": twopc.commit}

# one backoff slot of modeled compute between a hot-row abort and its
# retry round — a NIC doorbell-ish quantum, priced via the sim tracer
BACKOFF_SLOT_S = 1e-6


def _dyadic(items: list) -> list:
    """Split a list into greedy power-of-two-sized chunks (23 -> 16+4+2+1)."""
    out, i = [], 0
    while i < len(items):
        size = 1 << ((len(items) - i).bit_length() - 1)
        out.append(items[i:i + size])
        i += size
    return out


def backoff_slots(txn_id: int, attempt: int) -> int:
    """Bounded-exponential retry backoff, jittered by a Fibonacci-hash of
    the transaction id — deterministic (no runtime RNG, replayable traces)
    yet decorrelated across the txns that just collided on the same hot
    row, which is the whole point of jitter."""
    h = (int(txn_id) * 0x9E3779B1 + int(attempt) * 0x85EBCA77) & 0xFFFFFFFF
    return h % (1 << min(int(attempt), 16))


@dataclass(frozen=True)
class QueryResult:
    value: object                       # operator output (device array)
    variant: str                        # strategy that actually ran
    alternatives: tuple                 # costed Alternatives, argmin first
    plan: Plan
    stats: dict = field(default_factory=dict)   # fabric counter delta
                                                # (calibrate=True only;
                                                # trace-time, empty on
                                                # cached re-execution)

    @property
    def planned(self) -> str:
        return next(a.name for a in self.alternatives if a.chosen)


@dataclass(frozen=True)
class Explain:
    plan: str                           # plan.describe()
    kind: str                           # join_agg | group_agg
    alternatives: tuple                 # argmin first
    inputs: dict                        # cost-model inputs (bytes, sel, ...)

    @property
    def chosen(self) -> str:
        return next(a.name for a in self.alternatives if a.chosen)

    def pretty(self) -> str:
        lines = [f"plan: {self.plan}",
                 f"inputs: {self.inputs}"]
        lines += [a.pretty() for a in self.alternatives]
        return "\n".join(lines)


class Database:
    """Tables + sessions + planner over one fabric transport."""

    def __init__(self, transport=None, *, net="rdma",
                 model_nodes: Optional[int] = None, jit: bool = True):
        """net: what the planner models the wire as — a
        :class:`~repro.fabric.NetworkProfile`, a preset name
        ("ethernet_1g" ... "rdma_edr"), or a legacy key; see
        docs/netsim.md.

        jit=False runs commit bodies eagerly — the right trade for
        workloads that commit many *distinctly-shaped* one-off waves
        (fig_scale's worker sweep), where per-shape compile time dwarfs
        the device work; steady-shape serving keeps the default."""
        self.transport = transport or fabric.LocalTransport()
        self._jit = bool(jit)
        self.pool = fabric.NamPool()
        nodes = (model_nodes if model_nodes is not None else
                 (self.transport.n if self.transport.n > 1
                  else DEFAULT_MODEL_NODES))
        self.planner = Planner(net=net, nodes=nodes)
        self.tables: dict = {}
        # timestamp oracle: cid 1 is the load epoch, live txns start at 2
        self.pool.alloc("oracle/clock", (1,), jnp.uint32, ("replicated",))
        self._clock = jnp.full((1,), 2, jnp.uint32)
        self._jit_cache: dict = {}
        # request numbers: the ids of the spans of each commit wave,
        # query and session (repro.obs)
        self._waves = itertools.count()
        self._queries = itertools.count()
        self._sessions = itertools.count()
        # per-txn outcome economics (commit/abort/retry counts — the
        # contention side of the ledger the wire counters can't see)
        self.txn_stats = {"commits": 0, "aborts": 0, "retries": 0,
                          "backoff_slots": 0}

    # ------------------------------------------------------------ tables --

    def create_table(self, name: str, num_records: int, *,
                     payload_words: int = 4, version_slots: int = 1,
                     partitioning: str = "range",
                     num_timestamps: int = 60_000) -> Table:
        schema = TableSchema(name=name, num_records=num_records,
                             payload_words=payload_words,
                             version_slots=version_slots,
                             partitioning=partitioning)
        t = Table(schema, self.pool, self.transport,
                  num_timestamps=num_timestamps)
        self.tables[name] = t
        return t

    def load_table(self, name: str, keys, vals, *,
                   partitioning: str = "hash") -> Table:
        """Create + bulk-load an OLAP relation in one call (payload word 0
        holds the value column; hash partitioning = shuffle by key)."""
        t = self.create_table(name, num_records=len(keys), payload_words=1,
                              partitioning=partitioning)
        return t.load(keys, vals)

    def table(self, name_or_table) -> Table:
        if isinstance(name_or_table, Table):
            return name_or_table
        return self.tables[name_or_table]

    # ---------------------------------------------------- timestamp oracle --

    def claim_cids(self, k: int) -> np.ndarray:
        """Claim k commit timestamps with one FETCH_ADD on the oracle word
        (every client bumps the same counter — §3.2's decentralized pull)."""
        with obs.span("namdb.sync.claim"):
            fetched, self._clock = self.transport.fetch_add(
                self._clock, jnp.zeros((k,), jnp.int32),
                jnp.ones((k,), jnp.uint32), region="oracle/clock")
            return np.asarray(fetched, np.uint32)

    def read_timestamp(self, **ids) -> int:
        """Current read snapshot: every cid below the clock is decided
        (committed or burned — RSI aborts consume their slot too).
        ``ids`` tag its ``namdb.sync.oracle`` span (``Session.begin``
        passes ``session``)."""
        with obs.span("namdb.sync.oracle", **ids):
            return int(self._clock[0]) - 1

    # ---------------------------------------------------------- sessions --

    def session(self, isolation: str = "rsi") -> Session:
        return Session(self, isolation=isolation)

    def snapshot_read(self, table, recs, rid: Optional[int] = None):
        """Vectorized snapshot read outside any session (recs of any
        shape): newest version with CID <= rid (default: the oracle's
        current read timestamp), as counted one-sided READs.
        Returns (payload, read_cids, ok)."""
        with obs.span("namdb.snapshot_read"):
            t = self.table(table)
            rid = self.read_timestamp() if rid is None else int(rid)
            return rsi.read_snapshot(t.store, jnp.asarray(recs, jnp.int32),
                                     jnp.uint32(rid),
                                     transport=self.transport,
                                     region_ns=f"{t.schema.name}/")

    def commit(self, sessions: List[Session], *, chunks: int = 1,
               priority=None, max_retries: int = 0) -> np.ndarray:
        """Commit a wave of concurrent sessions as ONE batched fabric
        commit (one routed prepare + one routed install round trip; both
        rounds reuse a single RoutePlan — the wave is binned to home
        shards once).  Returns the per-session committed mask.

        Sessions may write several tables: the wave's writes in all of
        them go through one routed prepare round (one install round per
        table), every transaction takes one CID for all its tables, and
        it commits in all of them or in none.

        max_retries: re-run aborted writers up to this many extra rounds.
        Each retry waits out :func:`backoff_slots` (deterministic jitter by
        txn id — replayable, no runtime RNG; priced as sim compute when a
        tracer is attached), re-reads its write set's current versions
        (counted READs, *after* the abort round's commit-complete fence)
        and revalidates against them with a fresh cid.  Outcomes land in
        ``txn_stats`` / the ``"txn"`` entry of :meth:`fabric_stats`."""
        if not sessions:
            return np.zeros((0,), bool)
        with obs.span("namdb.commit", wave=next(self._waves)):
            self._commit_wave(sessions, chunks=chunks, priority=priority)
            self._retry_losers(sessions, chunks=chunks,
                               max_retries=max_retries)
        return np.asarray([bool(s.committed) for s in sessions], bool)

    def _commit_wave(self, sessions: List[Session], *, chunks: int = 1,
                     priority=None) -> np.ndarray:
        """One commit round for one wave — no retries, no accounting."""
        if not sessions:
            return np.zeros((0,), bool)
        isolation = sessions[0].isolation
        if any(s.isolation != isolation for s in sessions):
            raise ValueError("mixed isolation levels in one commit wave")
        # read-only sessions commit trivially under SI (no validate+lock)
        wave = sessions
        for s in wave:
            if not s.tables:
                s.committed = True
        sessions = [s for s in wave if s.tables]
        if not sessions:
            return np.ones((len(wave),), bool)
        # every table the wave writes, in one routed commit: one prepare
        # round for all of them, one CID per transaction shared by all
        tables = [self.table(n)
                  for n in sorted({n for s in sessions for n in s.tables})]
        if len(tables) > 1 and isolation != "rsi":
            raise ValueError(
                f"the {isolation} backend commits one table per wave, got "
                f"{[t.schema.name for t in tables]}: multi-table "
                "transactions commit under rsi")
        txns, cids = self._pack_txns(tables, sessions)
        prio = None if priority is None else jnp.asarray(priority, jnp.int32)
        if len(tables) == 1:
            t, = tables
            ok, t.store = self._jit_commit(isolation, chunks,
                                           f"{t.schema.name}/")(
                t.store, txns[0], prio)
        else:
            ok, stores = self._jit_commit(
                isolation, chunks,
                tuple(f"{t.schema.name}/" for t in tables))(
                tuple(t.store for t in tables), tuple(txns), prio)
            for t, store in zip(tables, stores):
                t.store = store
        for t in tables:
            self._complete_bitvec(t, cids)
        with obs.span("namdb.sync.outcome"):
            ok = np.asarray(ok)
        self._assign_outcomes(sessions, ok, cids)
        return np.asarray([s.committed for s in wave], bool)

    def _complete_bitvec(self, t: Table, cids):
        """msg 3 completion: the routed commit body only flips bitvector
        bits inside each client shard's local range, but the facade's
        oracle hands out *globally* contiguous cids (scalar SI
        timestamps), so on a sharded NAM the out-of-range flips are
        finished here — unsignaled one-sided WRITEs of the clients' own
        slots (committed and aborted txns both burn theirs).  The store
        then goes back to its home-shard layout."""
        if self.transport.n > 1:
            with obs.span("namdb.commit.complete"):
                t.store["bitvec"] = self.transport.write(
                    t.store["bitvec"], jnp.asarray(cids, jnp.int32),
                    jnp.ones((len(cids),), bool),
                    region=f"{t.schema.name}/bitvec")
                t.store = self.transport.place(t.store)

    def _assign_outcomes(self, sessions, ok, cids):
        for s, committed, cid in zip(sessions, np.asarray(ok), cids):
            s.committed = bool(committed)
            s.cid = int(cid)
            s.attempts += 1
            if s.txn_id is None:
                # stable retry identity: the txn's FIRST claimed cid
                # (globally unique — retries claim fresh cids but keep
                # jittering off this one)
                s.txn_id = int(cid)

    # ------------------------------------------------- retry economics --

    def _retry_losers(self, sessions: List[Session], *, chunks: int,
                      max_retries: int):
        """Bounded retry loop over a wave's aborted writers + outcome
        accounting for the whole wave (commits include read-only txns)."""
        losers = [s for s in sessions if s.tables and not s.committed]
        self.txn_stats["aborts"] += len(losers)
        attempt = 1
        while losers and attempt <= max_retries:
            with obs.span("namdb.commit.retry", attempt=attempt):
                self._backoff(losers, attempt)
                self._refresh_losers(losers)
                self.txn_stats["retries"] += len(losers)
                # dyadic chunking: retry waves run in power-of-two sizes
                # so the whole sweep's wave shapes form a tiny closed set
                # and the jit / eager op caches stay warm (loser counts
                # are otherwise all distinct).  A chunk-2 txn that loses
                # a row to chunk 1 just fails validation and burns this
                # attempt — the same bounded-retry semantics, one round
                # later.
                for chunk in _dyadic(losers):
                    self._commit_wave(chunk, chunks=chunks)
                losers = [s for s in losers if not s.committed]
                self.txn_stats["aborts"] += len(losers)
            attempt += 1
        self.txn_stats["commits"] += sum(bool(s.committed) for s in sessions)

    def _refresh_losers(self, losers: List[Session]):
        """Batched retry refresh: per written table, ONE counted READ
        re-fetches the current lock|CID word of every loser's write set in
        it (the retry wave pays one verb per table, not one per session —
        same coalescing argument as group commit), then each session
        revalidates against its slice.  Issued after the losing round's
        commit-complete fence, which is what makes the retry race-free
        (``fabric.check`` has the seeded counterexample).  Equivalent to
        per-session :meth:`Session.refresh_read_cids`."""
        refreshed = []         # (session, table, its records' words)
        for name in sorted({n for s in losers for n in s.tables}):
            mine = [s for s in losers if name in s.tables]
            per = [s.writes(name)[0] for s in mine]
            t = self.table(name)
            with obs.span("namdb.sync.refresh"):
                words = self.transport.read(
                    t.store["words"],
                    jnp.asarray(np.concatenate(per), jnp.int32),
                    region=f"{name}/words")
                words = (np.asarray(words, np.uint32)
                         & np.uint32(int(rsi.CID_MASK)))
            split = np.cumsum([r.shape[0] for r in per])[:-1]
            refreshed += [(s, name, w)
                          for s, w in zip(mine, np.split(words, split))]
        rid = self.read_timestamp()
        for s, name, words in refreshed:
            s.revalidate(name, words, rid)

    def _backoff(self, losers: List[Session], attempt: int):
        slots = sum(backoff_slots(s.txn_id or 0, attempt) for s in losers)
        self.txn_stats["backoff_slots"] += slots
        tracer = getattr(self.transport, "tracer", None)
        if tracer is not None and slots:
            # losers back off concurrently: the wave waits out the LONGEST
            # jitter, not the sum (the sum is the economics counter above)
            worst = max(backoff_slots(s.txn_id or 0, attempt)
                        for s in losers)
            tracer.emit_compute(worst * BACKOFF_SLOT_S)

    def commit_grouped(self, groups: List[List[Session]], *,
                       chunks: Optional[int] = None, priority=None,
                       max_retries: int = 0) -> List[np.ndarray]:
        """Commit K per-worker session groups as ONE coalesced RSI wave
        (:func:`repro.core.rsi.commit_grouped`): one RoutePlan build and
        one prepare/install/grant collective triple for the whole group,
        with per-chunk doorbells keeping the wire message counts
        bit-identical to K solo :meth:`commit` calls.  Timestamps are
        claimed group-by-group, so cid assignment matches the sequential
        order too.  Returns the per-group committed masks; retry
        semantics as in :meth:`commit` (losers across all groups retry
        together as plain waves)."""
        groups = [list(g) for g in groups]
        flat = [s for g in groups for s in g]
        if not flat:
            return [np.zeros((0,), bool) for _ in groups]
        if any(s.isolation != "rsi" for s in flat):
            raise ValueError("commit_grouped is RSI-only")
        for s in flat:
            if not s.tables:
                s.committed = True
        writer_groups = [[s for s in g if s.tables] for g in groups]
        writer_groups = [g for g in writer_groups if g]
        if writer_groups:
            t = self._one_table("commit_grouped",
                                [s for g in writer_groups for s in g])
            packed = [self._pack_txns([t], g) for g in writer_groups]
            batches = [txns for (txns,), _ in packed]
            cids = np.concatenate([c for _, c in packed])
            oks, t.store = self._jit_commit_grouped(
                chunks, f"{t.schema.name}/", len(batches))(
                t.store, batches,
                None if priority is None else
                [jnp.asarray(p, jnp.int32) for p in priority])
            with obs.span("namdb.sync.outcome"):
                ok = np.concatenate([np.asarray(o) for o in oks])
            self._complete_bitvec(t, cids)
            self._assign_outcomes(
                [s for g in writer_groups for s in g], ok, cids)
        self._retry_losers(flat, chunks=1, max_retries=max_retries)
        return [np.asarray([bool(s.committed) for s in g], bool)
                for g in groups]

    def _jit_commit_grouped(self, chunks, region_ns: str, K: int):
        key = ("commit_grouped", K, chunks, region_ns)

        def fn(store, batches, prio):
            return rsi.commit_grouped(store, batches,
                                      transport=self.transport,
                                      priority=prio, chunks=chunks,
                                      region_ns=region_ns)
        if (not self._jit
                or getattr(self.transport, "recorder", None) is not None):
            return fn          # eager: exact recorded access intervals
        if key not in self._jit_cache:
            self._jit_cache[key] = jax.jit(fn)
        return self._jit_cache[key]

    def _one_table(self, entry: str, sessions: List[Session]) -> Table:
        names = {n for s in sessions for n in s.tables}
        if len(names) != 1:
            raise ValueError(
                f"{entry} commits one table per wave, got {sorted(names)}: "
                "commit multi-table transactions with Database.commit")
        return self.table(names.pop())

    def _pack_txns(self, tables: List[Table], sessions: List[Session]):
        """Batch one wave of writer sessions into a TxnBatch per table
        (the wave's T rows of fixed W write slots, record -1 = unused —
        also where a transaction does not write the table) and claim its
        commit timestamps: one per transaction, shared by its tables."""
        with obs.span("namdb.commit.pack", tables=len(tables)):
            T = len(sessions)
            packed = []
            for t in tables:
                name = t.schema.name
                writes = [s.writes(name) if name in s.tables else None
                          for s in sessions]
                W = max(w[0].shape[0] for w in writes if w is not None)
                m = t.schema.payload_words
                recs = np.full((T, W), -1, np.int32)
                pay = np.zeros((T, W, m), np.uint32)
                rcids = np.zeros((T, W), np.uint32)
                for i, w in enumerate(writes):
                    if w is not None and w[0].shape[0]:
                        r, p, rc = w
                        recs[i, :r.shape[0]] = r
                        pay[i, :r.shape[0]] = p
                        rcids[i, :r.shape[0]] = rc
                packed.append((recs, rcids, pay))
            cids = self.claim_cids(T)
            cid = jnp.asarray(cids)
            txns = [rsi.TxnBatch(write_recs=jnp.asarray(recs),
                                 read_cids=jnp.asarray(rcids),
                                 new_payload=jnp.asarray(pay), cid=cid)
                    for recs, rcids, pay in packed]
            return txns, cids

    def commit_pipelined(self, waves: List[List[Session]], *,
                         chunks: int = 1,
                         max_retries: int = 0) -> List[np.ndarray]:
        """Commit K *dependent* session waves with wave i's install round
        trip overlapping wave i+1's prepare round trip
        (:func:`repro.core.rsi.commit_pipelined` — RSI only).  Semantically
        identical to K sequential :meth:`commit` calls on the same waves;
        the overlap changes the schedule, never the outcome.  Returns the
        per-wave committed masks."""
        waves = [list(w) for w in waves]
        writer_meta = []        # (sessions, cids) per writer wave, in order
        txns_list = []
        table = None
        for w in waves:
            if any(s.isolation != "rsi" for s in w):
                raise ValueError("commit_pipelined is RSI-only")
            for s in w:
                if not s.tables:
                    s.committed = True
            writers = [s for s in w if s.tables]
            if not writers:
                continue
            t = self._one_table("commit_pipelined", writers)
            if table is None:
                table = t
            elif t is not table:
                raise ValueError(
                    "commit_pipelined commits one table across its waves, "
                    f"got {table.schema.name!r} and {t.schema.name!r}: "
                    "commit multi-table transactions with Database.commit")
            (txns,), cids = self._pack_txns([t], writers)
            txns_list.append(txns)
            writer_meta.append((writers, cids))
        if txns_list:
            oks, table.store = self._jit_commit_pipelined(
                chunks, f"{table.schema.name}/", len(txns_list))(
                table.store, txns_list)
            for (sessions, cids), ok in zip(writer_meta, oks):
                self._complete_bitvec(table, cids)
                with obs.span("namdb.sync.outcome"):
                    ok = np.asarray(ok)
                self._assign_outcomes(sessions, ok, cids)
        self._retry_losers([s for w in waves for s in w], chunks=chunks,
                           max_retries=max_retries)
        return [np.asarray([s.committed for s in w], bool) for w in waves]

    def _jit_commit_pipelined(self, chunks: int, region_ns: str, K: int):
        key = ("commit_pipelined", K, chunks, region_ns)

        def fn(store, txns_list):
            return rsi.commit_pipelined(store, txns_list,
                                        transport=self.transport,
                                        chunks=chunks, region_ns=region_ns)
        if (not self._jit
                or getattr(self.transport, "recorder", None) is not None):
            return fn          # eager: exact recorded access intervals
        if key not in self._jit_cache:
            self._jit_cache[key] = jax.jit(fn)
        return self._jit_cache[key]

    def _jit_commit(self, isolation: str, chunks: int, region_ns: str = ""):
        key = ("commit", isolation, chunks, region_ns)
        backend = _BACKENDS[isolation]
        if (not self._jit
                or getattr(self.transport, "recorder", None) is not None):
            # a schedule recorder needs concrete verb indices: run the
            # commit body eagerly (uncached) so the recorded access
            # intervals are exact, not whole-region conservative
            return lambda store, txns, prio: backend(
                store, txns, transport=self.transport, priority=prio,
                chunks=chunks, region_ns=region_ns)
        if key not in self._jit_cache:
            self._jit_cache[key] = jax.jit(
                lambda store, txns, prio: backend(
                    store, txns, transport=self.transport, priority=prio,
                    chunks=chunks, region_ns=region_ns))
        return self._jit_cache[key]

    # ------------------------------------------------------------ queries --

    def scan(self, table) -> Plan:
        name = table.schema.name if isinstance(table, Table) else table
        if name not in self.tables:
            raise KeyError(f"no table {name!r}")
        return Plan("scan", table=name)

    def _planner_for(self, profile, load: int = 0) -> Planner:
        """The db's planner, or a per-(profile, load) one (same modeled
        cluster) for sweeping the 1GbE -> EDR axis and the tenant-load
        axis without touching db state."""
        load = max(int(load), 0)
        if profile is None and load == self.planner.load:
            return self.planner
        base = profile if profile is not None else self.planner.profile
        return Planner(net=base, nodes=self.planner.nodes, load=load)

    def _analyze(self, plan: Plan, planner: Optional[Planner] = None):
        """(kind, alternatives argmin-first, cost-model inputs)."""
        planner = planner or self.planner
        kind = plan.kind()
        if kind == "join_agg":
            join = plan.children[0]
            left, right = join.children
            rtab = self.table(left.scan_table())
            stab = self.table(right.scan_table())
            sel = left.selectivity() * right.selectivity()
            nr, ns = rtab.stats()["bytes"], stab.stats()["bytes"]
            alts = planner.join_alternatives(nr, ns, sel)
            return kind, alts, {"nr_bytes": nr, "ns_bytes": ns, "sel": sel,
                                "net": planner.net, "load": planner.load,
                                "profile": planner.profile.name}
        if kind == "group_agg":
            if plan.groups is None:
                raise ValueError("a group aggregate needs "
                                 ".aggregate(groups=G); bare .aggregate() "
                                 "is the scalar join aggregate")
            child = plan.children[0]
            tab = self.table(child.scan_table())
            nb = tab.stats()["bytes"]
            alts = planner.agg_alternatives(nb, plan.groups)
            return kind, alts, {"nbytes": nb, "groups": plan.groups,
                                "nodes": planner.nodes,
                                "net": planner.net, "load": planner.load,
                                "profile": planner.profile.name}
        raise ValueError(f"cannot plan a bare {kind} — add .aggregate()")

    def explain(self, plan: Plan, *, profile=None, load: int = 0) -> Explain:
        """Costed alternatives for a plan, argmin first — no execution.
        `profile` prices the plan on another point of the network axis
        (preset name or NetworkProfile) without changing db state;
        `load` prices it under that many concurrent tenant streams
        (``sim.contended_profile``, docs/netsim.md) — the argmin under
        contention can differ from the isolated one (fig10)."""
        kind, alts, inputs = self._analyze(plan,
                                           self._planner_for(profile, load))
        return Explain(plan.describe(), kind, tuple(alts), inputs)

    def execute(self, plan: Plan, *, force_variant: Optional[str] = None,
                capacity_factor: float = 2.0,
                calibrate: bool = False, profile=None,
                load: int = 0) -> QueryResult:
        """Run a plan with the planner's choice (or `force_variant` for
        benchmark grids).  Returns value + the full costed explain.
        `profile` plans under another network profile (the executed
        operators are the same code; only the choice moves).

        calibrate=True re-runs the compiled operator once more and feeds
        the planner this shape's traced fabric byte counters plus the
        *cached-run* wall clock (compile time excluded) minus the variant's
        modeled compute share, so later plans are priced with the measured
        wire rate, and returns that counter delta as ``stats`` (empty
        without calibrate).  Needs a fresh plan shape on this database —
        counters accumulate at trace time only (see docs/fabric.md)."""
        with obs.span("namdb.execute", query=next(self._queries)):
            with obs.span("namdb.execute.plan"):
                kind, alts, inputs = self._analyze(
                    plan, self._planner_for(profile, load))
            variant = force_variant or Planner.chosen(alts)
            if force_variant:
                known = {a.name for a in alts}
                if force_variant not in known:
                    raise ValueError(
                        f"{force_variant!r} not in {sorted(known)}")
            if kind == "join_agg":
                join = plan.children[0]
                rtab = self.table(join.children[0].scan_table())
                stab = self.table(join.children[1].scan_table())
                f = self._jit_join(variant, capacity_factor)
                args = rtab.scan_arrays() + stab.scan_arrays()
            else:
                tab = self.table(plan.children[0].scan_table())
                f = self._jit_agg(variant, plan.groups)
                args = tab.scan_arrays()
            before = self._stats_totals() if calibrate else None
            with obs.span("namdb.execute.run"):
                value = jax.block_until_ready(f(*args))
            stats = {}
            if calibrate:
                stats = self._stats_delta(before)
                t0 = time.perf_counter()
                with obs.span("namdb.execute.run"):
                    value = jax.block_until_ready(f(*args))  # now cached
                elapsed = time.perf_counter() - t0
                if stats:
                    self.planner.calibrate(
                        stats, elapsed,
                        compute_s=self.planner.compute_share(kind, variant,
                                                             inputs))
        return QueryResult(value=value, variant=variant,
                           alternatives=tuple(alts), plan=plan, stats=stats)

    def _jit_join(self, variant: str, capacity_factor: float):
        key = ("join", variant, capacity_factor)
        if key not in self._jit_cache:
            self._jit_cache[key] = jax.jit(shuffle.make_distributed_join(
                self.transport, variant, capacity_factor=capacity_factor))
        return self._jit_cache[key]

    def _jit_agg(self, variant: str, groups: int):
        key = ("agg", variant, groups)
        if key not in self._jit_cache:
            mk = (aggregation.dist_agg if variant == "dist_agg"
                  else aggregation.rdma_agg)
            self._jit_cache[key] = jax.jit(mk(self.transport, groups))
        return self._jit_cache[key]

    # ------------------------------------------------------- observability --

    def _stats_totals(self) -> dict:
        return {k: dict(v) for k, v in self.transport.stats().items()}

    def _stats_delta(self, before: dict) -> dict:
        out = {}
        for verb, s in self.transport.stats().items():
            b = before.get(verb, {})
            d = {}
            for k, v in s.items():
                if isinstance(v, dict):
                    # queue_hist: histogram delta per bucket
                    bv = b.get(k, {})
                    hd = {kk: vv - bv.get(kk, 0) for kk, vv in v.items()
                          if vv - bv.get(kk, 0)}
                    if hd:
                        d[k] = hd
                elif k == "peak_outstanding":
                    # a high-water mark, not a counter: report the
                    # current peak, it cannot be differenced
                    d[k] = v
                else:
                    d[k] = v - b.get(k, 0)
            numeric = {k: v for k, v in d.items()
                       if k not in ("peak_outstanding",)
                       and not isinstance(v, dict)}
            if any(numeric.values()):
                out[verb] = d
        return out

    def fabric_stats(self) -> dict:
        """Cumulative per-verb message/byte counters (trace-time; see
        docs/fabric.md for semantics), plus a ``"txn"`` pseudo-verb with
        the commit/abort/retry economics once any transaction has
        committed through this database (msgs/bytes stay 0 — outcomes
        aren't wire traffic; the wire side of a retry shows up under the
        real verbs it reissues)."""
        stats = dict(self.transport.stats())
        if any(self.txn_stats.values()):
            stats["txn"] = {"calls": self.txn_stats["commits"]
                            + self.txn_stats["aborts"],
                            "msgs": 0, "bytes": 0, **self.txn_stats}
        # two-tier traffic: once any verb ran tiered (read_hot/read_cold,
        # write_hot/write_cold), summarize the hot-tier hit rate per verb
        # under a "tiers" pseudo-verb so the read storm is visible next to
        # the raw per-tier counters (peak_outstanding/queue_hist live in
        # the read_cold/... entries themselves)
        rates = {}
        for verb in ("read", "write"):
            hot = stats.get(f"{verb}_hot", {}).get("msgs", 0)
            cold = stats.get(f"{verb}_cold", {}).get("msgs", 0)
            if hot + cold:
                rates[f"{verb}_hot_rate"] = hot / (hot + cold)
        if rates:
            stats["tiers"] = {"calls": 0, "msgs": 0, "bytes": 0, **rates}
        return stats
