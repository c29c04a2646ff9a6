"""Pluggable transports: where the verbs and the router actually run.

A transport binds the verb layer (``repro.fabric.verbs``) and the request
router (``repro.fabric.route``) to an execution substrate:

  * :class:`LocalTransport` — one shard, no collectives.  ``route`` stays a
    local radix partition, ``exchange``/``psum``/``all_gather`` are
    identities.  This is the single-node degenerate case of the NAM
    architecture, useful for ground truth and for measuring the pure
    compute path.
  * :class:`MeshTransport` — the NAM deployment: protocol bodies run under
    ``shard_map`` over a named mesh axis, ``route`` pairs the radix
    partition with a (chunkable) ``all_to_all``, and ``psum`` /
    ``all_gather`` are the real collectives.

Every transport **counts messages and bytes per verb** (read / write / cas /
fetch_add / route / exchange / psum / all_gather).  Counting happens at
trace time — economics depend only on static shapes, so each traced
(logical) execution accumulates exactly once; benchmarks report the
resulting per-call counts next to the paper's analytic model.  Because a
cached jit never re-traces, ``reset_stats()`` followed by a call to an
already-compiled function records nothing — use a fresh transport (and
re-jit) per experiment.

Counter semantics: these are **capacity counts** — the fixed-buffer wire
reservations of the paper's software-managed-buffer design, not occupancy.
``route``/``exchange`` bytes are exact (a fixed (n, cap) buffer travels in
full regardless of fill); verb msgs count every buffer slot handed to the
verb, which is exact under ``LocalTransport`` (cap = batch size) and an
upper bound per shard under ``MeshTransport`` (each home shard scans its
full n*cap receive buffer).  A ``route`` is ``n * chunks`` messages — the
fields and the valid mask travel in ONE packed u32 buffer per peer per
pipelined chunk, independent of field count — and its bytes are the
packed buffer (word-padded rows, valid lane included).  ``plan_route`` is
local compute: counted in ``plan_builds``, never in ``stats()``.

A transport may also carry a :class:`~repro.fabric.netsim.NetworkProfile`
(``profile=`` — a preset name like ``"rdma_edr"`` or a profile instance).
With a profile bound, every counted verb additionally accumulates
``modeled_s``: the wall-clock the counted traffic *would* cost on that
network (setup + per-message + bandwidth terms, see docs/netsim.md), so a
single run prices itself on any point of the paper's 1GbE -> EDR axis.
``modeled_time()`` totals it — or re-prices the same counters under a
different profile, which is how ``benchmarks/run.py --profile all`` sweeps
the axis without re-running the workload.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.fabric import netsim
from repro.fabric import router as _router
from repro.fabric import verbs as _verbs


def _row_bytes(arr) -> int:
    return math.prod(arr.shape[1:]) * arr.dtype.itemsize


def _depth_bucket(q: int) -> str:
    """Power-of-two queue-depth bucket label: "0", "1-1", "2-3", "4-7"...
    (string keys so the histogram survives a BENCH_*.json round trip)."""
    if q <= 0:
        return "0"
    lo = 1 << (int(q).bit_length() - 1)
    return f"{lo}-{2 * lo - 1}"


class Transport:
    """Base transport: verb dispatch + trace-time message/byte accounting.

    profile: optional network profile (name or instance) — when bound,
    counted verbs also accumulate modeled wall-clock (``modeled_s``).

    recorder: optional :class:`~repro.fabric.check.ScheduleRecorder` —
    when attached, verbs called with a ``region=`` name append access
    records and synchronization points append ordering edges, feeding the
    one-sided race detector (``repro.fabric.check``, pass 2).  Recording
    is observation only: it never changes computation or counters.

    tracer: optional :class:`~repro.fabric.sim.EventTracer` — when
    attached, every counted verb call also appends one
    :class:`~repro.fabric.sim.SimEvent` (verb, msgs, bytes, issue order,
    window), so the run can be replayed through the contention simulator
    on any profile (``sim.replay``, docs/netsim.md "netsim v2").  Like
    the recorder, observation only."""

    axis: Optional[str] = None

    def __init__(self, profile=None, recorder=None, tracer=None):
        self._stats: dict = {}
        self._local_stats: dict = {}
        self.plan_builds: int = 0
        self.profile = (netsim.get_profile(profile)
                        if profile is not None else None)
        self.recorder = recorder
        self.tracer = tracer

    # ------------------------------------------------------ accounting ---

    def _count(self, verb: str, msgs: int, nbytes: int, *,
               window: int = 0, collective: bool = False):
        s = self._stats.setdefault(verb, {"calls": 0, "msgs": 0, "bytes": 0})
        s["calls"] += 1
        s["msgs"] += int(msgs)
        s["bytes"] += int(nbytes)
        # load context for from_counters() fits: how many of this call's
        # requests are in flight at once (the declared window caps it) and
        # how many sat queued behind the window, bucketed
        outstanding = min(int(msgs), window) if window else int(msgs)
        s["peak_outstanding"] = max(s.get("peak_outstanding", 0),
                                    outstanding)
        hist = s.setdefault("queue_hist", {})
        b = _depth_bucket(int(msgs) - outstanding)
        hist[b] = hist.get(b, 0) + 1
        if self.profile is not None:
            s["modeled_s"] = (s.get("modeled_s", 0.0)
                              + self.profile.t_call(msgs, nbytes))
        if self.tracer is not None:
            self.tracer.emit(verb, msgs, nbytes,
                             collective=collective and self.n > 1,
                             window=window, fanout=self.n)

    def count_local(self, verb: str, msgs: int, nbytes: int = 0, *,
                    window: int = 0):
        """Count LOCAL-tier traffic (e.g. hot-tier block hits of a
        :class:`~repro.fabric.verbs.TieredRegion`): same counter schema as
        :meth:`_count` — calls/msgs/bytes/peak_outstanding/queue_hist —
        but kept out of the wire ledger: no ``modeled_s``, no tracer
        event (local memory costs no NIC and no link), and excluded from
        :meth:`modeled_time`.  The counters still surface in
        :meth:`stats` (disjoint verb names like ``read_hot``), which is
        how hot/cold hit rates reach ``fabric_stats()`` and the BENCH
        JSON."""
        s = self._local_stats.setdefault(
            verb, {"calls": 0, "msgs": 0, "bytes": 0})
        s["calls"] += 1
        s["msgs"] += int(msgs)
        s["bytes"] += int(nbytes)
        outstanding = min(int(msgs), window) if window else int(msgs)
        s["peak_outstanding"] = max(s.get("peak_outstanding", 0),
                                    outstanding)
        hist = s.setdefault("queue_hist", {})
        b = _depth_bucket(int(msgs) - outstanding)
        hist[b] = hist.get(b, 0) + 1

    def stats(self) -> dict:
        """{verb: {calls, msgs, bytes, peak_outstanding, queue_hist
        [, modeled_s]}} accumulated since reset (``modeled_s`` only when a
        profile is bound; ``queue_hist`` maps power-of-two depth buckets
        like "0"/"1-1"/"2-3" to call counts).  Tiered verbs appear under
        suffixed names (``read_cold`` = wire traffic to a cold region,
        ``read_hot`` = local hot-tier hits via :meth:`count_local` — the
        latter carry no ``modeled_s`` and never enter
        :meth:`modeled_time`)."""
        out = {}
        for src in (self._stats, self._local_stats):
            for k, v in src.items():
                d = dict(v)
                if "queue_hist" in d:
                    d["queue_hist"] = dict(d["queue_hist"])
                out[k] = d
        return out

    def reset_stats(self):
        self._stats = {}
        self._local_stats = {}
        self.plan_builds = 0

    def modeled_time(self, profile=None) -> float:
        """Modeled wall-clock of all counted traffic.  With ``profile``
        given, re-price the same counters under that network instead of
        the bound one — counters are workload, profiles are the axis."""
        p = netsim.get_profile(profile) if profile is not None \
            else self.profile
        if p is None:
            raise ValueError("no profile bound to this transport — pass "
                             "profile= here or at construction")
        return p.modeled_time(self._stats)

    # ------------------------------------------------------- recording ---

    def record_access(self, verb: str, region, idx, *,
                      region_len: Optional[int] = None, meta=None):
        """Record-only hook: log a region access that did not go through a
        verb method (e.g. the RSI payload install, whose bytes are already
        billed to the routed install buffer).  No counting, no compute."""
        if self.recorder is not None and region is not None:
            self.recorder.record(verb, region, idx, region_len=region_len,
                                 meta=meta)

    def _rec_fence(self, kind: str):
        """Record a global ordering edge (a route round-trip / collective
        synchronizes every agent's view of the regions)."""
        if self.recorder is not None:
            self.recorder.fence(kind)

    # ----------------------------------------------------------- verbs ---

    @staticmethod
    def _tiered(verb: str, tier) -> str:
        """Counter key of a tiered verb call: ``read`` -> ``read_cold``
        when the access targets the cold tier of a
        :class:`~repro.fabric.verbs.TieredRegion`.  The recorder still
        sees the plain READ/WRITE (race semantics are tier-blind); only
        the counters, modeled time, and the sim trace carry the tier."""
        return f"{verb}_{tier}" if tier else verb

    def read(self, region_arr, idx, *, region=None, tier=None):
        self._count(self._tiered("read", tier), idx.size,
                    idx.size * _row_bytes(region_arr))
        out = _verbs.read(region_arr, idx)
        if self.recorder is not None and region is not None:
            self.recorder.record("READ", region, idx,
                                 region_len=region_arr.shape[0])
        return out

    def write(self, region_arr, idx, values, *, region=None, tier=None):
        self._count(self._tiered("write", tier), idx.size,
                    values.size * values.dtype.itemsize)
        out = _verbs.write(region_arr, idx, values)
        if self.recorder is not None and region is not None:
            self.recorder.record("WRITE", region, idx,
                                 region_len=region_arr.shape[0])
        return out

    def cas(self, words, idx, expected, new, priority=None, *, region=None):
        self._count("cas", idx.size,
                    idx.size * (expected.dtype.itemsize + new.dtype.itemsize))
        ok, out = _verbs.cas(words, idx, expected, new, priority=priority)
        if self.recorder is not None and region is not None:
            self.recorder.record("CAS", region, idx,
                                 region_len=words.shape[0], ok=ok, new=new)
        return ok, out

    def fetch_add(self, words, idx, delta, priority=None, *, region=None):
        self._count("fetch_add", idx.size, idx.size * delta.dtype.itemsize)
        out = _verbs.fetch_add(words, idx, delta, priority=priority)
        if self.recorder is not None and region is not None:
            self.recorder.record("FETCH_ADD", region, idx,
                                 region_len=words.shape[0])
        return out

    # ---------------------------------------------------- async verbs ----

    def _deferred(self, value, acc):
        """Wrap a verb result in a Completion whose wait() fires the
        deferred completion fence (if a recorder saw the access)."""
        rec = self.recorder
        on_wait = (lambda: rec.complete(acc)) if acc is not None else None
        return _verbs.Completion(value, on_wait=on_wait)

    def read_async(self, region_arr, idx, *, region=None, tier=None):
        """Async READ: issue -> overlap -> ``wait()``.  Counts and computes
        exactly like :meth:`read` (JAX arrays are functional — the value is
        ready at issue), but the ordering edge is withheld: the access is
        recorded *deferred* and the READ-completion fence fires only when
        the returned Completion is waited.  An unwaited async READ is an
        unsignaled one-sided request — later writes to the same rows race
        it, and ``fabric.check`` will say so."""
        self._count(self._tiered("read", tier), idx.size,
                    idx.size * _row_bytes(region_arr))
        out = _verbs.read(region_arr, idx)
        acc = None
        if self.recorder is not None and region is not None:
            acc = self.recorder.record("READ", region, idx,
                                       region_len=region_arr.shape[0],
                                       deferred=True)
        return self._deferred(out, acc)

    def write_async(self, region_arr, idx, values, *, region=None,
                    tier=None):
        """Async WRITE.  Same counting/compute as :meth:`write`; the
        difference from the sync verb is that ``wait()`` is a *signaled*
        write — it fires a write-completion fence (an ordering edge the
        plain one-sided WRITE never has), so a waited async WRITE can
        legally precede a dependent access where an unwaited one races."""
        self._count(self._tiered("write", tier), idx.size,
                    values.size * values.dtype.itemsize)
        out = _verbs.write(region_arr, idx, values)
        acc = None
        if self.recorder is not None and region is not None:
            acc = self.recorder.record("WRITE", region, idx,
                                       region_len=region_arr.shape[0],
                                       deferred=True)
        return self._deferred(out, acc)

    # ---------------------------------------------------------- router ---

    def _route_counted(self, fields, dest, *, cap, chunks, plan, mask,
                       window, overlap):
        """Shared body of :meth:`route`/:meth:`route_async`: count the
        wire traffic and run the router — NO fence (the caller decides
        whether the round-trip edge fires now or at ``wait()``)."""
        n = self.n
        if plan is not None:
            cap = plan.cap
            if window is None:
                window = plan.window
        elif cap is None:
            raise ValueError("route needs cap= (or a plan=)")
        nbytes = n * cap * _router.WORD_BYTES * _router.packed_row_words(
            fields)
        self._count("route", n * chunks, nbytes,
                    window=int(window or 0), collective=True)
        # double-buffered path: the router drives the per-chunk pipeline
        # itself, so hand it a plain single-chunk exchange of chunk width.
        exchange = (self._make_exchange(cap // chunks, 1) if overlap
                    else self._make_exchange(cap, chunks))
        with jax.named_scope("fabric.route"):
            return _router.route(fields, dest, n=n, cap=cap, chunks=chunks,
                                 exchange=exchange, plan=plan, mask=mask,
                                 window=window, overlap=overlap)

    def route(self, fields, dest=None, *, cap: Optional[int] = None,
              chunks: int = 1, plan=None, mask=None,
              window: Optional[int] = None, overlap: bool = False):
        """Radix-route a request pytree into (n, cap) buffers and exchange
        them with the peers (see ``repro.fabric.route``).

        Message accounting matches the packed wire format: the fields and
        the valid mask travel in ONE contiguous (n*cap, row_words) u32
        buffer, so a route is ``n * chunks`` messages (one buffer per peer
        per pipelined chunk) **regardless of field count**, and its bytes
        are the packed buffer (word-padded rows, valid lane included).

        plan=: reuse a :class:`~repro.fabric.router.RoutePlan` from
        :meth:`plan_route` (skips the rank-in-bucket pass); mask= unsends
        requests from a reused plan without re-ranking.

        window=: doorbell-batching cap — max in-flight peer buffers when
        this route is priced under contention (defaults to the plan's;
        0/None = post everything at once).  The exchanged bits are
        identical at any window: it feeds the outstanding-request
        counters and the event trace, and ``repro.fabric.sim`` prices it
        (docs/netsim.md "netsim v2").

        overlap=: run the double-buffered chunk pipeline (chunk k+1 packs
        while chunk k is on the wire — ``repro.fabric.router.route``'s
        ``overlap``).  Identical bits and identical counters; a sync
        overlapped route still fences at return."""
        res = self._route_counted(fields, dest, cap=cap, chunks=chunks,
                                  plan=plan, mask=mask, window=window,
                                  overlap=overlap)
        self._rec_fence("route-roundtrip")
        return res

    def route_async(self, fields, dest=None, *, cap: Optional[int] = None,
                    chunks: int = 1, plan=None, mask=None,
                    window: Optional[int] = None, overlap: bool = True):
        """Async route: issue -> overlap -> ``wait()``.  Counts and
        computes exactly like :meth:`route` (default ``overlap=True``:
        the double-buffered pipeline is the point of going async), but
        the **route-roundtrip global fence** moves from issue to the
        returned Completion's ``wait()``.  Work interleaved between issue
        and wait genuinely overlaps the exchange — and accesses that need
        the routed buffers MUST come after ``wait()``, or the race
        detector flags them against the in-flight route."""
        res = self._route_counted(fields, dest, cap=cap, chunks=chunks,
                                  plan=plan, mask=mask, window=window,
                                  overlap=overlap)
        return _verbs.Completion(
            res, on_wait=lambda: self._rec_fence("route-roundtrip"))

    def plan_route(self, dest, *, cap: int, window: int = 0):
        """Precompute the slot assignment for ``dest`` (one sort-free
        rank-in-bucket pass) for reuse across routed rounds.  Local
        compute, not wire traffic — counted in ``plan_builds``, not in
        ``stats()``.  ``window`` declares the plan's doorbell-batching cap
        (see :class:`~repro.fabric.router.RoutePlan`)."""
        self.plan_builds += 1
        return _router.plan_route(dest, n=self.n, cap=cap, window=window)

    # ------------------------------------------------ substrate hooks ----

    @property
    def n(self) -> int:
        raise NotImplementedError

    def _make_exchange(self, cap: int, chunks: int):
        """Exchange callable handed to the router (None = stay local)."""
        raise NotImplementedError

    def run(self, body, args, out_reps):
        """Execute a per-shard protocol body over sharded args.  out_reps:
        bool (single output) or tuple of bool — True = replicated output."""
        raise NotImplementedError

    def place(self, regions):
        """Lay a pytree of region arrays out on their home shards (axis 0
        split across the transport's shards), so protocol bodies find
        each shard's rows where they run."""
        raise NotImplementedError

    def shard_index(self):
        raise NotImplementedError

    def psum(self, x):
        raise NotImplementedError

    def all_gather(self, x):
        raise NotImplementedError

    def exchange(self, v, chunks: int = 1):
        """Paired reverse exchange of a (n*cap, ...) buffer — the response
        return path for routed requests."""
        raise NotImplementedError


class LocalTransport(Transport):
    """Single shard: the router partitions locally, collectives are
    identities. All counters still accumulate (loopback traffic), so the
    measured message economics stay comparable with a MeshTransport run."""

    @property
    def n(self) -> int:
        return 1

    def _make_exchange(self, cap, chunks):
        return None

    def run(self, body, args, out_reps):
        return body(*args)

    def place(self, regions):
        return regions

    def shard_index(self):
        return jnp.int32(0)

    def psum(self, x):
        self._count("psum", 1, x.size * x.dtype.itemsize,
                    collective=True)
        self._rec_fence("psum")
        return x

    def all_gather(self, x):
        self._count("all_gather", 1, x.size * x.dtype.itemsize,
                    collective=True)
        self._rec_fence("all_gather")
        return x

    def exchange(self, v, chunks: int = 1):
        self._count("exchange", chunks, v.size * v.dtype.itemsize,
                    collective=True)
        self._rec_fence("exchange")
        return v


class MeshTransport(Transport):
    """NAM deployment over a mesh axis: bodies run under shard_map, routed
    buffers travel on the paired (chunkable) all_to_all."""

    def __init__(self, mesh, axis: str, profile=None, recorder=None,
                 tracer=None):
        super().__init__(profile=profile, recorder=recorder, tracer=tracer)
        self.mesh = mesh
        self.axis = axis

    @property
    def n(self) -> int:
        return self.mesh.shape[self.axis]

    def _make_exchange(self, cap, chunks):
        n, axis = self.n, self.axis
        return lambda v: _router.chunked_all_to_all(v, axis, n, cap, chunks)

    def run(self, body, args, out_reps):
        from jax.sharding import PartitionSpec as P
        in_specs = tuple(P(self.axis) for _ in args)
        if isinstance(out_reps, bool):
            out_specs = P() if out_reps else P(self.axis)
        else:
            out_specs = tuple(P() if r else P(self.axis) for r in out_reps)
        return jax.shard_map(body, mesh=self.mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False)(*args)

    def place(self, regions):
        from jax.sharding import NamedSharding, PartitionSpec as P
        return jax.device_put(regions,
                              NamedSharding(self.mesh, P(self.axis)))

    def shard_index(self):
        return jax.lax.axis_index(self.axis)

    def psum(self, x):
        self._count("psum", self.n, x.size * x.dtype.itemsize,
                    collective=True)
        self._rec_fence("psum")
        return jax.lax.psum(x, self.axis)

    def all_gather(self, x):
        self._count("all_gather", self.n,
                    self.n * x.size * x.dtype.itemsize, collective=True)
        self._rec_fence("all_gather")
        return jax.lax.all_gather(x, self.axis, tiled=True)

    def exchange(self, v, chunks: int = 1):
        cap = v.shape[0] // self.n
        self._count("exchange", self.n * chunks,
                    v.size * v.dtype.itemsize, collective=True)
        self._rec_fence("exchange")
        return _router.chunked_all_to_all(v, self.axis, self.n, cap, chunks)
