"""fabric-check: static analysis for the one-sided verb fabric.

The paper moves protocol logic off the remote CPU and onto one-sided verbs,
which leaves the *client* carrying the whole correctness burden: nothing on
the far side serializes conflicting READ/WRITE/CAS accesses, and the hot
path's performance rests on fragile trace invariants (one ``all_to_all``
per route direction, zero ``sort`` primitives, a packed u32 wire).  This
module makes both mechanically checkable ("The End of a Myth" argues such
protocols are only trustworthy when their ordering invariants are) with two
passes sharing one report format:

**Pass 1 — jaxpr lint** (:func:`lint_jaxpr` / :func:`lint_fn`): walk a
``jax.make_jaxpr`` trace *structurally* — recursing into ``scan`` /
``cond`` / ``pjit`` / ``shard_map`` sub-jaxprs, never string-matching the
printed jaxpr — under pluggable rules:

  * :class:`CollectiveBudget` — exact collective counts per traced fn
    (a route = exactly ONE ``all_to_all`` out and one back; a syntactic
    site inside a ``scan`` body counts once, not per iteration);
  * :class:`SortFree` — zero ``sort`` primitives in the verb hot paths
    (route / cas / fetch_add / rsi.commit / twopc);
  * :class:`NoHostTransfer` — no host callbacks or device<->host transfer
    primitives inside a verb trace;
  * :class:`PackedWire` — everything crossing an ``all_to_all`` is the
    packed uint32 wire format (docs/fabric.md#the-packed-wire-format).

**Pass 2 — one-sided race detector** (:class:`ScheduleRecorder` +
:func:`check_schedule`): an opt-in recorder on any
:class:`~repro.fabric.Transport` captures per-verb access records (verb
kind, region, slot interval, round index, issuing agent, commit wave) and
ordering edges (route round-trips are global fences; READ / CAS /
FETCH_ADD completions fence their issuing agent; a FETCH_ADD on a declared
epoch region is a global publication fence — the paramserver pattern).
``check_schedule`` derives the happens-before relation from those edges
and reports:

  * ``ww-race`` / ``rw-race`` — WRITE/WRITE and READ/WRITE conflicts on
    overlapping intervals with no ordering path;
  * ``lost-update`` — a plain READ-modify-WRITE on a region concurrently
    touched by a CAS / FETCH_ADD (or a bare WRITE racing an atomic);
  * ``lock-protocol`` — an install WRITE to a protected row whose lock
    word was not CAS-acquired by that session wave
    (:meth:`ScheduleRecorder.declare_locks`);
  * ``staleness`` — a parameter-server pull observing an epoch older than
    ``current - k`` (:meth:`ScheduleRecorder.note_pull`).

**CLI**: ``python -m repro.fabric.check --figure all`` (or
``tools/fabriccheck.py``) lints the canned hot-path traces and race-checks
eager schedules of the real protocols (RSI + 2PC session waves, the lock
table, the parameter-server trainer loop), per figure; ``--json`` writes
the summary that ``benchmarks/run.py --check`` embeds into
``BENCH_<figure>.json``.  Rule catalog: docs/check.md.
"""
from __future__ import annotations

import argparse
import json
import sys
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Callable, Dict, Iterable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# ---------------------------------------------------------------- report --


@dataclass(frozen=True)
class Violation:
    """One rule breach.  ``where`` is a jaxpr path (pass 1) or a region
    (pass 2); ``detail`` names the offending primitive or verb pair."""
    rule: str
    where: str
    detail: str

    def __str__(self) -> str:
        return f"[{self.rule}] {self.where}: {self.detail}"

    def as_dict(self) -> dict:
        return {"rule": self.rule, "where": self.where,
                "detail": self.detail}


@dataclass
class Report:
    """Outcome of one pass over one target."""
    target: str
    rules_run: Tuple[str, ...]
    violations: List[Violation]

    @property
    def ok(self) -> bool:
        return not self.violations

    def render(self) -> str:
        head = f"{'PASS' if self.ok else 'FAIL'} {self.target} " \
               f"(rules: {', '.join(self.rules_run)})"
        return "\n".join([head] + [f"  {v}" for v in self.violations])


def summarize(reports: Iterable[Report]) -> dict:
    """Merge reports into the JSON block ``benchmarks/run.py --check``
    embeds: ``{rules_run, violations, targets, ok}``."""
    reports = list(reports)
    rules = sorted({r for rep in reports for r in rep.rules_run})
    vs = [dict(target=rep.target, **v.as_dict())
          for rep in reports for v in rep.violations]
    return {"rules_run": rules, "violations": vs,
            "targets": [rep.target for rep in reports],
            "ok": not vs}


# ----------------------------------------------- pass 1: jaxpr walking ---


def _as_jaxprs(v):
    """Sub-jaxprs hiding in one eqn param value (ClosedJaxpr, Jaxpr, or a
    list/tuple of them) — duck-typed so no private jax.core imports."""
    if hasattr(v, "eqns"):
        return (v,)
    if isinstance(v, (list, tuple)):
        return tuple(x for x in v if hasattr(x, "eqns"))
    return ()


def iter_eqns(jaxpr, path: Tuple[str, ...] = ()):
    """Yield ``(path, eqn)`` over a (closed) jaxpr and every sub-jaxpr
    reachable through eqn params — ``scan`` bodies, ``cond`` branches,
    ``pjit``/``shard_map`` inner jaxprs — structurally.  ``path`` is the
    tuple of enclosing primitive names, so a site inside a scan reports as
    ``scan/...`` and is counted once regardless of trip count."""
    for eqn in jaxpr.eqns:
        yield path, eqn
        for v in eqn.params.values():
            for sub in _as_jaxprs(v):
                yield from iter_eqns(sub, path + (eqn.primitive.name,))


def count_primitive(jaxpr, name: str) -> int:
    """Structural count of syntactic sites of primitive ``name`` (each
    site inside a scan counts once) — replaces ``str(jaxpr).count(...)``,
    which can false-positive on names embedded in other text and cannot
    attribute counts to sub-jaxprs."""
    return sum(1 for _, e in iter_eqns(jaxpr) if e.primitive.name == name)


def _fmt_path(path: Tuple[str, ...]) -> str:
    return "/".join(path) if path else "<top>"


class Rule:
    """A lint rule: ``run(jaxpr) -> [Violation]``."""
    name = "rule"

    def run(self, jaxpr) -> List[Violation]:  # pragma: no cover
        raise NotImplementedError


class SortFree(Rule):
    """No ``sort`` primitive anywhere in the trace: sorts are the TPU's
    weakest op and the fabric hot paths were rebuilt sort-free (PR 5)."""
    name = "sort-free"

    def run(self, jaxpr):
        return [Violation(self.name, _fmt_path(p),
                          "sort primitive in a verb hot-path trace "
                          "(sort-free binning/arbitration is the contract)")
                for p, e in iter_eqns(jaxpr) if e.primitive.name == "sort"]


class CollectiveBudget(Rule):
    """Exact per-trace collective counts, e.g. ``{"all_to_all": 1}`` for
    one routed direction.  Counted once per syntactic site (scan bodies
    included) — trip counts don't inflate the budget."""
    name = "collective-budget"

    def __init__(self, budget: Dict[str, int]):
        self.budget = dict(budget)

    def run(self, jaxpr):
        out = []
        for prim, want in self.budget.items():
            got = count_primitive(jaxpr, prim)
            if got != want:
                out.append(Violation(
                    self.name, "<top>",
                    f"{got} {prim} site(s) traced, budget is {want}"))
        return out


class NoHostTransfer(Rule):
    """No host callbacks or device<->host transfers inside a verb trace:
    the NAM hot path must stay on-device (a hidden callback would put a
    remote CPU back into the paper's zero-server-CPU path)."""
    name = "no-host-transfer"
    DENY = frozenset({
        "pure_callback", "io_callback", "debug_callback", "callback",
        "python_callback", "outside_call", "host_callback_call",
        "device_put", "infeed", "outfeed",
    })

    def run(self, jaxpr):
        return [Violation(self.name, _fmt_path(p),
                          f"host-side primitive '{e.primitive.name}' "
                          "inside a verb trace")
                for p, e in iter_eqns(jaxpr)
                if e.primitive.name in self.DENY]


class PackedWire(Rule):
    """Everything crossing an ``all_to_all`` must be the packed uint32
    wire format (one word-lane buffer per routed batch, PR 5) — a raw
    leaf on the collective means someone bypassed ``pack_fields``."""
    name = "packed-wire"

    def run(self, jaxpr):
        out = []
        for p, e in iter_eqns(jaxpr):
            if e.primitive.name != "all_to_all":
                continue
            for v in e.invars:
                dt = getattr(getattr(v, "aval", None), "dtype", None)
                if dt is not None and dt != jnp.uint32:
                    out.append(Violation(
                        self.name, _fmt_path(p) or "<top>",
                        f"all_to_all operand dtype {dt} is not the packed "
                        "uint32 wire format"))
        return out


#: the standing hot-path rule set; targets add their CollectiveBudget.
HOT_PATH_RULES: Tuple[Rule, ...] = (SortFree(), NoHostTransfer(),
                                    PackedWire())


def lint_jaxpr(jaxpr, rules: Iterable[Rule], *,
               target: str = "<jaxpr>") -> Report:
    rules = tuple(rules)
    vs = [v for r in rules for v in r.run(jaxpr)]
    return Report(target, tuple(r.name for r in rules), vs)


def lint_fn(fn: Callable, *args, rules: Iterable[Rule],
            target: Optional[str] = None) -> Report:
    """Trace ``fn(*args)`` with ``jax.make_jaxpr`` and lint the result."""
    jaxpr = jax.make_jaxpr(fn)(*args)
    return lint_jaxpr(jaxpr, rules,
                      target=target or getattr(fn, "__name__", "<fn>"))


# ------------------------------------- pass 2: the schedule recorder -----

READ, WRITE, CAS, FETCH_ADD = "READ", "WRITE", "CAS", "FETCH_ADD"
ATOMICS = frozenset({CAS, FETCH_ADD})
#: verbs whose completion the issuing agent must await before using the
#: result — recording one auto-fences that agent (a one-sided round trip).
_COMPLETION_VERBS = frozenset({READ, CAS, FETCH_ADD})


def _concrete(x) -> Optional[np.ndarray]:
    """np.asarray(x), or None when x is an abstract tracer."""
    if x is None:
        return None
    try:
        return np.asarray(x)
    except Exception:  # TracerArrayConversionError et al.
        return None


@dataclass
class Access:
    """One recorded verb access: who touched which rows of which region,
    in which round (global-fence epoch) and commit wave."""
    seq: int
    verb: str
    region: str
    lo: int
    hi: int                       # [lo, hi) row interval
    rows: Optional[np.ndarray]    # concrete touched rows; None = whole
                                  # interval (abstract / traced idx)
    agent: str
    wave: int
    gfence: int                   # global fences seen before this access
    afence: int                   # this agent's local fences before it
    meta: dict = field(default_factory=dict)

    def describe(self) -> str:
        return (f"{self.verb}#{self.seq}(agent={self.agent}, "
                f"round={self.gfence})")


@dataclass(frozen=True)
class Fence:
    """One ordering edge in the happens-before graph: everything recorded
    before it happens-before everything after (global scope) or everything
    the same agent records after (local scope)."""
    seq: int                      # position in the access stream
    kind: str                     # route-roundtrip | read-completion | ...
    scope: Optional[str]          # None = global barrier, else agent name


def _overlap(a: Access, b: Access):
    """Overlapping rows of two same-region accesses, or None.  Returns a
    printable description of the intersection."""
    if a.region != b.region:
        return None
    if a.rows is not None and b.rows is not None:
        inter = np.intersect1d(a.rows, b.rows)
        return _fmt_rows(inter) if inter.size else None
    lo, hi = max(a.lo, b.lo), min(a.hi, b.hi)
    return f"rows {lo}:{hi}" if hi > lo else None


def _fmt_rows(rows: np.ndarray) -> str:
    rows = np.asarray(rows).ravel()
    if rows.size == 0:
        return "rows {}"
    if rows.size > 8:
        return f"rows {int(rows.min())}:{int(rows.max()) + 1} " \
               f"({rows.size} rows)"
    return "rows {" + ", ".join(str(int(r)) for r in rows) + "}"


class ScheduleRecorder:
    """Opt-in verb-schedule recorder for a fabric transport.

    Attach with ``transport.recorder = ScheduleRecorder()`` (or the
    ``recorder=`` constructor kwarg); verbs called with a ``region=`` name
    then append :class:`Access` records, and synchronization points append
    :class:`Fence` edges:

      * ``route()`` / ``exchange()`` / ``psum`` / ``all_gather`` — global
        fences (a routed round trip synchronizes every agent's view);
      * READ / CAS / FETCH_ADD — local fences for the issuing agent (the
        caller must await the completion to use the result);
      * a FETCH_ADD on a region declared with :meth:`declare_epoch` — an
        additional *global* fence (the epoch bump publishes every write
        before it: the paramserver's version-clock pattern);
      * plain WRITE — **no fence**: unsignaled one-sided writes are the
        whole point, and the races they enable are what
        :func:`check_schedule` hunts.

    ``agent(name)`` scopes accesses to a logical issuer (a PS worker, a
    session wave); ``begin_wave()`` advances the commit-wave counter that
    the lock-protocol rule checks acquisitions against.
    """

    def __init__(self):
        self.accesses: List[Access] = []
        self.notes: List[dict] = []
        self.fences: List[Fence] = []
        self._gfence = 0
        self._afence: Dict[str, int] = {}
        self._agents: List[str] = ["main"]
        self._wave = 0
        self.lock_protocols: Dict[str, dict] = {}
        self.epoch_protocols: Dict[str, dict] = {}

    # -------------------------------------------------- declarations ----

    def declare_locks(self, lock_region: str, protected: Iterable[str],
                      *, lock_bit: int = 1 << 31):
        """Declare ``lock_region`` a lock-word column guarding the row
        spaces of ``protected`` regions: a successful CAS installing a
        word with ``lock_bit`` set acquires that row for the current wave;
        an install WRITE to a protected row outside the acquiring wave is
        a ``lock-protocol`` violation."""
        self.lock_protocols[lock_region] = {
            "protected": tuple(protected), "bit": int(lock_bit)}

    def declare_epoch(self, epoch_region: str, *, params_region: str,
                      staleness: int):
        """Declare ``epoch_region`` a version clock for ``params_region``
        with bounded staleness ``k``: FETCH_ADDs on it become global
        publication fences, and pulls noted with :meth:`note_pull` must
        observe an epoch >= current - k."""
        self.epoch_protocols[epoch_region] = {
            "params_region": params_region, "staleness": int(staleness)}

    # ---------------------------------------------------- structure -----

    @property
    def current_agent(self) -> str:
        return self._agents[-1]

    @contextmanager
    def agent(self, name: str):
        """Attribute accesses inside the block to logical agent ``name``."""
        self._agents.append(str(name))
        try:
            yield self
        finally:
            self._agents.pop()

    def begin_wave(self, label: Optional[str] = None) -> int:
        self._wave += 1
        if label:
            self.note("wave", wave=self._wave, label=label)
        return self._wave

    def fence(self, kind: str = "fence", *, local: bool = False,
              agent: Optional[str] = None):
        """Record an ordering edge: global barrier (default) or a local
        completion fence for the current agent.  ``agent=`` pins a local
        fence to a specific issuer regardless of the current agent
        context — a deferred ``Completion.wait()`` may fire outside the
        ``with rec.agent(...)`` block that issued the verb."""
        scope = (str(agent) if agent is not None
                 else self.current_agent) if local else None
        if local:
            self._afence[scope] = self._afence.get(scope, 0) + 1
        else:
            self._gfence += 1
        self.fences.append(Fence(len(self.accesses), kind, scope))

    # ------------------------------------------------------- events -----

    def record(self, verb: str, region: str, idx, *,
               region_len: Optional[int] = None, ok=None, new=None,
               meta: Optional[dict] = None,
               deferred: bool = False) -> Access:
        """Append one verb access.  ``idx`` may be traced — the record
        then covers the whole region conservatively.  CAS records on a
        declared lock region also compute the acquired row set (rows where
        the CAS succeeded installing the lock bit).

        ``deferred=True`` (the async verbs) withholds the completion
        fence this verb would normally auto-append — the issuer overlaps
        other work and the fence fires at ``Completion.wait()`` via
        :meth:`complete`.  An issued-but-never-waited async verb is
        therefore exactly an unsignaled one-sided request, and the races
        it enables are what :func:`check_schedule` reports."""
        cidx = _concrete(idx)
        if cidx is not None:
            rows = np.unique(cidx[cidx >= 0]).astype(np.int64)
            lo, hi = ((int(rows.min()), int(rows.max()) + 1) if rows.size
                      else (0, 0))
        else:
            rows = None
            lo, hi = 0, int(region_len) if region_len else (1 << 62)
        meta = dict(meta or {})
        if verb == CAS and region in self.lock_protocols:
            bit = self.lock_protocols[region]["bit"]
            cok, cnew = _concrete(ok), _concrete(new)
            if cidx is not None and cok is not None and cnew is not None:
                acq = cidx[(cidx >= 0) & cok
                           & ((cnew.astype(np.int64) & bit) != 0)]
                meta["acquired"] = np.unique(acq).astype(np.int64)
        a = Access(seq=len(self.accesses), verb=verb, region=str(region),
                   lo=lo, hi=hi, rows=rows, agent=self.current_agent,
                   wave=self._wave, gfence=self._gfence,
                   afence=self._afence.get(self.current_agent, 0),
                   meta=meta)
        self.accesses.append(a)
        if not deferred:
            if verb in _COMPLETION_VERBS:
                self.fence(f"{verb.lower()}-completion", local=True)
            if verb == FETCH_ADD and region in self.epoch_protocols:
                self.fence("epoch-publish")
        return a

    def complete(self, access: Access):
        """Fire the deferred completion edge of an async verb recorded
        with ``deferred=True`` — the ``Completion.wait()`` fence.  Always
        a local fence for the *issuing* agent (whatever agent context is
        active when the caller finally waits); a waited WRITE becomes a
        signaled write, the local ordering edge a plain WRITE lacks.  A
        FETCH_ADD on a declared epoch region additionally publishes
        globally, exactly as its synchronous completion would."""
        self.fence(f"{access.verb.lower()}-completion", local=True,
                   agent=access.agent)
        if access.verb == FETCH_ADD \
                and access.region in self.epoch_protocols:
            self.fence("epoch-publish")

    def note(self, kind: str, **meta):
        """Append a semantic (non-verb) event, e.g. a PS pull
        observation."""
        self.notes.append({"kind": kind, "seq": len(self.accesses), **meta})

    def note_pull(self, *, region: str, worker, observed_epoch: int,
                  current_epoch: int, staleness: int):
        """Record a bounded-stale parameter pull: which epoch the worker's
        served view carries vs the clock's current value."""
        self.note("ps_pull", region=str(region), worker=worker,
                  observed=int(observed_epoch), current=int(current_epoch),
                  staleness=int(staleness))

    # ----------------------------------------------- happens-before -----

    def happens_before(self, a: Access, b: Access) -> bool:
        """a -> b iff an ordering path exists: a global fence separates
        them, or they share an agent and a local completion fence does."""
        if a.seq >= b.seq:
            return False
        return a.gfence < b.gfence or (a.agent == b.agent
                                       and a.afence < b.afence)

    def concurrent(self, a: Access, b: Access) -> bool:
        return not self.happens_before(a, b) \
            and not self.happens_before(b, a)

    def summary(self) -> dict:
        return {"accesses": len(self.accesses), "fences": len(self.fences),
                "waves": self._wave, "notes": len(self.notes),
                "regions": sorted({a.region for a in self.accesses})}


SCHEDULE_RULES = ("ww-race", "rw-race", "lost-update", "lock-protocol",
                  "staleness")


def check_schedule(rec: ScheduleRecorder, *,
                   target: str = "schedule") -> Report:
    """Race-check a recorded schedule: pairwise conflicts with no
    happens-before path, lost updates around atomics, lock-protocol
    violations, and staleness-bound breaches.  Every violation names the
    offending verb pair (``VERB#seq``) and the region."""
    vs: List[Violation] = []
    seen = set()

    def emit(rule, region, detail, *seqs):
        key = (rule, region, tuple(sorted(seqs)))
        if key not in seen:
            seen.add(key)
            vs.append(Violation(rule, region, detail))

    by_region: Dict[str, List[Access]] = {}
    for a in rec.accesses:
        by_region.setdefault(a.region, []).append(a)

    for region, accs in by_region.items():
        for i, a in enumerate(accs):
            for b in accs[i + 1:]:
                ov = _overlap(a, b)
                if ov is None or not rec.concurrent(a, b):
                    continue
                pair = (a.verb, b.verb)
                if pair == (WRITE, WRITE):
                    emit("ww-race", region,
                         f"{a.describe()} || {b.describe()} on '{region}' "
                         f"{ov}: overlapping WRITEs with no ordering path",
                         a.seq, b.seq)
                elif READ in pair and WRITE in pair:
                    emit("rw-race", region,
                         f"{a.describe()} || {b.describe()} on '{region}' "
                         f"{ov}: READ concurrent with an unordered WRITE",
                         a.seq, b.seq)
                elif WRITE in pair and (a.verb in ATOMICS
                                        or b.verb in ATOMICS):
                    w, c = (a, b) if a.verb == WRITE else (b, a)
                    emit("lost-update", region,
                         f"plain {w.describe()} racing atomic "
                         f"{c.describe()} on '{region}' {ov}: the plain "
                         "WRITE can overwrite the atomic's update",
                         a.seq, b.seq)

    # lost updates around a plain RMW window: READ ->hb-> WRITE by one
    # agent, an atomic lands with no ordering into that window.
    for region, accs in by_region.items():
        atomics = [c for c in accs if c.verb in ATOMICS]
        if not atomics:
            continue
        for r in accs:
            if r.verb != READ:
                continue
            for w in accs:
                if (w.verb != WRITE or w.agent != r.agent
                        or not rec.happens_before(r, w)
                        or _overlap(r, w) is None):
                    continue
                for c in atomics:
                    ov = _overlap(c, w)
                    if ov is None:
                        continue
                    if (c.agent == r.agent and rec.happens_before(r, c)
                            and rec.happens_before(c, w)):
                        # the agent's OWN atomic, program-ordered inside
                        # its READ->WRITE window: the writer holds the
                        # CAS result before writing, so nothing is lost
                        # unknowingly (the retry loop's refresh READ ->
                        # prepare CAS -> install WRITE).  Another agent's
                        # atomic stays flagged even when fenced into the
                        # window — the read predates it, so the write-
                        # back still loses its value.
                        continue
                    if not rec.happens_before(c, r) \
                            and not rec.happens_before(w, c):
                        emit("lost-update", region,
                             f"RMW {r.describe()} -> {w.describe()} by "
                             f"'{r.agent}' on '{region}' with concurrent "
                             f"{c.describe()} {ov}: the read-modify-write "
                             "loses the atomic's update",
                             r.seq, w.seq, c.seq)

    # lock protocol: install WRITEs to protected rows must be covered by a
    # CAS lock acquisition in the same wave.
    for lock_region, proto in rec.lock_protocols.items():
        protected = set(proto["protected"])
        held: Dict[int, set] = {}
        for a in rec.accesses:
            if a.verb == CAS and a.region == lock_region:
                acq = a.meta.get("acquired")
                if acq is not None:
                    held.setdefault(a.wave, set()).update(int(r)
                                                          for r in acq)
            elif a.verb == WRITE and a.region in protected:
                if a.rows is None:
                    continue          # traced install: nothing provable
                bad = [int(r) for r in a.rows
                       if int(r) not in held.get(a.wave, set())]
                if bad:
                    emit("lock-protocol", a.region,
                         f"install {a.describe()} to '{a.region}' "
                         f"{_fmt_rows(np.asarray(bad))} in wave {a.wave}: "
                         f"lock word in '{lock_region}' was not "
                         "CAS-acquired by that session wave",
                         a.seq, a.wave)

    # staleness: every noted pull must observe epoch >= current - k.
    for n in rec.notes:
        if n["kind"] != "ps_pull":
            continue
        lag = n["current"] - n["observed"]
        if lag > n["staleness"]:
            emit("staleness", n["region"],
                 f"pull by worker '{n['worker']}' observed epoch "
                 f"{n['observed']} at current epoch {n['current']} on "
                 f"'{n['region']}': lag {lag} exceeds the bounded-"
                 f"staleness k={n['staleness']}",
                 ("pull", n["seq"], n["worker"]))

    return Report(target, SCHEDULE_RULES, vs)


# ------------------------------------------------ canned lint targets ----

ROUTE_CAP = 32


def _mesh_transport():
    from repro.fabric import MeshTransport
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1,), ("fabric",))
    return MeshTransport(mesh, "fabric")


def lint_route(num_fields: int = 3, *, chunks: int = 1,
               response: bool = False, window: int = 0,
               overlap: bool = False) -> Report:
    """Lint one routed direction (plus optionally the paired response
    exchange) under a mesh transport: budget = 1 all_to_all out (+1 back),
    sort-free, host-free, packed u32 on the wire.  ``window`` routes with
    a doorbell-batching cap — a pacing declaration the simulator prices
    (docs/netsim.md); the lint proves the windowed trace emits the SAME
    single fused collective (pacing must never unfuse the wire).
    ``overlap`` lints the double-buffered chunk pipeline under the SAME
    budget: the per-chunk exchanges live inside one scan, i.e. one
    syntactic site — overlapping compute with the wire must never unfuse
    it either."""
    tp = _mesh_transport()

    def body(*leaves):
        fields = {f"f{i}": leaf for i, leaf in enumerate(leaves)}
        dest = (leaves[0] % jnp.uint32(tp.n)).astype(jnp.int32)
        res = tp.route(fields, dest, cap=ROUTE_CAP, chunks=chunks,
                       window=window or None, overlap=overlap)
        tot = sum(jnp.sum(leaf) for leaf in
                  jax.tree_util.tree_leaves(res.fields))
        if response:
            grant = tp.exchange(res.valid.astype(jnp.uint32))
            tot = tot + jnp.sum(grant)
        return tot

    args = tuple(jnp.ones((16,), jnp.uint32) for _ in range(num_fields))
    budget = CollectiveBudget({"all_to_all": 2 if response else 1})
    name = (f"route[{num_fields}f,chunks={chunks}"
            + (",response" if response else "")
            + (f",window={window}" if window else "")
            + (",overlap" if overlap else "") + "]")
    return lint_fn(lambda *a: tp.run(body, a, out_reps=True), *args,
                   rules=HOT_PATH_RULES + (budget,), target=name)


def lint_verbs() -> List[Report]:
    """Lint the atomic verbs' traces: sort-free, host-free, zero
    collectives (arbitration is pure local vector work)."""
    from repro import fabric
    words = jnp.zeros((64,), jnp.uint32)
    idx = jnp.array([0, 1, 1, -1], jnp.int32)
    u = jnp.ones((4,), jnp.uint32)
    rules = HOT_PATH_RULES + (CollectiveBudget({"all_to_all": 0}),)
    return [lint_fn(fabric.cas, words, idx, u, u, rules=rules,
                    target="verbs/cas"),
            lint_fn(fabric.fetch_add, words, idx, u, rules=rules,
                    target="verbs/fetch_add")]


#: all_to_all sites in ONE commit wave: prepare route + grant exchange +
#: install route (the install reuses the prepare's RoutePlan, so a fourth
#: site would mean the plan-reuse contract broke).
COMMIT_ALL_TO_ALL_BUDGET = 3


def commit_all_to_all_budget(waves: int = 1) -> int:
    """Collective budget of a commit of ``waves`` (possibly pipelined)
    transaction waves: every wave contributes its own prepare route +
    grant exchange + install route, whether the waves run back-to-back or
    with wave i's install overlapping wave i+1's prepare.  The former rule
    hard-coded the three *sequential* sites of a single wave on one
    RoutePlan, wrongly rejecting the pipelined trace — the budget scales
    with waves, and the *ordering* burden moves to the explicit
    ``Completion.wait()`` fences the race detector checks."""
    return COMMIT_ALL_TO_ALL_BUDGET * int(waves)


def lint_commit(protocol: str = "rsi") -> Report:
    """Lint a full commit wave's trace under a mesh transport."""
    from repro.core import rsi, twopc
    tp = _mesh_transport()
    cfg = rsi.StoreCfg(num_records=16, payload_words=2, num_timestamps=32)
    store = rsi.init_store(cfg)
    txns = rsi.TxnBatch(write_recs=jnp.zeros((4, 2), jnp.int32),
                        read_cids=jnp.zeros((4, 2), jnp.uint32),
                        new_payload=jnp.zeros((4, 2, 2), jnp.uint32),
                        cid=jnp.arange(4, dtype=jnp.uint32))
    commit = {"rsi": rsi.commit, "2pc": twopc.commit}[protocol]
    rules = HOT_PATH_RULES + (
        CollectiveBudget({"all_to_all": commit_all_to_all_budget(1)}),)
    return lint_fn(lambda s, t: commit(s, t, transport=tp), store, txns,
                   rules=rules, target=f"{protocol}.commit")


def lint_commit_pipelined(waves: int = 2) -> Report:
    """Lint the pipelined commit's trace: 3 all_to_all sites *per wave*
    (:func:`commit_all_to_all_budget`), sort-free, host-free, packed wire
    — the double-buffered schedule must not change what's on the wire."""
    from repro.core import rsi
    tp = _mesh_transport()
    cfg = rsi.StoreCfg(num_records=16, payload_words=2, num_timestamps=32)
    store = rsi.init_store(cfg)
    wv = [rsi.TxnBatch(write_recs=jnp.zeros((4, 2), jnp.int32),
                       read_cids=jnp.zeros((4, 2), jnp.uint32),
                       new_payload=jnp.zeros((4, 2, 2), jnp.uint32),
                       cid=jnp.arange(4 * i, 4 * i + 4, dtype=jnp.uint32))
          for i in range(waves)]
    rules = HOT_PATH_RULES + (
        CollectiveBudget({"all_to_all": commit_all_to_all_budget(waves)}),)
    return lint_fn(
        lambda s, w: rsi.commit_pipelined(s, w, transport=tp),
        store, wv, rules=rules,
        target=f"rsi.commit_pipelined[waves={waves}]")


def lint_commit_grouped(groups: int = 3) -> Report:
    """Lint the group commit's trace: K coalesced session batches are
    still ONE commit wave — 3 all_to_all sites TOTAL
    (:func:`commit_all_to_all_budget` of one wave, not of K), sort-free,
    host-free, packed wire.  This is the whole point of fig_scale's
    tentpole: the chunked doorbells keep the wire traffic bit-identical
    to K solo commits while the collective count collapses 3K -> 3."""
    from repro.core import rsi
    tp = _mesh_transport()
    cfg = rsi.StoreCfg(num_records=16, payload_words=2, num_timestamps=64)
    store = rsi.init_store(cfg)
    gs = [rsi.TxnBatch(write_recs=jnp.zeros((2, 2), jnp.int32),
                       read_cids=jnp.zeros((2, 2), jnp.uint32),
                       new_payload=jnp.zeros((2, 2, 2), jnp.uint32),
                       cid=jnp.arange(2 * g, 2 * g + 2, dtype=jnp.uint32))
          for g in range(groups)]
    rules = HOT_PATH_RULES + (
        CollectiveBudget({"all_to_all": commit_all_to_all_budget(1)}),)
    return lint_fn(
        lambda s, g: rsi.commit_grouped(s, g, transport=tp),
        store, gs, rules=rules,
        target=f"rsi.commit_grouped[groups={groups}]")


def lint_ps_push() -> Report:
    """Lint the parameter server's routed push body: one all_to_all,
    packed wire, sort-free."""
    from repro.analytics import ParameterServer
    tp = _mesh_transport()
    params = {"w": jnp.zeros((16, 8), jnp.float32)}
    ps = ParameterServer(params, transport=tp, block=8, num_shards=4)
    S, L = ps.num_shards, ps.shard_len
    codes = jnp.zeros((S, L), jnp.int8)
    scale = jnp.zeros((S, L // ps.block), jnp.float32)
    rules = HOT_PATH_RULES + (CollectiveBudget({"all_to_all": 1}),)
    return lint_fn(lambda c, s: tp.run(ps._push_body, (c, s), False),
                   codes, scale, rules=rules, target="paramserver.push")


# -------------------------------------- canned protocol race schedules ---


def record_session_waves(isolation: str = "rsi") -> ScheduleRecorder:
    """Run real session waves (conflicting writers, snapshot reads, a
    serving-style lock table) eagerly through a recording transport and
    return the schedule."""
    from repro.core import rsi
    from repro.db import Database
    from repro.fabric import LocalTransport
    rec = ScheduleRecorder()
    tp = LocalTransport()
    tp.recorder = rec
    db = Database(tp)
    t = db.create_table("acct", 32, payload_words=2, num_timestamps=128)
    t.seed(np.arange(8), vals=np.ones((8, 2), np.uint32))
    rec.declare_locks("acct/words", ("acct/payload", "acct/cids"),
                      lock_bit=int(rsi.LOCK_BIT))
    # wave 1: two sessions, records 1 contended
    s1, s2 = db.session(isolation), db.session(isolation)
    s1.begin()
    pay, rc, _ = s1.get("acct", [0, 1])
    s1.put("acct", [0, 1], np.asarray(pay) + 1, read_cids=np.asarray(rc))
    s2.begin()
    pay2, rc2, _ = s2.get("acct", [1, 2])
    s2.put("acct", [1, 2], np.asarray(pay2) + 2, read_cids=np.asarray(rc2))
    db.commit([s1, s2])
    # wave 2: a fresh snapshot read + a disjoint commit
    s3 = db.session(isolation).begin()
    pay3, rc3, _ = s3.get("acct", [3])
    s3.put("acct", [3], np.asarray(pay3) + 3, read_cids=np.asarray(rc3))
    db.commit([s3])
    db.snapshot_read("acct", [0, 1, 2, 3])
    # the serving pattern: decode-slot claims on a dedicated lock table
    slots = db.create_table("slots", 4, payload_words=1, num_timestamps=8)
    for row in slots.claim_locks(2, tag=1):
        slots.release_lock(row)
    return rec


def record_paramserver(staleness: int = 2, steps: int = 3,
                       workers: int = 2) -> ScheduleRecorder:
    """Run the PS trainer loop (ticket claims off the decentralized queue,
    bounded-stale pulls, compressed routed pushes) eagerly through a
    recording transport and return the schedule."""
    from repro.analytics import ParameterServer
    from repro.core import workqueue
    from repro.fabric import LocalTransport
    rec = ScheduleRecorder()
    tp = LocalTransport()
    tp.recorder = rec
    params = {"w": jnp.ones((8, 4), jnp.float32),
              "b": jnp.zeros((4,), jnp.float32)}
    ps = ParameterServer(params, transport=tp, staleness=staleness,
                         block=8, num_shards=4)
    head = jnp.zeros((1,), jnp.uint32)
    for step in range(steps):
        _, head = workqueue.claim_ticket_ranges(
            head, jnp.ones((workers,), jnp.uint32), transport=tp)
        for w in range(workers):
            view, _ = ps.pull(worker=w)
            grads = jax.tree.map(
                lambda p: jnp.full_like(p, 0.01 * (w + 1)), view)
            ps.push(grads, worker=w)
    return rec


def record_windowed_route() -> ScheduleRecorder:
    """Route a windowed request batch through a recording transport with
    one-sided WRITEs landing before and READs after: a windowed route is
    still ONE fused collective round trip, i.e. a global fence, so the
    cross-agent write->read pairs on the landed region must record clean
    at any window (pacing changes timing, never ordering)."""
    from repro.fabric import LocalTransport
    rec = ScheduleRecorder()
    tp = LocalTransport()
    tp.recorder = rec
    words = jnp.zeros((64,), jnp.uint32)
    idx = jnp.arange(8, dtype=jnp.int32)
    with rec.agent("producer"):
        words = tp.write(words, idx, jnp.ones((8,), jnp.uint32),
                         region="sim/buf")
    plan = tp.plan_route(idx % tp.n, cap=16, window=4)
    tp.route({"k": words[:8]}, plan=plan)       # windowed global fence
    with rec.agent("consumer"):
        tp.read(words, idx, region="sim/buf")
    return rec


def record_overlapped_route() -> ScheduleRecorder:
    """The shipped double-buffered route schedule: a producer's async
    WRITE lands (and is waited — a *signaled* write), an async overlapped
    route goes on the wire, the issuer overlaps local work, and the
    consumer READs the landed region only after ``Completion.wait()``.
    That wait IS the route-roundtrip global fence, so the schedule
    records clean; omit either wait and the same accesses race (the
    seeded fixtures in tests/test_check.py)."""
    from repro.fabric import LocalTransport
    rec = ScheduleRecorder()
    tp = LocalTransport()
    tp.recorder = rec
    words = jnp.zeros((64,), jnp.uint32)
    idx = jnp.arange(8, dtype=jnp.int32)
    with rec.agent("producer"):
        wc = tp.write_async(words, idx, jnp.ones((8,), jnp.uint32),
                            region="async/buf")
        words = wc.wait()                    # signaled write completion
    plan = tp.plan_route(idx % tp.n, cap=16, window=4)
    c = tp.route_async({"k": words[:8]}, plan=plan, chunks=2)
    c.wait()                                 # route-roundtrip fence
    with rec.agent("consumer"):
        tp.read(words, idx, region="async/buf")
    return rec


def record_pipelined_commit(waves: int = 2) -> ScheduleRecorder:
    """Run the pipelined RSI commit (wave i's install round overlapping
    wave i+1's prepare) eagerly through a recording transport with the
    lock protocol declared, and return the schedule — the proof that the
    shipped overlap's explicit completion edges keep every install WRITE
    inside its acquiring wave and ordered before the next wave's CAS."""
    from repro.core import rsi
    from repro.db import Database
    from repro.fabric import LocalTransport
    rec = ScheduleRecorder()
    tp = LocalTransport()
    tp.recorder = rec
    db = Database(tp)
    t = db.create_table("acct", 32, payload_words=2, num_timestamps=128)
    t.seed(np.arange(8), vals=np.ones((8, 2), np.uint32))
    rec.declare_locks("acct/words", ("acct/payload", "acct/cids"),
                      lock_bit=int(rsi.LOCK_BIT))
    wave_list = []
    for wv in range(waves):
        s = db.session().begin()
        recs = [2 * wv, 2 * wv + 1]
        pay, rc, _ = s.get("acct", recs)
        s.put("acct", recs, np.asarray(pay) + 1,
              read_cids=np.asarray(rc))
        wave_list.append([s])
    db.commit_pipelined(wave_list)
    return rec


def record_grouped_commit(max_retries: int = 1) -> ScheduleRecorder:
    """Run a contended group commit with bounded retry eagerly through a
    recording transport and return the schedule.  Two worker groups hit
    the same hot row, so the losing session retries: the retry's refresh
    READ of the lock|CID words happens strictly AFTER the prior wave's
    commit-complete fence (the grant exchange is a global fence), which
    is why the schedule records clean — drop that ordering and the same
    re-read races the winner's install WRITE (the seeded fixture in
    ``tests/test_check.py``)."""
    from repro.core import rsi
    from repro.db import Database
    from repro.fabric import LocalTransport
    rec = ScheduleRecorder()
    tp = LocalTransport()
    tp.recorder = rec
    db = Database(tp)
    t = db.create_table("acct", 32, payload_words=2, num_timestamps=128)
    t.seed(np.arange(8), vals=np.ones((8, 2), np.uint32))
    rec.declare_locks("acct/words", ("acct/payload", "acct/cids"),
                      lock_bit=int(rsi.LOCK_BIT))
    groups = []
    for w in range(2):
        s = db.session().begin()
        recs = [0, 4 + w]                   # record 0 is the hot row
        pay, rc, _ = s.get("acct", recs)
        s.put("acct", recs, np.asarray(pay) + w + 1,
              read_cids=np.asarray(rc))
        groups.append([s])
    db.commit_grouped(groups, max_retries=max_retries)
    return rec


def lint_paged_decode(blocks: int = 2) -> List[Report]:
    """Lint the paged-decode data paths (docs/serving.md): page-in — ONE
    batched one-sided READ of cold KV blocks unpacked bit-exact into the
    dense decode state — and swap-out — the inverse pack.  Both must stay
    inside the hot-path budget: sort-free (residency is host bookkeeping,
    never a device sort), host-free, packed u32 lanes, and ZERO
    collectives (paging is pure one-sided traffic; a collective in the
    decode loop would serialize every slot on the slowest page-in)."""
    from repro import fabric as F
    from repro.serving.paging import PagedKV
    slots, max_seq, bk = 2, 32, 8
    state = {"caches": {"k": jnp.zeros((2, slots, max_seq, 4),
                                       jnp.bfloat16),
                        "v": jnp.zeros((2, slots, max_seq, 4),
                                       jnp.bfloat16)},
             "pos": jnp.zeros((), jnp.int32)}
    kv = PagedKV(state, slots=slots, max_seq=max_seq, block_tokens=bk)
    cold = jnp.zeros((16, kv.block_words), jnp.uint32)
    js = list(range(blocks))

    def page_in(cold, state):
        rows = F.read(cold, jnp.arange(blocks, dtype=jnp.int32))
        return kv.insert_blocks(state, 1, js, rows)

    def swap_out(state):
        return kv.extract_blocks(state, 1, js)

    rules = HOT_PATH_RULES + (CollectiveBudget({"all_to_all": 0}),)
    return [lint_fn(page_in, cold, state, rules=rules,
                    target=f"serve/page_in[{blocks}b]"),
            lint_fn(swap_out, state, rules=rules,
                    target=f"serve/swap_out[{blocks}b]")]


#: tiny serving model shared by the recorded serve targets (one param
#: init + one decode compile per process, like test fixtures do).
_SERVE_MODEL: list = []


def _serve_model():
    from repro.configs import get_config, reduce_config
    from repro.models import api
    if not _SERVE_MODEL:
        cfg = reduce_config(get_config("glm4-9b"))
        params = api.init_params(cfg, jax.random.PRNGKey(0))
        _SERVE_MODEL.append((cfg, params))
    return _SERVE_MODEL[0]


def record_paged_decode(*, hot_frac: float = 0.25,
                        prefetch: bool = True) -> ScheduleRecorder:
    """Run a real paged serving engine (tiny model, more resident
    requests than dense slots, so every round swaps KV blocks through the
    two-tier store) through a recording transport and return the
    schedule.  The ordering edges that make it record clean are exactly
    the shipped ones: evict write-backs are *signaled* WRITEs
    (``write_async(...).wait()`` — the completion fence orders each
    write-back before any later page-in READ of the same block), slot
    releases are signaled for the same reason (the release WRITE vs the
    next swap-in's re-claim CAS is otherwise a lost update), and every
    prefetch Completion is waited before its blocks are used.  Drop any
    of those waits and the same schedule races (the seeded fixtures in
    ``tests/test_check.py``)."""
    from repro.db import Database
    from repro.fabric import LocalTransport
    from repro.serving.engine import Request, ServeEngine
    cfg, params = _serve_model()
    rec = ScheduleRecorder()
    tp = LocalTransport()
    tp.recorder = rec
    db = Database(tp)
    eng = ServeEngine(cfg, params, slots=2, max_seq=64, db=db, paged=True,
                      block_tokens=8, max_resident=4, hot_frac=hot_frac,
                      prefetch=prefetch)
    reqs = [Request(rid=i, prompt=np.array([2 + i, 5], np.int32),
                    max_new_tokens=3) for i in range(4)]
    eng.run(reqs)
    eng.quiesce()
    return rec


def race_paged_decode(*, hot_frac: float = 0.25,
                      prefetch: bool = True) -> Report:
    return check_schedule(
        record_paged_decode(hot_frac=hot_frac, prefetch=prefetch),
        target=f"serve/paged[hot={hot_frac:g}"
               f"{',prefetch' if prefetch else ''}]")


def race_sessions(isolation: str = "rsi") -> Report:
    return check_schedule(record_session_waves(isolation),
                          target=f"sessions/{isolation}")


def race_windowed_route() -> Report:
    return check_schedule(record_windowed_route(),
                          target="route/windowed")


def race_paramserver() -> Report:
    return check_schedule(record_paramserver(),
                          target="paramserver/trainer")


def race_overlapped_route() -> Report:
    return check_schedule(record_overlapped_route(),
                          target="route/overlapped")


def race_pipelined_commit(waves: int = 2) -> Report:
    return check_schedule(record_pipelined_commit(waves),
                          target=f"rsi/pipelined[waves={waves}]")


def race_grouped_commit(max_retries: int = 1) -> Report:
    return check_schedule(record_grouped_commit(max_retries),
                          target=f"rsi/grouped[retries={max_retries}]")


# ------------------------------------------------------- CLI plumbing ----

SUITES: Dict[str, Callable[[], List[Report]]] = {
    "route": lambda: [lint_route(1), lint_route(5),
                      lint_route(3, chunks=4),
                      lint_route(2, response=True)],
    "verbs": lint_verbs,
    "rsi": lambda: [lint_commit("rsi"), race_sessions("rsi")],
    "2pc": lambda: [lint_commit("2pc"), race_sessions("2pc")],
    "paramserver": lambda: [lint_ps_push(), race_paramserver()],
    # netsim v2: the windowed route trace must stay within the
    # one-collective budget, and the write->route(window)->read schedule
    # must record race-clean (docs/netsim.md "netsim v2")
    "sim": lambda: [lint_route(2, window=4),
                    lint_route(3, chunks=2, window=2),
                    race_windowed_route()],
    # async verbs + double-buffered routes (docs/fabric.md "the async
    # contract"): the overlapped chunk pipeline keeps the one-collective
    # budget, the pipelined commit is 3 sites per wave, and the shipped
    # async schedules — overlapped route, pipelined RSI commit — record
    # race-clean under their explicit Completion.wait() fences
    "async": lambda: [lint_route(3, chunks=4, overlap=True),
                      lint_route(2, response=True, overlap=True),
                      lint_commit_pipelined(2),
                      race_overlapped_route(),
                      race_pipelined_commit()],
    # group commit + abort/retry economics (docs/db.md "group commit"):
    # K coalesced sessions stay inside ONE wave's 3-collective budget,
    # and the contended grouped schedule — retry refresh READ behind the
    # commit-complete fence — records race-clean
    "scale": lambda: [lint_commit_grouped(3),
                      lint_commit_grouped(1),
                      race_grouped_commit(1)],
    # two-tier KV paging (docs/serving.md): the page-in/swap-out packs
    # stay sort-free/collective-free, and the real paged engine schedule
    # — signaled write-backs, signaled slot releases, waited prefetches —
    # records race-clean both with a cold tier in play (hot=0.25,
    # prefetch) and in the all-hot release/re-claim regime
    "serve": lambda: [*lint_paged_decode(2),
                      race_paged_decode(hot_frac=0.25, prefetch=True),
                      race_paged_decode(hot_frac=1.0, prefetch=False)],
}

#: which check suites gate each paper figure (benchmarks/run.py --check).
FIGURE_SUITES: Dict[str, Tuple[str, ...]] = {
    "fig2": ("verbs", "route"),
    "fig6": ("rsi", "2pc"),
    "fig7": ("route",),
    "fig8a": ("route", "async"),
    "fig8b": ("route", "verbs"),
    "fig9": ("paramserver", "route"),
    "fig10": ("sim", "route"),
    "fig_scale": ("scale", "rsi"),
    "fig_serve": ("serve", "sim"),
}


def run_suite(name: str) -> List[Report]:
    return list(SUITES[name]())


def check_figure(figure: str) -> List[Report]:
    """All reports gating one figure (suites may repeat across figures;
    each run is independent)."""
    return [rep for s in FIGURE_SUITES[figure] for rep in run_suite(s)]


def check_all() -> List[Report]:
    """Every suite once — the ``make check`` gate."""
    return [rep for s in SUITES for rep in run_suite(s)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="fabriccheck",
        description="fabric-check: jaxpr lint + one-sided race detector "
                    "for the verb fabric (docs/check.md)")
    ap.add_argument("--figure", default=None,
                    choices=sorted(FIGURE_SUITES) + ["all"],
                    help="check the suites gating one figure, or every "
                         "suite once ('all', the make-check gate)")
    ap.add_argument("--suite", default=None, choices=sorted(SUITES),
                    help="run a single named suite")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write the merged summary JSON here")
    ap.add_argument("-q", "--quiet", action="store_true",
                    help="only print failures and the final line")
    args = ap.parse_args(argv)
    if args.suite:
        reports = run_suite(args.suite)
    elif args.figure and args.figure != "all":
        reports = check_figure(args.figure)
    else:
        reports = check_all()
    for rep in reports:
        if not rep.ok or not args.quiet:
            print(rep.render())
    summ = summarize(reports)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(summ, f, indent=2, sort_keys=True)
        print(f"wrote {args.json}", file=sys.stderr)
    n_bad = len(summ["violations"])
    print(f"fabriccheck: {len(reports)} targets, "
          f"{len(summ['rules_run'])} rules, {n_bad} violation(s)")
    return 1 if n_bad else 0


if __name__ == "__main__":
    sys.exit(main())
