"""Spans and compile records of the program, kept in a bounded log.

``span(name, **ids)`` times a stretch of host code (never code inside a
jitted function: there it would time the tracing).  While a profile is
being taken it also opens a ``jax.profiler.TraceAnnotation`` of the same
name, so the span shows in the profile on the clock of the device's ops
(an annotation costs about a microsecond even with no profiler, and a
checkout wave holds about a thousand spans).  On exit it logs one
record, which :func:`spans` returns as a :class:`Record`:

  name      the span's name (``namdb.commit``, ``namdb.sync.oracle``...)
  start_ns  ``time.perf_counter_ns()`` on entry
  end_ns    ``time.perf_counter_ns()`` on exit
  parent    ``(name, start_ns)`` of the enclosing span on the same
            thread, None at the top: the span that caused this one
  ids       what identifies the request (a commit wave, a query, a
            session); a span given none carries its parent's

Every trace and compile that JAX reports through ``jax.monitoring`` is
logged as a ``jax.compile`` record, with ids ``phase`` (``trace`` or
``compile``) and ``fun``, and parent the span that was open.

The log is always on, like a flight recorder: it keeps the newest
``MAXLEN`` records and costs two clock reads and one append per span.
:func:`spans` and :func:`summary` read it.
"""
from __future__ import annotations

import collections
import threading
import time
from typing import NamedTuple, Optional

import jax

MAXLEN = 1 << 18
COMPILE = "jax.compile"
_EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "trace",
           "/jax/core/compile/backend_compile_duration": "compile"}

_now = time.perf_counter_ns
_Annotation = jax.profiler.TraceAnnotation
_profiling = jax.profiler.TraceAnnotation.is_enabled
# entries are flat tuples of strings and ints, (name, start_ns, end_ns,
# parent name, parent start_ns, key, value, key, value...), which the
# garbage collector stops tracking at its first pass: an entry holding a
# dict or a tuple stays tracked into the oldest generation, and a full log
# made full collections more frequent and 2^18 objects longer
_log: collections.deque = collections.deque(maxlen=MAXLEN)


class Record(NamedTuple):
    name: str
    start_ns: int
    end_ns: int
    parent: Optional[tuple]
    ids: dict


class _Thread(threading.local):
    def __init__(self):
        self.stack = []     # (name, start_ns, flat ids) of the open spans


_tls = _Thread()


def _flat(ids: dict) -> tuple:
    out = ()
    for kv in ids.items():
        out += kv
    return out


def _record(entry) -> Record:
    name, start, end, pname, pstart = entry[:5]
    return Record(name, start, end,
                  None if pname is None else (pname, pstart),
                  dict(zip(entry[5::2], entry[6::2])))


class span:
    """Context manager: one host span, logged on exit (see the module
    docstring)."""
    __slots__ = ("name", "ids", "pname", "pstart", "start", "_ann")

    def __init__(self, name: str, **ids):
        self.name = name
        self.ids = _flat(ids) if ids else ()

    def __enter__(self) -> "span":
        stack = _tls.stack
        if stack:
            self.pname, self.pstart, pids = stack[-1]
            if pids:
                self.ids = pids + self.ids
        else:
            self.pname = self.pstart = None
        if _profiling():
            self._ann = _Annotation(self.name)
            self._ann.__enter__()
        else:
            self._ann = None
        self.start = _now()
        stack.append((self.name, self.start, self.ids))
        return self

    def __exit__(self, *exc) -> bool:
        end = _now()
        _tls.stack.pop()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        _log.append((self.name, self.start, end, self.pname, self.pstart,
                     *self.ids))
        return False


def _on_event(event: str, duration_s: float, **kwargs):
    phase = _EVENTS.get(event)
    if phase is None:
        return
    end = _now()
    stack = _tls.stack
    pname, pstart = stack[-1][:2] if stack else (None, None)
    _log.append((COMPILE, end - int(duration_s * 1e9), end, pname, pstart,
                 "phase", phase, "fun", kwargs.get("fun_name")))


jax.monitoring.register_event_duration_secs_listener(_on_event)


def spans(lo_ns: Optional[int] = None,
          hi_ns: Optional[int] = None) -> list:
    """The logged records whose end lies in ``[lo_ns, hi_ns]`` (either
    bound open where None), oldest first."""
    return [_record(r) for r in list(_log)
            if (lo_ns is None or r[2] >= lo_ns)
            and (hi_ns is None or r[2] <= hi_ns)]


def summary(lo_ns: Optional[int] = None,
            hi_ns: Optional[int] = None) -> dict:
    """Per name, of the records ending in ``[lo_ns, hi_ns]``: ``count``,
    ``total_ms`` and ``self_ms`` (duration minus that of its child spans).
    ``jax.compile`` records are not subtracted as children: a trace holds
    the traces of the functions it calls, so they overlap one another."""
    log = [_record(r) for r in list(_log)]
    child_ns = collections.Counter()
    for r in log:
        if r.parent is not None and r.name != COMPILE:
            child_ns[r.parent] += r.end_ns - r.start_ns
    out = {}
    for r in log:
        if ((lo_ns is not None and r.end_ns < lo_ns)
                or (hi_ns is not None and r.end_ns > hi_ns)):
            continue
        s = out.setdefault(r.name, {"count": 0, "total_ms": 0.0,
                                    "self_ms": 0.0})
        dur = r.end_ns - r.start_ns
        s["count"] += 1
        s["total_ms"] += dur / 1e6
        s["self_ms"] += (dur - child_ns[(r.name, r.start_ns)]) / 1e6
    return out
