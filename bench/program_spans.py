"""What the readers of the program's own spans and counters share: the
program's span log (``repro.obs``) cut to the cell's measured window.

The window is ``[end - window_s, end]`` on the generators' clock
(``time.perf_counter``, which ``repro.obs`` reads in nanoseconds), where
``end`` is the end of the window's last wave or query.  A unit of work is
a span of the program (``namdb.commit`` for a wave, ``namdb.execute`` for
a query) that ends in the window.

In a trace without a chip (the CPU's) the readers find nothing and
return None.  On a chip they find nothing, and so stop a traced run
(``harness.MetricNotFound``), where the window holds no unit span or no
span of the kind a reader measures: a program that renames or drops its
spans cannot switch a metric off unseen.  Only a program without
``repro.obs`` (one from before the span log), or a cell that ran no
window (a recorded trace read alone), reads 0 on a chip."""
from __future__ import annotations

ZERO = object()     # no span log in the program, or no window: reads 0


class Window:
    """The program's records that end in a window, and its units."""

    def __init__(self, records, unit: str):
        self.records = records
        self.units = sum(r.name == unit for r in records)


def window(ctx, end_s, unit: str):
    """The cell's window: None without a chip, ZERO where the program
    keeps no span log or the cell ran no window."""
    if not ctx.trace.chips():
        return None
    try:
        from repro import obs
    except ImportError:
        return ZERO
    if end_s is None:
        return ZERO
    hi = int(end_s * 1e9)
    return Window(obs.spans(hi - int(ctx.cell.window_s * 1e9), hi), unit)


def checkout(ctx):
    """A checkout window: it ends with its last wave."""
    waves = getattr(ctx.cell, "waves", None)
    return window(ctx, waves[-1].t_end if waves else None, "namdb.commit")


def join(ctx):
    """A join window: it ends with its last query."""
    answers = getattr(ctx.cell, "answers", None)
    return window(ctx, answers[-1][1] if answers else None,
                  "namdb.execute")


def per_unit(win, kind, value):
    """Sum of ``value(record)`` over the records whose name ``kind``
    accepts, per unit; None where the window holds no unit or no record
    of the kind."""
    if win is None or win is ZERO:
        return None if win is None else 0.0
    of_kind = [r for r in win.records if kind(r.name)]
    if not win.units or not of_kind:
        return None
    return sum(value(r) for r in of_kind) / win.units


def ms(record) -> float:
    return (record.end_ns - record.start_ns) / 1e6


def one(record) -> int:
    return 1


def compiles(win):
    """The ``jax.compile`` records (traces and compiles) in the window;
    None where it holds no unit."""
    if win is None or win is ZERO:
        return None if win is None else 0
    if not win.units:
        return None
    return sum(r.name == "jax.compile" for r in win.records)
