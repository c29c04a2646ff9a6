"""Bytes a route must move, computed from its shapes alone.

A route (``repro.fabric.router.route``) reads each request row once and
writes the ``(n * cap)``-slot wire buffer once, each slot one packed row:
the row's 32-bit word lanes plus the trailing valid lane.  That is the
route's contract, whatever implements the scatter, so a roofline share
built on it stays comparable when the implementation changes."""
from __future__ import annotations

WORD_BYTES = 4


def packed_row_words(field_words) -> int:
    """Wire width of one packed row in 32-bit lanes: every field's word
    lanes (each field's row padded up to whole words) plus the valid
    lane.  ``field_words`` lists each field's row width in words."""
    return sum(int(w) for w in field_words) + 1


def route_bytes(rows: int, field_words, *, n: int, cap: int) -> int:
    """Least HBM traffic of one route of ``rows`` requests into ``n``
    buckets of ``cap`` slots: the request rows read (without the valid
    lane, which the route adds) plus the whole wire buffer written."""
    width = packed_row_words(field_words)
    return WORD_BYTES * (rows * (width - 1) + n * cap * width)


def least_time_s(nbytes: float, peaks: dict) -> float:
    """The least time the chip's HBM takes to move ``nbytes``."""
    return nbytes / peaks["hbm_bytes_per_s"]
