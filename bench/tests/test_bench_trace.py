"""The trace reduction, the route bytes and the peaks table, checked on
small traces recorded on a TPU v5 lite (``bench/testdata``: two checkout
waves and one 2^24-tuple join query; the host spans were renamed to the
harness's ``bench.*`` names and a ``bench.window`` span was added around
them)."""
import json
import os

import jax.numpy as jnp
import pytest

from bench import peaks, roofline, trace

DATA = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "testdata")


@pytest.fixture(scope="module", params=["checkout", "join"])
def recorded(request):
    return request.param, trace.read(os.path.join(DATA, request.param
                                                  + "_trace.json"))


def _busy_by_sweep(intervals, lo, hi):
    """Busy time by a sweep over interval end points (independent of
    ``trace.union``)."""
    points = sorted([(max(s, lo), 1) for s, e in intervals if e > lo
                     and s < hi] + [(min(e, hi), -1) for s, e in intervals
                                    if e > lo and s < hi])
    busy, depth, last = 0, 0, None
    for t, d in points:
        if depth > 0:
            busy += t - last
        depth += d
        last = t
    return busy


@pytest.mark.parametrize("text,want", [
    ("%fusion.3 = u32[8]{0} fusion(u32[8]{0} %a), kind=kLoop",
     ("fusion.3", "fusion")),
    ("%while.4 = (s32[]{:T(128)}, u32[33554432]{0:T(1024)}) while((s32[], "
     "u32[33554432]) %tuple.23), condition=%c", ("while.4", "while")),
    ("%copy-start = (u32[2]{0}, u32[2]{0}, u32[]{:S(2)}) copy-start(u32[2] "
     "%a)", ("copy-start", "copy-start")),
    ("%all-to-all.1 = u32[4,8]{1,0} all-to-all(u32[4,8]{1,0} %x)",
     ("all-to-all.1", "all-to-all")),
    ("jit_f(123)", ("jit_f(123)", "jit_f(123)")),
])
def test_parse_op(text, want):
    assert trace.parse_op(text) == want


def test_union_merges_overlaps():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]


def test_busy_union_and_idle_share(recorded):
    tag, tr = recorded
    lo, hi = tr.window()
    want = _busy_by_sweep([(o[0], o[1]) for o in tr.ops[0]], lo, hi)
    assert tr.busy_s() == pytest.approx(want / 1e9, rel=1e-12)
    assert tr.idle_pct() == pytest.approx(
        100 * (1 - want / (hi - lo)), rel=1e-12)
    # the checkout's chip idles while the host builds waves; one join
    # query keeps it busy
    assert (tr.idle_pct() > 90) if tag == "checkout" else \
        (tr.idle_pct() < 1)


def test_events_by_name(recorded):
    tag, tr = recorded
    ops = tr.ops[0]
    sort = sum(e - s for s, e, n, op, p in ops if op == "sort") / 1e9
    assert tr.op_seconds(lambda n, op, p: op == "sort") == \
        pytest.approx(sort)
    kernels = [o for o in ops if o[3] == "tpu_custom_call"]
    # the route kernel runs twice per join query (R and S) and once per
    # commit round of a checkout wave
    assert len(kernels) == (2 if tag == "join" else 4)
    top = tr.top_ops(3)
    assert len(top) == 3 and top[0][1] >= top[1][1] >= top[2][1]
    if tag == "join":
        # the sort-probe's searchsorted: a while loop of gathers
        assert top[0][0] == "jit_f:fusion.52 (fusion)"
        assert tr.count_spans("bench.execute") == 1
    else:
        commit = tr.module_seconds(
            lambda n: trace.program(n) == "jit__lambda")
        assert 0.005 < commit / 2 < 0.05       # seconds per wave
        assert len(tr.span_seconds("bench.wave_build")) == 2


def test_idle_gaps_are_named_by_host_spans(recorded):
    tag, tr = recorded
    gaps = tr.idle_gaps(4)
    assert [g[1] for g in gaps] == sorted((g[1] for g in gaps),
                                          reverse=True)
    want = "bench.wave_build" if tag == "checkout" else "bench.execute"
    assert gaps[0][0] == want


def test_trace_round_trips_through_json(recorded):
    _, tr = recorded
    again = trace.Trace.from_json(tr.to_json())
    assert again.busy_s() == tr.busy_s() and again.spans == tr.spans


def test_load_reads_host_spans_of_a_live_trace(tmp_path):
    import jax
    x = jnp.ones((64, 64))
    f = jax.jit(lambda a: (a @ a).sum())
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.execute"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    tr = trace.load(str(tmp_path))
    assert tr.count_spans("bench.execute") == 1
    assert tr.window_s() > 0
    assert tr.chips() == []          # the CPU has no TPU plane


def test_route_bytes_match_the_program_wire_width():
    from repro.fabric import LocalTransport, router
    fields = {"k": jnp.zeros((64,), jnp.uint32),
              "v": jnp.zeros((64,), jnp.uint32)}
    assert roofline.packed_row_words([1, 1]) == \
        router.packed_row_words(fields) == 3
    # the wire buffer the transport bills is the written part of the
    # contract; the rest is the rows read
    t = LocalTransport()
    t.route(fields, jnp.zeros((64,), jnp.int32), cap=128)
    billed = t.stats()["route"]["bytes"]
    assert roofline.route_bytes(64, [1, 1], n=1, cap=128) == \
        billed + 64 * 2 * 4
    assert roofline.route_bytes(1 << 24, [1, 1], n=1, cap=1 << 25) == \
        4 * ((1 << 24) * 2 + (1 << 25) * 3)


def test_peaks_table():
    p = peaks.peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9 and p["source"]
    assert roofline.least_time_s(819e9, p) == 1.0
    with pytest.raises(ValueError, match="no published peaks"):
        peaks.peaks("TPU v99")
    assert set(p) == {"hbm_bytes_per_s", "source"}


def test_union_seconds_counts_nested_ops_once(recorded):
    tag, tr = recorded
    every = tr.union_seconds(lambda n, op, p: True)
    assert every == pytest.approx(tr.busy_s(), rel=1e-12)
    loops = tr.union_seconds(lambda n, op, p: op == "while")
    if tag == "join":
        # the sort-probe's searchsorted loop, most of the query
        assert 0.6 < loops / tr.busy_s() < 0.9
    else:
        assert loops == 0


def _cell(root, workload):
    from bench import harness
    return harness.find_cell(root, json.load(open(os.path.join(
        root, "BENCHMARK.json"))), workload)


class _Join:
    @staticmethod
    def route_shapes():
        n = 1 << 24
        return [dict(rows=n, field_words=(1, 1), n=1, cap=2 * n)] * 2


def _recorded(tag):
    return trace.read(os.path.join(DATA, tag + "_trace.json"))


def test_join_readers_on_a_recorded_query():
    """Every per-layer metric listed for join.1node reads the recorded
    v5e query; the probe, the sorts and the route kernel together hold
    most of its device time."""
    from bench import harness
    from bench.tests.tiny import ROOT
    tr = _recorded("join")
    ctx = harness.Context(tr, _Join(), peaks.peaks("TPU v5 lite"))
    cell = _cell(ROOT, "join.1node")
    got = harness.read_per_layer(ROOT, cell, ctx)
    assert set(got) == {m["name"] for m in cell.per_layer}
    probe = got["join_probe_ms"]["value"]
    assert probe == pytest.approx(1e3 * tr.union_seconds(
        lambda n, op, p: op == "while"))
    assert 0 < got["route_roofline_pct"]["value"] < 100
    assert probe > 10 * got["join_sort_ms"]["value"]


def test_a_listed_reader_that_finds_nothing_raises():
    """A device trace in which a metric listed for the cell finds nothing
    (here: the checkout's trace read as the join's) stops the run."""
    from bench import harness
    from bench.tests.tiny import ROOT
    tr = _recorded("checkout")
    ctx = harness.Context(tr, _Join(), peaks.peaks("TPU v5 lite"))
    with pytest.raises(harness.MetricNotFound, match="found nothing in the device trace"):
        harness.read_per_layer(ROOT, _cell(ROOT, "join.1node"), ctx)
