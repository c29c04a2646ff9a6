"""The readers of the program's own spans and counters, on the tiny cells
run through their generators on the CPU: with a trace that has a chip
they read the program's span log, with the CPU's empty trace nothing, and
on a chip a window without the spans they read stops the run.  And
``begin_ms``, which the generators' clock feeds, on the same cells."""
import dataclasses
import json
import os
import sys
import time
from types import SimpleNamespace

import pytest

from bench import harness, trace
from bench.tests.tiny import tiny_root

SEED = (1 << 31) + 977
CHECKOUT = ("device_syncs_per_wave", "commit_pack_ms",
            "window_compiles.checkout")
JOIN = ("window_compiles.join",)
CHECKOUT_CELLS = ["checkout.uniform", "checkout.4node"]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root(tmp_path_factory.mktemp("spans"))


def _run(root, workload):
    cell = harness.find_cell(root, json.load(open(os.path.join(
        root, "BENCHMARK.json"))), workload)
    sut = cell.generator.build(cell.cfg, cell.traffic, SEED)
    sut.warm()
    sut.window(0.5)
    return cell, sut


@pytest.fixture(scope="module")
def checkout(root):
    return _run(root, "checkout.uniform")


@pytest.fixture(scope="module")
def join(root):
    return _run(root, "join.1node")


def _read(root, name, sut, tr):
    reader = harness.load_module(root, f"bench/metrics/{name}.py")
    return reader.read(harness.Context(tr, sut, {}))


def _chip():
    """A trace with one chip, as on the TPU (the readers ask only whether
    the trace has one)."""
    return trace.Trace(ops={0: []}, modules={0: []})


def test_the_metrics_are_listed_for_their_cells(root):
    spec = json.load(open(os.path.join(root, "BENCHMARK.json")))
    for names, cells in ((CHECKOUT + ("begin_ms",), CHECKOUT_CELLS),
                         (JOIN, ["join.1node", "join.4node"])):
        for name in names:
            m, = [m for m in spec["per_layer"] if m["name"] == name]
            assert m["workloads"] == cells


def test_checkout_readers_read_the_program_spans(root, checkout):
    cell, sut = checkout
    got = {n: _read(root, n, sut, _chip()) for n in CHECKOUT}
    assert got["device_syncs_per_wave"] == cell.traffic[
        "sessions_per_wave"] + 3
    assert got["commit_pack_ms"] > 0
    assert got["window_compiles.checkout"] == 0


def test_join_reader_reads_the_program_spans(root, join):
    cell, sut = join
    assert _read(root, "window_compiles.join", sut, _chip()) == 0


def test_readers_find_nothing_without_a_chip(root, checkout, join):
    for names, (_, sut) in ((CHECKOUT, checkout), (JOIN, join)):
        for name in names:
            assert _read(root, name, sut, trace.Trace()) is None, name


def test_a_program_without_a_span_log_reads_zero(root, checkout, join,
                                                 monkeypatch):
    """The parent of the span log (a program without ``repro.obs``): on a
    chip every reader still gives a number, so that a listed metric does
    not stop the parent's traced run."""
    import repro
    monkeypatch.delattr(repro, "obs")
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    for names, (_, sut) in ((CHECKOUT, checkout), (JOIN, join)):
        assert {n: _read(root, n, sut, _chip()) for n in names} == {
            n: 0 for n in names}


def _window(body):
    """A checkout cell whose window holds just what ``body`` runs."""
    start = time.perf_counter()
    body()
    end = time.perf_counter()
    return SimpleNamespace(waves=[SimpleNamespace(t_end=end)],
                           window_s=end - start)


def test_a_window_without_a_wave_stops_a_traced_run(root, checkout):
    """Where the program logs spans but none is a wave, every reader of
    the checkout finds nothing, and the harness raises for each."""
    from repro import obs

    def body():
        with obs.span("t.not_a_wave"):
            pass
    cell, _ = checkout
    ctx = harness.Context(_chip(), _window(body), {})
    for m in cell.per_layer:
        if m["name"] in CHECKOUT:
            alone = dataclasses.replace(cell, per_layer=[m])
            with pytest.raises(harness.MetricNotFound, match=m["name"]):
                harness.read_per_layer(root, alone, ctx)


def test_a_renamed_span_is_not_read_as_zero(root, checkout, monkeypatch):
    """Waves but no span of the kind a reader measures (the program renamed
    its syncs): the reader finds nothing rather than 0."""
    from repro import obs
    real = obs.spans

    def renamed(lo=None, hi=None):
        return [r._replace(name=r.name.replace("namdb.sync.", "x."))
                for r in real(lo, hi)]
    monkeypatch.setattr(obs, "spans", renamed)
    _, sut = checkout
    assert _read(root, "device_syncs_per_wave", sut, _chip()) is None
    assert _read(root, "commit_pack_ms", sut, _chip()) > 0


def test_a_compile_in_the_window_is_counted(root):
    """A program first traced inside the window is a recompile there."""
    import jax
    import jax.numpy as jnp
    from repro import obs
    x = jnp.arange(3)

    def body():
        with obs.span("namdb.commit"):
            jax.jit(lambda v: v + 1)(x).block_until_ready()
    # the lambda's trace and compile, and the trace of the jitted add in it
    assert _read(root, "window_compiles.checkout", _window(body),
                 _chip()) >= 2


def _wave(begin_s, failed=False):
    return SimpleNamespace(begin_s=begin_s, failed=failed)


def test_begin_ms_reads_the_generators_begin_time(root):
    """The begins' host time per wave, over the window's waves that have
    an outcome: a set-up wave and a failed one do not count.  It is a
    host-clock metric, so it reads with or without a chip."""
    cell = SimpleNamespace(first=1, waves=[
        _wave(9.0), _wave(0.25), _wave(0.75), _wave(5.0, failed=True)])
    for tr in (_chip(), trace.Trace()):
        assert _read(root, "begin_ms", cell, tr) == pytest.approx(500.0)
    assert _read(root, "begin_ms", SimpleNamespace(
        first=1, waves=[_wave(9.0)]), _chip()) is None


def test_begin_ms_reads_a_run_of_the_generator(root, checkout):
    """On the tiny cell every window wave times its begins, and the
    begins are part of the wave's build."""
    _, sut = checkout
    waves = sut.waves[sut.first:]
    assert waves and all(0 < w.begin_s < w.t_end - w.t_begin[0]
                         for w in waves)
    assert _read(root, "begin_ms", sut, _chip()) == pytest.approx(
        1e3 * sum(w.begin_s for w in waves) / len(waves))
