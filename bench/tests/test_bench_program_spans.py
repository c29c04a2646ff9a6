"""The readers of the program's own spans and counters, on the tiny cells
run through their generators on the CPU: with a trace that has a chip
they read the program's span log, with the CPU's empty trace nothing, and
on a chip a window without the spans they read stops the run."""
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
CHECKOUT = ("begin_ms", "device_syncs_per_wave", "commit_pack_ms",
            "window_compiles.checkout")
JOIN = ("window_compiles.join",)


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
    for names, cells in ((CHECKOUT, ["checkout.uniform"]),
                         (JOIN, ["join.1node", "join.4node"])):
        for name in names:
            m, = [m for m in spec["per_layer"] if m["name"] == name]
            assert m["workloads"] == cells


def test_checkout_readers_read_the_program_spans(root, checkout):
    cell, sut = checkout
    got = {n: _read(root, n, sut, _chip()) for n in CHECKOUT}
    assert got["device_syncs_per_wave"] == cell.traffic[
        "sessions_per_wave"] + 3
    assert got["begin_ms"] > 0 and got["commit_pack_ms"] > 0
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
    for name in ("begin_ms", "device_syncs_per_wave"):
        assert _read(root, name, sut, _chip()) is None, name
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
