"""The three-table checkout on four storage nodes (``checkout.4node``) at
a tiny size on four virtual CPU devices: correct traced and untraced, its
control (each table committed on its own) not; and the cell's two new
readers on synthetic windows."""
import json
import os
import subprocess
import sys
import time
from types import SimpleNamespace

import pytest

from bench import harness, trace
from bench.tests.tiny import ROOT, tiny_root

# the new cell's sizes at a tiny scale, set in this file's own copy
TINY = {"num_products": 256, "record_bytes": 16,
        "orders_insert_rows": 16 * 256, "orderlines_insert_rows": 48 * 256}
SESSIONS = 16                   # a multiple of the 4 shards

_RUNS = r"""
import json, os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
root, repo = sys.argv[1], sys.argv[2]
sys.path[:0] = [repo, os.path.join(repo, "src")]
from bench import harness
harness.compile_cache = lambda root: None
for mode in ("plain", "traced", "control"):
    out = harness.run("checkout.4node", (1 << 31) + 977, 0.3,
                      mode == "traced", root=root, require_tpu=False,
                      control=mode == "control")
    print("RESULT " + json.dumps(dict(out, mode=mode)))
"""


def _tiny_4node_root(tmp_path):
    root = tiny_root(tmp_path)
    for rel, over in (("configs/s43_checkout_4node.json", TINY),
                      ("traffic/waves_orders_uniform.json",
                       {"sessions_per_wave": SESSIONS})):
        path = os.path.join(root, "bench", rel)
        d = json.load(open(path))
        d.update(over)
        json.dump(d, open(path, "w"))
    return root


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    root = _tiny_4node_root(tmp_path_factory.mktemp("four"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    env.pop("PYTHONPATH", None)
    r = subprocess.run([sys.executable, "-c", _RUNS, root, ROOT], env=env,
                       capture_output=True, text=True, timeout=900)
    out = {}
    for line in r.stdout.splitlines():
        if line.startswith("RESULT "):
            res = json.loads(line[len("RESULT "):])
            out[res["mode"]] = res
    assert set(out) == {"plain", "traced", "control"}, r.stderr[-3000:]
    return out


@pytest.mark.parametrize("mode", ["plain", "traced"])
def test_the_cell_runs_correct_on_four_devices(runs, mode):
    out = runs[mode]
    assert out["correct"], out["checks"]
    assert out["device"]["count"] == 4
    assert out["attempted"] > 0 and out["attempted"] % SESSIONS == 0
    assert "torn_txns" in out["checks"]
    if mode == "plain":
        assert set(out["metrics"]) == {"txn_per_s", "txn_p95_ms",
                                       "setup_s"}
    else:
        # the CPU trace has no chip: only the host-clock readers
        assert set(out["metrics"]) == {"wave_build_ms", "commit_call_ms",
                                       "begin_ms"}


def test_the_control_is_torn(runs):
    """Each table committed as a commit of its own: a transaction whose
    product update loses still shows its order and order lines."""
    out = runs["control"]
    assert not out["correct"]
    assert out["checks"]["torn_txns"]["value"] > 0
    assert out["checks"]["readback_mismatch"]["value"] > 0


def test_the_cell_is_listed_with_its_metrics():
    spec = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cell = harness.find_cell(ROOT, spec, "checkout.4node")
    assert cell.chips == 4
    assert {m["name"] for m in cell.end_to_end} == {
        "txn_per_s", "txn_p95_ms", "setup_s"}
    assert {"all_to_all_ms.checkout", "commit_complete_ms", "begin_ms",
            "device_syncs_per_wave", "commit_pack_ms",
            "window_compiles.checkout"} <= {m["name"] for m in cell.per_layer}


def _window_trace(ops, waves):
    """A trace of a 10 ms window on two chips, with ``waves``
    ``bench.commit`` spans in it."""
    return trace.Trace(
        ops={0: ops, 1: ops}, modules={0: [], 1: []},
        spans=[(0, 10_000_000, "bench.window")]
        + [(i * 1000, i * 1000 + 500, "bench.commit") for i in range(waves)])


def test_all_to_all_reader_on_a_synthetic_window():
    reader = harness.load_module(
        ROOT, "bench/metrics/all_to_all_ms.checkout.py")
    ops = [(100, 400, "all-to-all.1", "all-to-all", "jit__lambda"),
           (500, 600, "all-to-all-start.2", "all-to-all-start",
            "jit__lambda"),
           (700, 900, "fusion.3", "fusion", "jit__lambda")]

    def read(ops):
        return reader.read(harness.Context(_window_trace(ops, 2), None, {}))
    assert read(ops) == pytest.approx(1e3 * 400e-9 / 2)
    assert read([o for o in ops if o[3] == "fusion"]) is None


def test_commit_complete_reader_on_a_synthetic_window():
    """Two sharded waves, each finishing three tables."""
    from repro import obs
    reader = harness.load_module(ROOT, "bench/metrics/commit_complete_ms.py")
    start = time.perf_counter()
    for wave in range(2):
        with obs.span("namdb.commit", wave=wave):
            for _ in range(3):
                with obs.span("namdb.commit.complete"):
                    time.sleep(0.001)
    cell = SimpleNamespace(waves=[SimpleNamespace(t_end=time.perf_counter())],
                           window_s=time.perf_counter() - start)
    chip = trace.Trace(ops={0: []}, modules={0: []})
    got = reader.read(harness.Context(chip, cell, {}))
    assert 3.0 <= got < 50.0          # three spans of >= 1 ms per wave
    # without a chip (the CPU's trace) it reads nothing
    assert reader.read(harness.Context(trace.Trace(), cell, {})) is None
