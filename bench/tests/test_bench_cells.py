"""Each cell end to end on the CPU at a tiny size, through the same
harness, generators, readers and references the chip runs; and a cell and a
metric added as new files only."""
import json
import os

import pytest

from bench import harness
from bench.tests.tiny import tiny_root

SEED = (1 << 31) + 977          # seeds above 2**31 must work


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root(tmp_path_factory.mktemp("cells"))


@pytest.fixture(autouse=True)
def no_compile_cache(monkeypatch):
    monkeypatch.setattr(harness, "compile_cache", lambda root: None)


def _spec(root):
    return json.load(open(os.path.join(root, "BENCHMARK.json")))


@pytest.mark.parametrize("workload", ["checkout.uniform", "join.1node"])
@pytest.mark.parametrize("traced", [False, True])
def test_cell_runs_correct(root, workload, traced):
    out = harness.run(workload, SEED, 0.5, traced, root=root,
                      require_tpu=False)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "checks"
    assert all(c["value"] <= c["limit"] for c in out["checks"].values())
    spec = _spec(root)
    if traced:
        # the CPU has no device trace: the host-clock readers report,
        # the device readers find nothing and stay silent
        names = {m["name"] for m in spec["per_layer"]
                 if workload in m["workloads"]}
        assert set(out["metrics"]) <= names
        assert out["device"]["window_s"] > 0
        assert "breakdown" in out
        if workload == "checkout.uniform":
            assert set(out["metrics"]) == {"wave_build_ms",
                                           "commit_call_ms", "begin_ms"}
    else:
        names = {m["name"] for m in spec["end_to_end"]
                 if workload in m.get("workloads", [workload])}
        assert set(out["metrics"]) == names
        assert all(m["value"] > 0 for m in out["metrics"].values())


def test_same_seed_same_inputs(root):
    """Two runs of one seed drive the same transactions: the same
    committed sets, whatever the window's length."""
    from bench.generators import checkout_waves
    cell = harness.find_cell(root, _spec(root), "checkout.uniform")
    a = checkout_waves.build(cell.cfg, cell.traffic, SEED)
    b = checkout_waves.build(cell.cfg, cell.traffic, SEED)
    for sut in (a, b):
        sut.warm()
    assert all((x.ok == y.ok).all() and (x.cids == y.cids).all()
               for x, y in zip(a.waves, b.waves))


def test_new_cell_and_metric_are_found_from_new_files(root, tmp_path):
    """A later PR adds a traffic file, a reader and entries: nothing that
    exists is edited."""
    import shutil
    new = str(tmp_path / "extended")
    shutil.copytree(root, new)
    traffic = json.load(open(os.path.join(
        new, "bench", "traffic", "waves_uniform.json")))
    traffic.update(sessions_per_wave=8)
    with open(os.path.join(new, "bench", "traffic", "waves_small.json"),
              "w") as f:
        json.dump(traffic, f)
    with open(os.path.join(new, "bench", "metrics", "commits_traced.py"),
              "w") as f:
        f.write("def read(ctx):\n"
                "    return ctx.trace.count_spans('bench.commit')\n")
    spec = _spec(new)
    spec["workloads"].append(
        {"name": "checkout.small", "config": "s43_checkout",
         "traffic": "waves_small", "chips": 1, "why": "test"})
    for m in spec["end_to_end"]:
        if "checkout.uniform" in m.get("workloads", []):
            m["workloads"].append("checkout.small")
    spec["per_layer"].append(
        {"name": "commits_traced", "unit": "count", "better": "higher",
         "source": "host_clock", "layer": "facade", "moves": "txn_per_s",
         "workloads": ["checkout.small"]})
    with open(os.path.join(new, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    out = harness.run("checkout.small", SEED, 0.3, True, root=new,
                      require_tpu=False)
    assert out["correct"], out["checks"]
    assert out["metrics"]["commits_traced"]["value"] >= 1
    assert out["attempted"] % 8 == 0
    out = harness.run("checkout.small", SEED, 0.3, False, root=new,
                      require_tpu=False)
    assert {"txn_per_s", "txn_p95_ms", "setup_s"} == set(out["metrics"])
