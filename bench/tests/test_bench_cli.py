"""The command's refusals; the four-node join on four virtual devices,
with and without the exchange between them; and the checkout on four
storage nodes (its configuration changed in data files only)."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench.tests.tiny import ROOT, tiny_root


def _env(**extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **extra)
    env.pop("XLA_FLAGS", None)
    env.pop("PYTHONPATH", None)
    return env


def test_refuses_without_a_tpu():
    r = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "checkout.uniform", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=ROOT, env=_env(),
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0 and r.stdout == ""
    assert "no TPU" in r.stderr


def test_refuses_without_the_program(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's files."""
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench")
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    r = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "join.1node", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=tmp_path, env=_env(),
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0 and r.stdout == ""


_FOUR = r"""
import json, os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
root, repo, fault = sys.argv[1], sys.argv[2], sys.argv[3]
sys.path[:0] = [repo, os.path.join(repo, "src")]
from bench import harness
harness.compile_cache = lambda root: None
path = os.path.join(root, "BENCHMARK.json")
spec = json.load(open(path))
workload = "join.4node"
if fault == "no_exchange":
    from repro.fabric import transport
    transport.MeshTransport._make_exchange = lambda self, cap, chunks: None
    transport.MeshTransport.exchange = lambda self, v, chunks=1: v
if fault == "checkout":
    # the checkout on four storage nodes: data files only
    cfg_path = os.path.join(root, "bench", "configs", "s43_checkout.json")
    cfg = json.load(open(cfg_path))
    cfg["num_storage_nodes"] = 4
    json.dump(cfg, open(cfg_path, "w"))
    for w in spec["workloads"]:
        if w["name"] == "checkout.uniform":
            w["chips"] = 4
    json.dump(spec, open(path, "w"))
    workload = "checkout.uniform"
out = harness.run(workload, 99, 0.3, fault == "traced", root=root,
                  require_tpu=False)
print("RESULT " + json.dumps(out))
"""


@pytest.mark.parametrize("fault",
                         ["none", "traced", "no_exchange", "checkout"])
def test_four_nodes_on_four_devices(tmp_path, fault):
    root = tiny_root(tmp_path)
    r = subprocess.run([sys.executable, "-c", _FOUR, root, ROOT, fault],
                       env=_env(), capture_output=True, text=True,
                       timeout=600)
    line = [x for x in r.stdout.splitlines() if x.startswith("RESULT ")]
    assert line, r.stderr[-3000:]
    out = json.loads(line[0][len("RESULT "):])
    assert out["device"]["count"] == 4
    assert out["correct"] == (fault != "no_exchange"), out["checks"]
