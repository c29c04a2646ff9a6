"""chip_smoke.py's phases, rehearsed at a tiny size on the CPU: the same
functions the chip runs, with the Pallas partitioner in interpret mode
where a test forces it, and the four-chip phase on four virtual devices."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from repro.db import Database
from repro.fabric import router

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
import chip_smoke  # noqa: E402

TINY_OLTP = dict(products=64, waves=2, sessions=16, payload_words=4)


@pytest.fixture(params=["jnp", "pallas"])
def scatter(request, monkeypatch):
    """Route every scatter through one backend (on the chip the platform
    picks pallas; here the interpreter runs the same kernel)."""
    chosen = request.param
    real = router._scatter
    monkeypatch.setattr(router, "_scatter", lambda rows, plan, mask, b:
                        real(rows, plan, mask, b or chosen))
    return chosen


def test_oltp_phase(scatter):
    out = chip_smoke.oltp_phase(Database(), **TINY_OLTP)
    assert out["committed"] > 0 and out["aborted"] > 0
    assert out["txns"] == 32 and out["reads_checked"] > 0


def test_olap_phase(scatter):
    out = chip_smoke.olap_phase(Database(), tuples=2048, groups=64)
    assert {k.split(":")[0] for k in out} >= {"join/planned", "agg/dist_agg",
                                              "agg/rdma_agg"}


def test_route_phase():
    text = chip_smoke.route_phase(rows=4096)
    assert "tpu_custom_call" not in text     # the CPU default is jnp


_MESH = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, sys.argv[1])
import chip_smoke
from repro.launch.mesh import make_mesh
out = chip_smoke.mesh_phase(make_mesh((4,), ("data",)), products=64,
                            waves=2, sessions=16, payload_words=4,
                            tuples=2048)
assert out["committed"] > 0 and out["aborted"] > 0, out
print("MESH_PHASE_OK")
"""


def _env():
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"),
               JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return env


def test_mesh_phase_on_four_devices():
    r = subprocess.run([sys.executable, "-c", _MESH, ROOT], env=_env(),
                       capture_output=True, text=True, timeout=600)
    assert "MESH_PHASE_OK" in r.stdout, r.stderr[-3000:]


@pytest.mark.parametrize("alone", [False, True])
def test_script_refuses_without_tpu(tmp_path, alone):
    """No TPU, or no repo beside the script: non-zero, no result line."""
    script = os.path.join(ROOT, "chip_smoke.py")
    if alone:
        script = str(shutil.copy(script, tmp_path))
    env = _env()
    if alone:
        env.pop("PYTHONPATH")
    r = subprocess.run([sys.executable, script], env=env, cwd=tmp_path,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    for line in r.stdout.splitlines():
        with pytest.raises(ValueError):
            json.loads(line)
