"""A copy of the benchmark whose cells run at a tiny size on the CPU:
the same cells, generators, readers and references, with the configuration
and traffic files swapped for tiny ones."""
from __future__ import annotations

import json
import os
import shutil

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

TINY_CONFIGS = {
    "s43_checkout": {"num_products": 256, "record_bytes": 16,
                     "insert_rows": 16 * 4 * 256},
    # values up to 1000 keep the exact sum below 2**32 but far above
    # 2**24, where a float32 sum rounds, as at the full size
    "s54_olap_1node": {"tuples_per_node": 2048, "value_max": 1000},
    "s54_olap_4node": {"tuples_per_node": 512, "value_max": 1000},
}
TINY_TRAFFIC = {
    "waves_uniform": {"sessions_per_wave": 16},
    "join_sel50": {},
}


def tiny_root(tmp_path) -> str:
    """A benchmark root under ``tmp_path`` with every configuration and
    traffic file cut to a tiny size; returns its path."""
    root = str(tmp_path / "bench_root")
    shutil.copytree(os.path.join(ROOT, "bench"), os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    for name, over in TINY_CONFIGS.items():
        path = os.path.join(root, "bench", "configs", name + ".json")
        cfg = json.load(open(path))
        cfg.update(over)
        json.dump(cfg, open(path, "w"))
    for name, over in TINY_TRAFFIC.items():
        path = os.path.join(root, "bench", "traffic", name + ".json")
        traffic = json.load(open(path))
        traffic.update(over)
        json.dump(traffic, open(path, "w"))
    return root
