"""One run of one cell: find everything the cell names, set it up, measure
a window, check the answers against the reference, and build the result.

``BENCHMARK.json`` names each cell's configuration (its ``file``) and
traffic; the traffic file ``bench/traffic/<traffic>.json`` names its
generator, ``bench/generators/<generator>.py``; each per-layer metric
is read by ``bench/metrics/<metric>.py``.  Adding a cell, a traffic mix
or a metric adds files and entries and edits none."""
from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import shutil
import time
from dataclasses import dataclass

import jax

from bench import trace as trace_mod
from bench.common import span
from bench.peaks import peaks as peaks_of

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class NoAccelerator(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


class MetricNotFound(RuntimeError):
    """A per-layer metric's reader found nothing in a device trace of a
    cell that ``BENCHMARK.json`` lists the metric for."""


def load_json(root: str, rel: str):
    with open(os.path.join(root, rel)) as f:
        return json.load(f)


def load_module(root: str, rel: str):
    """Import a file of the benchmark by its path under ``root``."""
    path = os.path.join(root, rel)
    name = "bench_" + rel.replace("/", "_").replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    """A workload entry of ``BENCHMARK.json`` with what it names."""
    name: str
    chips: int
    cfg: dict
    traffic: dict
    generator: object
    end_to_end: list
    per_layer: list


def _listed(metric: dict, workload: str, reported: set) -> bool:
    """Whether a metric belongs to a cell: by its ``workloads`` list, or,
    for a per-layer metric without one, by the end-to-end metric it
    moves."""
    if "workloads" in metric:
        return workload in metric["workloads"]
    return "moves" not in metric or metric["moves"] in reported


def find_cell(root: str, spec: dict, workload: str) -> Cell:
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    cfg = load_json(root, configs[w["config"]]["file"])
    traffic = load_json(root, f"bench/traffic/{w['traffic']}.json")
    generator = load_module(
        root, f"bench/generators/{traffic['generator']}.py")
    e2e = [m for m in spec["end_to_end"] if _listed(m, workload, set())]
    reported = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"]
             if _listed(m, workload, reported)]
    return Cell(workload, w["chips"], cfg, traffic, generator, e2e, layer)


def _devices(chips: int, require_tpu: bool):
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise NoAccelerator(f"no TPU: JAX sees {devices[0].platform}")
    if len(devices) < chips:
        raise NoAccelerator(f"the cell needs {chips} chips, JAX sees "
                            f"{len(devices)}")
    return devices


def _memory_peak(devices):
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def compile_cache(root: str):
    """JAX's persistent compilation cache at the program's fixed path in
    the checkout (``$JAX_COMPILATION_CACHE_DIR`` where set), keeping every
    program, so that only a cell's first run in a checkout compiles."""
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache(root)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def read_per_layer(root: str, cell: Cell, ctx) -> dict:
    """The cell's per-layer metrics, each from its reader.  A reader finds
    nothing only in a trace without device events (the CPU's): in a
    device trace, a metric listed for the cell whose reader finds nothing
    raises, so that a renamed program or op cannot switch a metric off
    unseen.  A metric matched by what it moves, not listed, is left out
    where its reader finds nothing."""
    metrics = {}
    for m in cell.per_layer:
        reader = load_module(root, f"bench/metrics/{m['name']}.py")
        value = reader.read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        elif ctx.trace.chips() and cell.name in m.get("workloads", ()):
            raise MetricNotFound(
                f"{m['name']}: its reader (bench/metrics/{m['name']}.py) "
                f"found nothing in the device trace of {cell.name}")
    return metrics


@dataclass
class Context:
    """What a per-layer metric's reader sees."""
    trace: "trace_mod.Trace"
    cell: object               # the generator's cell (route shapes...)
    peaks: dict


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        root: str = ROOT, t_start: float = None, require_tpu: bool = True,
        control: bool = False) -> dict:
    """Run one cell and return its result, ``checks`` last.  ``control``
    runs the generator's control in the program's place."""
    t_start = time.perf_counter() if t_start is None else t_start
    spec = load_json(root, "BENCHMARK.json")
    cell = find_cell(root, spec, workload)
    devices = _devices(cell.chips, require_tpu)
    used = devices[:cell.chips]
    kind = used[0].device_kind
    peaks = peaks_of(kind) if require_tpu else {}
    compile_cache(root)
    with (cell.generator.control() if control
          else contextlib.nullcontext()):
        sut = cell.generator.build(cell.cfg, cell.traffic, seed)
        sut.warm()
        setup_s = time.perf_counter() - t_start
        tdir = os.path.join(root, ".bench_trace")
        if trace:
            shutil.rmtree(tdir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(tdir, profiler_options=opts)
        try:
            with span("bench.window"):
                sut.window(seconds)
        finally:
            if trace:
                jax.profiler.stop_trace()
        memory_peak = _memory_peak(used)
        sut.collect()
    attempted, failed = sut.attempted, sut.failed
    metrics, breakdown = {}, None
    device = {"platform": used[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    if trace:
        tr = trace_mod.load(tdir, chips=cell.chips)
        shutil.rmtree(tdir, ignore_errors=True)
        metrics = read_per_layer(root, cell, Context(tr, sut, peaks))
        device["busy_s"] = tr.busy_s()
        device["window_s"] = tr.window_s()
        breakdown = {"device_ops": tr.top_ops(10),
                     "idle_gaps": tr.idle_gaps(10)}
    else:
        values = dict(sut.end_to_end(), setup_s=setup_s)
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    sut.free()
    checks = sut.checks()
    out = {"correct": all(c.ok for c in checks) and failed == 0,
           "attempted": attempted, "failed": failed, "metrics": metrics,
           "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                     for c in checks}
    return out
