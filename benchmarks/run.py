"""Benchmark harness — one module per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run [--figure fig8a]
                                          [--profile rdma_edr | all]
                                          [--json DIR]

Prints ``name,us_per_call,derived`` CSV. With ``--json DIR``, also writes a
machine-readable ``BENCH_<figure>.json`` per figure (rows plus the fabric
transport's per-verb message/byte counters when the figure measures them)
so the perf trajectory is comparable across PRs.

``--time`` runs each figure's measured hot path through the shared
warmup + median-of-k harness (``benchmarks/timing.py``) and adds a
``measured_s`` dict ({row name: seconds}) to every figure's JSON, next to
the modeled numbers — the repo's falsifiable wall-clock baseline.  The
harness errors if a figure forgets to emit it.  Fig 8a additionally emits
an ``overlap`` block (double-buffered route on vs off, plus the sim's
replay pricing of the schedule) and asserts on < off when timed.

``--check`` additionally runs ``fabriccheck`` (the jaxpr lint + one-sided
race detector, ``repro.fabric.check``) over each figure's gating suites
and embeds a ``check: {rules_run, violations}`` block in the JSON; any
violation fails the run.

``--profile`` selects the network profile(s) the modeled/planned parts run
under (``repro.fabric.netsim`` presets; ``all`` sweeps the paper's whole
1GbE -> IPoIB -> FDR -> EDR axis).  Measured figures run their device work
ONCE — counters are workload, profiles are the axis — and re-price /
re-plan per profile, which is how each figure emits the paper's crossover
curves (docs/netsim.md).

Fig 2/3 are model+calibration surrogates (no real NIC here); Fig 6 combines
the measured RSI commit path with the paper's message-economics model; Fig 7
is the analytic cost model; Fig 8a/8b are measured end-to-end operator
runtimes through the ``repro.db`` facade (planner choice + forced grid);
Fig 9 (ours, §6) is sync all-reduce vs the bounded-stale NAM parameter
server under straggler skew.  Output schema: docs/benchmarks.md.
"""
import argparse
import json
import os
import sys

from benchmarks import (fig2_microbench, fig6_rsi, fig7_costmodel,
                        fig8a_joins, fig8b_agg, fig9_ml, fig10_contention,
                        fig_scale, fig_serve)
from repro.fabric import netsim
from repro.launch.cache import enable_compile_cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

MODULES = {
    "fig2": fig2_microbench,
    "fig6": fig6_rsi,
    "fig7": fig7_costmodel,
    "fig8a": fig8a_joins,
    "fig8b": fig8b_agg,
    "fig9": fig9_ml,
    "fig10": fig10_contention,
    "fig_scale": fig_scale,
    "fig_serve": fig_serve,
}


def _figure_key(name: str):
    """Numeric figure order: fig2 ... fig9, fig10, then the unnumbered
    (ours) figures like fig_scale (not lexicographic)."""
    digits = "".join(c for c in name if c.isdigit())
    return (int(digits) if digits else 99, name)


def _run_module(mod, profiles, timed):
    """Normalize run() output: rows, or (rows, extras dict)."""
    res = mod.run(profiles=profiles, timed=timed)
    if isinstance(res, tuple):
        rows, extras = res
    else:
        rows, extras = res, {}
    rows, extras = list(rows), dict(extras)
    if timed and not extras.get("measured_s"):
        raise RuntimeError(f"{mod.__name__} emitted no measured_s under "
                           "--time")
    return rows, extras


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", "--figure", dest="only", default=None,
                    metavar="FIGURE",
                    help="run one figure (--figure is an alias; see --list)")
    ap.add_argument("--list", action="store_true",
                    help="list the registered figures and exit")
    ap.add_argument("--profile", default=None,
                    metavar="NAME|all",
                    help="network profile preset(s): one of "
                         f"{sorted(netsim.PROFILES)}, a legacy key "
                         f"({sorted(netsim.ALIASES)}), or 'all' to sweep "
                         "the whole axis (default: each figure's own)")
    ap.add_argument("--json", default=None, metavar="DIR",
                    help="write BENCH_<figure>.json result files here")
    ap.add_argument("--time", action="store_true",
                    help="measure device wall-clock (warmup + median-of-k)"
                         " and emit measured_s per figure")
    ap.add_argument("--check", action="store_true",
                    help="run fabriccheck (jaxpr lint + race detector) "
                         "over each figure's gating suites and embed a "
                         "check: {rules_run, violations} block in the "
                         "JSON (docs/check.md)")
    args = ap.parse_args()
    if args.list:
        for name in sorted(MODULES, key=_figure_key):
            doc = (MODULES[name].__doc__ or "").strip().splitlines()[0]
            print(f"{name:<7} {doc}")
        return
    if args.only is not None and args.only not in MODULES:
        ap.error(f"unknown figure {args.only!r} — valid figures: "
                 f"{', '.join(sorted(MODULES, key=_figure_key))}")
    if args.profile is None:
        profiles = None                       # each module's default
    elif args.profile == "all":
        profiles = tuple(netsim.PROFILES)
    else:
        profiles = (netsim.get_profile(args.profile).name,)
    names = [args.only] if args.only else sorted(MODULES, key=_figure_key)
    enable_compile_cache(ROOT)
    if args.json:
        os.makedirs(args.json, exist_ok=True)
    print("name,us_per_call,derived")
    failed = []
    for name in names:
        try:
            rows, extras = _run_module(MODULES[name], profiles, args.time)
        except Exception as e:  # noqa: BLE001
            failed.append((name, e))
            print(f"{name}/ERROR,0,{type(e).__name__}:{e}", file=sys.stderr)
            continue
        if args.check:
            from repro.fabric import check as fabric_check
            summ = fabric_check.summarize(fabric_check.check_figure(name))
            extras["check"] = {"rules_run": summ["rules_run"],
                               "violations": summ["violations"],
                               "targets": summ["targets"]}
            status = "clean" if summ["ok"] else \
                f"{len(summ['violations'])} violation(s)"
            print(f"{name}/fabriccheck: {len(summ['targets'])} targets, "
                  f"{status}", file=sys.stderr)
            if not summ["ok"]:
                failed.append((name, RuntimeError("fabriccheck violations")))
        for row, us, derived in rows:
            print(f"{row},{us:.2f},{derived}")
        for row, s in sorted(extras.get("measured_s", {}).items()):
            print(f"{row}/measured,{s * 1e6:.2f},median_wallclock")
        if args.json:
            payload = {
                "figure": name,
                "profile": (args.profile or "default"),
                "timed": args.time,
                "rows": [{"name": row, "us_per_call": us,
                          "derived": derived}
                         for row, us, derived in rows],
                **extras,
            }
            path = os.path.join(args.json, f"BENCH_{name}.json")
            with open(path, "w") as f:
                json.dump(payload, f, indent=2, sort_keys=True)
            print(f"wrote {path}", file=sys.stderr)
    if failed:
        raise SystemExit(f"benchmarks failed: {[n for n, _ in failed]}")


if __name__ == "__main__":
    main()
