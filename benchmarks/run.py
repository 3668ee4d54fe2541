"""Benchmark entry point: one suite per paper table/figure.

  python -m benchmarks.run [--full] [--only NAME] [--json PATH]

Emits ``name,value,derived`` CSV per suite and writes a machine-readable
``BENCH_sweep.json`` artifact (per-scenario rows + per-suite wall-clock)
so the perf trajectory is diffable across PRs. Default budgets keep the
whole run CPU-tractable; --full expands to the paper's complete grids
(including the 768-scenario Table-1 sweep).

The harness always runs with `repro.obs` tracing enabled: each suite's
artifact entry carries a ``wall_breakdown`` (per-phase wall seconds —
plan builds, client train, selection, eval, ...) next to its ``wall_s``,
and the artifact's top-level ``obs`` section records the run's counters
and cache hit rates. These are *informational* wall-clock telemetry —
machine-dependent, so `check_regression.py` reports them as trend rows
but never fails on them; the metric rows themselves are simulation-time
quantities and stay bitwise identical with tracing on or off. Pass
``--trace OUT.json`` to additionally dump the full Chrome/Perfetto
trace.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

from benchmarks import (
    bench_accuracy,
    bench_idle,
    bench_kernels,
    bench_roofline,
    bench_round_duration,
    bench_scale,
    bench_speedup,
    bench_sweep,
)
from benchmarks.common import emit

from repro import obs  # noqa: E402  (benchmarks.common puts src/ on path)
from repro.compile_cache import use_compile_cache  # noqa: E402

# Every suite takes (full, execution, link_model, workload, algorithms,
# codec);
# suites that never run gradients ignore the execution axis (it only
# changes how gradients run), only the Table-1 sweep carries the
# link-model axis (it owns the comms-pricing claims) and the algorithms
# axis (an explicit registry-name list replacing its built-in suite),
# and the workload axis re-prices the sweep/accuracy suites for a
# registry workload (e.g. the LM suite: lm_tiny / lm_moe_tiny /
# lm_rwkv6_tiny / lm_hybrid_tiny). The sweep is timing-only by default,
# so requesting an execution mode switches it to real training
# (otherwise the rows would be mislabelled host numbers).
SUITES = {
    "kernels": lambda full, ex, lm, wl, al, cd: bench_kernels.run(),
    "round_duration": lambda full, ex, lm, wl, al, cd:
        bench_round_duration.run(quick=not full),
    "idle": lambda full, ex, lm, wl, al, cd: bench_idle.run(quick=not full),
    "speedup": lambda full, ex, lm, wl, al, cd: bench_speedup.run(
        train=True, rounds=150 if full else 100, execution=ex),
    "accuracy": lambda full, ex, lm, wl, al, cd: bench_accuracy.run(
        quick=not full, rounds=150 if full else 100, execution=ex,
        workload=wl),
    "sweep768": lambda full, ex, lm, wl, al, cd: bench_sweep.run(
        quick=not full, train=ex is not None, execution=ex,
        link_model=lm, workload=wl, algorithms=al, codec=cd),
    "scale": lambda full, ex, lm, wl, al, cd: bench_scale.run(
        quick=not full),
    "roofline": lambda full, ex, lm, wl, al, cd: bench_roofline.run(),
}

DEFAULT_JSON = os.path.join(os.path.dirname(__file__), "..",
                            "BENCH_sweep.json")


def _span_totals() -> dict[str, float]:
    s = obs.metrics_summary()
    return {k: v["total_s"] for k, v in s.get("spans", {}).items()}


def _breakdown(before: dict[str, float], after: dict[str, float],
               min_s: float = 0.005) -> dict[str, float]:
    """Per-phase wall seconds spent between two span-total snapshots."""
    out = {}
    for name, total in after.items():
        d = total - before.get(name, 0.0)
        if d >= min_s:
            out[name] = round(d, 3)
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None, choices=list(SUITES) + [None])
    ap.add_argument("--json", default=DEFAULT_JSON,
                    help="machine-readable artifact path ('' disables)")
    ap.add_argument("--execution", default=None, choices=("host", "mesh"),
                    help="client-update execution mode for training suites")
    ap.add_argument("--link-model", default=None,
                    choices=("constant", "budget"),
                    help="comms pricing for the Table-1 sweep (budget = "
                         "slant-range LinkBudget re-rated from cached "
                         "plan geometry)")
    from repro.core import workload_names
    ap.add_argument("--workload", default=None, choices=workload_names(),
                    help="re-price the sweep/accuracy suites for a "
                         "registry workload (default: the seed's "
                         "femnist_mlp constants)")
    ap.add_argument("--algorithms", default=None, metavar="A,B,...",
                    help="comma-separated registry algorithm names for "
                         "the Table-1 sweep (replaces its built-in "
                         "suite; unknown names error up front)")
    from repro.comms.codec import codec_names
    ap.add_argument("--codec", default=None, choices=codec_names(),
                    help="uplink transfer codec for the Table-1 sweep "
                         "(compressed client returns; with --execution "
                         "the accuracy cost is measured on the real "
                         "training path)")
    ap.add_argument("--trace", default=None, metavar="OUT.json",
                    help="write the full Chrome/Perfetto trace of the run "
                         "(per-suite wall breakdowns land in the artifact "
                         "regardless)")
    args = ap.parse_args(argv)
    use_compile_cache()

    algorithms = None
    if args.algorithms:
        algorithms = tuple(
            a.strip() for a in args.algorithms.split(",") if a.strip())
        from repro.core import ALGORITHMS, algorithm_names
        unknown = sorted(a for a in algorithms if a not in ALGORITHMS)
        if unknown:
            ap.error(f"unknown algorithm(s) {unknown}; registered "
                     f"algorithms: {algorithm_names()}")

    # The harness owns wall-clock telemetry: tracing is always on here
    # (it only observes walls; metric rows are simulation-time values and
    # stay bitwise identical — see tests/test_obs.py).
    obs.enable()
    artifact: dict = {"schema": 1, "generated_unix": round(time.time(), 1),
                      "full": bool(args.full), "only": args.only,
                      "execution": args.execution,
                      "link_model": args.link_model,
                      "workload": args.workload,
                      "codec": args.codec,
                      "suites": {}}
    names = [args.only] if args.only else list(SUITES)
    failed: list[str] = []
    t_total = time.perf_counter()
    for name in names:
        print(f"# ==== {name} ====")
        t0 = time.perf_counter()
        spans0 = _span_totals()
        try:
            rows = SUITES[name](args.full, args.execution, args.link_model,
                                args.workload, algorithms, args.codec)
            emit(rows)
            wall = time.perf_counter() - t0
            print(f"# {name}: {len(rows)} rows in {wall:.1f}s")
            artifact["suites"][name] = {
                "wall_s": round(wall, 2),
                "wall_breakdown": _breakdown(spans0, _span_totals()),
                "rows": [list(r) for r in rows],
            }
        except Exception as e:  # noqa: BLE001
            # Record the failure and keep going, so the other suites still
            # land in the artifact; the run exits non-zero at the end.
            traceback.print_exc()
            print(f"# {name}: FAILED {repr(e)[:300]}")
            failed.append(name)
            artifact["suites"][name] = {
                "wall_s": round(time.perf_counter() - t0, 2),
                "error": repr(e)[:300],
            }
        sys.stdout.flush()
    artifact["wall_s_total"] = round(time.perf_counter() - t_total, 2)
    summary = obs.metrics_summary()
    artifact["obs"] = {"counters": summary["counters"],
                       "rates": summary["rates"]}
    if args.trace:
        obs.write_chrome_trace(args.trace)
        print(f"# obs wrote trace to {args.trace}")
    if args.only and args.json == DEFAULT_JSON:
        # Don't clobber the cross-PR trend artifact with a partial run;
        # pass --json explicitly to write one anyway.
        print("# --only run: skipping default BENCH_sweep.json write")
    elif args.json:
        # Merge over an existing artifact: suites this run didn't execute
        # (notably the committed `sweep_ci` baseline the CI regression
        # gate compares against — benchmarks/check_regression.py) must
        # survive a refresh of the others.
        if os.path.exists(args.json):
            try:
                with open(args.json) as f:
                    prior = json.load(f).get("suites", {})
                for name, suite in prior.items():
                    artifact["suites"].setdefault(name, suite)
            except (json.JSONDecodeError, AttributeError):
                pass  # corrupt artifact: overwrite it
        with open(args.json, "w") as f:
            json.dump(artifact, f, indent=1)
        print(f"# wrote {os.path.normpath(args.json)}")
    if failed:
        sys.exit(f"benchmark suite(s) failed: {', '.join(failed)}")


if __name__ == "__main__":
    main()
