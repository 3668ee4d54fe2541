"""What tracing costs in one cell, and where its idle device time goes,
by program layer:

    python3 bench/tracing_modes.py --workload <cell> --seed <n> --seconds <s>

Builds the cell and makes its checked call as `bench/run.py` does, then
calls the program's entry in stretches of whole calls:

- `off`, `obs_nosync` (`repro.obs` on with `sync=False`, no profiler) and
  `obs_sync` (`repro.obs` on, blocking: the traced run's span stretch),
  `--seconds` each, in that order, `--repeats` times: updates per second
  in each mode;
- `profiled`: `PROFILE_SECONDS` under the profiler with `repro.obs` off
  (the traced run's profiled stretch): its idle share, by
  `bench.trace.reduce`;
- `attributed`: `PROFILE_SECONDS` under the profiler with `repro.obs` on
  and `sync=False`: its idle share, and its idle time split by program
  layer (`bench.attribution.attribute`) in milliseconds per update, with
  the program's host-device traffic counters per update.

Every call is held bitwise to the checked call (`runs_differ`). Prints
one JSON object as the last line of standard output. TPU only: it exits
non-zero, and prints no result, where JAX finds no TPU or fewer chips
than the cell asks for.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

MODES = ("off", "obs_nosync", "obs_sync")


def _profiled(bench_cell, seconds: float, obs_sync: bool | None):
    """Calls in a profiled stretch with `repro.obs` off (None) or on with
    the given `sync`. Returns (results, reduced trace, attribution, obs
    counters)."""
    import jax
    from bench import attribution, harness, trace
    from repro import obs

    prof_dir = tempfile.mkdtemp(prefix="bench_profile_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    tracing = (contextlib.nullcontext() if obs_sync is None
               else obs.tracing(sync=obs_sync))
    try:
        jax.profiler.start_trace(prof_dir, profiler_options=opts)
        try:
            with tracing as tracer:
                out, _ = harness._stretch(bench_cell, seconds, annotate=True)
        finally:
            jax.profiler.stop_trace()
        loaded = trace.load(prof_dir)
    finally:
        shutil.rmtree(prof_dir, ignore_errors=True)
    counters = dict(tracer.counters) if tracer is not None else {}
    return (out, trace.reduce(loaded), attribution.attribute(loaded),
            counters)


def measure(spec: dict, seed: int, seconds: float, repeats: int, *,
            require_tpu: bool = True) -> dict:
    """The stretches above for one cell; returns the result object."""
    from bench import harness
    from bench.cell import Cell, same_results
    from repro import obs

    chips = int(spec["cell"]["chips"])
    devs = harness.devices_for(chips, require_tpu)
    bench_cell = Cell(spec["cfg"], spec["mix"], seed, {})
    checked, _ = bench_cell.checked_call()
    per_call = bench_cell.updates(checked)
    calls: list = []
    rates = {m: [] for m in MODES}
    for _ in range(repeats):
        for mode in MODES:
            with (obs.tracing(sync=mode == "obs_sync") if mode != "off"
                  else contextlib.nullcontext()):
                out, wall = harness._stretch(bench_cell, seconds)
            calls += out
            rates[mode].append(per_call * len(out) / wall)
    out, reduced, _, _ = _profiled(bench_cell, harness.PROFILE_SECONDS, None)
    calls += out
    out, attr_reduced, attr, counters = _profiled(
        bench_cell, harness.PROFILE_SECONDS, False)
    calls += out
    updates = per_call * len(out)
    result = {
        "workload": spec["cell"]["name"], "seed": seed,
        "device": {"platform": devs[0].platform,
                   "kind": devs[0].device_kind, "count": chips},
        "updates_per_call": per_call,
        "rounds_per_s": rates,
        "profiled": None if reduced is None else {
            "idle_share": reduced["idle_share"],
            "window_s": reduced["window_s"]},
        "attributed": None,
        "traffic_per_update": {
            "h2d_mb": counters.get("sim.h2d_bytes", 0) / 1e6 / updates,
            "d2h_kb": counters.get("sim.d2h_bytes", 0) / 1e3 / updates,
            "host_syncs": counters.get("sim.host_syncs", 0) / updates},
        "runs_differ": sum(not same_results(checked, r) for r in calls),
        "calls": len(calls),
    }
    if attr is not None:
        idle_ms = {k: v / updates for k, v in attr["idle_ms"].items()}
        result["attributed"] = {
            "idle_share": attr_reduced["idle_share"],
            "window_s": attr["window_s"], "updates": updates,
            "idle_ms_per_update": idle_ms,
            "idle_ms_sum_per_update": sum(idle_ms.values()),
            "idle_ms_per_update_from_share":
                attr_reduced["idle_share"] * attr["window_s"] * 1e3
                / updates}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--repeats", type=int, default=2)
    args = ap.parse_args(argv)

    from bench import harness
    spec = harness.load_spec(args.workload)
    try:
        harness.devices_for(int(spec["cell"]["chips"]), require_tpu=True)
    except harness.NoDevice as e:
        print(f"tracing_modes: {e}", file=sys.stderr)
        return 1
    harness.use_bench_cache()
    result = measure(spec, args.seed, args.seconds, args.repeats)
    result["process_s"] = time.perf_counter() - T0
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
