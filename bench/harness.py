"""The benchmark's run: set-up, the measured window, the traced stretches,
the check against the plain reference, and the result line.

Everything a cell needs is found by name: its configuration in
`bench/configs/<config>.json` with the plain model `<config>.py` beside
it (`init`, `apply`, the data loss `loss`, `forward_flops`), the data
generator the configuration names in `bench/traffic/<generator>.py`, its
mix in `bench/traffic/<traffic>.json`, its limits in
`bench/limits/<cell>.json`, each metric's reader in
`bench/metrics/<metric>.py`, and the peaks in `bench/peaks.json`.
"""
from __future__ import annotations

import contextlib
import gc
import importlib.util
import json
import os
import resource
import shutil
import sys
import tempfile
import time
import types

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
PROFILE_SECONDS = 2.0     # the profiled stretch, in whole calls
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
CACHE_TIME_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"


class NoDevice(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


# ------------------------------------------------------------ loading --
def _json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def _module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_spec(name: str, root: str = ROOT) -> dict:
    """Everything the harness needs for cell `name`, read from files."""
    bench = _json(root, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    cfg_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    cfg = _json(root, cfg_entry["file"])
    model = _module(os.path.splitext(os.path.join(root, cfg_entry["file"]))[0]
                    + ".py", f"bench_config_{cell['config']}")
    kind = {m["name"]: "end_to_end" for m in bench["end_to_end"]}
    kind.update({m["name"]: "per_layer" for m in bench["per_layer"]})
    metrics = []
    for m in bench["end_to_end"] + bench["per_layer"]:
        listed = m.get("workloads")
        if listed is None or name in listed:
            metrics.append(dict(m, kind=kind[m["name"]]))
    here = os.path.join(root, "bench")
    return {"cell": cell, "cfg": cfg, "model": model,
            "mix": _json(here, "traffic", cell["traffic"] + ".json"),
            "limits": _json(here, "limits", name + ".json"),
            "metrics": metrics, "peaks": _json(here, "peaks.json"),
            "root": root}


def reader(metric: str, root: str = ROOT):
    """The `read(ctx)` of `bench/metrics/<metric>.py`."""
    path = os.path.join(root, "bench", "metrics", metric + ".py")
    return _module(path, "bench_metric_" + metric.replace(".", "_")).read


# ----------------------------------------------------------- compiles --
class Compiles:
    """Counts JAX's compile and persistent-cache events (installed once)."""

    _installed = None

    def __init__(self):
        self.compiles = self.hits = 0
        self.seconds = 0.0

    @classmethod
    def get(cls) -> "Compiles":
        if cls._installed is None:
            from jax import monitoring
            rec = cls()

            def on_duration(event, secs, **_):
                if event == COMPILE_EVENT:
                    rec.compiles += 1
                    rec.seconds += secs
                elif event == CACHE_TIME_EVENT:
                    rec.seconds += secs

            def on_event(event, **_):
                if event == CACHE_HIT_EVENT:
                    rec.hits += 1

            monitoring.register_event_duration_secs_listener(on_duration)
            monitoring.register_event_listener(on_event)
            cls._installed = rec
        return cls._installed

    def snapshot(self) -> tuple[int, int, float]:
        return self.compiles, self.hits, self.seconds


def use_bench_cache() -> str:
    """JAX's persistent compile cache at one fixed path in the checkout
    (or where `JAX_COMPILATION_CACHE_DIR` says), caching every program."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def devices_for(chips: int, require_tpu: bool):
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoDevice(f"no TPU: JAX's devices are {devs[0].platform}; the "
                       "benchmark measures only on a TPU")
    if len(devs) < chips:
        raise NoDevice(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return devs


# ---------------------------------------------------------------- run --
def run_cell(spec: dict, seed: int, seconds: float, trace: bool, *,
             t0: float, require_tpu: bool = True, log=None,
             parts: dict | None = None) -> dict:
    """One run of one cell. Returns the result object (the last line).
    `parts` may hold set-up parts timed before the call (start-up, device
    init); the run adds its own."""
    log = log or (lambda msg: print(msg, file=sys.stderr, flush=True))
    cell, mix, cfg, model = (spec["cell"], spec["mix"], spec["cfg"],
                             spec["model"])
    parts = dict(parts or {})
    devs = devices_for(int(cell["chips"]), require_tpu)
    import jax
    from bench.cell import Cell, program_seed, same_results
    compiles = Compiles.get()
    c0 = compiles.snapshot()

    bench_cell = Cell(cfg, mix, seed, parts)
    t = time.perf_counter()
    checked_results, checked_params = bench_cell.checked_call()
    parts["warmup_s"] = time.perf_counter() - t
    c1 = compiles.snapshot()
    parts.update(compile_s=c1[2] - c0[2], compiles=c1[0] - c0[0],
                 cache_hits=c1[1] - c0[1])
    per_call = bench_cell.updates(checked_results)
    setup_s = time.perf_counter() - t0
    print(json.dumps({"setup_parts": parts}), flush=True)

    ctx = types.SimpleNamespace(
        setup_s=setup_s, parts=parts, steps=bench_cell.step_counts(
            checked_results), peak=None, profile=None, profile_calls=0,
        spans=[], obs_updates=0, window_compiles=None, window=None)
    kind = devs[0].device_kind
    if trace:
        ctx.peak = spec["peaks"].get(kind)
        if ctx.peak is None:
            raise KeyError(f"device kind {kind!r} is not in bench/peaks.json")
    fwd = model.forward_flops(cfg)
    ctx.useful_flops_per_call = 3 * fwd * mix["batch_size"] * ctx.steps[0]

    calls = []
    if trace:
        from repro import obs
        w0 = compiles.snapshot()
        with obs.tracing() as tracer:
            out, _ = _stretch(bench_cell, seconds)
        calls += out
        ctx.spans, ctx.obs_updates = tracer.events, per_call * len(out)
        prof_dir = tempfile.mkdtemp(prefix="bench_profile_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0    # Python's tracer would slow the host
        try:
            jax.profiler.start_trace(prof_dir, profiler_options=opts)
            try:
                out, _ = _stretch(bench_cell, PROFILE_SECONDS, annotate=True)
            finally:
                jax.profiler.stop_trace()
            calls += out
            w1 = compiles.snapshot()
            from bench import trace as trace_mod
            ctx.profile = trace_mod.reduce(trace_mod.load(prof_dir))
            ctx.profile_calls = len(out)
        finally:
            shutil.rmtree(prof_dir, ignore_errors=True)
        ctx.window_compiles = (w1[0] - w0[0]) + (w1[1] - w0[1])
    else:
        w0, u0, durations = compiles.snapshot(), _host_usage(), []
        calls, wall = _stretch(bench_cell, seconds, durations=durations)
        w1, u1 = compiles.snapshot(), _host_usage()
        ctx.window = {"seconds": wall, "calls": len(calls),
                      "updates": per_call * len(calls)}
        print(json.dumps({"window_parts": window_parts(durations, u0, u1)}),
              flush=True)
        if w1[:2] != w0[:2]:
            log(f"warning: {w1[0] - w0[0]} compiles and {w1[1] - w0[1]} "
                "cache hits inside the window")

    peak_bytes = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                     for d in devs[:int(cell["chips"])])
    differ = sum(not same_results(checked_results, r) for r in calls)
    plan_differs = bench_cell.plan_differs(checked_results)
    schedule = bench_cell.schedule(checked_results)
    datas = [dict(s.data.__dict__) for s in bench_cell.sims]
    attempted = len(calls)
    del calls, bench_cell
    gc.collect()

    from bench import check, reference
    t = time.perf_counter()
    refs = []
    with jax.default_matmul_precision("highest"):
        for data, sched in zip(datas, schedule):
            ref = reference.Reference(model, cfg, mix, data)
            refs.append(ref.follow(program_seed(seed), sched))
    values = check.numbers(checked_params, refs)
    values["runs_differ"] = float(differ)
    values["plan_differs"] = float(plan_differs)
    ok, rows = check.verdict(values, spec["limits"])
    log(f"reference check {time.perf_counter() - t:.3f} s")

    metrics = {}
    want = "per_layer" if trace else "end_to_end"
    for m in spec["metrics"]:
        if m["kind"] != want:
            continue
        value = reader(m["name"], spec.get("root", ROOT))(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devs[0].platform, "kind": kind,
              "count": int(cell["chips"]), "memory_peak_bytes": peak_bytes}
    result = {"correct": ok, "attempted": attempted, "failed": differ,
              "metrics": metrics, "device": device}
    if ctx.profile is not None:
        device.update(busy_s=ctx.profile["busy_s"],
                      window_s=ctx.profile["window_s"])
        result["breakdown"] = ctx.profile["breakdown"]
    # The compared numbers come last, on stderr and in the result.
    result["checks"] = {r["name"]: {"value": r["value"], "limit": r["limit"]}
                        for r in rows}
    for r in rows:
        log(f"check {r['name']} = {r['value']!r} (limit {r['limit']!r})")
    return result


def _stretch(bench_cell, seconds: float, annotate=False, durations=None):
    """Whole calls that start within `seconds`, at least one. Returns
    (results, seconds from the first start to the last end); each call's
    seconds are appended to `durations` if given."""
    import jax
    out = []
    start = time.perf_counter()
    while not out or time.perf_counter() - start < seconds:
        ctx = (jax.profiler.TraceAnnotation("bench.run") if annotate
               else contextlib.nullcontext())
        t = time.perf_counter()
        with ctx:
            out.append(bench_cell.call())
        if durations is not None:
            durations.append(time.perf_counter() - t)
    return out, time.perf_counter() - start


def _host_usage() -> dict:
    """The process's CPU seconds, page faults and context switches."""
    ru = resource.getrusage(resource.RUSAGE_SELF)
    return {"cpu_s": ru.ru_utime + ru.ru_stime, "minflt": ru.ru_minflt,
            "majflt": ru.ru_majflt, "nvcsw": ru.ru_nvcsw,
            "nivcsw": ru.ru_nivcsw}


def window_parts(durations: list[float], u0: dict, u1: dict) -> dict:
    """What the host did in the window, for a look at a slow run: each
    call's seconds (least, median, most) and the change of `_host_usage`."""
    d = sorted(durations)
    parts = {"calls": len(d), "call_min_s": d[0],
             "call_median_s": d[len(d) // 2], "call_max_s": d[-1]}
    parts.update({k: u1[k] - u0[k] for k in u0})
    parts["cpus"] = len(os.sched_getaffinity(0))
    return parts
