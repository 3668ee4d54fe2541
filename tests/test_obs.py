"""repro.obs: tracer semantics, exporters, logger, and the two promises
the subsystem is built on — untraced runs are bitwise identical, and the
disabled hot path costs (well) under 1% on meaningful work."""
import json
import math
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from repro import obs
from repro.obs import trace as obs_trace


@pytest.fixture(autouse=True)
def _no_global_tracer():
    """Tests must not leak a global tracer into the rest of the suite."""
    prev = obs.get_tracer()
    obs.disable()
    yield
    obs_trace._tracer = prev


# ------------------------------------------------------- disabled path --


def test_disabled_span_is_shared_noop():
    assert not obs.enabled()
    s1 = obs.span("anything", foo=1)
    s2 = obs.span("else")
    assert s1 is s2                      # one shared singleton, no alloc
    with s1 as sp:
        sp.set(bar=2)                    # attribute attach is a no-op
    obs.count("nope", 5)                 # counter bump is a no-op
    assert obs.metrics_summary() == {}


def test_export_requires_tracer(tmp_path):
    with pytest.raises(RuntimeError, match="not enabled"):
        obs.write_chrome_trace(str(tmp_path / "t.json"))


def test_disabled_overhead_under_one_percent():
    """A disabled span() around meaningful work costs < 1% wall.

    Measured as (per-call cost of the disabled hot path) vs (one
    meaningful unit of work, ~100 µs of math): the direct ratio is what
    the <1% promise means, and it is robust where whole-loop A/B wall
    comparisons flake on scheduler noise."""
    assert not obs.enabled()
    calls, works, repeats = 20_000, 50, 7

    def span_loop():                     # the disabled hot path, x calls
        for _ in range(calls):
            with obs.span("overhead.probe"):
                pass

    def empty_loop():                    # loop overhead to subtract out
        for _ in range(calls):
            pass

    def work_loop():                     # x works of ~100 µs each
        s = 0.0
        for _ in range(works):
            for j in range(3000):
                s += math.sqrt(j + 1.5)
        return s

    span_loop(), empty_loop(), work_loop()        # warm up
    best = {"span": float("inf"), "empty": float("inf"),
            "work": float("inf")}
    for _ in range(repeats):             # interleave: drift hits all three
        for key, fn in (("span", span_loop), ("empty", empty_loop),
                        ("work", work_loop)):
            t0 = time.perf_counter()
            fn()
            best[key] = min(best[key], time.perf_counter() - t0)
    per_call = max(best["span"] - best["empty"], 0.0) / calls
    per_work = best["work"] / works
    assert per_call < 0.01 * per_work, \
        (f"disabled span() costs {per_call * 1e6:.3f} µs/call — "
         f">= 1% of a {per_work * 1e6:.0f} µs unit of work")


# -------------------------------------------------------- enabled path --


def test_nested_spans_record_depth_and_duration():
    with obs.tracing() as t:
        with obs.span("outer", idx=7):
            with obs.span("inner"):
                time.sleep(0.002)
    names = [ev["name"] for ev in t.events]
    assert names == ["inner", "outer"]   # completion order
    inner, outer = t.events
    assert inner["depth"] == 1 and outer["depth"] == 0
    assert outer["args"] == {"idx": 7}
    assert inner["dur_us"] >= 2000
    assert outer["dur_us"] >= inner["dur_us"]
    # inner nests inside outer on the time axis
    assert inner["ts_us"] >= outer["ts_us"]
    assert inner["ts_us"] + inner["dur_us"] <= \
        outer["ts_us"] + outer["dur_us"] + 1.0
    assert inner["t_wall"] >= outer["t_wall"] - 1e-3


def test_span_set_and_error_annotation():
    with obs.tracing() as t:
        with obs.span("phase", a=1) as sp:
            sp.set(b=2, a=3)
        with pytest.raises(ValueError):
            with obs.span("boom"):
                raise ValueError("x")
    phase, boom = t.events
    assert phase["args"] == {"a": 3, "b": 2}
    assert boom["args"]["error"] == "ValueError"


def test_counters_rates_and_summary():
    with obs.tracing():
        obs.count("cache.hit", 3)
        obs.count("cache.miss")
        obs.count("plain", 2)
        with obs.span("p"):
            pass
        with obs.span("p"):
            time.sleep(0.001)
        s = obs.metrics_summary()
    assert s["counters"] == {"cache.hit": 3, "cache.miss": 1, "plain": 2}
    assert s["rates"] == {"cache.hit_rate": 0.75}
    assert s["spans"]["p"]["count"] == 2
    assert s["spans"]["p"]["total_s"] >= s["spans"]["p"]["max_s"] > 0
    assert s["wall_s"] >= 0
    assert "dropped_events" not in s


def test_max_events_cap_drops_and_reports():
    with obs.tracing(max_events=3) as t:
        for i in range(5):
            with obs.span("s", i=i):
                pass
        s = obs.metrics_summary()
    assert len(t.events) == 3
    assert t.dropped_events == 2
    assert s["dropped_events"] == 2


def test_threaded_spans_keep_independent_stacks():
    # All four threads hold their spans at once, so their idents (which
    # the OS may reuse after a thread exits) are distinct.
    barrier = threading.Barrier(4, timeout=30)

    def worker():
        with obs.span("outer"):
            with obs.span("inner"):
                barrier.wait()

    with obs.tracing() as t:
        threads = [threading.Thread(target=worker) for _ in range(4)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
    assert len(t.events) == 8
    by_tid = {}
    for ev in t.events:
        by_tid.setdefault(ev["tid"], []).append(ev)
    assert len(by_tid) == 4
    for evs in by_tid.values():
        assert sorted(ev["depth"] for ev in evs) == [0, 1]


def test_tracing_restores_previous_tracer():
    outer = obs.enable()
    with obs.tracing() as inner:
        assert obs.get_tracer() is inner
    assert obs.get_tracer() is outer


# --------------------------------------------------------- exporters --


def test_chrome_trace_shape_and_validator(tmp_path):
    from benchmarks.check_trace import validate

    with obs.tracing() as t:
        with obs.span("bench.plan_build", kind="x"):
            with obs.span("sim.round", idx=0):
                with obs.span("sim.eval"):
                    pass
        obs.count("bench.disk_cache.hit")
        obs.count("bench.disk_cache.miss")
        doc = obs.chrome_trace(t)
    assert doc["displayTimeUnit"] == "ms"
    assert doc["metadata"]["summary"]["counters"]["bench.disk_cache.hit"] == 1
    phs = {ev["ph"] for ev in doc["traceEvents"]}
    assert phs == {"M", "X", "C"}
    # the CI validator accepts it end-to-end
    assert validate(doc, ["bench.plan_build", "sim.round", "sim.eval"]) == []
    # and catches a broken trace
    assert validate({"traceEvents": []}, []) != []
    bad = json.loads(json.dumps(doc))
    bad["traceEvents"] = [ev for ev in bad["traceEvents"]
                          if ev["ph"] != "C"]
    bad["metadata"]["summary"]["counters"] = {}
    assert any("cache" in p for p in
               validate(bad, ["sim.round"]))


def test_validator_rejects_partial_overlap():
    from benchmarks.check_trace import validate

    doc = {"traceEvents": [
        {"name": "a", "ph": "X", "pid": 1, "tid": 1, "ts": 0, "dur": 10},
        {"name": "b", "ph": "X", "pid": 1, "tid": 1, "ts": 5, "dur": 10},
        {"name": "c.hit", "ph": "C", "pid": 1, "tid": 0, "ts": 15,
         "args": {"c.hit": 1}},
    ], "metadata": {"summary": {}}}
    assert any("partially overlaps" in p for p in validate(doc, ["a"]))


def test_write_exporters(tmp_path):
    trace_path = tmp_path / "trace.json"
    jsonl_path = tmp_path / "trace.jsonl"
    with obs.tracing():
        with obs.span("w", k=1):
            pass
        obs.count("c.hit", 2)
        obs.write_chrome_trace(str(trace_path))
        obs.write_jsonl(str(jsonl_path))
    with open(trace_path) as f:
        doc = json.load(f)
    assert any(ev["name"] == "w" for ev in doc["traceEvents"])
    lines = [json.loads(ln) for ln in jsonl_path.read_text().splitlines()]
    spans = [ln for ln in lines if ln["type"] == "span"]
    counters = [ln for ln in lines if ln["type"] == "counter"]
    assert spans[0]["name"] == "w" and spans[0]["args"] == {"k": 1}
    assert counters == [{"type": "counter", "name": "c.hit", "value": 2,
                         "t_wall": counters[0]["t_wall"]}]


# ----------------------------------------------------------- logger --


def test_log_record_quiet_by_default(monkeypatch, capsys):
    monkeypatch.delenv("REPRO_LOG", raising=False)
    obs.set_logging(None)
    rec = obs.log_record("ev", a=1)
    assert rec["event"] == "ev" and rec["a"] == 1 and "t_wall" in rec
    assert capsys.readouterr().err == ""


def test_log_record_env_toggle(monkeypatch):
    import io

    obs.set_logging(None)
    monkeypatch.setenv("REPRO_LOG", "1")
    buf = io.StringIO()
    obs.log_record("ev", a=1, _stream=buf)
    line = json.loads(buf.getvalue())
    assert line["event"] == "ev" and line["a"] == 1
    for off in ("0", "", "false", "FALSE"):
        monkeypatch.setenv("REPRO_LOG", off)
        assert not obs.log_enabled()
    monkeypatch.setenv("REPRO_LOG", "0")
    obs.set_logging(True)                # override beats the env var
    try:
        assert obs.log_enabled()
    finally:
        obs.set_logging(None)


# ------------------------------------------- end-to-end sim guarantees --


def _tiny_sim():
    from repro.core import ALGORITHMS
    from repro.orbits import (
        WalkerStar,
        compute_access_windows,
        station_subnetwork,
    )
    from repro.sim import ConstellationSim, SimConfig

    c = WalkerStar(1, 3)
    aw = compute_access_windows(c, station_subnetwork(1),
                                horizon_s=4 * 86400.0)
    cfg = SimConfig(max_rounds=3, horizon_s=4 * 86400.0, train=False,
                    eval_every=2, seed=0)
    return ConstellationSim(c, station_subnetwork(1), ALGORITHMS["fedavg"],
                            cfg=cfg, access=aw)


def test_traced_run_bitwise_identical_and_instrumented():
    """Tracing observes walls only: simulated results are identical, and
    the acceptance span chain (round -> eval) + counters are recorded."""
    base = _tiny_sim().run()
    with obs.tracing() as t:
        traced = _tiny_sim().run()
        s = obs.metrics_summary()
    assert [r.t_end for r in traced.rounds] == \
        [r.t_end for r in base.rounds]
    assert [r.participants for r in traced.rounds] == \
        [r.participants for r in base.rounds]
    assert traced.accuracy_curve == base.accuracy_curve
    names = {ev["name"] for ev in t.events}
    assert {"sim.round", "sim.select", "sim.eval"} <= names
    assert s["counters"]["sim.rounds"] == 3
    assert s["counters"]["sim.evals"] == 2   # eval_every=2 over 3 rounds
    # round spans enclose their select/eval children
    rounds = [ev for ev in t.events if ev["name"] == "sim.round"]
    assert all(ev["depth"] == 0 for ev in rounds)


def test_traced_jit_call_flags_every_compile():
    """A retrace for a new input shape is a compile too, not only the
    first call of a jitted function."""
    from repro.sim.engine import traced_jit_call

    f = jax.jit(lambda x: x * 2)
    with obs.tracing() as t:
        for n in (2, 2, 3, 3):
            with obs.span("sim.client_train") as sp:
                traced_jit_call(sp, f, jnp.ones(n))
        s = obs.metrics_summary()
    assert [ev["args"]["jit_compile"] for ev in t.events] == \
        [True, False, True, False]
    assert s["counters"]["sim.jit_compiles"] == 2


def test_mesh_fedbuff_flags_its_retraces():
    """The mesh step's output params carry the client mesh's sharding, so
    the second and third FedBuff flushes retrace; every compile of the
    step is flagged on its `sim.client_train` span."""
    from repro.core import ALGORITHMS
    from repro.data import synth_femnist
    from repro.orbits import (
        WalkerStar,
        compute_access_windows,
        station_subnetwork,
    )
    from repro.sim import ConstellationSim, SimConfig

    c, st = WalkerStar(2, 3), station_subnetwork(3)
    aw = compute_access_windows(c, st, horizon_s=4 * 86400.0)
    cfg = SimConfig(max_rounds=4, horizon_s=4 * 86400.0, eval_every=4,
                    max_steps=4, seed=0)
    sim = ConstellationSim(c, st, ALGORITHMS["fedbuff"], cfg=cfg, access=aw,
                           data=synth_femnist(c.n_sats, seed=0),
                           workload="femnist_mlp", execution="mesh")
    with obs.tracing() as t:
        sim.run()
    flags = [ev["args"]["jit_compile"] for ev in t.events
             if ev["name"] == "sim.client_train"]
    compiles = sum(f._cache_size() for f in sim._mesh_steps.values())
    assert sum(flags) == compiles > len(sim._mesh_steps)
