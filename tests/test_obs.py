"""repro.obs: tracer semantics, exporters, logger, and the two promises
the subsystem is built on — untraced runs are bitwise identical, and the
disabled hot path costs (well) under 1% on meaningful work."""
import json
import math
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
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
    assert not obs.enabled() and not obs.syncing()
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


def test_spans_record_parent_and_one_clock():
    """Each event names the span that enclosed it (by open-order id), and
    its wall time is the tracer's origin plus its offset."""
    with obs.tracing() as t:
        with obs.span("a"):
            with obs.span("b"):
                with obs.span("c"):
                    pass
            with obs.span("d"):
                pass
        with obs.span("e"):
            pass
    ev = {e["name"]: e for e in t.events}
    assert [ev[n]["id"] for n in "abcde"] == [0, 1, 2, 3, 4]
    assert [ev[n]["parent"] for n in "abcde"] == [None, 0, 1, 0, None]
    for e in t.events:
        assert e["t_wall"] == pytest.approx(t.t0_wall + e["ts_us"] / 1e6,
                                            rel=0, abs=1e-9)


@pytest.mark.parametrize("sync", [True, False])
def test_sync_flag_and_syncing(sync):
    assert not obs.syncing()
    with obs.tracing(sync=sync) as t:
        assert t.sync is sync and obs.syncing() is sync
    assert obs.enable(sync=sync).sync is sync and obs.syncing() is sync
    obs.disable()
    assert not obs.syncing()


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


def _train_sim(execution="host", alg="fedavg", data=None, **cfg_kw):
    """A trained run small enough for the CPU: 6 satellites, 3 rounds,
    shards of at most 40 samples, 4 local steps."""
    from repro.core import ALGORITHMS
    from repro.data import synth_femnist
    from repro.orbits import (
        WalkerStar,
        compute_access_windows,
        station_subnetwork,
    )
    from repro.sim import ConstellationSim, SimConfig

    c, st = WalkerStar(2, 3), station_subnetwork(3)
    horizon = 4 * 86400.0
    cfg = dict(max_rounds=3, horizon_s=horizon, eval_every=2, max_steps=4,
               clients_per_round=5, seed=0)
    cfg.update(cfg_kw)
    return ConstellationSim(
        c, st, ALGORITHMS[alg], cfg=SimConfig(**cfg),
        access=compute_access_windows(c, st, horizon_s=horizon),
        data=data or synth_femnist(c.n_sats, seed=0, min_samples=20,
                                   max_samples=40, eval_samples=8),
        workload="femnist_mlp", execution=execution)


def _same_result(a, b) -> bool:
    return (a.rounds == b.rounds and a.accuracy_curve == b.accuracy_curve
            and all(np.array_equal(x, y) for x, y in zip(
                jax.tree.leaves(a.final_params),
                jax.tree.leaves(b.final_params), strict=True)))


def test_traced_run_bitwise_identical_and_instrumented():
    """Tracing observes walls only: simulated results are identical, with
    or without waiting on the device, and the acceptance span chain
    (run -> round -> eval) + counters are recorded."""
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
    assert {"sim.run", "sim.round", "sim.select", "sim.eval"} <= names
    assert s["counters"]["sim.rounds"] == 3
    assert s["counters"]["sim.evals"] == 2   # eval_every=2 over 3 rounds
    # one run span encloses the rounds, which enclose select/eval
    run, = [ev for ev in t.events if ev["name"] == "sim.run"]
    rounds = [ev for ev in t.events if ev["name"] == "sim.round"]
    assert run["depth"] == 0 and run["parent"] is None
    assert all(ev["depth"] == 1 and ev["parent"] == run["id"]
               for ev in rounds)
    # a trained run, untraced / traced blocking / traced not blocking
    sim = _train_sim()
    off = sim.run()
    for sync in (True, False):
        with obs.tracing(sync=sync):
            assert _same_result(sim.run(), off), sync


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


@pytest.mark.parametrize("alg,counts", [
    ("ground_assisted", 2),    # a station's visit: rounds of 1 or 2 clients
    ("fedbuff", 1),
])
def test_aggregate_compiles_once_per_client_count(alg, counts):
    """The host loop's server update is one jitted program: each
    distinct client count compiles it once, on its first update, and a
    second run of the same sim compiles nothing."""
    sim = _train_sim(alg=alg, max_rounds=6)
    with obs.tracing() as t:
        sim.run()
        first = [ev["args"] for ev in t.events
                 if ev["name"] == "sim.aggregate"]
        compiles = t.counter("sim.jit_compiles")
        sim.run()
        second = [ev["args"] for ev in t.events
                  if ev["name"] == "sim.aggregate"][len(first):]
        assert t.counter("sim.jit_compiles") == compiles
    assert first and len(second) == len(first)
    seen = set()
    for args in first:
        assert args["jit_compile"] is (args["clients"] not in seen)
        seen.add(args["clients"])
    assert not any(args["jit_compile"] for args in second)
    assert sim._aggregator()._cache_size() == len(seen) == counts


def test_each_sim_traces_its_own_aggregate(monkeypatch):
    """A fault planted in the aggregation functions reaches a sim built
    after it, and a sim built after it is undone is bitwise the first:
    no trace is shared across sims. Every sim stays alive, as in one
    process that calibrates, so no cache entry dies with its sim."""
    from bench.tests import small

    sims = [_train_sim(max_rounds=2)]
    sound = sims[0].run().final_params
    small.half_clients(monkeypatch)
    sims.append(_train_sim(max_rounds=2))
    faulted = sims[1].run().final_params
    monkeypatch.undo()
    sims.append(_train_sim(max_rounds=2))
    again = sims[2].run().final_params
    assert any(not np.array_equal(a, b) for a, b in zip(
        jax.tree.leaves(faulted), jax.tree.leaves(sound), strict=True))
    for a, b in zip(jax.tree.leaves(again), jax.tree.leaves(sound),
                    strict=True):
        np.testing.assert_array_equal(a, b)


def test_spans_on_the_profiler_host_plane(tmp_path):
    """Under the profiler, a traced run's spans are annotations on the
    host plane, nested there as the tracer recorded them."""
    from jax.profiler import ProfileData

    sim = _train_sim()
    sim.run()                                   # compile outside
    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs.tracing(sync=False) as t:
            sim.run()
    finally:
        jax.profiler.stop_trace()
    path, = tmp_path.glob("**/*.xplane.pb")
    host = [p for p in ProfileData.from_file(str(path)).planes
            if p.name == "/host:CPU"][0]
    seen = {}
    for line in host.lines:
        for ev in line.events:
            if ev.name.startswith("sim."):
                seen.setdefault(ev.name, []).append(
                    (ev.start_ns, ev.start_ns + ev.duration_ns, line.name))
    ours = {}
    for ev in t.events:
        ours.setdefault(ev["name"], []).append(ev)
    assert {"sim.run", "sim.round", "sim.client_train"} <= set(seen)
    assert sorted(seen) == sorted(ours)
    where = {}                                  # tracer id -> xplane span
    for name, evs in ours.items():
        assert len(seen[name]) == len(evs), name
        for ev, iv in zip(sorted(evs, key=lambda e: e["ts_us"]),
                          sorted(seen[name])):
            where[ev["id"]] = iv
    assert len({iv[2] for iv in where.values()}) == 1    # one thread
    for ev in t.events:
        if ev["parent"] is not None:
            (s0, e0, _), (s1, e1, _) = where[ev["id"]], where[ev["parent"]]
            assert s1 <= s0 and e0 <= e1, ev["name"]
    train = [ev for ev in t.events if ev["name"] == "sim.client_train"]
    by_id = {ev["id"]: ev for ev in t.events}
    assert train and all(
        by_id[by_id[ev["parent"]]["parent"]]["name"] == "sim.run"
        and by_id[ev["parent"]]["name"] == "sim.round" for ev in train)


# ------------------------------------------------ host-device traffic --

ROW = 28 * 28 * 1 * 4      # bytes of one FEMNIST sample on the device


def _eval_slots(sim, t: float) -> int:
    """Clients in the evaluation batch at `t`: the evaluation stage's
    selection (or the first clients when it selects none), padded to a
    power of two."""
    c = min(sim.cfg.clients_per_round, sim.constellation.n_sats)
    plans = sim.alg.selector.select(
        sim.aw, t, range(sim.constellation.n_sats), c, sim.alg.strategy,
        sim.hw, sim.alg.local_epochs, sim.alg.min_epochs, plan=sim.plan)
    m = len(plans) or min(c, sim.data.n_clients)
    return 1 << (m - 1).bit_length()


def _eval_traffic(sim, res) -> tuple[int, int]:
    """(bytes uploaded, host syncs) of a run's evaluations: eval shards,
    labels and counts per padded slot; one accuracy read each."""
    per_slot = sim.data.x_eval.shape[1] * (ROW + 4) + 4
    return (sum(_eval_slots(sim, t) * per_slot
                for _, t, _ in res.accuracy_curve), len(res.accuracy_curve))


def _shard_bytes(data) -> int:
    """Bytes on the device of one client's training shard, labels and
    sample count."""
    return data.x.shape[1] * (ROW + 4) + 4


def _loop_traffic(sim, res, slots=None) -> tuple[int, int]:
    """(bytes uploaded, host syncs) a loop run's records imply.

    The host loop uploads every client's shard, labels and sample count
    once per trained run, then per round each participant's row number
    and step count. The mesh path (`slots`) uploads per round, for each
    of `slots(n)` padded pod slots, a shard, labels, sample count and
    step count. Both upload per round the n participants' aggregation
    weights, and their staleness where it comes from the host (FedBuff;
    the barrier's zeros are made on the device); then the evaluations
    and the final model's read."""
    shard = _shard_bytes(sim.data)
    per_client = 4 if sim.alg.synchronous else 8
    if slots is None:
        h2d = sim.data.n_clients * shard if res.rounds else 0
        h2d += sum((4 + 4 + per_client) * len(rec.participants)
                   for rec in res.rounds)
    else:
        h2d = sum(slots(len(rec.participants)) * (shard + 4)
                  + per_client * len(rec.participants) for rec in res.rounds)
    eval_h2d, evals = _eval_traffic(sim, res)
    return h2d + eval_h2d, evals + 1


def _gathered_rows(res) -> int:
    return sum(len(rec.participants) for rec in res.rounds)


@pytest.mark.parametrize("alg", ["fedavg", "fedbuff"])
def test_traffic_counters_match_the_schedule(alg):
    sim = _train_sim(alg=alg, record_params=True)
    with obs.tracing(sync=False) as t:
        res = sim.run()
    h2d, syncs = _loop_traffic(sim, res)
    syncs += len(res.rounds)                 # record_params reads each
    model = 4 * sum(np.size(a) for a in jax.tree.leaves(res.final_params))
    c = t.counters
    assert res.rounds and c["sim.h2d_bytes"] == h2d
    assert c["sim.host_syncs"] == syncs
    assert c["sim.d2h_bytes"] == 4 * len(res.accuracy_curve) + model * (
        len(res.rounds) + 1)
    assert c["sim.gathered_rows"] == _gathered_rows(res)
    run, = [ev for ev in t.events if ev["name"] == "sim.run"]
    assert run["args"]["h2d_bytes"] == h2d
    assert run["args"]["host_syncs"] == syncs
    assert run["args"]["d2h_bytes"] == c["sim.d2h_bytes"]
    assert run["args"]["gathered_rows"] == _gathered_rows(res)


def _batched_traffic(sims, results) -> tuple[int, int, int]:
    """(bytes uploaded, host syncs, rows gathered) of a trained batched
    sweep of synchronous scenarios. Once: of each distinct dataset
    array, the shard, labels and sample count of every client some
    round names, and the server learning rates and proximal terms. Per
    lockstep round, for every (scenario, slot): its row number, steps,
    rng key (2 words), weight and staleness, and the gather of its row.
    Then the evaluations, one key split per scenario and round, and the
    final models' reads."""
    from repro.sim import ConstellationSim

    R = max(len(r.rounds) for r in results)
    C = ConstellationSim._bound(
        [max(len(rec.participants) for r in results for rec in r.rounds)])
    B = len(sims)
    named = {}
    for sim, res in zip(sims, results):
        named.setdefault(id(sim.data.x), (sim.data, set()))[1].update(
            k for rec in res.rounds for k in rec.participants)
    h2d = sum(len(used) * _shard_bytes(d) for d, used in named.values())
    h2d += R * B * C * (4 + 4 + 8 + 4 + 4) + 2 * 4 * B
    syncs = 0
    for sim, res in zip(sims, results):
        eval_h2d, evals = _eval_traffic(sim, res)
        h2d += eval_h2d
        syncs += len(res.rounds) + evals + 1
    return h2d, syncs, R * B * C


def test_batched_traffic_counters_match_the_schedule():
    from repro.sim.batched import BatchedSweep

    sims = [_train_sim(), _train_sim(eval_every=1, max_rounds=2)]
    with obs.tracing(sync=False) as t:
        results = BatchedSweep(sims).run()
    h2d, syncs, rows = _batched_traffic(sims, results)
    assert t.counters["sim.h2d_bytes"] == h2d
    assert t.counters["sim.host_syncs"] == syncs
    assert t.counters["sim.gathered_rows"] == rows
    assert any(ev["name"] == "sim.batched.assemble" for ev in t.events)


def test_batched_sweep_uploads_a_shared_dataset_once():
    """Scenarios whose datasets share their arrays share the rows on the
    device: the union of the clients their rounds name goes up once."""
    import dataclasses

    from repro.sim.batched import BatchedSweep

    first = _train_sim()
    sims = [first, _train_sim(eval_every=1, max_rounds=2,
                              data=dataclasses.replace(first.data))]
    with obs.tracing(sync=False) as t:
        results = BatchedSweep(sims).run()
    h2d, _, rows = _batched_traffic(sims, results)
    assert t.counters["sim.h2d_bytes"] == h2d
    assert t.counters["sim.gathered_rows"] == rows
    union = {k for r in results for rec in r.rounds for k in rec.participants}
    twice = sum(len({k for rec in r.rounds for k in rec.participants})
                for r in results)
    assert len(union) < twice     # the two scenarios name common clients


def test_timing_only_runs_upload_no_shards():
    from repro.sim.batched import BatchedSweep

    sim = _train_sim(train=False)
    with obs.tracing(sync=False) as t:
        assert sim.run().rounds
        assert BatchedSweep([_train_sim(train=False)]).run()[0].rounds
    assert t.counters.get("sim.h2d_bytes", 0) == 0
    assert t.counters.get("sim.gathered_rows", 0) == 0


def test_each_run_uploads_its_own_shards():
    """The shards stay on the device for one `run()` only: a second run
    of the same sim uploads them again, and none are kept between."""
    sim = _train_sim()
    with obs.tracing(sync=False) as t:
        first = sim.run()
        assert sim._shards is None
        second = sim.run()
    assert sim._shards is None and _same_result(first, second)
    h2d, _ = _loop_traffic(sim, first)
    runs = [ev["args"] for ev in t.events if ev["name"] == "sim.run"]
    assert [a["h2d_bytes"] for a in runs] == [h2d, h2d]
    assert [a["gathered_rows"] for a in runs] == [_gathered_rows(first)] * 2


def _gather_case(case):
    """(parts, row numbers, the x, y, n slab the host used to build for
    them): `resident_shards`' input, its gather's, and what it replaces."""
    from repro.core.workload import get_workload
    from repro.data import synth_femnist

    if case in ("host_loop", "lm_tokens"):
        d = (get_workload("lm_tiny").make_data(4, seed=0)
             if case == "lm_tokens"
             else synth_femnist(6, seed=0, min_samples=20, max_samples=40,
                                eval_samples=8))
        ks = [3, 1, 3]
        return d, ks, (d.x[ks], d.y[ks], d.n[ks])
    # The batched sweep's slab: datasets of different sample counts, the
    # padding slots repeating the first client, a finished scenario's
    # lane all zeros.
    a = synth_femnist(6, seed=0, min_samples=20, max_samples=40,
                      eval_samples=8)
    b = synth_femnist(5, seed=1, min_samples=10, max_samples=24,
                      eval_samples=8)
    parts = [(a, np.array([0, 2, 5])), (b, np.array([1, 4]))]
    lanes = [(a, [5, 0, 5, 5]), (b, [4, 1, 4, 4]), None]
    idx = np.array([[2, 0, 2, 2], [4, 3, 4, 4], [5, 5, 5, 5]], np.int32)
    x = np.zeros((3, 4, 40) + a.x.shape[2:], a.x.dtype)
    y = np.zeros((3, 4, 40), a.y.dtype)
    n = np.zeros((3, 4), np.int32)
    for i, lane in enumerate(lanes):
        if lane is not None:
            d, ks = lane
            x[i, :, :d.x.shape[1]] = d.x[ks]
            y[i, :, :d.x.shape[1]] = d.y[ks]
            n[i] = d.n[ks]
    return parts, idx, (x, y, n)


@pytest.mark.parametrize("case", ["host_loop", "lm_tokens", "batched_slab"])
def test_device_gather_is_the_host_slab(case):
    """The rows gathered on the device are, bit for bit, the slab the
    host built and uploaded: same shape, dtype and values, zeros past
    each dataset's samples."""
    from repro.sim.engine import resident_shards, to_device

    parts, idx, host = _gather_case(case)
    with obs.tracing(sync=False) as t:
        got = resident_shards(parts)(idx)
    assert t.counters["sim.gathered_rows"] == np.size(idx)
    for g, want in zip(got, host, strict=True):
        want = np.asarray(to_device(want))
        g = jax.device_get(g)
        assert (g.shape, g.dtype) == (want.shape, want.dtype)
        assert g.tobytes() == want.tobytes()


MESH_TRAFFIC = r"""
import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")
import jax
assert jax.device_count() == 4
from repro import obs
from tests.test_obs import _loop_traffic, _train_sim

sim = _train_sim(execution="mesh", clients_per_round=6)
with obs.tracing(sync=False) as t:
    res = sim.run()
size = lambda n: max(1, min(4, n))
h2d, syncs = _loop_traffic(sim, res,
                           slots=lambda n: -(-n // size(n)) * size(n))
assert any(len(r.participants) > 4 for r in res.rounds), res.rounds
assert t.counters["sim.h2d_bytes"] == h2d, (t.counters, h2d)
assert t.counters["sim.host_syncs"] == syncs, (t.counters, syncs)
assert "sim.gathered_rows" not in t.counters, t.counters
print("MESH_TRAFFIC_OK")
"""


def test_mesh_traffic_counters_match_the_schedule():
    """The mesh path on four forced CPU devices uploads every padded pod
    slot."""
    import os
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(root, "src"), root, env.get("PYTHONPATH", "")])
    env.setdefault("JAX_PLATFORMS", "cpu")
    out = subprocess.run([sys.executable, "-c", MESH_TRAFFIC], env=env,
                         capture_output=True, text=True, timeout=600,
                         cwd=root)
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    assert "MESH_TRAFFIC_OK" in out.stdout


@pytest.fixture
def strict_reads(monkeypatch):
    """Device-to-host reads outside an explicit `jax.device_get` raise.

    On an accelerator `jax.transfer_guard_device_to_host("disallow")`
    does this. On the CPU a read copies nothing, so the guard never
    fires there: the conversions an implicit read goes through are
    wrapped to raise outside `device_get`'s own scope."""
    from jax._src import array, config

    state = config.guard_lib.thread_local_state

    def strict(fn, name):
        def read(*args, **kwargs):
            if (args and isinstance(args[0], jax.Array)
                    and not state().explicit_device_get):
                raise AssertionError(f"implicit device read: {name}")
            return fn(*args, **kwargs)
        return read

    for name in ("__float__", "__int__", "__bool__", "__index__", "item",
                 "tolist", "__array__"):
        monkeypatch.setattr(array.ArrayImpl, name,
                            strict(getattr(array.ArrayImpl, name), name))
    for name in ("asarray", "array"):
        monkeypatch.setattr(np, name, strict(getattr(np, name), name))
    with jax.transfer_guard_device_to_host("disallow"):
        yield


@pytest.mark.parametrize("traced", [False, True])
def test_no_implicit_device_reads(traced, monkeypatch, request):
    """Every device-to-host read of a run is an explicit, counted
    `to_host`; tracing with `sync=False` adds no read and never waits."""
    from repro.sim.batched import BatchedSweep

    sim, sweep = _train_sim(record_params=True), BatchedSweep([_train_sim()])
    sim.run()                                # compile outside the guard
    sweep.run()
    request.getfixturevalue("strict_reads")

    def no_wait(x):
        raise AssertionError("block_until_ready with sync=False")

    if traced:
        monkeypatch.setattr(jax, "block_until_ready", no_wait)
        with obs.tracing(sync=False) as t:
            sim.run()
            sweep.run()
        assert t.counters["sim.host_syncs"] > 0
    else:
        sim.run()
        sweep.run()


def test_traced_jit_call_reads_the_cache_only_while_tracing():
    from repro.sim.engine import traced_jit_call

    f = jax.jit(lambda x: x + 1)
    reads = []

    class Probe:
        def __call__(self, *a):
            return f(*a)

        def _cache_size(self):
            reads.append(1)
            return f._cache_size()

    with obs.span("sim.client_train") as sp:
        traced_jit_call(sp, Probe(), jnp.ones(2))
    assert reads == []
    with obs.tracing(sync=False) as t:
        with obs.span("sim.client_train") as sp:
            traced_jit_call(sp, Probe(), jnp.ones(3))
    assert len(reads) == 2 and t.events[0]["args"]["jit_compile"]


# ------------------------------------------------------ routing counters --
# An expert layer holding 8 of 64 experts, whose selection bias sends
# every token to experts 0-5: each live local step puts T = batch x
# seq_len picks on each of those six in every MoE layer, and none on 6, 7.
MOE_LAYERS, SEQ, BATCH = 2, 8, 2


def _routed_sim(**cfg_kw):
    import dataclasses
    from repro.core import ALGORITHMS
    from repro.core.workload import lm_workload
    from repro.models.lm.config import MLAConfig, ModelConfig, MoEConfig, \
        Segment
    from repro.orbits import WalkerStar, station_subnetwork
    from repro.sim import ConstellationSim, SimConfig
    cfg = ModelConfig(
        name="routed", arch_type="moe", n_layers=1 + MOE_LAYERS, d_model=16,
        n_heads=2, n_kv_heads=2, d_ff=32, vocab_size=64,
        segments=(Segment("attn", 1), Segment("moe", MOE_LAYERS)),
        moe=MoEConfig(n_experts=64, top_k=6, d_ff_expert=8, n_shared=2,
                      scoring="sigmoid", routed_scale=2.446,
                      held=tuple(range(8))),
        mla=MLAConfig(q_lora_rank=None, kv_lora_rank=8, rope_head_dim=4,
                      nope_head_dim=8, v_head_dim=8), dtype="float32")
    wl = lm_workload(cfg, name="routed", seq_len=SEQ,
                     with_routing_stats=True)

    base = wl.init_fn

    def init(rng):
        p = base(rng)
        p["segments"][1]["moe"]["router_bias"] = jnp.zeros(
            (MOE_LAYERS, 64)).at[:, :6].set(100.0)
        return p
    wl = dataclasses.replace(wl, init_fn=init)
    return ConstellationSim(
        WalkerStar(2, 5), station_subnetwork(13), ALGORITHMS["fedavg"],
        workload=wl, cfg=SimConfig(max_rounds=2, horizon_s=2 * 86400.0,
                                   clients_per_round=4, batch_size=BATCH,
                                   max_steps=4, eval_every=5, **cfg_kw))


@pytest.mark.parametrize("streamed", [False, True])
def test_routing_counters_read_the_hand_counted_picks(streamed, monkeypatch):
    """Stacked or streamed, the counters are the hand count over the
    live local steps of every client, and the run span carries them."""
    from repro.sim import engine
    if streamed:
        monkeypatch.setattr(engine, "copies_fit", lambda *a: False)
    sim = _routed_sim()
    with obs.tracing(sync=True) as t:
        res = sim.run()
    steps = sum(sim._steps_for(k, e) for r in res.rounds
                for k, e in zip(r.participants, r.epochs))
    tokens = BATCH * SEQ
    assert res.rounds and steps > 0
    assert t.counters["moe.local_picks"] == steps * MOE_LAYERS * 6 * tokens
    assert t.counters["moe.local_picks_max"] == steps * MOE_LAYERS * tokens
    assert t.counters["moe.local_picks_mean"] == \
        steps * MOE_LAYERS * 6 * tokens / 8
    assert t.counters["moe.dropped"] == 0
    run = next(s for s in t.events if s["name"] == "sim.run")
    assert run["args"]["local_picks"] == t.counters["moe.local_picks"]
    assert run["args"]["local_picks_max"] == \
        t.counters["moe.local_picks_max"]
    assert run["args"]["local_picks_mean"] == \
        t.counters["moe.local_picks_mean"]


def test_routing_counters_only_under_a_syncing_tracer():
    """Untraced, nothing is counted or read back; a tracer that does not
    sync counts no picks and reads nothing back for them."""
    obs.disable()
    plain = _routed_sim().run()
    with obs.tracing(sync=False) as t:
        quiet = _routed_sim().run()
    assert not any(k.startswith("moe.") for k in t.counters)
    with obs.tracing(sync=True) as t_sync:
        _routed_sim().run()
    assert t_sync.counters["sim.host_syncs"] > t.counters["sim.host_syncs"]
    for a, b in zip(jax.tree.leaves(plain.final_params),
                    jax.tree.leaves(quiet.final_params)):
        np.testing.assert_array_equal(a, b)
