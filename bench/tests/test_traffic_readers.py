"""The host-device traffic readers: sums over the outermost `sim.run`
spans' args, per update; nothing to read from a program without them."""
import types

import pytest

from bench import harness
from bench.tests import small


def _span(i, name, parent=None, **args):
    return {"name": name, "id": i, "parent": parent, "args": args,
            "ts_us": 0.0, "dur_us": 1.0, "tid": 1, "depth": 0}


def _ctx(spans, updates):
    return types.SimpleNamespace(spans=spans, obs_updates=updates)


def test_outermost_runs_are_summed_per_update():
    spans = [_span(0, "sim.run", h2d_bytes=3e6, host_syncs=2, d2h_bytes=8),
             _span(1, "sim.batched.plan", parent=2),
             _span(3, "sim.run", parent=1, h2d_bytes=5e6, host_syncs=7,
                   d2h_bytes=0),
             _span(2, "sim.run", h2d_bytes=9e6, host_syncs=4, d2h_bytes=8),
             _span(4, "sim.round", parent=2)]
    ctx = _ctx(spans, updates=6)
    assert harness.reader("h2d_mb_per_round")(ctx) == pytest.approx(2.0)
    assert harness.reader("host_syncs_per_round")(ctx) == pytest.approx(1.0)


def test_a_program_without_run_spans_reads_nothing():
    spans = [_span(0, "sim.round"), _span(1, "sim.run")]
    for name in ("h2d_mb_per_round", "host_syncs_per_round"):
        assert harness.reader(name)(_ctx(spans, 3)) is None
        assert harness.reader(name)(_ctx([], 3)) is None


@pytest.mark.parametrize("cell", ["mlp-fedavg_sched-c10s10-g13",
                                  "mlp-table1-batched"])
def test_readers_match_the_counters(cell):
    from bench.cell import Cell
    from repro import obs

    spec = small.small_spec(cell)
    bench_cell = Cell(spec["cfg"], spec["mix"], 2 ** 31 + 5, {})
    first = bench_cell.call()
    with obs.tracing() as tracer:
        bench_cell.call()
        bench_cell.call()
    ctx = _ctx(tracer.events, 2 * Cell.updates(first))
    c = tracer.counters
    assert harness.reader("h2d_mb_per_round")(ctx) == pytest.approx(
        c["sim.h2d_bytes"] / 1e6 / ctx.obs_updates)
    assert harness.reader("host_syncs_per_round")(ctx) == pytest.approx(
        c["sim.host_syncs"] / ctx.obs_updates)
