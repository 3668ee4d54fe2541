"""Attribution of idle device time to program layers, on small hand-made
traces: the layers sum to the idle time, the innermost span wins, a gap
that straddles a span edge is split, and only the driving thread's
program spans count."""
import pytest

from bench import attribution, trace

MS = 1e6   # nanoseconds
W = trace.WINDOW_EVENT


def _trace():
    # Window [0, 100) ms, one call. Chip 0 is busy 10-20 and 50-60 ms,
    # chip 1 50-70 ms. On the driving thread: sim.run 5-95 holds a round
    # 10-80 (select 12-30, in which comms.route 15-25; client_train
    # 40-60) and an eval 82-90. A JAX host event and another thread's
    # span are never a layer.
    return {
        "devices": {"/device:TPU:0": [("fusion", 10 * MS, 10 * MS),
                                      ("fusion", 50 * MS, 10 * MS)],
                    "/device:TPU:1": [("fusion", 50 * MS, 20 * MS)]},
        "host": [(W, 0.0, 100 * MS, "main"),
                 ("sim.run", 5 * MS, 90 * MS, "main"),
                 ("sim.round", 10 * MS, 70 * MS, "main"),
                 ("sim.select", 12 * MS, 18 * MS, "main"),
                 ("comms.route", 15 * MS, 10 * MS, "main"),
                 ("sim.client_train", 40 * MS, 20 * MS, "main"),
                 ("PjitFunction(update)", 41 * MS, 5 * MS, "main"),
                 ("sim.eval", 82 * MS, 8 * MS, "main"),
                 ("sim.aggregate", 0.0, 100 * MS, "worker")],
    }


def test_layers_sum_to_idle_time():
    out = attribution.attribute(_trace())
    reduced = trace.reduce(_trace())
    idle_ms = (1 - reduced["busy_s"] / reduced["window_s"]) \
        * reduced["window_s"] * 1e3
    assert sum(out["idle_ms"].values()) == pytest.approx(idle_ms, rel=1e-12)
    assert out["busy_s"] == pytest.approx(reduced["busy_s"])
    assert out["window_s"] == pytest.approx(reduced["window_s"])
    assert out["chips"] == 2
    assert set(out["idle_ms"]) == set(attribution.LAYERS.values()) | {
        attribution.OUTSIDE}


def test_innermost_span_wins_and_gaps_are_split():
    ms = attribution.attribute(_trace())["idle_ms"]
    # Chip 0 idle: 0-10, 20-50, 60-100; chip 1: 0-50, 70-100. Labels:
    # outside 0-5 and 95-100; run 5-10, 80-82, 90-95; round 10-12,
    # 30-40, 60-80; select 12-30 (comms.route inherits); client_train
    # 40-60; eval 82-90. Chip 0's 20-50 gap is split over select, round
    # and client_train.
    chip0 = {"outside": 10, "run": 5 + 2 + 5, "round": 10 + 20,
             "select": 10, "client_train": 10, "eval": 8}
    chip1 = {"outside": 10, "run": 5 + 2 + 5, "round": 2 + 10 + 10,
             "select": 18, "client_train": 10, "eval": 8}
    for layer in set(chip0) | set(chip1):
        assert ms[layer] == pytest.approx(
            (chip0.get(layer, 0) + chip1.get(layer, 0)) / 2), layer
    assert ms["aggregate"] == 0.0    # only another thread's span


def test_a_gap_with_no_program_span_is_outside():
    t = _trace()
    t["host"] = [ev for ev in t["host"] if not ev[0].startswith("sim.")]
    ms = attribution.attribute(t)["idle_ms"]
    assert ms["outside"] == pytest.approx(sum(ms.values()))
    assert ms["outside"] == pytest.approx((80 + 80) / 2)


def test_planning_claims_its_rounds():
    # The batched planner's own sim.round spans are planning (select);
    # sim.batched.assemble is round-loop work.
    t = _trace()
    t["host"] = [(W, 0.0, 100 * MS, "main"),
                 ("sim.batched.plan", 0.0, 30 * MS, "main"),
                 ("sim.round", 5 * MS, 10 * MS, "main"),
                 ("sim.batched.assemble", 30 * MS, 20 * MS, "main")]
    ms = attribution.attribute(t)["idle_ms"]
    assert ms["select"] == pytest.approx((20 + 30) / 2)
    assert ms["round"] == pytest.approx((20 + 20) / 2)


def test_window_edges_clip_spans():
    t = _trace()
    t["host"].append(("sim.select", -20 * MS, 25 * MS, "main"))
    ms = attribution.attribute(t)["idle_ms"]
    assert ms["select"] == pytest.approx((10 + 5 + 18 + 5) / 2)


def test_nothing_to_read_gives_none():
    t = _trace()
    t["devices"] = {"/device:TPU:0": []}
    assert attribution.attribute(t) is None
    t = _trace()
    t["host"] = [ev for ev in t["host"] if ev[0] != W]
    assert attribution.attribute(t) is None
