"""The reduction from a profiler trace to busy time, idle share and the
breakdown, on a small recorded trace."""
import pytest

from bench import trace

MS = 1e6   # nanoseconds


def _trace():
    # Window: two runs, [0, 40) and [40, 100) ms. Chip 0 has overlapping
    # ops (union 10-30 ms) and one op leaking past the window's end; chip
    # 1 is busy 50-70 ms. The driving thread dispatches 20-30 ms and
    # transfers 75-95 ms; another thread's event is never a gap's name.
    return {
        "devices": {
            "/device:TPU:0": [("fusion.1", 10 * MS, 15 * MS),
                              ("fusion.2", 20 * MS, 10 * MS),
                              ("copy.3", 95 * MS, 10 * MS)],
            "/device:TPU:1": [("fusion.1", 50 * MS, 20 * MS)],
        },
        "host": [(trace.WINDOW_EVENT, 0.0, 40 * MS, "main"),
                 (trace.WINDOW_EVENT, 40 * MS, 60 * MS, "main"),
                 ("PjitFunction(step)", 20 * MS, 10 * MS, "main"),
                 ("TransferToDevice", 75 * MS, 20 * MS, "main"),
                 ("ReadSyncFlag", 60 * MS, 5 * MS, "worker")],
    }


def test_busy_is_the_union_inside_the_window():
    out = trace.reduce(_trace())
    # chip 0: 10-30 (20 ms) + 95-100 (5 ms, clipped); chip 1: 20 ms.
    assert out["window_s"] == pytest.approx(0.1)
    assert out["busy_s"] == pytest.approx((0.025 + 0.020) / 2)
    assert out["idle_share"] == pytest.approx(1 - 0.0225 / 0.1)
    assert out["chips"] == 2


def test_breakdown_names_ops_and_gaps():
    out = trace.reduce(_trace())["breakdown"]
    ops = dict(out["device_ops"])
    assert ops["fusion.1"] == pytest.approx((0.015 + 0.020) / 2)
    assert ops["copy.3"] == pytest.approx(0.005 / 2)
    gaps = out["idle_gaps"]
    # Longest first: chip 0's 30-95 ms (middle 62.5 ms: no host event);
    # chip 1's 0-50 ms (middle 25 ms: the dispatch) and 70-100 ms
    # (middle 85 ms: the transfer).
    assert gaps[0][0] == "none" and gaps[0][1] == pytest.approx(0.065)
    assert ["TransferToDevice", pytest.approx(0.030)] in gaps
    assert ["PjitFunction(step)", pytest.approx(0.050)] in gaps
    assert len(gaps) <= trace.TOP and len(out["device_ops"]) <= trace.TOP


def test_nothing_to_read_gives_none():
    empty = _trace()
    empty["devices"] = {"/device:TPU:0": []}
    assert trace.reduce(empty) is None
    no_window = _trace()
    no_window["host"] = no_window["host"][2:]
    assert trace.reduce(no_window) is None


def test_op_names_carry_program_and_instruction():
    hlo = ("%fusion.126 = bf16[320,28,28]{2,1,0:T(8,128)(2,1)S(1)} "
           "fusion(bf16[10,350,28,28]{3,2,1,0} %bitcast.73), kind=kLoop")
    assert trace.op_name(hlo, "jit_update") == (
        "jit_update/%fusion.126 bf16[320,28,28]")
    assert trace.op_name(hlo, None) == "%fusion.126 bf16[320,28,28]"


def test_merge_clips_and_joins():
    assert trace.merge([(5, 8), (0, 2), (1, 3), (9, 20)], 1, 10) == [
        (1, 3), (5, 8), (9, 10)]
