"""`bench/tracing_modes.py` at a small size on the CPU: every mode runs,
all calls agree bitwise, the counters are read; and the command refuses
to measure without a TPU."""
from bench import tracing_modes
from bench.tests import small


def test_modes_run_and_agree():
    spec = small.small_spec("mlp-fedavg_sched-c10s10-g13")
    out = tracing_modes.measure(spec, 2 ** 31 + 5, 0.01, 1,
                                require_tpu=False)
    assert out["runs_differ"] == 0 and out["calls"] >= 6
    assert all(len(r) == 1 and r[0] > 0 for r in out["rounds_per_s"].values())
    assert set(out["rounds_per_s"]) == set(tracing_modes.MODES)
    traffic = out["traffic_per_update"]
    assert traffic["h2d_mb"] > 0 and traffic["host_syncs"] > 0
    # no TPU plane in a CPU profile: nothing to reduce or attribute
    assert out["profiled"] is None and out["attributed"] is None


def test_refuses_without_a_tpu(capsys):
    assert tracing_modes.main(["--workload", "mlp-table1-batched",
                               "--seed", "1"]) == 1
    assert "no TPU" in capsys.readouterr().err
