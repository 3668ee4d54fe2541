"""The benchmark measures only on a TPU: on the CPU it exits non-zero and
prints no result."""
import os
import subprocess
import sys

from bench import harness


def test_run_exits_nonzero_on_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(harness.BENCH, "run.py"),
         "--workload", "mlp-fedavg_sched-c10s10-g13", "--seed", "3",
         "--seconds", "1", "--trace", "0"],
        cwd=harness.ROOT, env=env, capture_output=True, text=True,
        timeout=120)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert '"correct"' not in proc.stdout
