"""The harness on the mesh executor, at a small size on four virtual CPU
devices: a sound run comes out correct; each fault, the exchange between
chips left out among them, does not."""
import json
import os
import subprocess
import sys

import pytest

from bench import harness
from bench.tests import small


@pytest.fixture(scope="module")
def cases():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run(
        [sys.executable, os.path.join(harness.BENCH, "tests",
                                      "mesh_cases.py")],
        cwd=harness.ROOT, env=env, capture_output=True, text=True,
        timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    rows = [json.loads(line) for line in proc.stdout.splitlines()
            if line.startswith('{"case"')]
    return {r["case"]: r for r in rows}


def test_sound_run_is_correct(cases):
    assert cases["sound"]["correct"], cases["sound"]["checks"]
    assert cases["sound"]["attempted"] >= 1


@pytest.mark.parametrize("fault", sorted(small.MESH_FAULTS))
def test_fault_is_not_correct(cases, fault):
    assert not cases[fault]["correct"], cases[fault]["checks"]
