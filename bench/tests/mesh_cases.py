"""Runs `mlp-fedavg_sched-c10s10-g13` under the mix
`c10s10-g13-fedavg_sched-mesh` (the mesh executor over all chips), the
four-chip cell still to be proven, at a small size on four virtual CPU
devices, sound and with each of `small.MESH_FAULTS` planted, and prints
one JSON line per case: {"case": ..., "correct": ..., "checks": ...}.

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \\
        python bench/tests/mesh_cases.py

The device count is fixed when JAX starts, so the tests run this in a
process of its own.
"""
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import pytest  # noqa: E402

from bench.tests import small  # noqa: E402

CELL = "mlp-fedavg_sched-c10s10-g13"
MIX = "c10s10-g13-fedavg_sched-mesh"


def main() -> int:
    for case in ["sound"] + sorted(small.MESH_FAULTS):
        with pytest.MonkeyPatch.context() as mp:
            if case != "sound":
                small.MESH_FAULTS[case](mp)
            out = small.run(small.small_spec(CELL, mix=MIX))
        print(json.dumps({"case": case, "correct": out["correct"],
                          "attempted": out["attempted"],
                          "checks": out["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
