"""The control, the plain reference computed in bfloat16 and put in the
program's place, at a size a CPU test run holds; the program's own
reading there comes out correct against the cell's limits.

Where the cell's limits were set between readings that only the cell's
own size on the chip gives (the CNN's bfloat16 control stalls there, and
reads within the chip's sound spread on the CPU at this size), the test
holds the control to the rule an upper reading has to meet: three times
the program's reading, on a training number that the cell compares.
"""
import pytest

from bench import calibrate, check
from bench.tests import small

CELLS = {"mlp-fedavg_sched-c10s10-g13": None,
         "cnn-fedbuff-c10s10-g13": 16,
         "mlp-table1-batched": None}
SEPARATES_HERE = {"mlp-fedavg_sched-c10s10-g13", "mlp-table1-batched"}
EXACT = {"runs_differ": 0.0, "plan_differs": 0.0}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_control_fails_and_program_passes(cell):
    spec = small.small_spec(cell, max_steps=CELLS[cell])
    row = calibrate.readings(spec, 2 ** 31 + 11, control=True,
                             faults=False)
    limits = spec["limits"]
    ok, rows = check.verdict(dict(row["program"], **EXACT), limits)
    assert ok, rows
    if cell in SEPARATES_HERE:
        ok, rows = check.verdict(dict(row["control"], **EXACT), limits)
        assert not ok, rows
    else:
        compared = [k for k in ("first_gap", "change_gap") if k in limits]
        assert any(row["control"][k] >= 3 * row["program"][k]
                   for k in compared), row
