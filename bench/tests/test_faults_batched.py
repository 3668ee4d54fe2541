"""The batched cell at a small size: sound, then each fault."""
import pytest

from bench.tests import small

CELL = "mlp-table1-batched"


def test_sound_run_is_correct():
    out = small.run(small.small_spec(CELL))
    assert out["correct"], out["checks"]


@pytest.mark.parametrize("fault", sorted(small.FAULTS))
def test_fault_is_not_correct(fault, monkeypatch):
    small.FAULTS[fault](monkeypatch)
    out = small.run(small.small_spec(CELL))
    assert not out["correct"], out["checks"]
