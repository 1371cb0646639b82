"""With the timed path broken underneath, the run must come out not
correct: once for each fault a one-chip cell can have."""
import pytest

from bench import cell
from bench.tests.faults import FAULTS
from bench.tests.helpers import tiny


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "answer_altered"])
def test_fault_is_caught(fault, tmp_path, monkeypatch):
    monkeypatch.setattr(cell, "TRACE_DIR", str(tmp_path / "trace"))
    cfg, mix = tiny()
    res = cell.run_cell(cfg, mix, seed=17, seconds=1.0,
                        fault=FAULTS[fault])
    assert any(v > 0 for v in res["checks"].values()), res["checks"]
