"""A whole run on the CPU at a tiny size, through the harness's own cell
function, against the program; and the control, which must fail."""
import pytest

from bench import cell, control
from bench.tests.helpers import tiny


@pytest.fixture(autouse=True)
def _trace_dir(tmp_path, monkeypatch):
    monkeypatch.setattr(cell, "TRACE_DIR", str(tmp_path / "trace"))


def test_sound_run_is_correct():
    cfg, mix = tiny()
    res = cell.run_cell(cfg, mix, seed=2**31 + 3, seconds=2.0)
    assert res["checks"] == {k: 0 for k in res["checks"]}
    assert res["attempted"] > mix["warmup_batches"]
    assert res["failed"] == 0
    assert res["e2e"]["updates_per_s"] > 0
    assert res["setup_s"] > 0


def test_control_fails():
    cfg, mix = tiny()
    rec = control.run_control(cfg, mix, seed=5, window_epochs=4)
    assert rec["correct"] is False
    assert rec["checks"]["normalize_mismatch"]["value"] > 0
    assert rec["checks"]["delta_mismatch"]["value"] > 0


def test_traced_run_traces_the_first_part_of_the_window(monkeypatch):
    monkeypatch.setattr(cell, "TRACE_S", 1.0)
    cfg, mix = tiny()
    res = cell.run_cell(cfg, mix, seed=9, seconds=2.0, trace=True)
    assert res["checks"] == {k: 0 for k in res["checks"]}
    w = res["window"]
    assert 0 < w["epochs_traced"] <= res["attempted"] - \
        mix["warmup_batches"]
    assert w["traced_s"] >= 1.0
    assert len(w["prep_ms"]) == w["epochs_traced"]
    from bench import trace
    assert trace.find_xplane(res["trace_dir"]).endswith(".xplane.pb")
