"""Every cell end to end on the CPU at a tiny size: the same run.py,
drivers and readers as on the chip. The four-chip cell runs on four
virtual devices."""

import pytest

from tiny import rehearse

CELLS = ["tenants_resident_sat", "tenants_resident_rate", "replicas_fold_1chip",
         "replicas_ring_4chip"]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_is_correct(cell):
    out = rehearse(cell, seed=(1 << 33) + 5, seconds=1.0)
    line = out["line"]
    assert line["correct"], line["checks"]
    assert line["device"]["platform"] == "cpu"
    assert line["attempted"] > 0 and line["failed"] == 0
    assert "setup_s" in line["metrics"]
    assert out["extra"]["counters"]["window_compiles"] == 0


@pytest.mark.parametrize("cell", CELLS)
def test_control_in_the_programs_place_is_not_correct(cell):
    line = rehearse(cell, seed=(1 << 33) + 6, seconds=0.5, control=1)["line"]
    assert not line["correct"], line["checks"]
    off = [v["value"] for k, v in line["checks"].items()
           if k in ("served_row_words_off", "state_words_off")]
    assert off and off[0] > 0


@pytest.mark.parametrize("cell", CELLS)
def test_traced_run_reports_no_device_metric_off_the_chip(cell):
    line = rehearse(cell, seed=99, seconds=0.5, trace=1)["line"]
    assert line["correct"]
    assert "busy_s" not in line["device"]
    for name in line["metrics"]:
        assert "idle" not in name and "roofline" not in name and "collective" not in name
