"""Every cell of ``BENCHMARK.json`` end to end on the CPU at a tiny size,
through the benchmark's own ``run.py``, drivers, references and readers:
the program's answer is correct, the control in its place is caught, and a
traced run reads no device metric off the chip. The four-chip cell runs on
the suite's virtual devices."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
for _p in (os.path.join(BENCH, "tests"), BENCH):
    if _p not in sys.path:
        sys.path.append(_p)

from tiny import TINY  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCHMARK = json.load(_f)
CELLS = [w["name"] for w in BENCHMARK["workloads"]]
# The map cell's tiny shape, as its own CPU rehearsal cuts it; the list
# cell's keeps the trace's insert share and a quarter of rows lagging.
SHAPES = dict(TINY, map_mvreg_fold_1chip=dict(
    replicas=16, keys=2048, writer_puts=4, writer_removes=4, check_span=8),
    list_automerge_1chip=dict(replicas=16, trace_ops=4096, trace_inserts=2875, chunk=128,
                              lagged_replicas=4, max_lag=2))


def run_tiny(cell, seed, seconds, trace=0, control=0):
    import run

    args = run.parse(["--workload", cell, "--seed", str(seed), "--seconds",
                      str(seconds), "--trace", str(trace), "--control", str(control)])
    return run.run_cell(args, BENCHMARK, overrides=SHAPES[cell])


@pytest.mark.parametrize("cell", CELLS)
def test_cell_is_correct(cell):
    out = run_tiny(cell, seed=(1 << 33) + 5, seconds=1.0)
    line = out["line"]
    assert line["correct"], line["checks"]
    assert line["device"]["platform"] == "cpu"
    assert line["attempted"] > 0 and line["failed"] == 0
    assert "setup_s" in line["metrics"]
    assert out["extra"]["counters"]["window_compiles"] == 0


@pytest.mark.parametrize("cell", CELLS)
def test_control_in_the_programs_place_is_not_correct(cell):
    line = run_tiny(cell, seed=(1 << 33) + 6, seconds=0.5, control=1)["line"]
    assert not line["correct"], line["checks"]
    off = [v["value"] for k, v in line["checks"].items()
           if k in ("served_row_words_off", "state_words_off")]
    assert off and off[0] > 0


@pytest.mark.parametrize("cell", CELLS)
def test_traced_run_reports_no_device_metric_off_the_chip(cell):
    line = run_tiny(cell, seed=99, seconds=0.5, trace=1)["line"]
    assert line["correct"], line["checks"]
    assert "busy_s" not in line["device"]
    for name in line["metrics"]:
        for device_word in ("idle", "roofline", "collective", "share"):
            assert device_word not in name, name


def test_bench_configs_contract():
    """The tenants configuration cites ``BENCH_CONFIGS.json``'s ``fanout``
    entry as its source: every key the two share agrees, except the ones
    the configuration lists as cut to size."""
    with open(os.path.join(ROOT, "BENCH_CONFIGS.json")) as f:
        fanout = json.load(f)["fanout"]
    with open(os.path.join(BENCH, "configs", "orswot_tenants_resident.json")) as f:
        config = json.load(f)
    shared = (set(fanout) & set(config)) - set(config["reduced"]) - {"_doc"}
    assert shared == {"actors", "deferred_cap", "dispatch_lanes", "elems",
                      "slab_depth", "slab_lanes", "window_cap"}
    for key in sorted(shared):
        assert config[key] == fanout[key], key
    assert set(config["reduced"]) <= set(fanout)
