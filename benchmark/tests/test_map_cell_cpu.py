"""The ``map_mvreg_fold_1chip`` cell end to end on the CPU at a tiny size:
the same run.py, driver, reference and readers as on the chip."""

import pytest

from tiny import bench

CELL = "map_mvreg_fold_1chip"
TINY_MAP = dict(replicas=16, keys=2048, writer_puts=4, writer_removes=4, check_span=8)


def rehearse(seed, seconds, trace=0, control=0):
    import run

    args = run.parse(["--workload", CELL, "--seed", str(seed), "--seconds", str(seconds),
                      "--trace", str(trace), "--control", str(control)])
    return run.run_cell(args, bench(), overrides=TINY_MAP)


def test_cell_is_correct():
    out = rehearse((1 << 33) + 5, 1.0)
    line = out["line"]
    assert line["correct"], line["checks"]
    assert line["device"]["platform"] == "cpu"
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["checks"]["rounds_checked"]["value"] == 4
    assert {"setup_s", "merges_per_s", "converge_p95_ms"} <= set(line["metrics"])
    assert out["extra"]["counters"]["window_compiles"] == 0


def test_control_in_the_programs_place_is_not_correct():
    line = rehearse((1 << 33) + 6, 0.5, control=1)["line"]
    assert not line["correct"], line["checks"]
    assert line["checks"]["state_words_off"]["value"] > 0


def test_traced_run_reports_no_device_metric_off_the_chip():
    line = rehearse(99, 0.5, trace=1)["line"]
    assert line["correct"]
    assert "busy_s" not in line["device"]
    names = set(line["metrics"])
    assert "map_fold_host_ms.mapfold" in names
    for name in names:
        assert "idle" not in name and "roofline" not in name and "share" not in name


def test_altered_fold_is_caught(monkeypatch):
    from crdt_tpu.parallel import anti_entropy

    real = anti_entropy.mesh_fold_map

    def altered(pop, mesh, **kw):
        out, of = real(pop, mesh, **kw)
        return out._replace(top=out.top.at[0].add(1)), of

    monkeypatch.setattr(anti_entropy, "mesh_fold_map", altered)
    line = rehearse(31, 0.5)["line"]
    assert line["checks"]["state_words_off"]["value"] > 0 and not line["correct"]


def test_map_roofline_bytes_by_hand():
    import roofline_map

    # 3 keys, 2 actors, 2 slots, 1 parked slot: top 2 words; each slot
    # wact, wctr, val, a flag and a 2-word clock (5 words + 1 byte); the
    # parked slot's 2-word clock, 3 key flags and its flag.
    one = 2 * 4 + 3 * 2 * (5 * 4 + 1) + (2 * 4 + 3 + 1)
    assert roofline_map.map_state_bytes(3, 2, 2, 1) == one
    assert roofline_map.map_fold_bytes(4, 3, 2, 2, 1) == 5 * one
    # The cell's replica: 98.6 MB at 2^20 keys, as the configuration says.
    assert roofline_map.map_state_bytes(1 << 20, 8, 2, 4) == pytest.approx(98.6e6, rel=1e-3)
