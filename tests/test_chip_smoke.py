"""chip_smoke.py off the chip: its device check refuses the CPU, and each
of its phases runs end to end at a tiny size through the Pallas
interpreter (the rehearsal before a chip call; the chip runs the same
code at the deployments' sizes)."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import chip_smoke


def test_device_check_fails_under_cpu_pin(capsys):
    assert chip_smoke.main([]) == 1
    assert chip_smoke.main(["--chips", "4"]) == 1
    assert '"ok"' not in capsys.readouterr().out


TINY_SERVE = dict(
    tenants=4096, lanes=1024, elems=4, actors=2, deferred_cap=2,
    slab_lanes=256, slab_depth=4, ops_per_cycle=600, hot_set=256,
    hot_shift=64, evict_cohort=16, retouch=4, oracle_sample=12,
)
TINY_FANOUT = dict(
    tenants=2048, lanes=512, subscribers=2048, elems=16, actors=4,
    deferred_cap=2, slab_lanes=256, slab_depth=4, dispatch_lanes=128,
    ops_per_cycle=256, hot_set=256, window_cap=4, client_sample=8,
)
TINY_STREAM = dict(replicas=64, universe=4096, actors=8, segment_cap=64,
                   block_rows=16)


@pytest.mark.parametrize("phase", ["fold", "stream", "serve", "mesh"])
def test_phase_runs_bit_identical_at_tiny_size(phase, monkeypatch):
    monkeypatch.setattr(chip_smoke, "INTERPRET", True)
    if phase == "fold":
        out = chip_smoke.phase_fold(dict(
            chip_smoke.CONFIG3, replicas=48, elems=256, chunk=16,
            slice_elems=128, pure_elems=16,
        ))
        assert out["equals_pure_chain"] and out["equals_tree_fold_slice"]
    elif phase == "stream":
        out = chip_smoke.phase_stream(TINY_STREAM)
    elif phase == "serve":
        out = chip_smoke.phase_serve(TINY_SERVE, TINY_FANOUT)
        assert out["restored_in_window"] >= 1 and out["wal_fsyncs"] >= 1
        assert out["wire_deferred"] >= 1 and out["wire_equals_interpreter"]
    else:
        out = chip_smoke.phase_mesh(dict(
            chip_smoke.MESH4, replicas=64, elems=512, chunk=16,
            writer_rows=4,
        ))
        assert out["gossip_residue"] == 0
    assert out["bit_identical"], out
