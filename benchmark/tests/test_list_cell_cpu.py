"""The ``list_automerge_1chip`` cell's own pieces on the CPU: an altered
apply is caught, the apply's roofline bytes by hand, and the program
reader that finds the apply by name on a recorded chip trace."""

import os

import pytest

from tiny import bench

CELL = "list_automerge_1chip"
TINY_LIST = dict(replicas=16, trace_ops=4096, trace_inserts=2875, chunk=128,
                 lagged_replicas=4, max_lag=2)


def rehearse(seed, seconds, trace=0, control=0):
    import run

    args = run.parse(["--workload", CELL, "--seed", str(seed), "--seconds", str(seconds),
                      "--trace", str(trace), "--control", str(control)])
    return run.run_cell(args, bench(), overrides=TINY_LIST)


def test_altered_apply_is_caught(monkeypatch):
    """A writer's replica (lag 0, so past chunk 0 whenever the window
    ends) that skips chunk 0 reads another text than its watermark's."""
    import numpy as np

    from crdt_tpu.models.list import BatchedList

    real = BatchedList.apply_chunks

    def altered(self, ids):
        ids = np.array(ids)
        if ids[0] == 0:
            ids[0] = -1
        return real(self, ids)

    monkeypatch.setattr(BatchedList, "apply_chunks", altered)
    line = rehearse(31, 0.5)["line"]
    assert line["checks"]["state_words_off"]["value"] > 0 and not line["correct"]


def test_list_roofline_bytes_by_hand():
    import roofline_list

    # Three live lanes (value and flag, read and written) and two chunks
    # of four lanes (slot, kind, value) read once.
    assert roofline_list.apply_bytes(3, 2, 4) == 3 * 2 * 5 + 2 * 4 * 9


def test_module_time_on_a_recorded_chip_trace():
    """The recorded v5e trace runs one jitted program three times; the
    device tracer caught two of them inside the window."""
    import trace_modules
    import trace_reduce

    path = os.path.join(os.path.dirname(__file__), "data", "v5e_small.xplane.pb")
    secs, runs = trace_modules.module_time(path, r"^jit__lambda\b")
    assert runs == 2
    red = trace_reduce.reduce(path)
    # A program's run spans its ops, and nothing else ran.
    assert red["busy_s"] <= secs == pytest.approx(red["busy_s"], rel=0.05)
    assert trace_modules.module_time(path, r"^jit__list_apply_chunks\b") is None
