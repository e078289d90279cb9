"""The trace reduction on events whose answer is known by hand."""

import pytest

import trace_reduce as tr
from trace_reduce import Event


def test_union_subtract_length():
    u = tr.union([(0, 10), (5, 15), (20, 30)])
    assert u == [(0, 15), (20, 30)]
    assert tr.length(u) == 25
    assert tr.subtract([(0, 30)], u) == [(15, 20)]
    assert tr.subtract([(0, 10), (12, 40)], [(5, 14), (30, 35)]) == [
        (0, 5), (14, 30), (35, 40)]


def test_reduce_events_by_hand():
    host = [Event("bench_window", 100, 1100),
            Event("span.step", 100, 600), Event("span.push", 600, 1100)]
    dev0 = [Event("fusion.1", 50, 200),            # clipped to 100..200
            Event("my_fold_kernel", 300, 500),
            Event("all-reduce.3", 450, 700),       # 500..700 exposed
            Event("fusion.2", 650, 800)]           # hides 650..700
    dev1 = [Event("my_fold_kernel", 100, 1100)]    # busy all window
    red = tr.reduce_events(host, [dev0, dev1])
    ns = 1e-9
    assert red["window_s"] == pytest.approx(1000 * ns)
    # dev0 busy: 100..200, 300..800 = 600; dev1: 1000. Mean 800.
    assert red["busy_s"] == pytest.approx(800 * ns)
    # dev0 collective 450..700 minus compute (300..500, 650..800) = 500..650.
    assert red["collective_exposed_s"] == pytest.approx(150 / 2 * ns)
    assert red["op_s"]["my_fold_kernel"] == pytest.approx(1200 * ns)
    assert red["op_n"]["my_fold_kernel"] == 2
    assert tr.op_time(red, "fold_kernel") == (pytest.approx(1200 * ns), 2)
    assert tr.op_time(red, "wire_kernel") is None
    # dev0 idle: 200..300 (in span.step) and 800..1100 (span.push).
    assert red["idle_gaps_s"]["span.step"] == pytest.approx(100 / 2 * ns)
    assert red["idle_gaps_s"]["span.push"] == pytest.approx(300 / 2 * ns)
    bd = tr.breakdown(red)
    assert bd["device_ops"][0][0] == "my_fold_kernel"
    assert bd["idle_gaps"][0][0] == "span.push"


def test_no_window_is_an_error():
    with pytest.raises(ValueError):
        tr.reduce_events([], [[]])


def test_recorded_chip_trace():
    """A trace recorded on the v5e (three calls of one jitted program, each
    under ``span.step``, with a 50 ms host sleep under ``span.sleep``
    between the second and third), reduced as a run reduces its own."""
    import os

    path = os.path.join(os.path.dirname(__file__), "data", "v5e_small.xplane.pb")
    host, devices = tr.load(path)
    assert len(devices) == 1 and devices[0]
    assert sum(e.name == "span.step" for e in host) == 3
    red = tr.reduce_events(host, devices)
    assert 0 < red["busy_s"] < red["window_s"]
    # Every device op of the program ran once per call. The recording holds
    # the device ops of two of the three calls: the first ran while the
    # device tracer was still starting.
    assert set(red["op_n"].values()) == {2}, red["op_n"]
    # The chip sat idle through the host's sleep, and the gap is put down to it.
    assert red["idle_gaps_s"]["span.sleep"] >= 0.045
    assert red["collective_exposed_s"] == 0
    assert red["busy_s"] == pytest.approx(sum(red["op_s"].values()), rel=1e-9)
