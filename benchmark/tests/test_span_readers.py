"""The program-span readers against a fake ``span_stats``, on the CPU."""

import os

import pytest

from run import load_module

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _reader(metric):
    return load_module(os.path.join(HERE, "metrics", f"{metric}.py"),
                       "metric_" + metric.replace(".", "_"))


def _agg(durations):
    return {"n": len(durations), "total_s": sum(durations), "attrs": {},
            "durations_s": list(durations)}


def _fake(monkeypatch, spans):
    from crdt_tpu import telemetry

    monkeypatch.setattr(telemetry, "span_stats",
                        lambda: {k: _agg(v) for k, v in spans.items()})


DISPATCH = {
    "fanout.dispatch": [0.100, 0.060, 0.080],
    "fanout.stack": [0.020, 0.010, 0.015],
    "fanout.wire_fetch": [0.030, 0.020, 0.025],
    "fanout.deliver": [0.010, 0.005, 0.001],
}


@pytest.mark.parametrize("metric", ["push_dispatch_ms.rate", "push_dispatch_ms.sat"])
def test_push_dispatch_own_pairs_calls(monkeypatch, metric):
    # Own times 40, 25 and 39 ms: the median of the call-by-call
    # differences, not the difference of the medians (35 ms).
    _fake(monkeypatch, DISPATCH)
    assert _reader(metric).read({}) == pytest.approx(39.0)


@pytest.mark.parametrize("metric", ["push_dispatch_ms.rate", "push_dispatch_ms.sat"])
@pytest.mark.parametrize("child", ["fanout.stack", "fanout.wire_fetch", "fanout.deliver"])
def test_push_dispatch_counts_that_differ_read_nothing(monkeypatch, metric, child):
    spans = dict(DISPATCH, **{child: DISPATCH[child][:2]})
    _fake(monkeypatch, spans)
    assert _reader(metric).read({}) is None
    del spans[child]
    _fake(monkeypatch, spans)
    assert _reader(metric).read({}) is None


def test_push_dispatch_without_spans_reads_nothing(monkeypatch):
    _fake(monkeypatch, {})
    assert _reader("push_dispatch_ms.rate").read({}) is None
    from crdt_tpu import telemetry

    monkeypatch.delattr(telemetry, "span_stats")
    assert _reader("push_dispatch_ms.sat").read({}) is None
