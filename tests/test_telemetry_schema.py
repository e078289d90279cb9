"""Exporter-drift gate: everything the observability drain writes must
validate against the committed schema (tools/telemetry_schema.json via
tools/check_telemetry_schema.py), so a renamed field or mistyped value
fails tier-1 instead of corrupting BENCH trajectories."""

import json
import os
import sys


ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "tools"))

import check_telemetry_schema as cts  # noqa: E402

from crdt_tpu import exporter, telemetry  # noqa: E402
from crdt_tpu.utils.metrics import Metrics, metrics  # noqa: E402


def _activity(tmp_path):
    metrics.count("schema_test.counter", 5)
    metrics.observe("schema_test.gauge", 2.5)
    # A JSONL path is a listener: spans record only while one exists.
    telemetry.configure_tracing(str(tmp_path / "activity_spans.jsonl"))
    try:
        with telemetry.span("schema_test.outer", shape="4x8"):
            with telemetry.span("schema_test.inner"):
                pass
    finally:
        telemetry.configure_tracing(None)


def test_drain_jsonl_validates_against_committed_schema(tmp_path):
    _activity(tmp_path)
    tel = telemetry.zeros()
    path = str(tmp_path / "metrics.jsonl")
    n = exporter.drain_jsonl(path, telemetry={"orswot_gossip": tel})
    assert n >= 4  # snapshot + telemetry + the two spans
    assert cts.validate_jsonl(path) == []
    # Appending a second drain keeps the file valid (append-only sink).
    exporter.drain_jsonl(path, spans=[])
    assert cts.validate_jsonl(path) == []
    kinds = [json.loads(l)["record"] for l in open(path)]
    assert {"snapshot", "telemetry", "span"} <= set(kinds)


def test_registry_snapshot_validates(tmp_path):
    _activity(tmp_path)
    assert cts.validate_snapshot(metrics.snapshot()) == []


def test_schema_rejects_drift(tmp_path):
    good = exporter.snapshot_record({"counters": {"a": 1}, "gauges": {}})
    assert cts.validate_record(good) == []
    # A renamed field, a stringly-typed counter, an unknown record.
    assert cts.validate_record({"record": "snapshot", "ts": 1.0,
                                "counters": {"a": "1"}, "gauges": {}})
    assert cts.validate_record({"record": "telemetry", "ts": 1.0,
                                "kind": "x", "merges": 1})  # missing fields
    assert cts.validate_record({"record": "wat", "ts": 1.0})
    assert cts.validate_record({"record": "span", "ts": 1.0, "name": "n",
                                "dur_s": "fast", "parent": None,
                                "attrs": {}})
    bad = tmp_path / "bad.jsonl"
    bad.write_text(json.dumps({"record": "snapshot"}) + "\nnot json\n")
    errs = cts.validate_jsonl(str(bad))
    assert any("line 1" in e for e in errs)
    assert any("line 2" in e for e in errs)
    # CLI contract: non-zero on violation, zero on a clean file.
    assert cts.main([str(bad)]) == 1
    ok = tmp_path / "ok.jsonl"
    ok.write_text(json.dumps(good) + "\n")
    assert cts.main([str(ok)]) == 0


def test_schema_validates_and_rejects_histogram_shapes():
    """The ISSUE 12 satellite: the ``histogram`` kind (bucket-edges
    array + counts one longer + exact total) validates the honest
    ``hist_*`` fields and REJECTS every malformation class."""
    good = exporter.telemetry_record("k", telemetry.zeros())
    assert cts.validate_record(good) == []

    def broken(**patch):
        rec = exporter.telemetry_record("k", telemetry.zeros())
        h = dict(rec["hist_residue"])
        h.update(patch)
        rec["hist_residue"] = patch.get("_whole", h)
        return cts.validate_record(rec)

    # Counts/edges length mismatch (the quantile-skewing class).
    errs = broken(counts=[0] * 3)
    assert any("counts" in e for e in errs)
    # Non-ascending edges.
    assert any("ascending" in e for e in broken(edges=[4.0, 2.0, 1.0]))
    # Negative / non-int counts.
    n = len(good["hist_residue"]["counts"])
    assert broken(counts=[-1] + [0] * (n - 1))
    assert broken(counts=["0"] * n)
    # Non-finite total, and a histogram that is not an object at all.
    assert any("total" in e for e in broken(total=float("inf")))
    assert broken(_whole="not-a-histogram")
    # A missing histogram field is drift like any other missing field.
    rec = exporter.telemetry_record("k", telemetry.zeros())
    del rec["hist_useful_bytes"]
    assert any("hist_useful_bytes" in e for e in cts.validate_record(rec))


def test_schema_validates_flight_records(tmp_path):
    """Flight-recorder dumps validate line-by-line through the same
    committed schema (the ``flight`` / ``flight_header`` records)."""
    from crdt_tpu import obs

    rec = obs.FlightRecorder(capacity=8)
    rec.record("probe", seq=1)
    path = str(tmp_path / "dump.jsonl")
    rec.dump(path, reason="schema-test")
    assert cts.validate_jsonl(path) == []
    # A key-less flight event is drift.
    assert cts.validate_record(
        {"record": "flight", "ts": 1.0, "type": "probe"}
    )


def test_prometheus_text_exposition():
    m = Metrics()
    m.count("anti_entropy.merges", 7)
    m.observe("elastic.orswot.headroom.n_members", 0.5)
    tel = telemetry.zeros()
    txt = exporter.prometheus_text(
        snapshot=m.snapshot(), telemetry={"orswot_gossip": tel}
    )
    assert "# TYPE anti_entropy_merges counter" in txt
    assert "anti_entropy_merges 7" in txt
    assert "elastic_orswot_headroom_n_members 0.5" in txt
    assert "elastic_orswot_headroom_n_members_count 1" in txt
    assert 'crdt_tpu_telemetry_merges{kind="orswot_gossip"} 0' in txt
    # Prometheus-legal names only (no dots survive sanitizing).
    for line in txt.splitlines():
        name = line.split("{")[0].split()[1 if line.startswith("#") else 0]
        if line.startswith("# TYPE"):
            name = line.split()[2]
        assert "." not in name


def test_prometheus_multi_kind_groups_samples_under_one_type_line():
    # A second "# TYPE" line for the same metric is invalid exposition:
    # with several kinds the samples must group field-major.
    txt = exporter.prometheus_text(
        snapshot={"counters": {}, "gauges": {}},
        telemetry={"orswot_fold": telemetry.zeros(),
                   "map_fold": telemetry.zeros()},
    )
    type_lines = [l for l in txt.splitlines() if l.startswith("# TYPE")]
    assert len(type_lines) == len(set(type_lines))
    assert 'crdt_tpu_telemetry_merges{kind="map_fold"} 0' in txt
    assert 'crdt_tpu_telemetry_merges{kind="orswot_fold"} 0' in txt


def test_span_survives_unserializable_attrs(tmp_path):
    import numpy as np

    path = str(tmp_path / "trace.jsonl")
    telemetry.configure_tracing(path)
    try:
        with telemetry.span("np_span", count=np.int32(3)):
            pass  # must not raise out of the finally block
    finally:
        telemetry.configure_tracing(None)
    assert cts.validate_jsonl(path) == []
    # The buffered event drains through the JSONL sink too.
    events = telemetry.drain_events()
    out = str(tmp_path / "drain.jsonl")
    assert exporter.drain_jsonl(out, snapshot={"counters": {}, "gauges": {}},
                                spans=events) == 1 + len(events)
    assert cts.validate_jsonl(out) == []


def test_span_events_nest_and_drain(tmp_path):
    telemetry.drain_events()  # clear
    telemetry.configure_tracing(str(tmp_path / "spans.jsonl"))
    try:
        with telemetry.span("outer_span", a=1):
            with telemetry.span("inner_span"):
                pass
    finally:
        telemetry.configure_tracing(None)
    events = telemetry.drain_events()
    assert [e["name"] for e in events] == ["inner_span", "outer_span"]
    inner, outer = events
    assert inner["parent"] == "outer_span"
    assert outer["parent"] is None
    assert outer["attrs"] == {"a": 1}
    assert all(cts.validate_record(e) == [] for e in events)
    assert telemetry.drain_events() == []  # drained
    # Span durations also land in the registry timer histogram.
    assert "outer_span_seconds" in metrics.snapshot()["gauges"]


def test_span_jsonl_file_sink(tmp_path):
    path = str(tmp_path / "trace.jsonl")
    telemetry.configure_tracing(path)
    try:
        with telemetry.span("file_span"):
            pass
    finally:
        telemetry.configure_tracing(None)
    assert cts.validate_jsonl(path) == []
    [rec] = [json.loads(l) for l in open(path)]
    assert rec["name"] == "file_span"
