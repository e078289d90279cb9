"""The postmortem-grade observability plane (ISSUE 12, crdt_tpu/obs/):

- in-kernel log2 histograms (obs/hist.py) riding the ``telemetry=``
  sidecar: bucket-boundary exactness, jit/host agreement, the δ-ring
  per-round fills (residue backlog, useful bytes, ack-window depth),
  host-timed dispatch wall-clock, and combine/fold semantics;
- the flight recorder (obs/recorder.py): ring bound + drop accounting,
  the ``(generation, round, rank)`` correlation key shared with
  ``telemetry.span``, dump/report round-trips, and the auto-dump
  failure boundaries (DrainRefused / DcnExchangeFailed / recovery);
- tools/obs_report.py: the bit-exact folded-counter cross-check
  against the live registry and the invariant audit;
- exporter edge cases (the ISSUE 12 satellite): Prometheus label
  escaping, histogram ``_bucket``/``_sum``/``_count`` exposition
  conformance, and JSONL/ring drain idempotence under concurrent
  producers;
- the ``obs`` static-check section: clean on the honest
  implementations, firing on both committed broken twins.
"""

import json
import os
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from crdt_tpu import exporter, obs, telemetry as tele
from crdt_tpu.obs import hist
from crdt_tpu.utils.metrics import metrics

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))

import obs_report  # noqa: E402

P_REPLICAS = 4


@pytest.fixture(autouse=True)
def _isolated_recorder():
    """Every test starts with no installed recorder and cannot leak
    one into the rest of the suite."""
    prev = obs.install(None)
    yield
    obs.install(prev)


def _mini_delta_gossip(telemetry=True, **kw):
    import random

    from crdt_tpu.faults.scenarios import mint_streams
    from crdt_tpu.models import BatchedOrswot
    from crdt_tpu.parallel import make_mesh, mesh_delta_gossip
    from crdt_tpu.parallel.delta import interval_accumulate
    from crdt_tpu.parallel.mesh import shard_orswot
    from crdt_tpu.utils import Interner

    p = P_REPLICAS
    sites, _ = mint_streams(random.Random(11), p, 3 * p)
    batched = BatchedOrswot.from_pure(
        sites,
        members=Interner(list(range(5))),
        actors=Interner([f"s{i}" for i in range(p)]),
    )
    mesh = make_mesh(p, 1)
    state = shard_orswot(batched.state, mesh)
    z = jax.tree.map(jnp.zeros_like, state)
    d0 = jnp.zeros(state.ctr.shape[:-1], bool)
    f0 = jnp.zeros(state.ctr.shape, state.ctr.dtype)
    dirty, fctx = interval_accumulate(d0, f0, z, state)
    return mesh_delta_gossip(
        state, dirty, fctx, mesh, local_fold="tree", telemetry=telemetry,
        **kw
    ), mesh


# ---- histograms -----------------------------------------------------------

def test_hist_bucket_boundaries_are_exact():
    # Right-closed (le-inclusive) buckets — the Prometheus contract: a
    # value exactly on an edge counts under that edge's le label.
    cases = {
        0.0: 0, 0.5: 0, 1.0: 0, 1.5: 1, 2.0: 1, 3.0: 2, 4.0: 2,
        1023.0: 10, 1024.0: 10, float(2 ** 30): hist.NBUCKETS - 2,
        float(2 ** 40): hist.NBUCKETS - 1, -3.0: 0,
    }
    for v, want in cases.items():
        assert int(hist.bucket_index(v)) == want, (v, want)


def test_hist_observe_jit_matches_host():
    sample = [0.0, 1.0, 2.0, 7.0, 1024.0, 3.5]

    def fold():
        h = hist.zeros()
        for v in sample:
            h = hist.observe(h, v)
        return h

    jitted = jax.jit(fold)()
    eager = fold()
    np.testing.assert_array_equal(
        np.asarray(jitted.counts), np.asarray(eager.counts)
    )
    assert int(np.asarray(jitted.counts).sum()) == len(sample)
    assert float(jitted.total) == float(np.float32(sum(sample)))


def test_hist_merge_adds_counts_and_totals():
    a = hist.observe(hist.zeros(), 4.0)
    b = hist.observe(hist.observe(hist.zeros(), 4.0), 100.0)
    m = hist.merge(a, b)
    assert int(np.asarray(m.counts).sum()) == 3
    assert float(m.total) == 108.0


def test_hist_summary_quantiles():
    h = hist.zeros()
    for _ in range(99):
        h = hist.observe(h, 1.0)   # bucket [1, 2)
    h = hist.observe(h, 1000.0)    # one outlier in [512, 1024)
    s = hist.summary(hist.to_dict(h))
    assert s["count"] == 100
    # 1.0 sits in the right-closed bucket [0, 1] — the bulk quantiles
    # interpolate inside it, the outlier never drags them up.
    assert 0.0 <= s["p50"] <= 1.0
    assert 0.0 <= s["p95"] <= 1.0
    assert s["p99"] <= 1.0 or s["p99"] >= 512.0  # boundary interpolation
    assert s["total"] == pytest.approx(99.0 + 1000.0)
    empty = hist.summary(hist.to_dict(hist.zeros()))
    assert empty["count"] == 0 and empty["p99"] == 0.0


def test_delta_ring_fills_round_histograms():
    out, _ = _mini_delta_gossip()
    tl = out[4]
    d = tele.to_dict(tl)
    rounds = 2 * (P_REPLICAS - 1) - 1  # pipelined certificate window
    # One observation per round per replica rank for both in-loop hists.
    assert sum(d["hist_useful_bytes"]["counts"]) == rounds * P_REPLICAS
    assert sum(d["hist_residue"]["counts"]) == rounds * P_REPLICAS
    # The per-round totals reconcile with the scalar counters: useful
    # rides the hist except the one post-loop digest-top exchange.
    assert 0.0 < d["hist_useful_bytes"]["total"] <= d["bytes_useful"]
    # No ack window -> empty ack-depth hist; dispatch is host-timed.
    assert sum(d["hist_ack_depth"]["counts"]) == 0
    assert sum(d["hist_dispatch_us"]["counts"]) == 1
    assert d["hist_dispatch_us"]["total"] > 0.0


def test_delta_ring_ack_window_fills_depth_histogram():
    out, _ = _mini_delta_gossip(ack_window=True)
    d = tele.to_dict(out[4])
    rounds = 2 * (P_REPLICAS - 1) - 1
    # One observation per ACK EXCHANGE: the pipelined loop body runs
    # rounds-1 times (the prologue ships round 0 with no ack yet, the
    # epilogue applies the final in-flight packet without one).
    assert sum(d["hist_ack_depth"]["counts"]) == (rounds - 1) * P_REPLICAS


def test_time_dispatch_noop_under_tracing_and_fills_concrete():
    z = tele.zeros()
    filled = tele.time_dispatch(z, 0.004)
    assert sum(tele.to_dict(filled)["hist_dispatch_us"]["counts"]) == 1
    # 4000 µs lands in (2048, 4096] = bucket 12.
    assert int(np.argmax(np.asarray(filled.hist_dispatch_us.counts))) == 12

    def traced(x):
        t = z._replace(merges=x)  # make the pytree traced
        return tele.time_dispatch(t, 0.004).hist_dispatch_us.counts

    counts = jax.jit(traced)(jnp.uint32(1))
    assert int(np.asarray(counts).sum()) == 0  # untouched under trace


def test_combine_folds_histograms():
    out, _ = _mini_delta_gossip()
    tl = out[4]
    both = tele.combine(tl, tl)
    d1 = tele.to_dict(tl)
    d2 = tele.to_dict(both)
    assert (
        sum(d2["hist_useful_bytes"]["counts"])
        == 2 * sum(d1["hist_useful_bytes"]["counts"])
    )
    assert d2["hist_useful_bytes"]["total"] == pytest.approx(
        2 * d1["hist_useful_bytes"]["total"]
    )


def test_record_applies_counter_increments_and_summary_gauges():
    out, _ = _mini_delta_gossip()
    tl = out[4]
    metrics.reset()
    tele.record("obs_probe", tl)
    snap = metrics.snapshot()
    inc = tele.counter_increments("obs_probe", tele.to_dict(tl))
    for name, n in inc.items():
        assert snap["counters"].get(name, 0) == n, name
    assert "telemetry.obs_probe.hist.useful_bytes.p99" in snap["gauges"]
    assert "telemetry.obs_probe.hist.dispatch_us.p99" in snap["gauges"]


# ---- flight recorder ------------------------------------------------------

def test_recorder_ring_bound_keeps_newest_and_counts_drops():
    rec = obs.FlightRecorder(capacity=4)
    for i in range(11):
        rec.record("probe", seq=i)
    evs = rec.events()
    assert [e["seq"] for e in evs] == [7, 8, 9, 10]
    assert rec.dropped == 7
    assert len(rec) == 4


def test_recorder_correlation_key_and_span_stamping(tmp_path):
    rec = obs.FlightRecorder(capacity=64, rank=3)
    obs.install(rec)
    rec.set_generation(2)
    rec.advance_round()
    assert rec.key() == (2, 1, 3)
    # Stale generations never rewind the key.
    rec.set_generation(1)
    assert rec.key() == (2, 1, 3)
    tele.drain_events()
    with tele.span("obs.test_span"):
        pass
    evs = [e for e in tele.drain_events() if e["name"] == "obs.test_span"]
    assert evs and (evs[0]["gen"], evs[0]["round"], evs[0]["rank"]) == (
        2, 1, 3,
    )
    ev = rec.record("probe", seq=0)
    assert (ev["gen"], ev["round"], ev["rank"]) == (2, 1, 3)


def test_emit_is_noop_without_recorder():
    assert obs.emit("probe", seq=1) is None
    assert obs.auto_dump("nothing-installed") is None
    assert obs.current_key() is None


def test_dump_report_roundtrip_bit_exact_and_tamper_detected(tmp_path):
    metrics.reset()
    rec = obs.FlightRecorder(capacity=256)
    obs.install(rec)
    out, _ = _mini_delta_gossip()  # tele.record emits a telemetry event
    rec.snapshot_delta()
    path = str(tmp_path / "dump.jsonl")
    rec.dump(path, reason="test")
    report = obs_report.build_report(path, snapshot=metrics.snapshot())
    assert report["ok"], (
        report["parse_errors"], report["counter_mismatches"],
        report["audit"],
    )
    assert report["events"] >= 2  # telemetry + telemetry_delta
    assert "delta_gossip.useful_bytes" in report["histograms"]
    assert report["histograms"]["delta_gossip.dispatch_us"]["p99"] > 0
    text = obs_report.render_text(report)
    assert "bit-exact" in text and "timeline" in text
    # Tamper with the live registry -> the cross-check must fail loudly.
    metrics.count("telemetry.delta_gossip.merges", 1)
    tampered = obs_report.build_report(path, snapshot=metrics.snapshot())
    assert not tampered["ok"]
    assert any(
        "merges" in m for m in tampered["counter_mismatches"]
    )


def test_report_audit_flags_certified_run_with_losses(tmp_path):
    rec = obs.FlightRecorder(capacity=64)
    obs.install(rec)
    fake = tele.to_dict(tele.zeros())
    fake.update(residue=0, faults_dropped=2, faults_rejected=1)
    rec.record("telemetry", kind="fake", **fake)
    path = str(tmp_path / "dump.jsonl")
    rec.dump(path, reason="audit-test")
    report = obs_report.build_report(path)
    assert any(
        f["check"] == "residue-certificate-vs-losses"
        and f["severity"] == "error"
        for f in report["audit"]
    )


def test_report_audit_flags_frontier_stall(tmp_path):
    rec = obs.FlightRecorder(capacity=64)
    obs.install(rec)
    for lag in (3, 3, 4, 5):
        fake = tele.to_dict(tele.zeros())
        fake.update(frontier_lag=lag)
        rec.record("telemetry", kind="stalled", **fake)
    path = str(tmp_path / "dump.jsonl")
    rec.dump(path, reason="audit-test")
    report = obs_report.build_report(path)
    assert any(
        f["check"] == "frontier-lag-stall" for f in report["audit"]
    )


def test_dump_header_is_self_describing(tmp_path):
    rec = obs.FlightRecorder(capacity=8)
    rec.record("probe", seq=0)
    path = str(tmp_path / "dump.jsonl")
    rec.dump(path, reason="header-test")
    with open(path) as f:
        header = json.loads(f.readline())
    assert header["record"] == "flight_header"
    assert header["version"] == 1
    assert header["events"] == 1
    # Every registered event type's schema rides the header.
    assert "rank_evicted" in header["event_types"]
    assert header["event_types"]["wal_fsync"]["fields"] == [
        "watermark", "bytes",
    ]


def test_auto_dump_on_drain_refused(tmp_path):
    from crdt_tpu.scaleout import DrainRefused, ScaleoutMesh
    from crdt_tpu.scaleout.mesh_scale import DrainCertificate

    rec = obs.FlightRecorder(capacity=64)
    obs.install(rec)
    obs.configure_auto_dump(str(tmp_path))
    try:
        sm = ScaleoutMesh(4)
        stale = DrainCertificate(
            generation=7, rank=1, residue=0, packets_lost=0,
            lanes_unacked=0,
        )
        with pytest.raises(DrainRefused):
            sm.drain(1, certificate=stale)
    finally:
        obs.configure_auto_dump(None)
    dumps = [p for p in os.listdir(tmp_path) if "drain" in p]
    assert dumps, "DrainRefused must auto-dump the flight artifact"
    loaded = obs_report.load_dump(str(tmp_path / dumps[0]))
    types = [e["type"] for e in loaded["events"]]
    assert "drain_refused" in types and "auto_dump" in types


def test_auto_dump_on_dcn_exchange_failed(tmp_path):
    from crdt_tpu.faults.retry import (
        DcnExchangeFailed, RetryPolicy, with_retries,
    )

    rec = obs.FlightRecorder(capacity=64)
    obs.install(rec)
    obs.configure_auto_dump(str(tmp_path))
    try:
        with pytest.raises(DcnExchangeFailed):
            with_retries(
                lambda: (_ for _ in ()).throw(RuntimeError("down")),
                RetryPolicy(attempts=2, base_delay=0.0, jitter=0.0),
                op="test-op", sleep=lambda _s: None,
            )
    finally:
        obs.configure_auto_dump(None)
    dumps = [p for p in os.listdir(tmp_path) if "dcn" in p]
    assert dumps
    loaded = obs_report.load_dump(str(tmp_path / dumps[0]))
    types = [e["type"] for e in loaded["events"]]
    assert "dcn_retry" in types and "dcn_exchange_failed" in types


def test_auto_dump_on_recovery(tmp_path):
    from crdt_tpu import durability as du
    from crdt_tpu.ops import orswot as ops

    rec = obs.FlightRecorder(capacity=64)
    obs.install(rec)
    obs.configure_auto_dump(str(tmp_path / "flight"))
    os.makedirs(tmp_path / "flight")
    try:
        w = du.Wal(str(tmp_path / "wal"))
        empty = ops.empty(4, 2, deferred_cap=2)
        state, report = du.recover_state(
            str(tmp_path / "snap"), w, empty, kind="orswot", default=empty,
        )
        w.close()
    finally:
        obs.configure_auto_dump(None)
    dumps = os.listdir(tmp_path / "flight")
    assert any("recovery" in p for p in dumps)
    types = [e["type"] for e in rec.events()]
    assert "recovery" in types


def test_scaleout_transitions_drive_generation_key():
    from crdt_tpu.scaleout import ScaleoutMesh

    rec = obs.FlightRecorder(capacity=64)
    obs.install(rec)
    sm = ScaleoutMesh(4, live=range(3))
    g0 = rec.key()[0]
    sm.admit(1)
    assert rec.key()[0] == sm.generation > g0
    types = [e["type"] for e in rec.events()]
    assert "generation" in types and "scaleout_admit" in types


# ---- exporter edge cases (the ISSUE 12 satellite) -------------------------

def test_prometheus_label_escaping():
    tricky = 'kind"with\\quotes\nand newline'
    text = exporter.prometheus_text(
        snapshot={"counters": {}, "gauges": {}},
        telemetry={tricky: tele.zeros()},
    )
    line = next(
        ln for ln in text.splitlines()
        if ln.startswith("crdt_tpu_telemetry_merges{")
    )
    # One physical exposition line, with quote/backslash/newline all
    # escaped (json string escaping == Prometheus label escaping).
    assert '\\"' in line and "\\\\" in line and "\\n" in line
    assert "\n" not in line


def test_prometheus_histogram_exposition_conformance():
    out, _ = _mini_delta_gossip()
    tl = out[4]
    text = exporter.prometheus_text(
        snapshot={"counters": {}, "gauges": {}},
        telemetry={"k": tl},
    )
    lines = text.splitlines()
    name = "crdt_tpu_telemetry_hist_useful_bytes"
    type_lines = [
        ln for ln in lines if ln == f"# TYPE {name} histogram"
    ]
    assert len(type_lines) == 1
    buckets = [ln for ln in lines if ln.startswith(f"{name}_bucket")]
    assert len(buckets) == hist.NBUCKETS
    # le labels present, cumulative and nondecreasing, +Inf last.
    cums = [int(ln.rsplit(" ", 1)[1]) for ln in buckets]
    assert all(b >= a for a, b in zip(cums, cums[1:]))
    assert 'le="+Inf"' in buckets[-1]
    assert 'le="1"' in buckets[0]
    count_line = next(ln for ln in lines if ln.startswith(f"{name}_count"))
    assert int(count_line.rsplit(" ", 1)[1]) == cums[-1]
    sum_line = next(ln for ln in lines if ln.startswith(f"{name}_sum"))
    d = tele.to_dict(tl)
    assert float(sum_line.rsplit(" ", 1)[1]) == pytest.approx(
        d["hist_useful_bytes"]["total"]
    )
    assert cums[-1] == sum(d["hist_useful_bytes"]["counts"])


def test_jsonl_drain_idempotent_under_concurrent_spans(tmp_path):
    tele.drain_events()
    obs.install(obs.FlightRecorder(capacity=8))  # a listener: spans record
    n_threads, per_thread = 4, 50
    stop = threading.Event()

    def producer(t):
        for i in range(per_thread):
            with tele.span(f"obs.conc.{t}.{i}"):
                pass

    threads = [
        threading.Thread(target=producer, args=(t,))
        for t in range(n_threads)
    ]
    drained = []
    path = str(tmp_path / "drain.jsonl")

    def drainer():
        while not stop.is_set():
            exporter.drain_jsonl(path)

    d = threading.Thread(target=drainer)
    for t in threads:
        t.start()
    d.start()
    for t in threads:
        t.join()
    stop.set()
    d.join()
    exporter.drain_jsonl(path)  # final sweep
    with open(path) as f:
        for line in f:
            rec = json.loads(line)
            if rec.get("record") == "span":
                drained.append(rec["name"])
    want = {
        f"obs.conc.{t}.{i}"
        for t in range(n_threads) for i in range(per_thread)
    }
    # Exactly once each: no event lost to a concurrent drain, none
    # written twice.
    assert sorted(drained) == sorted(want)


def test_recorder_drain_idempotent_under_concurrent_record():
    rec = obs.FlightRecorder(capacity=100000)
    n_threads, per_thread = 4, 200

    def producer(t):
        for i in range(per_thread):
            rec.record("probe", seq=t * per_thread + i)

    drained = []
    stop = threading.Event()

    def drainer():
        while not stop.is_set():
            drained.extend(rec.drain())

    threads = [
        threading.Thread(target=producer, args=(t,))
        for t in range(n_threads)
    ]
    d = threading.Thread(target=drainer)
    for t in threads:
        t.start()
    d.start()
    for t in threads:
        t.join()
    stop.set()
    d.join()
    drained.extend(rec.drain())
    seqs = sorted(e["seq"] for e in drained)
    assert seqs == list(range(n_threads * per_thread))
    assert rec.dropped == 0


def test_health_snapshot_shape():
    metrics.reset()
    metrics.observe("scaleout.generation", 3.0)
    metrics.observe("scaleout.live_ranks", 6.0)
    metrics.observe("telemetry.k.frontier_lag", 2.0)
    metrics.observe("telemetry.k.residue", 1.0)
    metrics.observe("durability.wal.watermark", 41.0)
    metrics.count("faults.gave_up", 2)
    rec = obs.FlightRecorder(capacity=8)
    rec.record("probe", seq=0)
    obs.install(rec)
    h = exporter.health()
    assert h["generation"] == 3
    assert h["live_ranks"] == 6
    assert h["frontier_lag"] == 2
    assert h["residue"] == 1
    assert h["last_durable_watermark"] == 41
    assert h["faults_gave_up"] == 2
    assert h["flight"]["events"] == 1
    json.dumps(h)  # must be servable as-is
    obs.install(None)
    assert exporter.health()["flight"] is None


# ---- the obs static-check section ----------------------------------------

def test_obs_static_checks_clean():
    assert obs.static_checks() == []


def test_recorder_conformance_broken_twin_fires():
    from crdt_tpu.analysis import fixtures

    assert obs.recorder_conformant(obs.FlightRecorder)
    assert not obs.recorder_conformant(fixtures.recorder_drops_events)


def test_histogram_conformance_broken_twin_fires():
    from crdt_tpu.analysis import fixtures

    assert obs.histogram_conformant(hist.observe)
    assert not obs.histogram_conformant(fixtures.histogram_miscounts)


def test_unregistered_obs_event_fails_discovery(monkeypatch):
    from crdt_tpu.analysis import registry

    assert registry.unregistered_obs_events() == []
    monkeypatch.delitem(registry._OBS_EVENTS, "rank_evicted")
    missing = registry.unregistered_obs_events()
    assert any(name == "rank_evicted" for name, _ in missing)
    # The site path points at the emitter, not just the name.
    site = next(w for name, w in missing if name == "rank_evicted")
    assert "membership.py" in site
