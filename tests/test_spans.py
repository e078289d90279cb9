"""Program spans (``crdt_tpu.telemetry.span``): recorded only while a
profiler session, a JSONL path or a flight recorder listens, with the
parent / self-time / attribute-sum arithmetic of ``span_stats``, and
placed at the layer boundaries of the serve loop, the fan-out push and
the anti-entropy entry points."""

import glob
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from crdt_tpu import obs, telemetry as tele
from crdt_tpu.utils.metrics import metrics

CAPS = dict(n_elems=8, n_actors=2, deferred_cap=2)


@pytest.fixture(autouse=True)
def _nobody_listens():
    """Every test starts with no recorder, no JSONL path and an empty
    aggregate, and leaves them so."""
    prev = obs.install(None)
    tele.configure_tracing(None)
    tele.reset_span_stats()
    tele.drain_events()
    yield
    obs.install(prev)
    tele.configure_tracing(None)
    tele.reset_span_stats()
    tele.drain_events()


def _host_annotations(trace_dir):
    """Names of the events on the host planes of the session's trace."""
    from jax.profiler import ProfileData

    [path] = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                    "*.xplane.pb"))
    pd = ProfileData.from_file(path)
    return {e.name for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events}


def test_span_records_nothing_without_a_listener():
    assert not jax.profiler.TraceAnnotation.is_enabled()
    with tele.span("spans_test.off", lanes=4) as sp:
        sp.attrs["live"] = 1
        with tele.span("spans_test.off_child"):
            pass
    assert tele.span_stats() == {}
    assert tele.drain_events() == []
    gauges = metrics.snapshot()["gauges"]
    assert "spans_test.off_seconds" not in gauges
    assert "spans_test.off_child_seconds" not in gauges


def test_recording_starts_and_stops_with_a_profiler_session(tmp_path):
    with tele.span("spans_test.before"):
        pass
    with jax.profiler.trace(str(tmp_path)):
        with tele.span("spans_test.during"):
            pass
    with tele.span("spans_test.after"):
        pass
    assert set(tele.span_stats()) == {"spans_test.during"}
    assert [e["name"] for e in tele.drain_events()] == ["spans_test.during"]
    assert "spans_test.during_seconds" in metrics.snapshot()["gauges"]
    # The span is an annotation on the profiler's host plane.
    names = _host_annotations(str(tmp_path))
    assert "spans_test.during" in names
    assert "spans_test.before" not in names and "spans_test.after" not in names


@pytest.mark.parametrize("listener", ["jsonl", "recorder"])
def test_nested_span_parent_self_time_and_attribute_sums(tmp_path, listener):
    if listener == "jsonl":
        tele.configure_tracing(str(tmp_path / "spans.jsonl"))
    else:
        obs.install(obs.FlightRecorder(capacity=8))
    with tele.span("t.outer"):
        for _ in range(2):
            with tele.span("t.dispatch", lanes=8) as sp:
                sp.attrs["live"] = 3
                with tele.span("t.stack"):
                    time.sleep(0.002)
        time.sleep(0.002)
    st = tele.span_stats()
    outer, disp, stack = st["t.outer"], st["t.dispatch"], st["t.stack"]
    assert (outer["n"], disp["n"], stack["n"]) == (1, 2, 2)
    assert disp["attrs"] == {"lanes": 16, "live": 6}
    assert outer["attrs"] == {} and stack["attrs"] == {}
    assert outer["self_s"] == pytest.approx(outer["total_s"] - disp["total_s"])
    assert disp["self_s"] == pytest.approx(disp["total_s"] - stack["total_s"])
    assert stack["self_s"] == stack["total_s"] >= 0.004
    assert outer["self_s"] >= 0.002
    assert sum(disp["durations_s"]) == pytest.approx(disp["total_s"])
    parents = {e["name"]: e["parent"] for e in tele.drain_events()}
    assert parents == {"t.outer": None, "t.dispatch": "t.outer",
                       "t.stack": "t.dispatch"}
    tele.reset_span_stats()
    assert tele.span_stats() == {}


def test_span_durations_ring_keeps_the_newest_calls():
    obs.install(obs.FlightRecorder(capacity=8))
    for i in range(4100):
        with tele.span("t.many", i=i):
            pass
    tele.drain_events()
    st = tele.span_stats()["t.many"]
    assert st["n"] == 4100 and len(st["durations_s"]) == 4096
    assert st["attrs"]["i"] == sum(range(4100))
    assert sum(st["durations_s"]) <= st["total_s"]


def _serving_round(tmp_path):
    """A tiny store driven through two loop steps and one push."""
    from crdt_tpu.fanout import FanoutPlane
    from crdt_tpu.parallel import make_mesh
    from crdt_tpu.serve import Evictor, IngestQueue, ServeLoop, Superblock
    from crdt_tpu.serve.wal import ServeWal

    mesh = make_mesh(1, 1)
    sb = Superblock(4, mesh, kind="orswot", caps=dict(CAPS))
    ev = Evictor(sb, str(tmp_path / "snap"))
    wal = ServeWal(str(tmp_path / "wal"))
    q = IngestQueue(sb, lanes=4, depth=4, evictor=ev, wal=wal)
    loop = ServeLoop(q)
    plane = FanoutPlane(sb, evictor=ev, window_cap=2, dispatch_lanes=4)
    plane.subscribe([0, 1, 2])
    for t in range(3):
        q.add(t, 0, 1, np.arange(8) < t + 1)
    loop.step()
    loop.step()
    plane.note_dirty([0, 1, 2])
    rep = plane.push()
    wal.close()
    return rep


def test_entry_points_emit_their_spans_with_the_right_parents(tmp_path):
    from crdt_tpu.ops import orswot as ops
    from crdt_tpu.parallel import make_mesh, mesh_fold
    from crdt_tpu.parallel.delta import mesh_delta_gossip

    mesh = make_mesh(2, 1)
    state = ops.empty(8, 2, 2, batch=(2,))
    dirty = jnp.zeros((2, 8), bool)
    fctx = jnp.zeros((2, 8, 2), jnp.uint32)
    with jax.profiler.trace(str(tmp_path / "trace")):
        rep = _serving_round(tmp_path)
        jax.block_until_ready(mesh_fold(state, mesh))
        jax.block_until_ready(mesh_delta_gossip(state, dirty, fctx, mesh))
    assert rep.cohorts == 3
    events = tele.drain_events()
    parents = {}
    for e in events:
        parents.setdefault(e["name"], set()).add(e["parent"])
    want = {
        "serve.step": {None},
        "serve.assemble": {"serve.step"},
        "serve.wal_commit": {"serve.step"},
        "serve.finish": {"serve.step"},
        "serve.persist_drain": {"serve.step"},
        "serve.issue": {"serve.step"},
        "fanout.push": {None},
        "fanout.warm": {"fanout.push"},
        "fanout.snapshot": {"fanout.push"},
        "fanout.cohorts": {"fanout.push"},
        "fanout.dispatch": {"fanout.push"},
        "fanout.stack": {"fanout.dispatch"},
        "fanout.wire_fetch": {"fanout.dispatch"},
        "fanout.deliver": {"fanout.dispatch"},
        "ae.fold": {None},
        "ae.fold.prep": {"ae.fold"},
        "ae.fold.dispatch": {"ae.fold"},
        "ae.fold.wait": {"ae.fold"},
        "ae.delta_gossip": {None},
        "ae.delta_gossip.prep": {"ae.delta_gossip"},
        "ae.delta_gossip.dispatch": {"ae.delta_gossip"},
        "ae.delta_gossip.wait": {"ae.delta_gossip"},
    }
    assert {k: parents.get(k) for k in want} == want
    assert not [n for n in parents if n.startswith("span.")]
    st = tele.span_stats()
    assert st["serve.step"]["n"] == 2
    assert st["fanout.dispatch"]["attrs"] == {"lanes": 4, "live": 3}
    # Every span is an annotation on the profiler's host plane.
    assert set(want) <= _host_annotations(str(tmp_path / "trace"))


def test_map_fold_spans_record_under_a_profiler_and_nothing_otherwise(tmp_path):
    """``mesh_fold_map``'s entry span and its prep / dispatch / wait
    children, with the padding inside ``ae.map_fold.prep``; off a profiler
    session the same calls record nothing."""
    from crdt_tpu.ops import map as map_ops
    from crdt_tpu.parallel import make_mesh
    from crdt_tpu.parallel.anti_entropy import mesh_fold_map

    mesh = make_mesh(2, 1)
    state = map_ops.empty(8, 2, sibling_cap=2, deferred_cap=2, batch=(3,))  # padded to 4
    before = metrics.snapshot()["gauges"].get("ae.map_fold_seconds", {}).get("n", 0)
    jax.block_until_ready(mesh_fold_map(state, mesh))
    assert tele.span_stats() == {} and tele.drain_events() == []
    assert metrics.snapshot()["gauges"].get("ae.map_fold_seconds", {}).get("n", 0) == before
    with jax.profiler.trace(str(tmp_path / "trace")):
        out, of = mesh_fold_map(state, mesh)
    assert not np.asarray(of).any() and out.top.shape == (2,)
    parents = {}
    for e in tele.drain_events():
        parents.setdefault(e["name"], set()).add(e["parent"])
    want = {"ae.map_fold": {None}, "ae.map_fold.prep": {"ae.map_fold"},
            "ae.map_fold.dispatch": {"ae.map_fold"}, "ae.map_fold.wait": {"ae.map_fold"}}
    assert parents == want
    st = tele.span_stats()
    assert all(st[name]["n"] == 1 for name in want)
    kids = sum(st[name]["total_s"] for name in want if name != "ae.map_fold")
    assert st["ae.map_fold"]["self_s"] == pytest.approx(st["ae.map_fold"]["total_s"] - kids)
    assert set(want) <= _host_annotations(str(tmp_path / "trace"))
