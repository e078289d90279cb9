"""δ-subscription fan-out plane tests (ISSUE 16).

The contract under test, layer by layer:

1. **Wire** — the cohort encode/decode (ops/fanout_kernels.py, the
   PR 14 fused wire format generalized to B·E client lanes) is a
   bit-exact round-trip, and ``keep ∪ defer`` covers every changed
   lane (nothing silently unshippable).
2. **Replay** (the ISSUE 16 property) — a subscriber replaying its δ
   stream from its acked watermark is BIT-IDENTICAL to the served
   tenant, including across subscriber churn, split watermark buckets
   (slow ackers), eviction/re-warm of the tenant underneath, and the
   dead-subscriber snapshot+suffix resync.
3. **Crash** — the ``fanout.ack.*`` crashpoints fuzzed alongside the
   ``serve.evict.*`` ones: a kill at any ack/resync boundary amid
   tenant eviction/restore recovers by idempotent re-ack + re-push,
   and every client still converges bit-identically.
4. **Observability** — the fan-out telemetry fields ride the sidecar
   through the exporter schema and the counter-increment mapping
   (tools/obs_report.py's replay source of truth).
5. **Gates** — surface-registry coverage, the registered
   ``mesh_fanout_push`` entry point, and the broken-twin detector
   (``fixtures.fanout_skips_watermark_bucket`` must be caught).
"""

import os
import shutil
import sys
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from crdt_tpu import telemetry as tele
from crdt_tpu.analysis import fixtures
from crdt_tpu.analysis.registry import (
    get_decomposer,
    registered_entry_names,
    unregistered_fanout_surfaces,
)
from crdt_tpu.durability import crashpoints
from crdt_tpu.fanout import (
    ClientReplica,
    FanoutPlane,
    fanout_covers_cohorts,
    static_checks,
)
from crdt_tpu.ops import superblock as sb_ops
from crdt_tpu.ops.fanout_kernels import (
    cohort_deltas,
    cohort_push_bytes,
    cohort_wire_decode,
    cohort_wire_encode,
)
from crdt_tpu.parallel import anti_entropy, make_mesh, mesh_fanout_push
from crdt_tpu.parallel import fanout_push as fanout_push_mod
from crdt_tpu.serve import Evictor, IngestQueue, Superblock
from crdt_tpu.utils.metrics import metrics

CAPS = dict(n_elems=8, n_actors=2, deferred_cap=2)


def _trees_equal(a, b) -> bool:
    return all(
        bool(jnp.array_equal(x, y))
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))
    )


def _mask(*on, e=8):
    return np.isin(np.arange(e), on)


def _touch(sb, plane, tenant, adds):
    """Apply adds to one tenant row directly (restoring first if the
    evictor paged it out) and note the dirt to the plane."""
    lane = sb.ensure_resident(tenant)
    row = sb_ops.unpack(sb.state, lane)
    for actor, ctr, mask in adds:
        row, _ = sb.tk.apply_add(
            row, jnp.int32(actor), jnp.uint32(ctr), jnp.asarray(mask)
        )
    sb.state = sb_ops.write_rows(
        sb.state, jnp.asarray([lane], jnp.int32),
        jax.tree.map(lambda x: x[None], row),
    )
    sb.dirty[tenant] = True
    plane.note_dirty([tenant])


def _deliver(rep, clients, drop=()):
    """Hand every cohort payload to its members' replicas —
    subscribers in ``drop`` lose the delivery (the lossy transport the
    ack protocol must survive)."""
    for cp in rep.pushes:
        for s in cp.members:
            if int(s) not in drop and int(s) in clients:
                clients[int(s)].apply_wire(cp.wire, cp.to_ver)
    for rs in rep.resyncs:
        for s in rs.members:
            if int(s) not in drop and int(s) in clients:
                clients[int(s)].adopt(rs.state, rs.to_ver)


def _ack_all(plane, clients, only=None):
    ids = sorted(clients) if only is None else [int(i) for i in only]
    for i in ids:
        clients[i].ack()
    plane.ack(ids, versions=[clients[i].ver for i in ids])


def _converge(plane, clients, rounds=4):
    """Drive push/deliver/ack until quiescent — the recovery loop."""
    for _ in range(rounds):
        rep = plane.push()
        if rep.cohorts == 0 and not rep.resyncs:
            return
        _deliver(rep, clients)
        _ack_all(plane, clients)
    raise AssertionError("fan-out did not quiesce")


# ---- 1. the cohort wire ---------------------------------------------------

def test_cohort_wire_round_trip_bit_exact():
    tk = sb_ops.tenant_kind("orswot")
    m = lambda *on: jnp.asarray(_mask(*on))  # noqa: E731
    base = tk.empty(**CAPS)
    base, _ = tk.apply_add(base, jnp.int32(0), jnp.uint32(1), m(0, 1))
    live = base
    live, _ = tk.apply_add(live, jnp.int32(1), jnp.uint32(1), m(2, 5))
    live, _ = tk.apply_add(live, jnp.int32(0), jnp.uint32(2), m(7))
    # Three cohorts: changed, unchanged, changed-from-bot.
    bot = tk.empty(**CAPS)
    rows = jax.tree.map(
        lambda a, b, c: jnp.stack([a, b, c]), live, base, live
    )
    bases = jax.tree.map(
        lambda a, b, c: jnp.stack([a, b, c]), base, base, bot
    )
    d = cohort_deltas("orswot", rows, bases)
    lanes, res = get_decomposer("orswot").split(bases)
    base_ctr = jax.tree.leaves(lanes)[0]
    wire = cohort_wire_encode(d, base_ctr)
    assert bool(jnp.array_equal(wire.keep | wire.defer, d.valid)), (
        "keep ∪ defer must cover every changed lane"
    )
    assert not bool(jnp.any(wire.keep & wire.defer))
    assert int(jnp.sum(d.valid[1])) == 0  # unchanged cohort is silent
    rt = cohort_wire_decode(wire, base_ctr, res)
    assert bool(jnp.array_equal(d.valid, rt.valid))
    for x, y in zip(jax.tree.leaves(d.lanes), jax.tree.leaves(rt.lanes)):
        sel = d.valid.reshape(d.valid.shape + (1,) * (x.ndim - 2))
        assert bool(jnp.array_equal(
            jnp.where(sel, x, jnp.zeros_like(x)),
            jnp.where(sel, y, jnp.zeros_like(y)),
        ))
    for x, y in zip(
        jax.tree.leaves(d.residual), jax.tree.leaves(rt.residual)
    ):
        assert bool(jnp.array_equal(x, y))
    pb = np.asarray(cohort_push_bytes(wire))
    assert pb.shape == (3,) and pb[0] > 0 and pb[2] >= pb[0]


def test_push_bytes_beat_full_rows():
    """The wire price of a sparse δ must be far below shipping the
    row — the bench's ≥10× claim in miniature (one changed element
    out of a big row)."""
    caps = dict(n_elems=64, n_actors=4, deferred_cap=2)
    tk = sb_ops.tenant_kind("orswot")
    base = tk.empty(**caps)
    base, _ = tk.apply_add(
        base, jnp.int32(0), jnp.uint32(1),
        jnp.asarray(_mask(0, 1, 2, e=64)),
    )
    live, _ = tk.apply_add(
        base, jnp.int32(1), jnp.uint32(1), jnp.asarray(_mask(9, e=64))
    )
    rows = jax.tree.map(lambda a: a[None], live)
    bases = jax.tree.map(lambda a: a[None], base)
    d = cohort_deltas("orswot", rows, bases)
    lanes, _res = get_decomposer("orswot").split(bases)
    wire = cohort_wire_encode(d, jax.tree.leaves(lanes)[0])
    pb = float(cohort_push_bytes(wire)[0])
    row_bytes = sum(
        x.size * x.dtype.itemsize for x in jax.tree.leaves(live)
    )
    assert pb * 10 <= row_bytes, (
        f"δ push {pb}B does not beat full row {row_bytes}B by 10x"
    )


# ---- 2. the replay property ----------------------------------------------

def test_replay_bit_identical_across_churn_and_resync():
    """The ISSUE 16 acceptance property: every subscriber replaying
    its δ stream from its acked watermark lands bit-identical to the
    served tenant — across slow ackers (split watermark buckets),
    subscriber churn, lost deliveries, and a dead subscriber degrading
    to snapshot+suffix resync."""
    mesh = make_mesh(1, 1)
    sb = Superblock(4, mesh, kind="orswot", caps=CAPS)
    plane = FanoutPlane(sb, window_cap=2, dispatch_lanes=4)
    q = IngestQueue(sb, lanes=4, depth=16)
    ids = list(plane.subscribe([0, 0, 1, 1, 2, 2]))
    clients = {
        int(i): ClientReplica("orswot", sb.empty_row()) for i in ids
    }
    dead = int(ids[1])      # never acks -> must fall back to resync
    rng = np.random.default_rng(7)
    ctr = np.zeros((4, CAPS["n_actors"]), np.int64)
    for cycle in range(8):
        for _ in range(6):
            t = int(rng.integers(0, 3))
            a = int(rng.integers(0, CAPS["n_actors"]))
            ctr[t, a] += 1
            q.add(t, a, int(ctr[t, a]), rng.random(8) < 0.4)
        q.drain()
        plane.note_dirty([0, 1, 2])
        rep = plane.push()
        # Subscriber ids[2] misses every other delivery (lossy link).
        drop = {dead} | ({int(ids[2])} if cycle % 2 else set())
        _deliver(rep, clients, drop=drop)
        _ack_all(plane, clients, only=[
            i for i in ids if int(i) not in drop
        ])
        if cycle == 3:  # churn: one leaves, one joins mid-stream
            gone = int(ids.pop(3))
            plane.unsubscribe([gone])
            del clients[gone]
            new = int(plane.subscribe([0])[0])
            ids.append(new)
            clients[new] = ClientReplica("orswot", sb.empty_row())
    assert plane.resyncs_total > 0, "dead subscriber never resynced"
    _converge(plane, clients)
    for i in ids:
        t = int(plane.sub_tenant[int(i)])
        assert clients[int(i)].equals(sb.row(t)), (
            f"subscriber {i} of tenant {t} diverged after replay"
        )


def test_subscription_survives_eviction_and_rewarm():
    """The registry keys on TENANT ids, never lanes: evicting the
    tenant out from under live subscriptions (and re-warming it on the
    next push) preserves both the ack windows and the δ stream."""
    root = tempfile.mkdtemp(prefix="fanout-evict-")
    try:
        mesh = make_mesh(1, 1)
        sb = Superblock(2, mesh, kind="orswot", caps=CAPS)
        ev = Evictor(sb, root)
        plane = FanoutPlane(sb, evictor=ev, window_cap=4,
                            dispatch_lanes=2)
        ids = plane.subscribe([0, 0])
        clients = {
            int(i): ClientReplica("orswot", sb.empty_row()) for i in ids
        }
        _touch(sb, plane, 0, [(0, 1, _mask(0, 1))])
        _deliver(plane.push(), clients)
        _ack_all(plane, clients, only=[ids[0]])  # split watermarks
        assert int(plane.sub_ver[int(ids[0])]) == 1
        assert ev.evict([0]) == 1
        assert not sb.is_resident(0)
        # Push re-warms through the evictor: the lagging subscriber
        # (ids[1], still at ⊥) gets its δ from the restored row.
        rep = plane.push()
        assert sb.is_resident(0)
        _deliver(rep, clients)
        _ack_all(plane, clients)
        # More writes post-rewarm keep streaming to both watermarks.
        _touch(sb, plane, 0, [(1, 1, _mask(5))])
        _converge(plane, clients)
        for i in ids:
            assert clients[int(i)].equals(sb.row(0))
    finally:
        shutil.rmtree(root, ignore_errors=True)


def test_push_pressure_cannot_evict_mid_cycle():
    """Re-warming one cohort's tenant under FULL lanes must never page
    out a tenant this same cycle already warmed: the pressure batch is
    as wide as the lane pool, so without the mid-cycle pin the restore
    would free (and hand to another tenant) a lane the cycle is about
    to snapshot and dispatch from — shipping another tenant's row as
    this cohort's δ base."""
    root = tempfile.mkdtemp(prefix="fanout-pressure-")
    try:
        mesh = make_mesh(1, 1)
        sb = Superblock(3, mesh, kind="orswot", caps=CAPS, n_lanes=2)
        ev = Evictor(sb, root, pressure_batch=2)
        plane = FanoutPlane(sb, evictor=ev, window_cap=4,
                            dispatch_lanes=2)
        ids = plane.subscribe([0, 1])
        clients = {
            int(i): ClientReplica("orswot", sb.empty_row()) for i in ids
        }
        # t1 gets content, then is paged out (durable record on disk).
        _touch(sb, plane, 1, [(0, 1, _mask(1, 2))])
        assert ev.evict([1]) == 1
        # t0 and the unsubscribed filler t2 fill both lanes.
        _touch(sb, plane, 0, [(0, 1, _mask(0, 3))])
        sb.ensure_resident(2)
        assert sb.free_lanes == 0
        rep = plane.push()
        assert rep.cohorts == 2
        assert sb.is_resident(0) and sb.is_resident(1)
        assert not sb.is_resident(2)  # only the filler paid the pressure
        _deliver(rep, clients)
        _ack_all(plane, clients)
        for i, t in zip(ids, (0, 1)):
            assert clients[int(i)].equals(sb.row(t))
        # A fan-out restore is a touch: t1's recency is fresh, so the
        # next pressure batch does not immediately re-evict it.
        assert int(ev.last_touch[1]) == ev.clock
    finally:
        shutil.rmtree(root, ignore_errors=True)


def test_stale_duplicate_ack_cannot_regress_watermark():
    """Lossy transports reorder and duplicate acks: a stale ack must
    neither regress the watermark below the client's decode base nor
    clear the pending mark of a newer still-outstanding ship (which
    would gate out the genuine ack behind it)."""
    mesh = make_mesh(1, 1)
    sb = Superblock(2, mesh, kind="orswot", caps=CAPS)
    plane = FanoutPlane(sb, window_cap=4, dispatch_lanes=2)
    (s,) = plane.subscribe([0])
    clients = {int(s): ClientReplica("orswot", sb.empty_row())}
    c = clients[int(s)]
    _touch(sb, plane, 0, [(0, 1, _mask(0))])
    _deliver(plane.push(), clients)
    c.ack()
    plane.ack([s], versions=[c.ver])  # genuine v1 ack
    assert int(plane.sub_ver[int(s)]) == 1
    _touch(sb, plane, 0, [(0, 2, _mask(4))])
    _deliver(plane.push(), clients)
    c.ack()  # the client's decode base is now v2
    # Reordered duplicates of the old acks land first…
    plane.ack([s], versions=[0])
    assert int(plane.sub_ver[int(s)]) == 1   # no regress below v1
    plane.ack([s], versions=[1])
    assert int(plane.sub_ver[int(s)]) == 1
    assert int(plane.sub_pend[int(s)]) == 2  # v2 ship still pending
    # …then the genuine v2 ack must still promote.
    plane.ack([s], versions=[c.ver])
    assert int(plane.sub_ver[int(s)]) == 2
    assert int(plane.sub_pend[int(s)]) == -1
    # The δ stream continues bit-exact from the promoted base.
    _touch(sb, plane, 0, [(1, 1, _mask(6))])
    _converge(plane, clients)
    assert c.equals(sb.row(0))


def test_ack_scalar_versions_broadcasts():
    mesh = make_mesh(1, 1)
    sb = Superblock(2, mesh, kind="orswot", caps=CAPS)
    plane = FanoutPlane(sb, window_cap=4, dispatch_lanes=2)
    ids = plane.subscribe([0, 0])
    clients = {
        int(i): ClientReplica("orswot", sb.empty_row()) for i in ids
    }
    _touch(sb, plane, 0, [(0, 1, _mask(0, 1))])
    _deliver(plane.push(), clients)
    for c in clients.values():
        c.ack()
    plane.ack(ids, versions=1)  # one scalar fans out to every id
    assert all(int(plane.sub_ver[int(i)]) == 1 for i in ids)
    assert all(int(plane.sub_pend[int(i)]) == -1 for i in ids)


# ---- 3. crashpoint fuzz ---------------------------------------------------

FANOUT_CRASHPOINTS = (
    "fanout.ack.pre_promote",
    "fanout.ack.post_promote",
    "fanout.ack.pre_resync",
    "serve.evict.pre_persist",
    "serve.evict.post_persist_pre_clear",
)


def test_ack_crashpoint_fuzz_with_eviction():
    """Kill at every ack/resync boundary (and inside the evict path
    underneath) — recovery is idempotent re-ack plus re-push, and
    every subscriber still converges bit-identically to the served
    tenant."""
    box = {}
    dirs = []

    def crash_run(name):
        box.clear()
        root = tempfile.mkdtemp(prefix="fanout-fuzz-")
        dirs.append(root)
        mesh = make_mesh(1, 1)
        sb = Superblock(
            2, mesh, kind="orswot",
            caps=dict(n_elems=4, n_actors=2, deferred_cap=2),
        )
        ev = Evictor(sb, root)
        plane = FanoutPlane(sb, evictor=ev, window_cap=1,
                            dispatch_lanes=2)
        ids = plane.subscribe([0, 0])
        clients = {
            int(i): ClientReplica("orswot", sb.empty_row()) for i in ids
        }
        box.update(plane=plane, sb=sb, clients=clients, ids=ids)
        m4 = lambda *on: _mask(*on, e=4)  # noqa: E731
        _touch(sb, plane, 0, [(0, 1, m4(0, 1))])
        rep = plane.push()
        _deliver(rep, clients)
        _ack_all(plane, clients, only=[ids[0]])  # ids[1] lags behind
        ev.evict([0])  # crosses the serve.evict.* points
        _touch(sb, plane, 0, [(1, 1, m4(2))])
        rep = plane.push()   # re-warm; ids[1] gap 2 > window -> resync
        _deliver(rep, clients)
        _ack_all(plane, clients)

    def recov():
        plane, clients = box["plane"], box["clients"]
        # Recovery: idempotent client-version re-ack, then re-push.
        _ack_all(plane, clients)
        _converge(plane, clients)
        got = [clients[int(i)].state for i in box["ids"]]
        want = [box["sb"].row(0)] * len(got)
        return got, want

    def equal(a, b):
        return all(_trees_equal(x, y) for x, y in zip(a, b))

    failures = crashpoints.fuzz(
        crash_run, recov, equal, names=FANOUT_CRASHPOINTS
    )
    for d in dirs:
        shutil.rmtree(d, ignore_errors=True)
    assert not failures, failures


# ---- 4. telemetry ---------------------------------------------------------

def test_fanout_telemetry_schema_and_counters():
    mesh = make_mesh(1, 1)
    sb = Superblock(2, mesh, kind="orswot", caps=CAPS)
    plane = FanoutPlane(sb, window_cap=4, dispatch_lanes=2)
    ids = plane.subscribe([0, 0, 1])
    _touch(sb, plane, 0, [(0, 1, _mask(0, 3))])
    _touch(sb, plane, 1, [(1, 1, _mask(2))])
    rep = plane.push(telemetry=True)
    tel = rep.telemetry
    assert tel is not None
    d = tele.to_dict(tel)
    assert d["subscribers_live"] == 3
    assert d["cohorts_per_dispatch"] == 2
    assert d["delta_push_bytes"] > 0
    assert d["resync_fallbacks"] == 0
    hist = d["hist_push_bytes"]
    assert sum(hist["counts"]) == 2 and hist["total"] > 0
    # Deliveries are priced per subscriber: tenant 0's cohort has two
    # members, so its cohort price counts twice in the byte counter.
    per_cohort = {
        cp.tenant: float(cohort_push_bytes(cp.wire)[0])
        for cp in rep.pushes
    }
    want = 2 * per_cohort[0] + per_cohort[1]
    assert d["delta_push_bytes"] == pytest.approx(want)
    from crdt_tpu import exporter
    sys.path.insert(
        0,
        os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "tools"),
    )
    import check_telemetry_schema
    assert check_telemetry_schema.validate_record(
        exporter.telemetry_record("fanout", tel)
    ) == []
    inc = tele.counter_increments("fanout", d)
    assert inc["telemetry.fanout.fanout.cohorts_per_dispatch"] == 2
    assert inc["telemetry.fanout.fanout.delta_push_bytes"] == int(want)
    assert inc["telemetry.fanout.fanout.resync_fallbacks"] == 0
    assert any(
        k.startswith("telemetry.fanout.hist.push_bytes.") for k in inc
    )


def test_resync_counts_fallbacks_and_bootstrap_bytes():
    mesh = make_mesh(1, 1)
    sb = Superblock(2, mesh, kind="orswot", caps=CAPS)
    plane = FanoutPlane(sb, window_cap=1, dispatch_lanes=2)
    ids = plane.subscribe([0, 0])
    clients = {
        int(i): ClientReplica("orswot", sb.empty_row()) for i in ids
    }
    for k in range(3):  # ids[1] never acks; gap outruns the window
        _touch(sb, plane, 0, [(0, k + 1, _mask(k))])
        rep = plane.push(telemetry=True)
        _deliver(rep, clients)
        _ack_all(plane, clients, only=[ids[0]])
    assert rep.resyncs, "dead subscriber never degraded to resync"
    d = tele.to_dict(rep.telemetry)
    assert d["resync_fallbacks"] >= 1
    assert d["bootstrap_bytes"] > 0
    rs = rep.resyncs[0]
    assert rs.report.ratio > 0
    _ack_all(plane, clients)
    _converge(plane, clients)
    for i in ids:
        assert clients[int(i)].equals(sb.row(0))


# ---- 5. gates -------------------------------------------------------------

def test_fanout_surfaces_registered_and_entry_point_known():
    assert unregistered_fanout_surfaces() == []
    assert "mesh_fanout_push" in registered_entry_names()


def test_detector_and_broken_twin():
    assert fanout_covers_cohorts(lambda plane: plane.push())
    assert not fanout_covers_cohorts(
        fixtures.fanout_skips_watermark_bucket
    )


@pytest.mark.slow
def test_fanout_static_checks_clean():
    assert static_checks() == []


def test_mesh_fanout_push_empty_lanes_are_free():
    """-1 dispatch lanes price zero bytes and stay silent on the
    wire."""
    mesh = make_mesh(1, 1)
    tk = sb_ops.tenant_kind("orswot")
    state = tk.empty(**CAPS, batch=(2,))
    row, _ = tk.apply_add(
        tk.empty(**CAPS), jnp.int32(0), jnp.uint32(1),
        jnp.asarray(_mask(0)),
    )
    state = sb_ops.write_rows(
        state, jnp.asarray([0], jnp.int32),
        jax.tree.map(lambda x: x[None], row),
    )
    bases = tk.empty(**CAPS, batch=(2,))
    idx = jnp.asarray([0, -1], jnp.int32)
    wire, pb = mesh_fanout_push(state, bases, idx, mesh)
    pb = np.asarray(pb)
    assert pb[0] > 0 and pb[1] == 0
    assert not bool(jnp.any(wire.valid[1]))


# ---- 6. the dispatch's wire specs, once per compiled program -------------

def _push_args(caps=CAPS, lanes=2, tenants=2):
    """A superblock with one live row, ``lanes`` base rows, and a
    dispatch that reads the live row in lane 0 (the rest empty)."""
    tk = sb_ops.tenant_kind("orswot")
    state = tk.empty(**caps, batch=(tenants,))
    row, _ = tk.apply_add(
        tk.empty(**caps), jnp.int32(0), jnp.uint32(1),
        jnp.asarray(_mask(0, 3, e=caps["n_elems"])),
    )
    state = sb_ops.write_rows(
        state, jnp.asarray([0], jnp.int32),
        jax.tree.map(lambda x: x[None], row),
    )
    bases = tk.empty(**caps, batch=(lanes,))
    idx = np.full(lanes, -1, np.int32)
    idx[0] = 0
    wts = np.zeros(lanes, np.float32)
    wts[0] = 3.0
    return state, bases, jnp.asarray(idx), jnp.asarray(wts)


@pytest.fixture
def push_traces(monkeypatch):
    """Against an empty program cache, count the traces of
    ``mesh_fanout_push``'s per-device core (abstract and compiled
    alike) and the ``fanout_push.spec_builds`` counter."""
    monkeypatch.setattr(anti_entropy, "_FN_CACHE", {})
    traced = []
    core = fanout_push_mod._local_push

    def counting(*a, **k):
        traced.append(a[0])
        return core(*a, **k)

    monkeypatch.setattr(fanout_push_mod, "_local_push", counting)

    def counts():
        builds = metrics.snapshot()["counters"].get(
            "fanout_push.spec_builds", 0
        )
        return len(traced), builds

    return counts


@pytest.mark.parametrize("telemetry", [False, True])
def test_fanout_push_repeat_call_traces_nothing(push_traces, telemetry):
    """The second call at one signature reuses the compiled program and
    its wire specs: no abstract trace of the core, no spec build."""
    mesh = make_mesh(1, 1)
    state, bases, idx, wts = _push_args()
    t0, b0 = push_traces()
    mesh_fanout_push(state, bases, idx, mesh, weights=wts,
                     telemetry=telemetry)
    t1, b1 = push_traces()
    assert b1 == b0 + 1 and t1 > t0
    for _ in range(2):
        mesh_fanout_push(state, bases, idx, mesh, weights=wts,
                         telemetry=telemetry)
    assert push_traces() == (t1, b1)


@pytest.mark.parametrize("change", ["dispatch_width", "widened_caps"])
def test_fanout_push_new_signature_builds_once(push_traces, change):
    """A new dispatch width, or caps widened under the superblock, is a
    new program: its specs are built exactly once more, with the same
    tracing as the first build, and then reused."""
    mesh = make_mesh(1, 1)
    t0, b0 = push_traces()
    state, bases, idx, wts = _push_args()
    mesh_fanout_push(state, bases, idx, mesh, weights=wts)
    t1, b1 = push_traces()
    if change == "dispatch_width":
        args = _push_args(lanes=4)
    else:
        args = _push_args(caps=dict(CAPS, n_elems=16))
    for _ in range(2):
        mesh_fanout_push(*args[:3], mesh, weights=args[3])
        assert push_traces() == (t1 + (t1 - t0), b1 + 1)


@pytest.mark.parametrize("telemetry", [False, True])
def test_fanout_push_cached_call_matches_a_fresh_build(push_traces,
                                                      telemetry):
    """A call served from the program cache ships the same wire and
    byte prices, bit for bit, as one made after the cache is cleared."""
    mesh = make_mesh(1, 1)
    state, bases, idx, wts = _push_args(lanes=4)

    def call():
        out = mesh_fanout_push(state, bases, idx, mesh, weights=wts,
                               telemetry=telemetry)
        return jax.tree.map(np.asarray, (out[0], out[1]))

    _, b0 = push_traces()
    call()
    cached = call()
    anti_entropy._FN_CACHE.clear()
    fresh = call()
    assert push_traces()[1] == b0 + 2
    for x, y in zip(jax.tree.leaves(cached), jax.tree.leaves(fresh)):
        assert x.dtype == y.dtype and x.shape == y.shape
        assert x.tobytes() == y.tobytes()
    assert np.asarray(cached[1])[0] > 0
