"""crdt_tpu.durability — crash-consistent durability.

PR 8 made the *mesh* survive lost packets, corruption, and dead ranks;
this package makes the *host process* survive. Four cooperating pieces
(see each module's docstring):

- :mod:`.wal` — a host-side append-only **write-ahead δ-log**: records
  are join-irreducible decomposition lanes (``delta_opt.decompose``,
  minted over the last logged state), framed with length + CRC so a
  torn tail is detected and truncated on open, with segment rotation
  and an ``every_n`` / ``on_round`` fsync policy. Accepted via ``wal=``
  on ``run_delta_ring`` + the four ``mesh_delta_gossip*`` flavors,
  ``delta_gossip_elastic``, and ``mesh_stream_fold*`` (which also
  persists ``StreamInterrupted`` resume state).
- :mod:`.snapshot` — **generational atomic snapshots** layered on
  ``checkpoint.py``: per-array content checksums in a manifest,
  fsync-before-rename, manifest-commit-last, retain-K generations,
  compact-on-save; snapshot + WAL-suffix replay reconstructs state
  bit-identically.
- :mod:`.recover` — the **recovery driver**: newest VALID generation
  (corrupt manifests/arrays fall back a generation with a longer
  replay), WAL suffix replayed through one memoised jitted scan-fold
  (the ``delta_opt/heal.py`` pattern), plus the **log-suffix rejoin**
  that upgrades PR 8's membership contract: a restarted rank recovers
  locally and ships snapshot-generation + log-suffix divergence lanes
  instead of receiving full state (tests/test_durability.py pins the
  byte win).
- :mod:`.crashpoints` — **deterministic crash-point injection**: every
  durability I/O boundary registers a named crashpoint; the fuzz loop
  kills at each one, recovers, and asserts bit-identity with the
  uninterrupted run (registration is the coverage contract).

Plus :func:`static_checks` — the ``durability`` section of
tools/run_static_checks.py: crashpoint coverage, the kill-then-recover
contract over every crashpoint, and the broken-twin detector gates
(the no-fsync WAL and the checksum-ignoring loader in
``analysis.fixtures`` must each be caught).
"""

from __future__ import annotations

from typing import List

from . import crashpoints
from .crashpoints import SimulatedCrash
from .recover import (
    RecoveryReport,
    RejoinReport,
    load_stream_resume,
    recover_model,
    recover_state,
    rejoin,
    replay,
)
from .snapshot import SnapshotCorrupt, loader_detects_corruption
from .wal import Wal, WalCorrupt, fsync_honored

from . import recover, snapshot, wal  # noqa: F401  (module re-exports)


def _probe_states(n: int = 6):
    """Tiny host pytrees for the static-check workload (full-state
    records: no registered kind or kernel compile needed — the δ-replay
    fuzz over real decompositions lives in tests/test_durability.py)."""
    import numpy as np

    return [
        {
            "top": np.arange(8, dtype=np.uint32) + i,
            "ctr": (np.arange(24, dtype=np.uint32).reshape(8, 3) * (i + 1)),
        }
        for i in range(n)
    ]


def _probe_workload(root: str, states) -> None:
    """The canonical micro-workload — crosses EVERY registered
    crashpoint when run uninterrupted: tiny segments force WAL
    rotation, retain=1 with repeated saves forces pruning, one
    serving-tier tenant persist/restore crosses the ``serve.evict.*``
    / ``serve.restore.*`` boundaries (crdt_tpu/serve/evict.py — the
    evict write-ordering the fuzz loop must be able to kill inside),
    one fan-out subscribe→push→ack round crosses the
    ``fanout.ack.*`` boundaries (crdt_tpu/fanout/plane.py — promote
    and resync, the subscription state the fuzz loop kills inside),
    and one WAL-logged pipelined flush + background persist drain
    crosses the ``serve.wal.*`` / ``serve.dispatch.*`` /
    ``serve.persist.*`` boundaries (crdt_tpu/serve/wal.py + loop.py).
    The serve and fanout tails never touch the main wal/snap dirs, so
    ``_probe_recover``'s last-durable-record contract is unchanged."""
    import os

    import jax
    import numpy as np

    w = Wal(
        os.path.join(root, "wal"), fsync="every_n", every_n=1,
        segment_bytes=256,
    )
    sdir = os.path.join(root, "snap")
    for i, s in enumerate(states[1:], 1):
        # jax.tree leaf order (the replay unflatten convention).
        w.append(
            {"rtype": "state", "kind": "probe"}, jax.tree.leaves(s),
        )
        if i % 2 == 0:
            snapshot.save_state(
                sdir, "probe", s, wal_seq=w.last_seq, retain=1,
            )
    w.close()
    from ..serve.evict import persist_tenant, restore_tenant

    persist_tenant(os.path.join(root, "serve"), "probe", 0, states[-1])
    restore_tenant(os.path.join(root, "serve"), "probe", 0, states[0])
    # The fan-out tail: window_cap=0 degrades the one dirty ⊥-watermark
    # subscriber straight to resync (fanout.ack.pre_resync — no wire
    # dispatch to compile), then the genuine ack promotes its watermark
    # (fanout.ack.pre_promote / post_promote). Host-side registry state
    # only — nothing durable, the recovery contract is untouched.
    from ..fanout import FanoutPlane
    from ..parallel import make_mesh
    from ..serve import Superblock

    sb = Superblock(
        1, make_mesh(1, 1), kind="orswot",
        caps=dict(n_elems=4, n_actors=2, deferred_cap=2),
    )
    plane = FanoutPlane(sb, window_cap=0, dispatch_lanes=1)
    ids = plane.subscribe([0])
    plane.note_dirty([0])
    plane.push()
    plane.ack(ids)
    # The pipelined-serving tail (ISSUE 18): one WAL-logged flush
    # crosses serve.wal.pre_log / serve.wal.post_log_pre_dispatch /
    # serve.dispatch.post_scatter_pre_ack, then one background persist
    # drain crosses serve.persist.background_drain. Writes only under
    # root/serve* (its own ServeWal dir + evictor tier), so
    # ``_probe_recover``'s last-durable-record contract over root/wal +
    # root/snap is untouched.
    from ..serve import (
        BackgroundPersister, Evictor, IngestQueue, ServeWal,
    )

    swal = ServeWal(os.path.join(root, "serve_wal"))
    try:
        ev = Evictor(sb, os.path.join(root, "serve_evict"))
        q = IngestQueue(sb, lanes=1, depth=2, evictor=ev, wal=swal)
        q.add(0, 0, 1, np.isin(np.arange(4), [0]))
        q.drain()
        bp = BackgroundPersister(ev, batch=1)
        bp.enqueue([0])
        bp.drain()
    finally:
        swal.close()


def _probe_recover(root: str, states):
    """Recovery for the probe workload: reopen the WAL (torn-tail
    truncation happens here), recover snapshot + suffix, and return
    the pair ``(recovered, expected)`` — expected is the state of the
    last DURABLE record (seq indexes the states list by construction).
    """
    import os

    w = Wal(os.path.join(root, "wal"))
    try:
        got, _ = recover_state(
            os.path.join(root, "snap"), w, states[0], kind="probe",
            default=states[0],
        )
        return got, states[w.last_seq]
    finally:
        w.close()


def static_checks() -> List:
    """The ``durability`` static-check section (Finding list, empty =
    clean):

    1. **crashpoint coverage** — every registered crashpoint must be
       crossed by the canonical micro-workload (a dead crashpoint is an
       I/O boundary the fuzz loop silently stopped exercising);
    2. **recovery contract** — for EVERY crashpoint, kill-then-recover
       on the probe workload lands exactly the last durable record,
       bit-identically (the full per-kind δ-decomposition matrix runs
       in tests/test_durability.py across tiers);
    3. **fsync policy** — ``wal.fsync_honored`` must pass the honest
       :class:`Wal` and FAIL the no-fsync broken twin
       (``analysis.fixtures.wal_skips_fsync``);
    4. **loader integrity** — ``snapshot.loader_detects_corruption``
       must pass the honest ``load_newest`` and FAIL the
       checksum-ignoring twin
       (``analysis.fixtures.snapshot_load_unchecked``).
    """
    import shutil
    import tempfile

    import numpy as np

    from ..analysis import fixtures
    from ..analysis.report import Finding

    findings: List[Finding] = []
    states = _probe_states()

    def equal(a, b):
        import jax

        xa = [np.asarray(x) for x in jax.tree.leaves(a)]
        xb = [np.asarray(x) for x in jax.tree.leaves(b)]
        return len(xa) == len(xb) and all(
            x.shape == y.shape and bool((x == y).all())
            for x, y in zip(xa, xb)
        )

    # 1. coverage
    tmp = tempfile.mkdtemp(prefix="durability-gate-")
    try:
        with crashpoints.recording() as crossed:
            _probe_workload(tmp, states)
        missing = sorted(set(crashpoints.registered()) - crossed)
        for name in missing:
            findings.append(Finding(
                "crashpoint-coverage", name,
                "registered crashpoint never crossed by the canonical "
                "workload — the fuzz loop cannot exercise this I/O "
                "boundary",
            ))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    # 2. kill-then-recover at every crashpoint — routed through the
    # one fuzz engine (crashpoints.fuzz), same as the test matrix.
    box: dict = {}
    dirs: List[str] = []

    def crash_run(name):
        box["dir"] = tempfile.mkdtemp(prefix="durability-fuzz-")
        dirs.append(box["dir"])
        _probe_workload(box["dir"], states)

    def recov():
        return _probe_recover(box["dir"], states)

    try:
        for failure in crashpoints.fuzz(crash_run, recov, equal):
            findings.append(Finding(
                "recovery-contract", failure.split(":", 1)[0], failure,
            ))
    finally:
        for d in dirs:
            shutil.rmtree(d, ignore_errors=True)

    # 3. fsync policy + broken twin
    tmp = tempfile.mkdtemp(prefix="durability-fsync-")
    try:
        if not fsync_honored(Wal, tmp):
            findings.append(Finding(
                "fsync-policy", "wal.Wal",
                "the honest WAL issued fewer fsync barriers than its "
                "every_n=1 policy promises — appends are not durable "
                "across power loss",
            ))
        if fsync_honored(fixtures.wal_skips_fsync, tmp):
            findings.append(Finding(
                "broken-fixture-missed", "wal_skips_fsync",
                "the no-fsync WAL twin PASSED the fsync detector — the "
                "durability gate is not actually firing",
            ))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    # 4. loader integrity + broken twin
    if not loader_detects_corruption(
        lambda d, t: snapshot.load_newest(d, t)
    ):
        findings.append(Finding(
            "loader-integrity", "snapshot.load_newest",
            "a flipped payload byte loaded without complaint — rotten "
            "state would reach a resuming mesh",
        ))
    if loader_detects_corruption(fixtures.snapshot_load_unchecked):
        findings.append(Finding(
            "broken-fixture-missed", "snapshot_load_unchecked",
            "the checksum-ignoring loader twin PASSED the corruption "
            "detector — the integrity gate is not actually firing",
        ))
    return findings


__all__ = [
    "RecoveryReport", "RejoinReport", "SimulatedCrash", "SnapshotCorrupt",
    "Wal", "WalCorrupt", "crashpoints", "fsync_honored",
    "load_stream_resume", "loader_detects_corruption", "recover",
    "recover_model", "recover_state", "rejoin", "replay", "snapshot",
    "static_checks", "wal",
]
