"""Crash recovery: newest valid generation + WAL-suffix replay.

The recovery invariant the crashpoint fuzz pins: for ANY kill point in
the durability I/O, ``recover_state`` (or ``recover_model``) applied
to the surviving files yields exactly the last state whose WAL record
was durable — bit-identically — and the caller resumes from there.
Mechanics:

1. ``snapshot.load_newest`` walks generations newest-first; a corrupt
   newest generation FALLS BACK one generation (the older manifest's
   smaller ``wal_seq`` just means a longer replay suffix) —
   ``durability.snapshot_fallback`` counts each skip;
2. the WAL suffix (``seq > generation.wal_seq``) replays through ONE
   memoised jitted scan-fold per (kind, shape signature) — the
   ``delta_opt/heal.py`` dispatch-collapse pattern: however many δ
   records the suffix holds, the host issues one program, not one
   dispatch per record. Positional reconstruction is exact
   (``decompose.reconstruct`` — the reconstruction law), so every
   replayed record lands the logged post-state bit-identically;
   full-``state`` records (elastic-widen fallbacks) adopt wholesale
   and re-anchor the scan at the new shapes.

Rejoin (:func:`rejoin`) is the membership-contract upgrade this
enables (crdt_tpu/faults/membership.py): a restarted rank recovers
LOCALLY from snapshot + log — no network — and the live peer then
ships only its join-irreducible decomposition over the recovered state
instead of a full state; reconstruction is bit-exact regardless of
whether the recovered state is a true lower bound (the positional
diff is unconditional — heal.py's argument), and the final join keeps
any recovered-but-unreplicated local content. The byte win (< 25% of
full-state resync) is pinned by
``tests/test_durability.py::test_rejoin_ships_fraction_and_lands_bit_identical``.
"""

from __future__ import annotations

import functools
import time
from typing import Any, NamedTuple, Optional, Tuple

import jax
import numpy as np

from .. import obs
from ..utils.metrics import metrics, state_nbytes
from . import snapshot as snap
from .snapshot import SnapshotCorrupt
from .wal import Wal


def _record_recovery(report: "RecoveryReport") -> None:
    """A completed recovery is a postmortem boundary BY DEFINITION —
    something died to need one. Record the event and auto-dump the
    flight artifact (obs/recorder.py; a no-op when no recorder is
    installed)."""
    obs.emit(
        "recovery", generation=report.generation,
        wal_seq_start=report.wal_seq_start,
        replayed=report.replayed_records,
        fallbacks=report.snapshot_fallbacks,
    )
    obs.auto_dump("recovery", generation=report.generation)


class RecoveryReport(NamedTuple):
    """One recovery pass's accounting."""

    generation: int           # generation loaded (0 = none, base used)
    wal_seq_start: int        # replay started after this seq
    replayed_records: int     # δ + full-state records replayed
    full_state_records: int   # of those, widen-fallback full states
    snapshot_fallbacks: int   # corrupt generations skipped
    seconds: float


class RejoinReport(NamedTuple):
    """Byte accounting for one log-suffix rejoin (vs full-state)."""

    lanes_shipped: int
    bytes_shipped: float      # decomposition payload over the wire
    bytes_full_state: float   # what full-state resync would ship
    ratio: float              # shipped / full — the headline quantity


@functools.lru_cache(maxsize=None)
def _replay_scan(kind: str, batched: bool):
    """One jitted scan-fold per (kind, batching): reconstruct every
    record of a homogeneous run in a single program (module docstring).
    jit re-traces per new shape signature; the lru keys the closure."""
    from ..analysis.registry import get_decomposer
    from ..delta_opt.decompose import reconstruct

    dec = get_decomposer(kind)

    def recon(s, d):
        return reconstruct(dec, s, d)

    @jax.jit
    def replay(state, stack):
        def body(s, d):
            if batched:
                return jax.vmap(recon)(s, d), None
            return recon(s, d), None
        out, _ = jax.lax.scan(body, state, stack)
        return out

    return replay


def _decomp_treedef(kind: str, state, batched: bool):
    """The treedef a δ record's leaves unflatten through — derived by
    ``eval_shape`` (no compute) of the decomposition of ``state`` over
    itself."""
    from ..delta_opt.decompose import decompose

    if batched:
        fn = lambda: jax.vmap(lambda s: decompose(kind, s, s))(state)
    else:
        fn = lambda: decompose(kind, state, state)
    return jax.tree.structure(jax.eval_shape(fn))


def replay(wal: Wal, state, kind: Optional[str] = None,
           since_seq: int = 0) -> Tuple[Any, int, int]:
    """Replay the WAL suffix ``seq > since_seq`` onto ``state``;
    returns ``(state, replayed_records, full_state_records)``.
    ``resume`` records are stream bookkeeping, not state transitions —
    skipped here (``load_stream_resume`` reads them)."""
    n_replayed = 0
    n_full = 0
    run: list = []           # homogeneous δ-record leaf lists
    run_sig = None           # (kind, batched, shapes) of the open run

    def flush_run(state):
        nonlocal run, run_sig
        if not run:
            return state
        rkind, batched, _ = run_sig
        treedef = _decomp_treedef(rkind, state, batched)
        stack = jax.tree.unflatten(
            treedef,
            [
                jax.device_put(np.stack([leaves[i] for leaves in run]))
                for i in range(len(run[0]))
            ],
        )
        state = _replay_scan(rkind, batched)(state, stack)
        run, run_sig = [], None
        return state

    for seq, meta, leaves in wal.records(since_seq):
        rtype = meta.get("rtype")
        if rtype == "resume":
            continue
        rkind = meta.get("kind")
        if kind is None:
            kind = rkind
        elif rkind != kind:
            raise RuntimeError(
                f"WAL record {seq} is kind {rkind!r}, replay is for "
                f"{kind!r} — one log per object (use separate WAL dirs)"
            )
        if rtype == "state":
            # Widen-fallback full state: adopt wholesale; the scan
            # re-anchors at the new shapes on the next δ run.
            state = flush_run(state)
            state = jax.tree.unflatten(
                jax.tree.structure(state),
                [jax.device_put(x) for x in leaves],
            )
            n_full += 1
            n_replayed += 1
            continue
        sig = (rkind, bool(meta.get("batched", True)),
               tuple((x.shape, str(x.dtype)) for x in leaves))
        if run and sig != run_sig:
            state = flush_run(state)
        run_sig = sig
        run.append(leaves)
        n_replayed += 1
    state = flush_run(state)
    jax.block_until_ready(jax.tree.leaves(state))
    metrics.count("durability.replayed_records", n_replayed)
    return state, n_replayed, n_full


def recover_state(
    snap_dir, wal: Wal, template, kind: Optional[str] = None,
    default=None,
):
    """Recover a raw mesh state: newest valid generation (falling back
    past corrupt ones) + WAL-suffix replay. ``template`` unflattens
    state-payload generations (the resuming caller knows its shapes);
    ``default`` is the genesis state when NO generation was ever
    committed (the log then replays from seq 0) — without it that case
    raises :class:`SnapshotCorrupt`. Returns ``(state, report)``."""
    t0 = time.perf_counter()
    fallbacks = 0
    try:
        payload, info = snap.load_newest(snap_dir, template)
        gens = snap.generations(snap_dir)
        fallbacks = len([g for g in gens if g > info.gen])
        state, since = payload, info.wal_seq
        gen = info.gen
        if kind is None and info.merge_kind:
            kind = info.merge_kind
    except SnapshotCorrupt:
        if default is None:
            raise
        state, since, gen = default, 0, 0
        fallbacks = len(snap.generations(snap_dir))
    state, n_replayed, n_full = replay(wal, state, kind, since)
    metrics.count("durability.recovery_rounds")
    report = RecoveryReport(
        generation=gen,
        wal_seq_start=since,
        replayed_records=n_replayed,
        full_state_records=n_full,
        snapshot_fallbacks=fallbacks,
        seconds=time.perf_counter() - t0,
    )
    _record_recovery(report)
    return state, report


def recover_model(snap_dir, wal: Wal, kind: Optional[str] = None):
    """Recover a checkpointable MODEL (model-payload generations): the
    restored model's ``.state`` replays the WAL suffix in place. The
    merge ``kind`` defaults to ``elastic.kind_of(model)``. Returns
    ``(model, report)``."""
    t0 = time.perf_counter()
    model, info = snap.load_newest(snap_dir)
    gens = snap.generations(snap_dir)
    fallbacks = len([g for g in gens if g > info.gen])
    if kind is None:
        from .. import elastic

        kind = elastic.kind_of(model)
    state, n_replayed, n_full = replay(wal, model.state, kind, info.wal_seq)
    model.state = state
    metrics.count("durability.recovery_rounds")
    report = RecoveryReport(
        generation=info.gen,
        wal_seq_start=info.wal_seq,
        replayed_records=n_replayed,
        full_state_records=n_full,
        snapshot_fallbacks=fallbacks,
        seconds=time.perf_counter() - t0,
    )
    _record_recovery(report)
    return model, report


def load_stream_resume(wal: Wal, template):
    """The newest stream resume point ``(acc, blocks_done)`` persisted
    by ``mesh_stream_fold*(wal=...)`` — or ``None`` when the log holds
    no resume record. ``blocks_done`` is ABSOLUTE in the original
    source (resumed runs compose via ``wal_base=``): re-enter the
    stream with ``init=acc``, the source re-chunked from
    ``blocks_done``, and ``wal_base=blocks_done`` so a further kill
    still resumes at the true position (the ``StreamInterrupted``
    contract, made durable)."""
    found = None
    for _, meta, leaves in wal.records(0):
        if meta.get("rtype") == "resume":
            found = (meta, leaves)
    if found is None:
        return None
    meta, leaves = found
    acc = jax.tree.unflatten(
        jax.tree.structure(template), [jax.device_put(x) for x in leaves]
    )
    return acc, int(meta["blocks_done"])


def rejoin(kind: str, live_state, recovered_state):
    """Log-suffix rejoin of one restarted rank against one live peer
    (module docstring): the peer ships ``decompose(live, recovered)``
    — only the divergence lanes — reconstruction lands the peer's
    state bit-exactly, and the final join keeps any recovered-but-
    unreplicated local content. Returns ``(healed, RejoinReport)``;
    counters ``durability.rejoin_bytes_shipped`` / ``_full``."""
    from ..analysis.registry import get_merge_kind
    from ..delta_opt.decompose import (
        decompose, decomposition_bytes, reconstruct,
    )

    d = decompose(kind, live_state, recovered_state)
    shipped = float(decomposition_bytes(d))
    recon = reconstruct(kind, recovered_state, d)  # == live, bit-exact
    mk = get_merge_kind(kind)
    out = mk.join(recon, recovered_state)
    healed = out[0] if isinstance(out, tuple) and len(out) == 2 else out
    full = float(state_nbytes(live_state))
    metrics.count("durability.rejoin_bytes_shipped", int(shipped))
    metrics.count("durability.rejoin_bytes_full", int(full))
    return healed, RejoinReport(
        lanes_shipped=int(jax.numpy.sum(d.valid)),
        bytes_shipped=shipped,
        bytes_full_state=full,
        ratio=shipped / full if full else 0.0,
    )


from ..analysis.registry import register_obs_event as _reg_ev  # noqa: E402

_reg_ev("recovery", subsystem="durability.recover",
        fields=("generation", "wal_seq_start", "replayed", "fallbacks"),
        module=__name__)


__all__ = [
    "RecoveryReport", "RejoinReport", "load_stream_resume",
    "recover_model", "recover_state", "rejoin", "replay",
]
