"""Mesh-level anti-entropy entry points.

These wrap the in-``shard_map`` collectives (collectives.py) into
device-count-agnostic calls: hand them a batched state [R, ...] and a
mesh, get back the converged lattice join — the TPU replacement for the
reference's "serialize state, caller transports bytes, merge on arrival"
loop (SURVEY.md §4.2 anti-entropy path).

``check_vma=False`` on every shard_map: the outputs *are* replicated
over the reduced axes (the join is idempotent and the overflow flags are
psum-reduced), but the static replication checker cannot see that
through ``ppermute``-based recursive doubling.

Entry points memoise their ``shard_map`` closures per (mesh, input
shapes) — without this every call re-traces and re-lowers the whole
collective program, which costs seconds per anti-entropy round.
"""

from __future__ import annotations

import time
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from ..ops import map as map_ops
from ..ops import map_map as nested_ops
from ..ops import map_orswot as mo_ops
from ..ops import orswot as ops
from ..ops.map import MapState
from ..ops.map_map import NestedMapState
from ..ops.map_orswot import MapOrswotState
from ..ops.orswot import OrswotState
from .collectives import (
    all_reduce_clock,
    all_reduce_join,
    all_reduce_lattice,
    ring_round,
)
from .mesh import (
    ELEMENT_AXIS,
    REPLICA_AXIS,
    map_orswot_out_specs,
    map_orswot_specs,
    map_out_specs,
    map_specs,
    nested_map_out_specs,
    nested_map_specs,
    orswot_out_specs,
    orswot_specs,
    pad_elements,
    pad_keys,
    pad_map_orswot,
    pad_nested_map,
    pad_replicas,
    pad_replicas_map,
)
from ..obs import hist as _hist
from ..utils.metrics import metrics, observe_depth, state_nbytes
from .. import telemetry as tele


_FN_CACHE: dict = {}


def _exchange_count(p: int) -> int:
    """Static per-device exchange count of the replica-axis all-reduce:
    log2(P) recursive-doubling hops on a power-of-two axis, P-1 shipped
    shards on the all_gather fallback (collectives.all_reduce_lattice).
    Feeds the telemetry merge/byte counters."""
    if p <= 1:
        return 0
    if p & (p - 1) == 0:
        return p.bit_length() - 1
    return p - 1


def _tel_reduced(folded, slots, merges_per_dev, bytes_per_dev,
                 sum_axes, residue=None, useful_per_dev=None):
    """Mesh-reduce per-device telemetry into replicated scalars (inside
    shard_map): throughput counters psum over the replica axis (and the
    element axis only for ``slots`` when the content plane is
    element-sharded — ``sum_axes``; None = caller already reduced);
    bytes psum over ALL devices (element copies physically transmit);
    final-state gauges pmax. ``useful_per_dev`` is the per-device
    post-mask payload byte count (δ-ring packets after digest gating);
    None = no mask exists, wire == useful (whole-state exchanges)."""
    both = (REPLICA_AXIS, ELEMENT_AXIS)
    wire = lax.psum(jnp.float32(bytes_per_dev), both)
    return tele.Telemetry(
        merges=lax.psum(jnp.uint32(merges_per_dev), REPLICA_AXIS),
        slots_changed=slots if sum_axes is None else lax.psum(slots, sum_axes),
        deferred_depth=lax.pmax(tele.device_depth(folded), both),
        bytes_exchanged=wire,
        bytes_useful=(
            wire if useful_per_dev is None
            else lax.psum(jnp.float32(useful_per_dev), both)
        ),
        residue=(
            jnp.zeros((), jnp.int32) if residue is None else residue
        ),
        widen_pressure=lax.pmax(tele.device_pressure(folded), both),
        # The reclaim fields are zero unless the stability path fills
        # them in (gossip_stab_fn's _replace); the stream fields are
        # filled host-side by the block loop (parallel/stream.py).
        reclaimed_slots=jnp.zeros((), jnp.uint32),
        reclaimed_bytes=jnp.zeros((), jnp.float32),
        frontier_lag=jnp.zeros((), jnp.uint32),
        stream_blocks=jnp.zeros((), jnp.uint32),
        stream_staged_bytes=jnp.zeros((), jnp.float32),
        stream_overlap_hit=jnp.zeros((), jnp.uint32),
        # The fault fields are zero unless the faults= path fills them
        # in (the entry's _replace on the counters psum — faults/).
        faults_dropped=jnp.zeros((), jnp.uint32),
        faults_rejected=jnp.zeros((), jnp.uint32),
        faults_delayed=jnp.zeros((), jnp.uint32),
        # The ack-window fields are zero unless the δ ring's
        # ack_window= path fills them in (delta_ring's _replace).
        bytes_acked_skipped=jnp.zeros((), jnp.float32),
        ack_window_depth=jnp.zeros((), jnp.uint32),
        # The durability fields are filled host-side by the wal= append
        # loop (delta_ring / stream) and the recovery driver
        # (crdt_tpu/durability/) — never in-kernel.
        wal_bytes=jnp.zeros((), jnp.float32),
        wal_fsyncs=jnp.zeros((), jnp.uint32),
        snapshots_written=jnp.zeros((), jnp.uint32),
        replayed_records=jnp.zeros((), jnp.uint32),
        torn_tail_truncated=jnp.zeros((), jnp.uint32),
        recovery_rounds=jnp.zeros((), jnp.uint32),
        # The scale-out fields are filled host-side by the membership
        # controller (crdt_tpu/scaleout/ ScaleoutMesh.annotate) — never
        # in-kernel.
        live_ranks=jnp.zeros((), jnp.uint32),
        scaleout_admits=jnp.zeros((), jnp.uint32),
        scaleout_drains=jnp.zeros((), jnp.uint32),
        bootstrap_bytes=jnp.zeros((), jnp.float32),
        # The packed-wire fields are zero unless the δ ring's fused=
        # path fills them in (delta_ring's _replace).
        wire_packed_bytes=jnp.zeros((), jnp.float32),
        # The serving-tier fields are filled host-side by the serve
        # layer (crdt_tpu/serve/ Superblock.annotate /
        # IngestQueue.annotate) — never in-kernel.
        live_tenants=jnp.zeros((), jnp.uint32),
        evicted_tenants=jnp.zeros((), jnp.uint32),
        ingest_coalesced_ops=jnp.zeros((), jnp.uint32),
        serve_wal_bytes=jnp.zeros((), jnp.float32),
        serve_overlap_hit=jnp.zeros((), jnp.uint32),
        rebalance_moves=jnp.zeros((), jnp.uint32),
        # The fan-out fields are filled by the subscription plane
        # (crdt_tpu/fanout/ FanoutPlane.annotate + mesh_fanout_push's
        # telemetry body) — never on the anti-entropy paths.
        subscribers_live=jnp.zeros((), jnp.uint32),
        cohorts_per_dispatch=jnp.zeros((), jnp.uint32),
        delta_push_bytes=jnp.zeros((), jnp.float32),
        resync_fallbacks=jnp.zeros((), jnp.uint32),
        # The geo-federation fields are filled host-side by the
        # federation front door (crdt_tpu/geo/ Federation.annotate) —
        # never in-kernel.
        regions_live=jnp.zeros((), jnp.uint32),
        geo_home_tenants=jnp.zeros((), jnp.uint32),
        geo_exchanges=jnp.zeros((), jnp.uint32),
        geo_exchange_bytes=jnp.zeros((), jnp.float32),
        geo_full_mirror_bytes=jnp.zeros((), jnp.float32),
        geo_failovers=jnp.zeros((), jnp.uint32),
        hist_geo_watermark_lag=_hist.zeros(),
        # The in-kernel histograms are zero unless the δ ring's loop
        # carry fills them in (delta_ring's _replace);
        # hist_dispatch_us is filled host-side (telemetry.time_dispatch
        # at the entry wrappers — never in-kernel).
        hist_residue=_hist.zeros(),
        hist_useful_bytes=_hist.zeros(),
        hist_ack_depth=_hist.zeros(),
        hist_packed_bytes=_hist.zeros(),
        hist_dispatch_us=_hist.zeros(),
        hist_ingest_batch=_hist.zeros(),
        hist_push_bytes=_hist.zeros(),
        # The trace-plane stage/freshness hists are filled host-side by
        # obs.trace.Tracer.annotate — never in-kernel.
        hist_queue_wait_us=_hist.zeros(),
        hist_dispatch_gap_us=_hist.zeros(),
        hist_durable_lag_us=_hist.zeros(),
        hist_push_lag_us=_hist.zeros(),
        hist_ack_lag_us=_hist.zeros(),
        hist_freshness_us=_hist.zeros(),
        # hist_persist_us is filled host-side by the serve layer's
        # BackgroundPersister (crdt_tpu/serve/loop.py) — never in-kernel.
        hist_persist_us=_hist.zeros(),
    )


def _cached(kind: str, state, mesh: Mesh, build, *extra, donate_argnums=()):
    """The memoised shard_map closure for ``kind`` on this (mesh, input
    shape/dtype signature): jit-wrapped once, so repeated anti-entropy
    rounds hit the trace/compile cache instead of re-lowering.
    ``donate_argnums`` rides the cache key — a donating call consumes
    its inputs, so it must never share a compiled program with the
    copying flavor."""
    sig = tuple(
        (tuple(x.shape), str(x.dtype)) for x in jax.tree.leaves(state)
    )
    key = (kind, mesh, sig, tuple(donate_argnums), *extra)
    fn = _FN_CACHE.get(key)
    if fn is None:
        fn = _FN_CACHE[key] = jax.jit(
            build(), donate_argnums=tuple(donate_argnums)
        )
    return fn


def _ring_donate_argnums(state, mesh: Mesh, donate: bool, n: int = 1):
    """The donate_argnums for a ring/gossip entry point whose outputs
    keep the ``[P, ...]`` per-device layout: the first ``n`` args
    (state pytree, and for δ flavors the dirty mask) alias their
    outputs in place — zero-copy — exactly when the padded replica axis
    equals the mesh's (one replica block row per device), which is the
    steady-state mesh shape. A larger batch reduces away leading rows,
    XLA would silently drop the donation (with a warning), so we fall
    back to the copying program and count the miss instead."""
    if not donate:
        return ()
    lead = jax.tree.leaves(state)[0].shape[0]
    if lead != mesh.shape[REPLICA_AXIS]:
        metrics.count("anti_entropy.donate_unaliasable")
        return ()
    return tuple(range(n))


def _consume(donate: bool, *trees) -> None:
    """Donation semantics for the entry points whose outputs cannot
    alias their inputs (the fold family reduces the replica axis away —
    no output shares the batched input's shape): the caller yielded
    ownership, so free the input buffers NOW rather than at whatever
    point the last reference dies. Already-deleted / tracer leaves are
    skipped (a donating ring call upstream may have consumed them)."""
    if not donate:
        return
    for tree in trees:
        for leaf in jax.tree.leaves(tree):
            try:
                leaf.delete()
            except Exception:
                pass  # tracers / already-donated buffers


def mesh_fold(
    state: OrswotState, mesh: Mesh, local_fold: str = "auto",
    telemetry: bool = False, donate: bool = False,
) -> Tuple[OrswotState, jax.Array]:
    """Full-mesh anti-entropy over the device mesh: every replica's state
    joined into one converged state, in one collective round.

    Plan: fold the device-local replica block (the fused one-HBM-pass
    Pallas kernel on TPU backends, the jnp log2 tree elsewhere —
    ``local_fold`` = "auto"|"fused"|"tree", see pallas_kernels
    ``fold_auto``), then one lattice-join all-reduce across the
    ``replica`` mesh axis. Element shards never communicate — the join
    is element-parallel (mesh.py). Returns (converged state [no replica
    axis, element-sharded], overflow flag); with ``telemetry=True`` a
    :class:`crdt_tpu.telemetry.Telemetry` pytree rides along as a third
    element (in-kernel counters — they survive an outer jit; the flag
    off traces exactly the flag-free program).

    ``donate=True`` consumes ``state``: the fold reduces the replica
    axis away, so no output can alias the batched input — the input
    buffers are instead freed as soon as the reduction lands rather
    than when the caller's last reference dies, halving the entry's
    resident HBM tail. The caller must not touch ``state`` afterwards
    (in-place aliasing is the *ring* family's mode — ``mesh_gossip*``
    keep the [P, ...] layout, so there donation really aliases)."""
    from ..ops.pallas_kernels import fold_auto

    def build():
        @partial(
            jax.shard_map,
            mesh=mesh,
            in_specs=(orswot_specs(),),
            out_specs=(orswot_out_specs(), P()),
            check_vma=False,
        )
        def fold_fn(local):
            folded, of_local = fold_auto(local, prefer=local_fold)
            joined, of_cross = all_reduce_join(folded, REPLICA_AXIS)
            of = (lax.psum(of_local.astype(jnp.int32), REPLICA_AXIS) > 0) | of_cross
            return joined, of

        return fold_fn

    def build_tel():
        n_ex = _exchange_count(mesh.shape[REPLICA_AXIS])

        @partial(
            jax.shard_map,
            mesh=mesh,
            in_specs=(orswot_specs(),),
            out_specs=(orswot_out_specs(), P(), tele.specs()),
            check_vma=False,
        )
        def fold_tel_fn(local):
            folded, of_local = fold_auto(local, prefer=local_fold)
            joined, of_cross = all_reduce_join(folded, REPLICA_AXIS)
            of = (lax.psum(of_local.astype(jnp.int32), REPLICA_AXIS) > 0) | of_cross
            local_rows = jax.tree.leaves(local)[0].shape[0]
            tel = _tel_reduced(
                joined,
                lax.psum(
                    ops.changed_members(folded, joined),
                    (REPLICA_AXIS, ELEMENT_AXIS),
                ),
                max(local_rows - 1, 0) + n_ex,
                tele.shipped_bytes(folded) * n_ex,
                sum_axes=None,  # already reduced above
            )
            return joined, of, tel

        return fold_tel_fn

    with tele.span("ae.fold"):
        with tele.span("ae.fold.prep"):
            orig = state
            state = pad_replicas(state, mesh.shape[REPLICA_AXIS])
            state = pad_elements(state, mesh.shape[ELEMENT_AXIS])
            metrics.count("anti_entropy.fold_rounds")
            metrics.count(
                "anti_entropy.merges",
                max(jax.tree.leaves(state)[0].shape[0] - 1, 0),
            )
            metrics.observe("anti_entropy.state_bytes", state_nbytes(state))
            observe_depth("anti_entropy.orswot_fold", state)
            fn = _cached(
                "orswot_fold", state, mesh,
                build_tel if telemetry else build, local_fold, telemetry,
            )
        t0 = time.perf_counter()
        with tele.span("ae.fold.dispatch"):
            out = fn(state)
        with tele.span("ae.fold.wait"):
            jax.block_until_ready(out)  # time device work, not async dispatch
        _consume(donate, state, orig)
        if telemetry and tele.is_concrete(out[2]):
            out = out[:2] + (tele.time_dispatch(
                out[2], time.perf_counter() - t0
            ),)
            tele.record("orswot_fold", out[2])
    return out


def _mesh_gossip_lattice(
    kind: str,
    state,
    mesh: Mesh,
    join_fn,
    fold_fn,
    in_specs,
    rounds: Optional[int] = None,
    cache_extra: tuple = (),
    telemetry: bool = False,
    slots_fn=None,
    element_sharded: bool = True,
    donate: bool = False,
    stability: bool = False,
    compact_fn=None,
    faults=None,
    lag_threshold=None,
):
    """Shared scaffold for ring anti-entropy: each device folds its
    local replica block, then runs ``rounds`` unit-shift gossip rounds.
    Bandwidth per round is one state per link — the bounded-traffic mode
    for DCN-crossing replica axes. Returns (per-device states [P, ...],
    overflow); with the default rounds = P-1 every row equals the full
    join.

    ``telemetry=True`` appends an in-kernel accumulated Telemetry pytree
    (telemetry.py) — per-round joins feed ``slots_fn`` (the kind's
    changed-lane counter; ``element_sharded`` picks the psum axes for it)
    and the shipped-state bytes; the flag off traces exactly the
    flag-free program.

    ``donate=True`` consumes the input state and — when the padded
    replica axis equals the mesh's, the steady-state shape — aliases
    the output rows onto the input buffers (``input_output_alias`` in
    the lowering; tools/check_aliasing.py gates it), so the gossip
    carries no second copy of the state in HBM. Larger batches cannot
    alias (the local fold reduces leading rows away); they fall back to
    freeing the input after the run and count
    ``anti_entropy.donate_unaliasable``.

    ``stability=True`` piggybacks the mesh-wide STABLE FRONTIER on the
    round (reclaim/frontier.py): one lax ``pmin`` over the replica axis
    of the PRE-fold input tops — the knowledge each replica entered
    with, so a straggler row pins the frontier — appended as the last
    output (replicated ``[A]``), and the kind's registered compaction
    kernel (``compact_fn``) runs in-kernel on the converged rows before
    they ship out. The flag off traces exactly the flag-free program
    (same HLO-identity discipline as ``telemetry=``); with both flags
    on, the Telemetry pytree carries ``reclaimed_slots`` /
    ``reclaimed_bytes`` / ``frontier_lag``.

    ``faults=`` (a ``crdt_tpu.faults.FaultPlan``) injects seeded
    drop/corrupt/delay faults on every ring exchange — each shipped
    state carries a checksum lane, corrupted arrivals are REJECTED
    (local state kept), the ring runs over the plan's LIVE ranks, and
    with ``stability=`` on the frontier ``pmin`` EXCLUDES evicted tops
    (the headline unpinning: a dead rank stops stalling reclamation;
    its own row is left uncompacted — a frontier past its knowledge
    must not retire its parked slots). A ``FaultCounters`` pytree is
    appended as the LAST output. Unlike the δ ring, loss here is never
    unsound — full states carry their own tops, a missed round only
    slows convergence (run more rounds, or heal with a fault-free run).

    ``lag_threshold=`` (host-side, needs ``stability=``): when the
    run's ``frontier_lag`` reaches it, ``reclaim.frontier_stalled``
    counts and a once-per-kind stall warning fires
    (reclaim/frontier.py ``watch_lag`` — the operator signal that a
    straggler is pinning the frontier and reclamation has stalled)."""
    if lag_threshold is not None and not stability:
        raise ValueError(
            "lag_threshold= needs stability=True: the stall alert "
            "watches the stable frontier, which only exists on the "
            "stability path — without it the alert would silently "
            "never arm"
        )
    if rounds is None:
        rounds = mesh.shape[REPLICA_AXIS] - 1
    faulted = faults is not None
    delay_mode = faulted and faults.delay > 0
    if faulted:
        from .. import faults as flt

        p = mesh.shape[REPLICA_AXIS]
        perm = flt.ring_perm(p, faults.evicted)
        snd_tbl = flt.sender_of(p, faults.evicted)
    argnums = _ring_donate_argnums(state, mesh, donate)

    def build():
        # ONE parameterized builder for every (telemetry, stability)
        # combination: the flag-off branches trace EXACTLY the pre-flag
        # program (Python conditionals emit nothing when off — the
        # HLO-identity pins in tests/test_telemetry.py and
        # tests/test_reclaim.py hold on this single body).
        from ..reclaim.frontier import frontier_lag as _lag, top_of as _top

        slots_of = slots_fn or tele.generic_slots_changed
        sum_axes = (
            (REPLICA_AXIS, ELEMENT_AXIS) if element_sharded
            else (REPLICA_AXIS,)
        )
        out_specs = [in_specs, P()]
        if telemetry:
            out_specs.append(tele.specs())
        if stability:
            out_specs.append(P())  # the frontier, replicated
        if faulted:
            out_specs.append(flt.counters_specs())

        @partial(
            jax.shard_map,
            mesh=mesh,
            in_specs=(in_specs,),
            out_specs=tuple(out_specs),
            check_vma=False,
        )
        def gossip_fn(local):
            if faulted:
                ev = flt.evicted_mask(faults, REPLICA_AXIS)
            if stability:
                # Frontier over the PRE-fold input tops: the knowledge
                # each replica ENTERED the round with — a straggler row
                # pins it.
                tmin = jnp.min(_top(local), axis=0)
                if faulted and faults.evicted:
                    # Eviction unpins: a dead rank's stale top leaves
                    # the pmin (the membership decision — its rejoin
                    # must be full-state resync, faults/membership.py).
                    tmin = jnp.where(
                        ev, jnp.asarray(jnp.iinfo(tmin.dtype).max,
                                        tmin.dtype), tmin
                    )
                frontier = lax.pmin(tmin, REPLICA_AXIS)
            folded, of = fold_fn(local)
            if telemetry:
                slots = jnp.zeros((), jnp.uint32)
            if faulted:
                fc = (
                    jnp.zeros((), jnp.uint32), jnp.zeros((), jnp.uint32),
                    jnp.zeros((), jnp.uint32), jnp.zeros((), jnp.int32),
                )
                if delay_mode:
                    held = jax.tree.map(jnp.zeros_like, folded)
                    heldv = jnp.zeros((), bool)
            for r in range(rounds):
                if faulted:
                    # The faulted exchange: checksum lane on the wire,
                    # per-round drop/corrupt/delay draws on the inbound
                    # link (faults.receive_wire — evicted self-loops
                    # masked out of the accounting), rejected/dropped
                    # deliveries deselected (full-state loss is never
                    # unsound — see above).
                    other, chk_in = jax.tree.map(
                        lambda x: lax.ppermute(x, REPLICA_AXIS, perm),
                        (folded, flt.checksum(folded)),
                    )
                    # The last round delivers a would-be-delayed state
                    # now — no later round to hold it for.
                    other, keep, fates = flt.receive_wire(
                        faults, r, REPLICA_AXIS, snd_tbl, other, chk_in,
                        delay_ok=delay_mode and r < rounds - 1,
                    )
                    base = folded
                    if delay_mode:
                        newh, of_h = join_fn(folded, held)
                        folded = flt.tree_select(heldv, newh, folded)
                        of = of | (of_h & heldv)
                    joined, of_r = join_fn(folded, other)
                    new = flt.tree_select(keep, joined, folded)
                    of_r = of_r & keep
                    if delay_mode:
                        held = flt.tree_select(fates[2], other, held)
                        heldv = fates[2]
                    fc = flt.tick_counters(fc, fates)
                    if telemetry:
                        slots = slots + slots_of(base, new)
                    folded, of = new, of | of_r
                    continue
                new, of_r = ring_round(
                    folded, REPLICA_AXIS, reduce_overflow=False,
                    join_fn=join_fn,
                )
                if telemetry:
                    slots = slots + slots_of(folded, new)
                folded, of = new, of | of_r
            if stability:
                freed = jnp.zeros((), jnp.uint32)
                freed_b = jnp.zeros((), jnp.float32)
                if compact_fn is not None:
                    compacted, freed, freed_b = compact_fn(folded, frontier)
                    if faulted and faults.evicted:
                        # Never compact an evicted rank's own row: the
                        # frontier may exceed its knowledge, and
                        # retiring parked slots it has not applied
                        # breaks its (resync-pending) local state.
                        compacted = flt.tree_select(~ev, compacted, folded)
                        freed = jnp.where(ev, 0, freed)
                        freed_b = jnp.where(ev, 0.0, freed_b)
                    folded = compacted
            of = lax.psum(of.astype(jnp.int32), (REPLICA_AXIS, ELEMENT_AXIS)) > 0
            outs = [jax.tree.map(lambda x: x[None], folded), of]
            if telemetry:
                local_rows = jax.tree.leaves(local)[0].shape[0]
                tel = _tel_reduced(
                    folded, slots,
                    max(local_rows - 1, 0) + rounds,
                    tele.shipped_bytes(folded) * rounds,
                    sum_axes,
                )
                if stability:
                    tel = tel._replace(
                        reclaimed_slots=lax.psum(freed, REPLICA_AXIS),
                        reclaimed_bytes=lax.psum(freed_b, REPLICA_AXIS),
                        frontier_lag=lax.pmax(
                            _lag(_top(folded), frontier), REPLICA_AXIS
                        ),
                    )
                if faulted:
                    tel = tel._replace(
                        faults_dropped=lax.psum(fc[0], REPLICA_AXIS),
                        faults_rejected=lax.psum(fc[1], REPLICA_AXIS),
                        faults_delayed=lax.psum(fc[2], REPLICA_AXIS),
                    )
                outs.append(tel)
            if stability:
                outs.append(frontier)
            if faulted:
                # Replica-axis psum only: the fault draw is per logical
                # link (element shards share the fate) — a both-axes sum
                # would count device shards, not packets.
                outs.append(flt.FaultCounters(
                    packets_dropped=lax.psum(fc[0], REPLICA_AXIS),
                    packets_rejected=lax.psum(fc[1], REPLICA_AXIS),
                    packets_delayed=lax.psum(fc[2], REPLICA_AXIS),
                    miss_streak=fc[3].reshape(1),
                ))
            return tuple(outs)

        return gossip_fn

    metrics.count(f"anti_entropy.{kind}_rounds", rounds)
    metrics.observe("anti_entropy.state_bytes", state_nbytes(state))
    observe_depth(f"anti_entropy.{kind}", state)
    t0 = time.perf_counter()
    with tele.span(f"ae.{kind}"):
        out = _cached(
            kind, state, mesh, build,
            rounds, telemetry, stability, faults, *cache_extra,
            donate_argnums=argnums,
        )(state)
        jax.block_until_ready(out)  # time device work, not async dispatch
    # Aliased buffers are already consumed by the donation; this frees
    # the leftovers — the unaliasable fallback, and originals that were
    # implicitly resharded onto the mesh (the executable then donated
    # the committed copy, not the caller's array).
    _consume(donate, state)
    if telemetry and tele.is_concrete(out[2]):
        out = out[:2] + (tele.time_dispatch(
            out[2], time.perf_counter() - t0
        ),) + out[3:]
        tele.record(kind, out[2])
    if faulted:
        from .. import faults as flt

        flt.record(out[-1])  # no-op under tracing
    if stability and lag_threshold is not None:
        from ..reclaim.frontier import frontier_lag, top_of, watch_lag

        frontier = out[2 + (1 if telemetry else 0)]
        lag = frontier_lag(top_of(out[0]), frontier)
        if not isinstance(lag, jax.core.Tracer):
            watch_lag(kind, int(lag), lag_threshold)
    return out


def mesh_gossip(
    state: OrswotState,
    mesh: Mesh,
    rounds: Optional[int] = None,
    local_fold: str = "auto",
    telemetry: bool = False,
    donate: bool = False,
    stability: bool = False,
    faults=None,
    lag_threshold=None,
) -> Tuple[OrswotState, jax.Array]:
    """Ring anti-entropy for ORSWOT replica batches (see
    ``_mesh_gossip_lattice``); the device-local pre-fold dispatches like
    ``mesh_fold`` (fused Pallas on TPU backends). ``telemetry=True``
    appends the in-kernel Telemetry pytree (telemetry.py);
    ``donate=True`` consumes ``state`` and aliases the converged rows
    onto its buffers in place (zero-copy — ``_mesh_gossip_lattice``);
    ``stability=True`` appends the mesh-wide stable frontier and
    compacts the rows in-kernel (reclaim/)."""
    from ..ops.pallas_kernels import fold_auto

    state = pad_replicas(state, mesh.shape[REPLICA_AXIS])
    state = pad_elements(state, mesh.shape[ELEMENT_AXIS])
    return _mesh_gossip_lattice(
        "orswot_gossip", state, mesh, ops.join,
        partial(fold_auto, prefer=local_fold), orswot_specs(), rounds,
        cache_extra=(local_fold,),
        telemetry=telemetry, slots_fn=ops.changed_members, donate=donate,
        stability=stability, compact_fn=ops.compact,
        faults=faults, lag_threshold=lag_threshold,
    )


def mesh_gossip_map(
    state: MapState, mesh: Mesh, rounds: Optional[int] = None,
    telemetry: bool = False, donate: bool = False,
    stability: bool = False,
    faults=None,
    lag_threshold=None,
) -> Tuple[MapState, jax.Array]:
    """Ring anti-entropy for the composition layer: Map<K, MVReg>
    replica blocks gossiped one neighbor per round over the replica
    axis, key shards independent."""
    state = pad_replicas_map(state, mesh.shape[REPLICA_AXIS])
    state = pad_keys(state, mesh.shape[ELEMENT_AXIS])
    return _mesh_gossip_lattice(
        "map_gossip", state, mesh, map_ops.join, map_ops.fold, map_specs(),
        rounds, telemetry=telemetry, slots_fn=map_ops.changed_keys,
        donate=donate, stability=stability, compact_fn=map_ops.compact,
        faults=faults, lag_threshold=lag_threshold,
    )


def mesh_gossip_map_orswot(
    state: MapOrswotState, mesh: Mesh, rounds: Optional[int] = None,
    telemetry: bool = False, donate: bool = False,
    stability: bool = False,
    faults=None,
    lag_threshold=None,
) -> Tuple[MapOrswotState, jax.Array]:
    """Ring anti-entropy for ``Map<K, Orswot>`` replica blocks (the
    Val-generic slab composition) over the replica axis."""
    state = pad_map_orswot(state, mesh.shape[REPLICA_AXIS], mesh.shape[ELEMENT_AXIS])
    return _mesh_gossip_lattice(
        "map_orswot_gossip", state, mesh,
        partial(mo_ops.join, element_axis=ELEMENT_AXIS),
        partial(mo_ops.fold, element_axis=ELEMENT_AXIS),
        map_orswot_specs(), rounds,
        telemetry=telemetry,
        slots_fn=lambda a, b: ops.changed_members(a.core, b.core),
        donate=donate, stability=stability, compact_fn=mo_ops.compact,
        faults=faults, lag_threshold=lag_threshold,
    )


def mesh_gossip_nested_map(
    state: NestedMapState, mesh: Mesh, rounds: Optional[int] = None,
    telemetry: bool = False, donate: bool = False,
    stability: bool = False,
    faults=None,
    lag_threshold=None,
) -> Tuple[NestedMapState, jax.Array]:
    """Ring anti-entropy for ``Map<K1, Map<K2, MVReg>>`` replica blocks
    over the replica axis."""
    state = pad_nested_map(state, mesh.shape[REPLICA_AXIS], mesh.shape[ELEMENT_AXIS])
    return _mesh_gossip_lattice(
        "nested_map_gossip", state, mesh,
        partial(nested_ops.join, element_axis=ELEMENT_AXIS),
        partial(nested_ops.fold, element_axis=ELEMENT_AXIS),
        nested_map_specs(), rounds,
        telemetry=telemetry,
        slots_fn=lambda a, b: map_ops.changed_keys(a.m, b.m),
        donate=donate, stability=stability, compact_fn=nested_ops.compact,
        faults=faults, lag_threshold=lag_threshold,
    )


def _mesh_fold_lattice(
    kind: str,
    state,
    mesh: Mesh,
    join_fn,
    fold_fn,
    in_specs,
    out_specs,
    telemetry: bool = False,
    slots_fn=None,
    element_sharded: bool = False,
    donate: bool = False,
    pad=None,
):
    """Shared scaffold for the map-family mesh folds: local log-tree
    fold per shard, replica-axis lattice-join all-reduce, and overflow
    flags reduced over BOTH axes (slab/deferred overflows can be
    key-shard-local, so every device must report the global flag).
    ``telemetry=True`` appends the in-kernel Telemetry pytree
    (telemetry.py); the flag off traces exactly the flag-free program.
    ``donate=True`` consumes the input batch: the fold reduces the
    replica axis away so no output aliases it — the buffers are freed
    as soon as the reduction lands (see ``mesh_fold``). ``pad`` fits the
    batch to the mesh inside the entry's ``ae.<kind>.prep`` span.

    Spans, as ``mesh_fold``'s: ``ae.<kind>`` around the whole entry, and
    under it ``.prep`` (padding, counters, depth gauge, program lookup),
    ``.dispatch`` and ``.wait`` (until the converged state is ready)."""

    def build():
        @partial(
            jax.shard_map,
            mesh=mesh,
            in_specs=(in_specs,),
            out_specs=(out_specs, P()),
            check_vma=False,
        )
        def mesh_fn(local):
            folded, of_local = fold_fn(local)
            joined, of_cross = all_reduce_lattice(
                folded, REPLICA_AXIS, join_fn, fold_fn
            )
            of = (lax.psum(of_local.astype(jnp.int32), REPLICA_AXIS) > 0) | of_cross
            of = lax.psum(of.astype(jnp.int32), ELEMENT_AXIS) > 0
            return joined, of

        return mesh_fn

    def build_tel():
        slots_of = slots_fn or tele.generic_slots_changed
        sum_axes = (
            (REPLICA_AXIS, ELEMENT_AXIS) if element_sharded
            else (REPLICA_AXIS,)
        )
        n_ex = _exchange_count(mesh.shape[REPLICA_AXIS])

        @partial(
            jax.shard_map,
            mesh=mesh,
            in_specs=(in_specs,),
            out_specs=(out_specs, P(), tele.specs()),
            check_vma=False,
        )
        def mesh_tel_fn(local):
            folded, of_local = fold_fn(local)
            joined, of_cross = all_reduce_lattice(
                folded, REPLICA_AXIS, join_fn, fold_fn
            )
            of = (lax.psum(of_local.astype(jnp.int32), REPLICA_AXIS) > 0) | of_cross
            of = lax.psum(of.astype(jnp.int32), ELEMENT_AXIS) > 0
            local_rows = jax.tree.leaves(local)[0].shape[0]
            tel = _tel_reduced(
                joined, slots_of(folded, joined),
                max(local_rows - 1, 0) + n_ex,
                tele.shipped_bytes(folded) * n_ex,
                sum_axes,
            )
            return joined, of, tel

        return mesh_tel_fn

    with tele.span(f"ae.{kind}"):
        with tele.span(f"ae.{kind}.prep"):
            orig = state
            if pad is not None:
                state = pad(state)
            metrics.count(f"anti_entropy.{kind}_rounds")
            metrics.count(
                "anti_entropy.merges",
                max(jax.tree.leaves(state)[0].shape[0] - 1, 0),
            )
            metrics.observe("anti_entropy.state_bytes", state_nbytes(state))
            observe_depth(f"anti_entropy.{kind}", state)
            fn = _cached(
                kind, state, mesh, build_tel if telemetry else build, telemetry
            )
        t0 = time.perf_counter()
        with tele.span(f"ae.{kind}.dispatch"):
            out = fn(state)
        with tele.span(f"ae.{kind}.wait"):
            jax.block_until_ready(out)  # time device work, not async dispatch
        _consume(donate, state, orig)
        if telemetry and tele.is_concrete(out[2]):
            out = out[:2] + (tele.time_dispatch(
                out[2], time.perf_counter() - t0
            ),)
            tele.record(kind, out[2])
    return out


def mesh_fold_map(
    state: MapState, mesh: Mesh, telemetry: bool = False,
    donate: bool = False,
) -> Tuple[MapState, jax.Array]:
    """Full-mesh anti-entropy for the composition layer (BASELINE config
    4): every replica's Map<K, MVReg> state joined into one converged
    state over the (replica × key) mesh. Key shards never communicate —
    the map join is key-wise independent (mesh.map_specs); the only
    collective is the lattice-join all-reduce over the replica axis.

    Returns (converged state [no replica axis, key-sharded], overflow).
    """
    def pad(st):
        st = pad_replicas_map(st, mesh.shape[REPLICA_AXIS])
        return pad_keys(st, mesh.shape[ELEMENT_AXIS])

    return _mesh_fold_lattice(
        "map_fold", state, mesh,
        map_ops.join, map_ops.fold,
        map_specs(), map_out_specs(),
        telemetry=telemetry, slots_fn=map_ops.changed_keys,
        element_sharded=True, donate=donate, pad=pad,
    )


def mesh_fold_map_orswot(
    state: MapOrswotState, mesh: Mesh, telemetry: bool = False,
    donate: bool = False,
) -> Tuple[MapOrswotState, jax.Array]:
    """Full-mesh anti-entropy for ``Map<K, Orswot>`` over the
    (replica × key) mesh: element shards hold whole keys (K*M blocks)
    and never exchange content; the collectives are the replica-axis
    lattice-join all-reduce plus the tiny slot-liveness reduction the
    dead-key scrub needs across key shards (ops/map_orswot.py
    ``_any_slots``). Returns (converged state, overflow[2])."""
    return _mesh_fold_lattice(
        "map_orswot_fold", state, mesh,
        partial(mo_ops.join, element_axis=ELEMENT_AXIS),
        partial(mo_ops.fold, element_axis=ELEMENT_AXIS),
        map_orswot_specs(), map_orswot_out_specs(),
        telemetry=telemetry,
        slots_fn=lambda a, b: ops.changed_members(a.core, b.core),
        element_sharded=True, donate=donate,
        pad=lambda st: pad_map_orswot(
            st, mesh.shape[REPLICA_AXIS], mesh.shape[ELEMENT_AXIS]),
    )


def mesh_fold_nested_map(
    state: NestedMapState, mesh: Mesh, telemetry: bool = False,
    donate: bool = False,
) -> Tuple[NestedMapState, jax.Array]:
    """Full-mesh anti-entropy for ``Map<K1, Map<K2, MVReg>>`` over the
    (replica × outer-key) mesh (K1*K2 blocks per shard). Returns
    (converged state, overflow[3])."""
    return _mesh_fold_lattice(
        "nested_map_fold", state, mesh,
        partial(nested_ops.join, element_axis=ELEMENT_AXIS),
        partial(nested_ops.fold, element_axis=ELEMENT_AXIS),
        nested_map_specs(), nested_map_out_specs(),
        telemetry=telemetry,
        slots_fn=lambda a, b: map_ops.changed_keys(a.m, b.m),
        element_sharded=True, donate=donate,
        pad=lambda st: pad_nested_map(
            st, mesh.shape[REPLICA_AXIS], mesh.shape[ELEMENT_AXIS]),
    )


def mesh_fold_gset(present: jax.Array, mesh: Mesh) -> jax.Array:
    """Converge a GSet replica batch ``present[R, M]`` over the mesh:
    member-sharded set union (logical OR) with the replica axis reduced —
    the simplest lattice (reference: src/gset.rs ``CvRDT::merge``).
    Returns the converged membership ``[M]`` (member-sharded)."""
    rsize = mesh.shape[REPLICA_AXIS]
    pad_r = (-present.shape[0]) % rsize
    if pad_r:
        present = jnp.pad(present, ((0, pad_r), (0, 0)))
    esize = mesh.shape[ELEMENT_AXIS]
    pad_m = (-present.shape[1]) % esize
    if pad_m:
        present = jnp.pad(present, ((0, 0), (0, pad_m)))
    m = present.shape[1] - pad_m

    def build():
        @partial(
            jax.shard_map,
            mesh=mesh,
            in_specs=(P(REPLICA_AXIS, ELEMENT_AXIS),),
            out_specs=P(ELEMENT_AXIS),
            check_vma=False,
        )
        def fold_fn(local):
            return (
                lax.psum(
                    jnp.any(local, axis=0).astype(jnp.int32), REPLICA_AXIS
                )
                > 0
            )

        return fold_fn

    metrics.count("anti_entropy.gset_fold_rounds")
    metrics.observe("anti_entropy.state_bytes", float(present.nbytes))
    with tele.span("ae.gset_fold"):
        out = _cached("gset_fold", present, mesh, build)(present)
        jax.block_until_ready(out)
    return out[:m]


def _pad_with_identity(states, rsize: int, ident):
    """Pad the replica axis to a multiple of the mesh's replica-axis
    size with join identities (absorbed by any lattice join)."""
    lead = jax.tree.leaves(states)[0].shape[0]
    if lead % rsize == 0:
        return states
    return jax.tree.map(
        lambda x, p: jnp.concatenate([x, p.astype(x.dtype)], axis=0),
        states,
        ident,
    )


def mesh_fold_lww(states, mesh: Mesh, telemetry: bool = False,
                  donate: bool = False):
    """Converge an LWWReg replica batch (LWWState with leading axis R)
    over the mesh's replica axis. Returns ``(state, conflict)``;
    conflict marks an equal-marker/different-value merge anywhere
    (reference: src/lwwreg.rs validate_merge)."""
    from ..ops import lwwreg as lww_ops

    rsize = mesh.shape[REPLICA_AXIS]
    pad_r = (-states.hi.shape[0]) % rsize
    states = _pad_with_identity(
        states, rsize, lww_ops.empty(batch=(pad_r,)) if pad_r else None
    )

    template = lww_ops.empty()
    return _mesh_fold_lattice(
        "lww_fold", states, mesh,
        lww_ops.join, lww_ops.fold,
        jax.tree.map(lambda _: P(REPLICA_AXIS), template),
        jax.tree.map(lambda _: P(), template),
        telemetry=telemetry, donate=donate,
    )


def mesh_fold_mvreg(states, mesh: Mesh, telemetry: bool = False,
                    donate: bool = False):
    """Converge an MVReg replica batch (MVRegState with leading axis R)
    over the mesh's replica axis: dominated contents die, concurrent
    siblings survive (reference: src/mvreg.rs ``CvRDT::merge``).
    Returns ``(state, overflow)``."""
    from ..ops import mvreg as mv

    rsize = mesh.shape[REPLICA_AXIS]
    pad_r = (-states.wact.shape[0]) % rsize
    s, a = states.wact.shape[-1], states.clk.shape[-1]
    states = _pad_with_identity(
        states, rsize, mv.empty(s, a, batch=(pad_r,)) if pad_r else None
    )

    template = mv.empty(s, a)
    return _mesh_fold_lattice(
        "mvreg_fold", states, mesh,
        mv.join, mv.fold,
        jax.tree.map(lambda _: P(REPLICA_AXIS), template),
        jax.tree.map(lambda _: P(), template),
        telemetry=telemetry, donate=donate,
    )


def _sparse_pad_and_template(states, rsize: int):
    """Identity-pad a sparse replica batch to the mesh's replica-axis
    size and build the (unbatched) spec template — shared shape plumbing
    for the sparse mesh entry points."""
    from ..ops import sparse_orswot as sp

    shape_args = (
        states.eid.shape[-1],
        states.top.shape[-1],
        states.dcl.shape[-2],
        states.didx.shape[-1],
    )
    pad_r = (-states.top.shape[0]) % rsize
    states = _pad_with_identity(
        states, rsize, sp.empty(*shape_args, batch=(pad_r,)) if pad_r else None
    )
    return states, sp.empty(*shape_args)


def mesh_fold_sparse(states, mesh: Mesh, telemetry: bool = False,
                     donate: bool = False):
    """Converge a SPARSE (segment-encoded) ORSWOT replica batch over the
    mesh's replica axis, with the segment table REPLICATED across the
    element axis — the simple layout for moderate dot counts. For true
    element scaling, partition the table by ``eid % S`` and use
    ``sparse_shard.mesh_fold_sparse_sharded`` (per-device state and join
    cost drop by S; restriction commutes with the join, so shard-local
    joins are exact). Returns ``(state, overflow[2])``."""
    from ..ops import sparse_orswot as sp

    states, template = _sparse_pad_and_template(
        states, mesh.shape[REPLICA_AXIS]
    )
    return _mesh_fold_lattice(
        "sparse_orswot_fold", states, mesh,
        sp.join, sp.fold,
        jax.tree.map(lambda _: P(REPLICA_AXIS), template),
        jax.tree.map(lambda _: P(), template),
        telemetry=telemetry, slots_fn=sp.changed_dots, donate=donate,
    )


def _sparse_mvmap_pad_and_template(states, rsize: int):
    """Identity-pad a sparse Map<K, MVReg> replica batch to the mesh's
    replica-axis size and build the (unbatched) spec template — shared
    shape plumbing for the two mesh entry points (the mvmap analog of
    ``_sparse_pad_and_template``)."""
    from ..ops import sparse_mvmap as smv

    shape_args = (
        states.kid.shape[-1],
        states.top.shape[-1],
        states.dcl.shape[-2],
        states.kidx.shape[-1],
    )
    pad_r = (-states.top.shape[0]) % rsize
    states = _pad_with_identity(
        states, rsize, smv.empty(*shape_args, batch=(pad_r,)) if pad_r else None
    )
    return states, smv.empty(*shape_args)


def mesh_fold_sparse_mvmap(
    states, mesh: Mesh, sibling_cap: int = 4, telemetry: bool = False,
    donate: bool = False,
):
    """Converge a SPARSE ``Map<K, MVReg>`` replica batch
    (ops/sparse_mvmap) over the mesh's replica axis, cell table
    replicated across the element axis — the layout that pairs with the
    backend's live-cell-proportional state (the key universe is
    virtual, so there is nothing to shard until cell counts demand it).
    Returns ``(state, overflow[3])``."""
    from ..ops import sparse_mvmap as smv

    states, template = _sparse_mvmap_pad_and_template(
        states, mesh.shape[REPLICA_AXIS]
    )
    return _mesh_fold_lattice(
        f"sparse_mvmap_fold_s{sibling_cap}", states, mesh,
        partial(smv.join, sibling_cap=sibling_cap),
        partial(smv.fold, sibling_cap=sibling_cap),
        jax.tree.map(lambda _: P(REPLICA_AXIS), template),
        jax.tree.map(lambda _: P(), template),
        telemetry=telemetry, slots_fn=smv.changed_cells, donate=donate,
    )


def mesh_gossip_sparse_mvmap(
    states, mesh: Mesh, rounds: Optional[int] = None, sibling_cap: int = 4,
    telemetry: bool = False, donate: bool = False,
    stability: bool = False,
    faults=None,
    lag_threshold=None,
):
    """Ring anti-entropy for SPARSE ``Map<K, MVReg>`` replica batches
    over the replica axis — per-round traffic is one cell table per
    link, proportional to LIVE cells, not the key universe. Same
    replicated-element-axis layout as ``mesh_fold_sparse_mvmap``."""
    from ..ops import sparse_mvmap as smv

    states, template = _sparse_mvmap_pad_and_template(
        states, mesh.shape[REPLICA_AXIS]
    )
    return _mesh_gossip_lattice(
        f"sparse_mvmap_gossip_s{sibling_cap}", states, mesh,
        partial(smv.join, sibling_cap=sibling_cap),
        partial(smv.fold, sibling_cap=sibling_cap),
        jax.tree.map(lambda _: P(REPLICA_AXIS), template), rounds,
        telemetry=telemetry, slots_fn=smv.changed_cells,
        element_sharded=False, donate=donate,
        stability=stability, compact_fn=smv.compact,
        faults=faults, lag_threshold=lag_threshold,
    )


def mesh_fold_sparse_nested(states, mesh: Mesh, level,
                            telemetry: bool = False, donate: bool = False):
    """Converge a SPARSE nested-map replica batch (any
    ``sparse_nest.SparseNestLevel`` composition — e.g. the
    ``Map<K1, Map<K2, MVReg>>`` of ops/sparse_mvmap.level_map_mvreg)
    over the mesh's replica axis, state replicated across the element
    axis. ``level`` carries the join/fold (and their static caps).
    Returns ``(state, flags[L+1])``."""
    states, template, kind = _sparse_nested_pad_and_key(
        states, mesh.shape[REPLICA_AXIS], level, "fold"
    )
    return _mesh_fold_lattice(
        kind, states, mesh,
        level.join, level.fold,
        jax.tree.map(lambda _: P(REPLICA_AXIS), template),
        jax.tree.map(lambda _: P(), template),
        telemetry=telemetry, donate=donate,
    )


def _sparse_nested_pad_and_key(states, rsize: int, level, op: str):
    """Identity-pad a nested sparse replica batch and derive the memo
    key for its mesh entry points. The key MUST come from the level's
    static shape/caps — an id()-based key could be reused after GC and
    resurrect a compiled closure with the wrong caps."""
    from ..ops.sparse_nest import _sparse_identity_like

    pad_r = (-jax.tree.leaves(states)[0].shape[0]) % rsize
    if pad_r:
        identity = _sparse_identity_like(jax.tree.map(
            lambda x: jnp.zeros((pad_r, *x.shape[1:]), x.dtype), states
        ))
        states = jax.tree.map(
            lambda s, p: jnp.concatenate([s, p], axis=0), states, identity
        )
    template = jax.tree.map(lambda x: x[0], states)
    spans, core = [], level
    while hasattr(core, "core"):
        spans.append(str(core.span))
        core = core.core
    kind = (
        f"sparse_nested_{op}_{'x'.join(spans)}"
        f"_s{getattr(core, 'sibling_cap', 0)}"
    )
    return states, template, kind


def mesh_gossip_sparse_nested(
    states, mesh: Mesh, level, rounds: Optional[int] = None,
    telemetry: bool = False, donate: bool = False,
    stability: bool = False,
    faults=None,
    lag_threshold=None,
):
    """Ring anti-entropy for SPARSE nested-map replica batches (any
    ``SparseNestLevel`` composition) over the replica axis — per-round
    traffic is one live-content-proportional state per link. State
    replicated across the element axis (the sharded fold is the
    element-scaling mode)."""
    from ..ops import sparse_nest as nest_ops

    states, template, kind = _sparse_nested_pad_and_key(
        states, mesh.shape[REPLICA_AXIS], level, "gossip"
    )
    return _mesh_gossip_lattice(
        kind, states, mesh, level.join, level.fold,
        jax.tree.map(lambda _: P(REPLICA_AXIS), template), rounds,
        telemetry=telemetry, element_sharded=False, donate=donate,
        stability=stability, compact_fn=nest_ops.compact,
        faults=faults, lag_threshold=lag_threshold,
    )


def mesh_gossip_sparse(
    states, mesh: Mesh, rounds: Optional[int] = None,
    telemetry: bool = False, donate: bool = False,
    stability: bool = False,
    faults=None,
    lag_threshold=None,
):
    """Ring anti-entropy for SPARSE (segment-encoded) ORSWOT replica
    batches over the replica axis (the bounded-bandwidth mode —
    per-round traffic is one segment table per link, which for sparse
    states is proportional to LIVE dots, not the universe). Same
    replicated-element-axis layout as ``mesh_fold_sparse``."""
    from ..ops import sparse_orswot as sp

    states, template = _sparse_pad_and_template(
        states, mesh.shape[REPLICA_AXIS]
    )
    return _mesh_gossip_lattice(
        "sparse_gossip", states, mesh, sp.join, sp.fold,
        jax.tree.map(lambda _: P(REPLICA_AXIS), template), rounds,
        telemetry=telemetry, slots_fn=sp.changed_dots,
        element_sharded=False, donate=donate,
        stability=stability, compact_fn=sp.compact,
        faults=faults, lag_threshold=lag_threshold,
    )


def gossip_elastic(model, mesh: Mesh, rounds: Optional[int] = None,
                   policy=None, telemetry: bool = False,
                   donate: bool = False, stability: bool = False,
                   reclaim=None, faults=None, lag_threshold=None):
    """Ring anti-entropy with elastic capacity recovery — the
    overflow→widen→resume loop at mesh scale (elastic.py).

    Runs the model family's ring gossip on ``model.state``; when a
    capacity lane overflows mid-round, the round's result is DISCARDED
    (the gossip entry points never commit to the model, and the join is
    idempotent, so re-entering from the pre-round state is sound), the
    implicated axis widens 2× (policy-configurable) with the live state
    re-encoded on device, and the ring re-enters. Because the widened
    state is bit-identical to a from-scratch wider model, the re-entered
    gossip converges to exactly the full join of the wider family —
    replicas pause, migrate, and rejoin; nothing replays.

    Returns ``(rows, widened)``: ``rows`` are the per-device converged
    states ([P, ...] — every row equals the full join after the default
    P-1 rounds, as in ``mesh_gossip``), ``widened`` the dict of axes
    grown along the way (empty when capacity sufficed). Widening is
    administrative — apply the same growth on every host holding the
    replica set before the next round (elastic.py module docstring).

    ``telemetry=True`` appends a Telemetry pytree folded across every
    attempt (``telemetry.combine``: counters from discarded overflow
    runs still count — they were real work — while the final-state
    gauges come from the successful run).

    ``donate=True`` donates each attempt's state into the ring (the
    gossip rows then alias it in place — ``_mesh_gossip_lattice``) and
    restores ``model.state`` from a pre-round device copy afterwards:
    the overflow→widen fallback needs the pre-round state alive across
    a failed attempt, so the wrapper trades the ring-internal second
    state copy for one explicit snapshot while keeping the model
    coherent either way.

    ``stability=True`` threads the flag into the ring (the rows come
    back compacted, the mesh-wide frontier rides as the LAST tuple
    element — reclaim/). ``reclaim=`` takes an ``elastic.Hysteresis``
    tracker and is the shrink analog of the widen loop: after the
    successful attempt it observes the model's occupancy and — once the
    low-water streak clears — narrows the implicated axes in place, so
    the model carries the reclaimed capacity into its next round
    (administrative, like widening: apply identically on every host).

    ``faults=`` threads a ``crdt_tpu.faults.FaultPlan`` into every
    attempt; the LAST tuple element is then the ``FaultCounters``
    pytree with packet counters summed across attempts.
    ``lag_threshold=`` is the frontier-stall alert
    (``_mesh_gossip_lattice``)."""
    from .. import elastic
    from ..models.map import BatchedMap
    from ..models.orswot import BatchedOrswot
    from ..models.sparse_mvmap import BatchedSparseMap
    from ..models.sparse_nested_map import BatchedSparseNestedMap
    from ..models.sparse_orswot import BatchedSparseOrswot

    policy = policy or elastic.DEFAULT_POLICY

    def plan(m):
        # (gossip runner, overflow-flag lane -> elastic axis)
        if isinstance(m, BatchedOrswot):
            return (
                lambda: mesh_gossip(m.state, mesh, rounds,
                                    telemetry=telemetry, donate=donate,
                                    stability=stability, faults=faults,
                                    lag_threshold=lag_threshold),
                ("deferred_cap",),
            )
        if isinstance(m, BatchedSparseOrswot):
            return (
                lambda: mesh_gossip_sparse(m.state, mesh, rounds,
                                           telemetry=telemetry,
                                           donate=donate,
                                           stability=stability,
                                           faults=faults,
                                           lag_threshold=lag_threshold),
                ("dot_cap", "deferred_cap"),
            )
        if isinstance(m, BatchedMap):
            return (
                lambda: mesh_gossip_map(m.state, mesh, rounds,
                                        telemetry=telemetry,
                                        donate=donate,
                                        stability=stability,
                                        faults=faults,
                                        lag_threshold=lag_threshold),
                ("sibling_cap", "deferred_cap"),
            )
        if isinstance(m, BatchedSparseMap):
            return (
                lambda: mesh_gossip_sparse_mvmap(
                    m.state, mesh, rounds, sibling_cap=m.sibling_cap,
                    telemetry=telemetry, donate=donate,
                    stability=stability, faults=faults,
                    lag_threshold=lag_threshold,
                ),
                ("cell_cap", "deferred_cap", "sibling_cap"),
            )
        if isinstance(m, BatchedSparseNestedMap):
            return (
                lambda: mesh_gossip_sparse_nested(
                    m.state, mesh, m.level, rounds, telemetry=telemetry,
                    donate=donate, stability=stability, faults=faults,
                    lag_threshold=lag_threshold,
                ),
                ("cell_cap", "deferred_cap", "sibling_cap",
                 "key_deferred_cap"),
            )
        raise TypeError(
            f"gossip_elastic covers the batched set/map family, got "
            f"{type(m).__name__}"
        )

    widened: dict = {}
    migrations = 0
    tel = None
    fcs = None
    while True:
        run, lanes = plan(model)
        if donate:
            snap = jax.tree.map(jnp.copy, model.state)
        out = run()
        if donate:
            model.state = snap
        if faults is not None:
            from .. import faults as flt

            fcs = flt.accumulate_counters(fcs, out[-1])
            out = out[:-1]
        rows, flags = out[0], out[1]
        frontier = out[-1] if stability else None
        if telemetry:
            tel = out[2] if tel is None else tele.combine(tel, out[2])
        flags = jnp.atleast_1d(flags)
        hot = tuple(
            axis for lane, axis in enumerate(lanes) if bool(flags[lane])
        )
        if not hot:
            if reclaim is not None:
                # The shrink half of the elastic loop: COMMIT the
                # converged rows into the model (the shrink must narrow
                # the state the model carries into its next round, not
                # the stale pre-round one), then let the hysteresis
                # decide — see elastic.Hysteresis. After a reclaim
                # round, read the model, not the returned rows (a
                # shrink leaves them at the old capacity).
                _commit_rows(model, rows)
                reclaim.observe(model)
            ret = [rows, widened]
            if telemetry:
                ret.append(tel)
            if stability:
                ret.append(frontier)
            if fcs is not None:
                ret.append(fcs)
            return tuple(ret) if len(ret) > 2 else (rows, widened)
        if migrations >= policy.max_migrations:
            raise RuntimeError(
                f"gossip still overflowing after {migrations} migrations "
                f"(axes grown: {widened}) — raise policy.factor or "
                f"max_migrations"
            )
        metrics.count("elastic.gossip_migrations")
        widened.update(elastic.widen(model, hot, policy))
        migrations += 1


def _commit_rows(model, rows) -> None:
    """Commit gossip rows back into a model for the reclaim path: slice
    the identity-padded tail off and assign — skipped (model untouched)
    when the mesh padded other axes too and shapes cannot line up
    (shrinking the pre-round state is still sound; narrow refuses
    anything unfit)."""
    lead = jax.tree.leaves(model.state)[0].shape[0]
    sliced = jax.tree.map(lambda x: x[:lead], rows)
    if all(
        a.shape == b.shape and a.dtype == b.dtype
        for a, b in zip(jax.tree.leaves(sliced), jax.tree.leaves(model.state))
    ):
        model.state = sliced


def mesh_fold_clocks(clocks: jax.Array, mesh: Mesh) -> jax.Array:
    """Converge a batch of vector clocks [R, A] (VClock / GCounter /
    PNCounter states) over the mesh: local max + ``pmax`` across the
    replica axis. BASELINE configs 1–2 at mesh scale."""
    rsize = mesh.shape[REPLICA_AXIS]
    r = clocks.shape[0]
    pad = (-r) % rsize
    if pad:
        clocks = jnp.concatenate(
            [clocks, jnp.zeros((pad, clocks.shape[1]), clocks.dtype)], axis=0
        )

    def build():
        @partial(
            jax.shard_map,
            mesh=mesh,
            in_specs=(P(REPLICA_AXIS, None),),
            out_specs=P(None),
            check_vma=False,
        )
        def fold_fn(local):
            return all_reduce_clock(jnp.max(local, axis=0), REPLICA_AXIS)

        return fold_fn

    return _cached("clock_fold", clocks, mesh, build)(clocks)


def mesh_fold_map3(state, mesh: Mesh, telemetry: bool = False,
                   donate: bool = False):
    """Full-mesh anti-entropy for ``Map<K1, Map<K2, Orswot>>`` over the
    (replica × outer-key) mesh (K1×K2×M blocks per shard; ops/map3.py
    depth-3 slab composition). Returns (converged state, overflow[3])."""
    from ..ops import map3 as map3_ops
    from .mesh import map3_out_specs, map3_specs, pad_map3

    return _mesh_fold_lattice(
        "map3_fold", state, mesh,
        partial(map3_ops.join, element_axis=ELEMENT_AXIS),
        partial(map3_ops.fold, element_axis=ELEMENT_AXIS),
        map3_specs(), map3_out_specs(),
        telemetry=telemetry,
        slots_fn=lambda a, b: ops.changed_members(a.mo.core, b.mo.core),
        element_sharded=True, donate=donate,
        pad=lambda st: pad_map3(st, mesh.shape[REPLICA_AXIS], mesh.shape[ELEMENT_AXIS]),
    )


def mesh_gossip_map3(
    state, mesh: Mesh, rounds: Optional[int] = None, telemetry: bool = False,
    donate: bool = False, stability: bool = False, faults=None,
    lag_threshold=None,
):
    """Ring anti-entropy for ``Map<K1, Map<K2, Orswot>>`` replica blocks
    over the replica axis."""
    from ..ops import map3 as map3_ops
    from .mesh import map3_specs, pad_map3

    state = pad_map3(state, mesh.shape[REPLICA_AXIS], mesh.shape[ELEMENT_AXIS])
    return _mesh_gossip_lattice(
        "map3_gossip", state, mesh,
        partial(map3_ops.join, element_axis=ELEMENT_AXIS),
        partial(map3_ops.fold, element_axis=ELEMENT_AXIS),
        map3_specs(), rounds,
        telemetry=telemetry,
        slots_fn=lambda a, b: ops.changed_members(a.mo.core, b.mo.core),
        donate=donate, stability=stability, compact_fn=map3_ops.compact,
        faults=faults, lag_threshold=lag_threshold,
    )


# ---- static-analysis registration (crdt_tpu.analysis) --------------------
#
# Every mesh entry point defined here registers its jit-cache kind, an
# example-args builder (an R == P batch of join identities, in the
# shared gate geometry of crdt_tpu.analysis.gate_states) and an
# invoker. tools/check_aliasing.py and crdt_tpu.analysis.jit_lint
# iterate this registry, and a public ``mesh_*`` entry that forgets to
# register fails discovery (tests/test_analysis.py).

from ..analysis import gate_states as _gs  # noqa: E402
from ..analysis.registry import register_entry_point as _reg_ep  # noqa: E402


def _reg(name, kind, mk, call, n_donated):
    _reg_ep(
        name, kind=kind,
        make_args=lambda mesh: (mk(_gs.replicas(mesh)),),
        invoke=lambda mesh, args: call(args[0], mesh),
        n_donated=n_donated,
        # The collective-semantics lint fails any collective touching an
        # axis name outside this set (jit_lint.py).
        mesh_axes=(REPLICA_AXIS, ELEMENT_AXIS),
    )


def _reg_gossip(name, kind, mk, call):
    _reg(name, kind, mk, call, n_donated=1)


def _reg_fold(name, kind, mk, call):
    _reg(name, kind, mk, call, n_donated=0)


_reg_gossip(
    "mesh_gossip", "orswot_gossip", _gs.mk_dense,
    lambda s, mesh: mesh_gossip(s, mesh, local_fold="tree", donate=True),
)
_reg_gossip(
    "mesh_gossip_map", "map_gossip", _gs.mk_map,
    lambda s, mesh: mesh_gossip_map(s, mesh, donate=True),
)
_reg_gossip(
    "mesh_gossip_map_orswot", "map_orswot_gossip", _gs.mk_map_orswot,
    lambda s, mesh: mesh_gossip_map_orswot(s, mesh, donate=True),
)
_reg_gossip(
    "mesh_gossip_nested_map", "nested_map_gossip", _gs.mk_nested_map,
    lambda s, mesh: mesh_gossip_nested_map(s, mesh, donate=True),
)
_reg_gossip(
    "mesh_gossip_map3", "map3_gossip", _gs.mk_map3,
    lambda s, mesh: mesh_gossip_map3(s, mesh, donate=True),
)
_reg_gossip(
    "mesh_gossip_sparse", "sparse_gossip", _gs.mk_sparse,
    lambda s, mesh: mesh_gossip_sparse(s, mesh, donate=True),
)
_reg_gossip(
    "mesh_gossip_sparse_mvmap", "sparse_mvmap_gossip_s4", _gs.mk_sparse_mvmap,
    lambda s, mesh: mesh_gossip_sparse_mvmap(s, mesh, donate=True),
)
_reg_gossip(
    "mesh_gossip_sparse_nested", f"sparse_nested_gossip_{_gs.GM}_s0",
    _gs.mk_sparse_nested,
    lambda s, mesh: mesh_gossip_sparse_nested(
        s, mesh, _gs.sparse_nested_level(), donate=True
    ),
)

_reg_fold(
    "mesh_fold", "orswot_fold", _gs.mk_dense,
    lambda s, mesh: mesh_fold(s, mesh, local_fold="tree"),
)
_reg_fold("mesh_fold_map", "map_fold", _gs.mk_map, mesh_fold_map)
_reg_fold(
    "mesh_fold_map_orswot", "map_orswot_fold", _gs.mk_map_orswot,
    mesh_fold_map_orswot,
)
_reg_fold(
    "mesh_fold_nested_map", "nested_map_fold", _gs.mk_nested_map,
    mesh_fold_nested_map,
)
_reg_fold("mesh_fold_map3", "map3_fold", _gs.mk_map3, mesh_fold_map3)
_reg_fold("mesh_fold_gset", "gset_fold", _gs.mk_gset, mesh_fold_gset)
_reg_fold("mesh_fold_lww", "lww_fold", _gs.mk_lww, mesh_fold_lww)
_reg_fold("mesh_fold_mvreg", "mvreg_fold", _gs.mk_mvreg, mesh_fold_mvreg)
_reg_fold(
    "mesh_fold_sparse", "sparse_orswot_fold", _gs.mk_sparse, mesh_fold_sparse
)
_reg_fold(
    "mesh_fold_sparse_mvmap", "sparse_mvmap_fold_s4", _gs.mk_sparse_mvmap,
    mesh_fold_sparse_mvmap,
)
_reg_fold(
    "mesh_fold_sparse_nested", f"sparse_nested_fold_{_gs.GM}_s0",
    _gs.mk_sparse_nested,
    lambda s, mesh: mesh_fold_sparse_nested(
        s, mesh, _gs.sparse_nested_level()
    ),
)
_reg_fold("mesh_fold_clocks", "clock_fold", _gs.mk_clocks, mesh_fold_clocks)

# Fault surfaces (crdt_tpu/faults/): every gossip entry above accepts
# faults=; registration is the coverage contract faults.static_checks
# enforces (an unregistered fault-capable public entry fails discovery).
from ..analysis.registry import register_fault_surface as _reg_fs  # noqa: E402

for _name in (
    "mesh_gossip", "mesh_gossip_map", "mesh_gossip_map_orswot",
    "mesh_gossip_nested_map", "mesh_gossip_map3", "mesh_gossip_sparse",
    "mesh_gossip_sparse_mvmap", "mesh_gossip_sparse_nested",
    "gossip_elastic",
):
    _reg_fs(_name, module=__name__)
