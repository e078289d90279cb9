"""jit-transparent telemetry: on-device convergence counters + spans.

The host-side registry (utils/metrics.py) goes blind exactly where
production traffic lives — inside jit, ``deferred_depth`` returns the
-1 traced sentinel and a fully jitted train/serve step records nothing.
This module is the device-side complement: a :class:`Telemetry` pytree
sidecar computed **in-kernel** with ``lax`` ops, so it survives
jit/shard_map, accumulates across gossip rounds, and returns alongside
state from the mesh entry points (``mesh_gossip*`` / ``mesh_fold*`` /
``run_delta_ring`` / ``gossip_elastic``) behind a ``telemetry=`` flag
that defaults off and traces NOTHING when disabled (the telemetry=False
program lowers to HLO identical to the flag-free one —
tests/test_telemetry.py pins this by ``lower().as_text()`` comparison).

The counters are the headline evaluation quantities of the δ-CRDT
literature (Almeida et al. 1603.01529; Enes et al. 1803.02750 — bytes
shipped and sync metadata per round), measured natively per round:

- ``merges``          — pairwise lattice-join applications (local fold
  joins, nominally rows-1, plus one per ring round per replica rank;
  all-reduce entry points count log2(P) / P-1 exchange joins),
  summed over replica ranks.
- ``slots_changed``   — content lanes the cross-replica joins actually
  changed (per-kind definition: dense ORSWOT members whose birth
  clocks changed, map keys whose cells changed, sparse dot/cell lanes
  changed; the generic fallback diffs every state plane).
- ``deferred_depth``  — final parked-slot depth: max over replicas of
  valid slots summed across every ``*dvalid`` buffer level (the same
  masked-epoch convention ``metrics.deferred_depth`` walks on host).
- ``bytes_exchanged`` — physical WIRE bytes shipped over mesh links:
  the per-device shipped pytree's STATIC bytes × exchanges, summed over
  ALL devices (element-axis copies each really transmit). Padded /
  invalid packet lanes count — this is what the links carry.
- ``bytes_useful``    — post-mask PAYLOAD bytes: only the packet lanes
  whose validity masks survive (δ-ring slot ``valid`` and parked
  ``*dvalid`` masks — :func:`packet_useful_bytes`), so digest gating's
  byte win is visible next to the unchanged wire count. Non-δ entry
  points ship whole states with no mask and report wire == useful.
- ``residue``         — the δ-ring convergence indicator
  (parallel/delta_ring.py); 0 for non-δ entry points.
- ``widen_pressure``  — max occupancy fraction over the bounded parked
  buffers (1.0 = at capacity: the in-jit analog of the
  ``elastic.<kind>.headroom`` gauges, which report 1 - this).
- ``reclaimed_slots`` / ``reclaimed_bytes`` — lanes retired and their
  static bytes discarded by in-kernel causal-stability compaction
  (reclaim/; populated by the ``stability=`` flag on the gossip entry
  points, 0 elsewhere — host-side reclamation paths count under the
  same names in the registry via ``reclaim.record_reclaim``).
- ``frontier_lag``    — max over replicas/actor lanes of
  ``top - stable_frontier`` (0 = fully stable mesh); a lag growing
  under steady traffic means a straggler is pinning the frontier and
  reclamation has stalled (reclaim/frontier.py).
- ``stream_blocks`` / ``stream_staged_bytes`` / ``stream_overlap_hit``
  — the replica-streaming fold's accounting (parallel/stream.py; the
  registry twins are ``stream.blocks`` / ``stream.staged_bytes`` /
  ``stream.overlap_hit``): blocks streamed through the accumulator,
  total bytes staged into device memory for them, and stagings whose
  upload was issued while the previous block's join was still in
  flight (the double-buffer overlap actually landing). Filled by the
  stream driver host-side — the per-block loop lives outside the
  kernels — and 0 on every non-streaming entry point.
- ``faults_dropped`` / ``faults_rejected`` / ``faults_delayed`` — the
  degraded-mesh accounting (crdt_tpu/faults/; registry twins
  ``telemetry.<kind>.faults.packets_*``): packets lost on an injected
  link drop, packets REJECTED by the in-kernel checksum lane
  (integrity.py — corrupted content is never joined), and packets the
  link held one round. Populated by the ``faults=`` flag on the mesh
  entry points, 0 elsewhere.
- ``bytes_acked_skipped`` / ``ack_window_depth`` — the ack-window
  accounting (crdt_tpu/delta_opt/ackwin.py; registry twins
  ``delta_opt.acked_skipped[.kind]``): payload bytes the per-link
  acked-interval window masked off the δ rings (the back-propagation
  win ON TOP of digest gating — ``bytes_useful`` already reflects it,
  this field attributes it), and the max per-device count of rows with
  a live acked watermark at run end. Populated by ``ack_window=True``
  on ``run_delta_ring`` and the ``mesh_delta_gossip*`` family, 0
  elsewhere.
- ``wal_bytes`` / ``wal_fsyncs`` / ``snapshots_written`` /
  ``replayed_records`` / ``torn_tail_truncated`` / ``recovery_rounds``
  — the crash-consistent durability accounting (crdt_tpu/durability/;
  registry twins ``durability.*``): δ-record payload bytes appended to
  the write-ahead log and fsync barriers issued for them (populated
  host-side by the ``wal=`` flag on the δ-ring entries and
  ``mesh_stream_fold*`` — the append loop lives outside the kernels,
  the ``stream_*`` discipline), snapshot generations committed, WAL
  records replayed by a recovery, torn/corrupt log tails truncated on
  open, and recovery passes completed. 0 on every non-durable run.
- ``live_ranks`` / ``scaleout_admits`` / ``scaleout_drains`` /
  ``bootstrap_bytes`` — the elastic mesh scale-out accounting
  (crdt_tpu/scaleout/; registry twins ``scaleout.admits`` /
  ``scaleout.drains`` / ``scaleout.bootstrap_bytes``): admitted ranks
  on the replica axis (a gauge — the mesh's current serving width),
  live rank joins completed, graceful drains whose drain-complete
  certificate was issued, and newcomer-bootstrap wire bytes (including
  fault re-ships). Filled host-side by ``ScaleoutMesh.annotate`` — the
  membership loop lives outside the kernels, the ``stream_*``/``wal_*``
  discipline — and 0 on every fixed-width run.

- ``wire_packed_bytes`` — the fused wire path's POST-PACKING byte
  count (crdt_tpu/parallel/wire.py; registry twins
  ``wire.packed_bytes[.kind]``): nonzero u32 words actually occupied
  on the bit-packed wire (bitmaps + u16-pair ids + watermark-encoded
  clock lanes), the bytes a zero-suppressing transport would carry —
  reported NEXT to ``bytes_exchanged`` (the static wire shape) and
  ``bytes_useful`` (the post-mask raw payload) so the packing win is
  attributable. 0 on every ``fused=False`` or non-δ run.

- ``live_tenants`` / ``evicted_tenants`` / ``ingest_coalesced_ops`` /
  ``hist_ingest_batch`` — the multi-tenant serving accounting
  (crdt_tpu/serve/; registry twins
  ``telemetry.<kind>.serve.ingest_coalesced_ops`` plus
  ``live_tenants``/``evicted_tenants`` gauges): the SERVED tenant
  population (every session the front door answers for — device
  residency may be far smaller under the lane indirection; the
  resident count rides the ``serve.*`` registry counters) and tenants
  currently parked in the durable tier (gauges, filled host-side by
  ``Superblock.annotate``), ops that shared an ingest slab lane with a
  predecessor (each one a device dispatch the coalescing queue
  amortized away), and the per-flush applied-batch-size distribution
  (``IngestQueue.annotate`` — the ``stream_*``/``wal_*`` host-side
  fill discipline; 0/empty on every non-serving run).
- ``subscribers_live`` / ``cohorts_per_dispatch`` /
  ``delta_push_bytes`` / ``resync_fallbacks`` / ``hist_push_bytes`` —
  the δ-subscription fan-out accounting (crdt_tpu/fanout/; registry
  twins ``telemetry.<kind>.fanout.*`` plus a ``subscribers_live``
  gauge): live registered subscribers (a gauge, filled host-side by
  ``FanoutPlane.annotate``), watermark cohorts decomposed per push
  dispatch (each one a shared δ-decompose amortized over its whole
  cohort), δ payload bytes actually pushed to subscribers (post
  zero-suppression — the bytes a thin client's wire carries), pushes
  that degraded to the snapshot+suffix bootstrap resync instead of a
  δ (slow/dead subscribers — scaleout/bootstrap.py), and the
  per-cohort push-bytes distribution (in-kernel, riding the
  ``mesh_fanout_push`` telemetry branch). 0/empty on every
  non-fan-out run.
- ``serve_wal_bytes`` / ``serve_overlap_hit`` / ``rebalance_moves`` /
  ``hist_persist_us`` — the pipelined serving-loop accounting
  (crdt_tpu/serve/wal.py, loop.py, shard.py; registry twins
  ``telemetry.<kind>.serve.wal_bytes`` / ``.serve.overlap_hit`` /
  ``.serve.rebalance_moves``): dirty-tenant WAL bytes group-committed
  ahead of the dispatches (the durability cost of the
  log-before-dispatch ack), pipelined rounds whose slab assembly + WAL
  append genuinely hid in-flight device time (the serving twin of
  ``stream_overlap_hit``), skew-driven shard-map override moves
  applied by ``apply_rebalance``, and the per-row background-persist
  wall-clock distribution (``BackgroundPersister`` — the persists the
  pipeline moved OFF the dispatch latency path). Filled host-side by
  ``IngestQueue.annotate`` / ``ServeLoop.annotate``; 0/empty on every
  non-serving run.
- ``regions_live`` / ``geo_home_tenants`` / ``geo_exchanges`` /
  ``geo_exchange_bytes`` / ``geo_full_mirror_bytes`` /
  ``geo_failovers`` / ``hist_geo_watermark_lag`` — the geo-federation
  accounting (crdt_tpu/geo/; registry twins
  ``telemetry.<kind>.geo.*`` plus ``regions_live``/
  ``geo_home_tenants`` gauges): live federation regions and tenants
  homed across them (gauges, filled host-side by
  ``Federation.annotate``), cross-region anti-entropy rounds
  completed, the δ-lane wire bytes those rounds actually shipped NEXT
  to the full-state mirroring baseline they undercut (the
  partial-replication economics, attributable per run), region-kill
  re-homings completed, and the per-read mirror watermark-lag
  distribution (geo/reads.py certificates — how stale local reads
  actually ran). 0/empty on every non-federated run.
- ``hist_residue`` / ``hist_useful_bytes`` / ``hist_ack_depth`` /
  ``hist_packed_bytes`` / ``hist_dispatch_us`` — the in-kernel
  DISTRIBUTIONS
  (crdt_tpu/obs/hist.py :class:`~crdt_tpu.obs.hist.Hist` subtrees:
  log2 bucket counts + exact total; registry summary twins
  ``telemetry.<kind>.hist.<name>.p50/p95/p99`` plus per-bucket
  counters): per-round per-device unshipped-backlog rows (the residue
  quantity, observed EVERY ring round inside the loop carry),
  per-round post-mask payload bytes (digest + ack-window gating's
  round-shape, not just its total), per-round ack-window depth
  (``ack_window=True`` only), and host-timed per-dispatch wall-clock
  in MICROSECONDS (filled at the host boundary by
  :func:`time_dispatch` — the ``stream_*``/``wal_*`` discipline;
  includes compile time on a cold jit cache). The first three
  accumulate lax-only in the δ-ring loop, so they survive jit and
  shard_map and psum across the mesh like every scalar counter;
  non-δ entry points leave them empty.

Every non-histogram field is a replicated scalar, so the whole pytree
costs one word of output per field (plus one 32-lane counter plane per
histogram) and no extra collectives beyond one psum/pmax fusion group.

Span tracing (:func:`span`) is the host-side half: a context manager
around a layer boundary that records only while someone listens — a
profiler session, a JSONL path (``configure_tracing``) or a flight
recorder — and is one flag check otherwise. A recorded span is a
``jax.profiler.TraceAnnotation`` on the device trace's clock, a
structured trace event (``drain_events`` empties the in-memory ring)
and a per-name aggregate (:func:`span_stats`). Exporting both worlds —
registry snapshots, Telemetry pytrees, spans — to Prometheus text and
JSONL lives in :mod:`crdt_tpu.exporter`.
"""

from __future__ import annotations

import json
import numbers
import threading
import time
from collections import deque
from typing import Any, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp

from .obs import hist as obs_hist
from .obs import recorder as _rec
from .utils.metrics import metrics


class Telemetry(NamedTuple):
    """On-device convergence counters (a pytree of replicated scalars)."""

    merges: jax.Array          # uint32 — join applications
    slots_changed: jax.Array   # uint32 — content lanes changed by joins
    deferred_depth: jax.Array  # uint32 — final max parked-slot depth
    bytes_exchanged: jax.Array # float32 — physical WIRE bytes over links
    bytes_useful: jax.Array    # float32 — post-mask payload bytes
    residue: jax.Array         # int32 — δ-ring residue (0 elsewhere)
    widen_pressure: jax.Array  # float32 — max parked-buffer occupancy
    reclaimed_slots: jax.Array # uint32 — lanes retired by compaction
    reclaimed_bytes: jax.Array # float32 — static bytes those lanes held
    frontier_lag: jax.Array    # uint32 — max(top - stable frontier)
    stream_blocks: jax.Array   # uint32 — replica blocks streamed
    stream_staged_bytes: jax.Array # float32 — bytes staged for blocks
    stream_overlap_hit: jax.Array  # uint32 — overlapped block uploads
    faults_dropped: jax.Array  # uint32 — packets lost to injected drops
    faults_rejected: jax.Array # uint32 — packets failing the checksum lane
    faults_delayed: jax.Array  # uint32 — packets held one round by a link
    bytes_acked_skipped: jax.Array # float32 — δ bytes the ack window masked
    ack_window_depth: jax.Array    # uint32 — max rows with a live ack mark
    wal_bytes: jax.Array           # float32 — δ-record bytes appended to WAL
    wal_fsyncs: jax.Array          # uint32 — fsync barriers for those appends
    snapshots_written: jax.Array   # uint32 — snapshot generations committed
    replayed_records: jax.Array    # uint32 — WAL records replayed on recovery
    torn_tail_truncated: jax.Array # uint32 — torn/corrupt WAL tails truncated
    recovery_rounds: jax.Array     # uint32 — recovery passes completed
    live_ranks: jax.Array          # uint32 — admitted ranks on the mesh axis
    scaleout_admits: jax.Array     # uint32 — live rank joins completed
    scaleout_drains: jax.Array     # uint32 — graceful drains certified
    bootstrap_bytes: jax.Array     # float32 — newcomer bootstrap wire bytes
    wire_packed_bytes: jax.Array   # float32 — post-packing bytes on the wire
    live_tenants: jax.Array        # uint32 — served tenant population
    evicted_tenants: jax.Array     # uint32 — tenants parked in the durable tier
    ingest_coalesced_ops: jax.Array  # uint32 — ops that shared a slab lane
    subscribers_live: jax.Array      # uint32 — live registered subscribers
    cohorts_per_dispatch: jax.Array  # uint32 — watermark cohorts decomposed
    delta_push_bytes: jax.Array      # float32 — δ bytes pushed to subscribers
    resync_fallbacks: jax.Array      # uint32 — pushes degraded to bootstrap
    serve_wal_bytes: jax.Array       # float32 — dirty-tenant WAL bytes appended
    serve_overlap_hit: jax.Array     # uint32 — pipelined rounds that hid device time
    rebalance_moves: jax.Array       # uint32 — skew-driven shard-map moves
    regions_live: jax.Array          # uint32 — live federation regions
    geo_home_tenants: jax.Array      # uint32 — tenants homed across live regions
    geo_exchanges: jax.Array         # uint32 — cross-region anti-entropy rounds
    geo_exchange_bytes: jax.Array    # float32 — δ bytes shipped cross-region
    geo_full_mirror_bytes: jax.Array # float32 — full-state mirroring baseline
    geo_failovers: jax.Array         # uint32 — region-kill re-homings
    hist_residue: obs_hist.Hist    # per-round unshipped-backlog rows
    hist_useful_bytes: obs_hist.Hist  # per-round post-mask payload bytes
    hist_ack_depth: obs_hist.Hist  # per-round ack-window depth
    hist_packed_bytes: obs_hist.Hist  # per-round post-packing wire bytes
    hist_dispatch_us: obs_hist.Hist   # host-timed dispatch wall-clock (µs)
    hist_ingest_batch: obs_hist.Hist  # per-flush coalesced-batch op count
    hist_push_bytes: obs_hist.Hist    # per-cohort δ push payload bytes
    hist_persist_us: obs_hist.Hist    # per-row background persist wall-clock (µs)
    # Trace-plane stage latencies (crdt_tpu/obs/trace.py — host-filled
    # per completed sampled trace via Tracer.annotate):
    hist_queue_wait_us: obs_hist.Hist    # submit → coalesce
    hist_dispatch_gap_us: obs_hist.Hist  # coalesce → dispatch
    hist_durable_lag_us: obs_hist.Hist   # dispatch → durable (WAL/persist)
    hist_push_lag_us: obs_hist.Hist      # dispatch → fan-out push
    hist_ack_lag_us: obs_hist.Hist       # push → client ack
    hist_freshness_us: obs_hist.Hist     # submit → client ack (end-to-end)
    hist_geo_watermark_lag: obs_hist.Hist  # per-read mirror watermark lag


def zeros() -> Telemetry:
    """The accumulation identity."""
    return Telemetry(
        merges=jnp.zeros((), jnp.uint32),
        slots_changed=jnp.zeros((), jnp.uint32),
        deferred_depth=jnp.zeros((), jnp.uint32),
        bytes_exchanged=jnp.zeros((), jnp.float32),
        bytes_useful=jnp.zeros((), jnp.float32),
        residue=jnp.zeros((), jnp.int32),
        widen_pressure=jnp.zeros((), jnp.float32),
        reclaimed_slots=jnp.zeros((), jnp.uint32),
        reclaimed_bytes=jnp.zeros((), jnp.float32),
        frontier_lag=jnp.zeros((), jnp.uint32),
        stream_blocks=jnp.zeros((), jnp.uint32),
        stream_staged_bytes=jnp.zeros((), jnp.float32),
        stream_overlap_hit=jnp.zeros((), jnp.uint32),
        faults_dropped=jnp.zeros((), jnp.uint32),
        faults_rejected=jnp.zeros((), jnp.uint32),
        faults_delayed=jnp.zeros((), jnp.uint32),
        bytes_acked_skipped=jnp.zeros((), jnp.float32),
        ack_window_depth=jnp.zeros((), jnp.uint32),
        wal_bytes=jnp.zeros((), jnp.float32),
        wal_fsyncs=jnp.zeros((), jnp.uint32),
        snapshots_written=jnp.zeros((), jnp.uint32),
        replayed_records=jnp.zeros((), jnp.uint32),
        torn_tail_truncated=jnp.zeros((), jnp.uint32),
        recovery_rounds=jnp.zeros((), jnp.uint32),
        live_ranks=jnp.zeros((), jnp.uint32),
        scaleout_admits=jnp.zeros((), jnp.uint32),
        scaleout_drains=jnp.zeros((), jnp.uint32),
        bootstrap_bytes=jnp.zeros((), jnp.float32),
        wire_packed_bytes=jnp.zeros((), jnp.float32),
        live_tenants=jnp.zeros((), jnp.uint32),
        evicted_tenants=jnp.zeros((), jnp.uint32),
        ingest_coalesced_ops=jnp.zeros((), jnp.uint32),
        subscribers_live=jnp.zeros((), jnp.uint32),
        cohorts_per_dispatch=jnp.zeros((), jnp.uint32),
        delta_push_bytes=jnp.zeros((), jnp.float32),
        resync_fallbacks=jnp.zeros((), jnp.uint32),
        serve_wal_bytes=jnp.zeros((), jnp.float32),
        serve_overlap_hit=jnp.zeros((), jnp.uint32),
        rebalance_moves=jnp.zeros((), jnp.uint32),
        regions_live=jnp.zeros((), jnp.uint32),
        geo_home_tenants=jnp.zeros((), jnp.uint32),
        geo_exchanges=jnp.zeros((), jnp.uint32),
        geo_exchange_bytes=jnp.zeros((), jnp.float32),
        geo_full_mirror_bytes=jnp.zeros((), jnp.float32),
        geo_failovers=jnp.zeros((), jnp.uint32),
        hist_residue=obs_hist.zeros(),
        hist_useful_bytes=obs_hist.zeros(),
        hist_ack_depth=obs_hist.zeros(),
        hist_packed_bytes=obs_hist.zeros(),
        hist_dispatch_us=obs_hist.zeros(),
        hist_ingest_batch=obs_hist.zeros(),
        hist_push_bytes=obs_hist.zeros(),
        hist_persist_us=obs_hist.zeros(),
        hist_queue_wait_us=obs_hist.zeros(),
        hist_dispatch_gap_us=obs_hist.zeros(),
        hist_durable_lag_us=obs_hist.zeros(),
        hist_push_lag_us=obs_hist.zeros(),
        hist_ack_lag_us=obs_hist.zeros(),
        hist_freshness_us=obs_hist.zeros(),
        hist_geo_watermark_lag=obs_hist.zeros(),
    )


def specs() -> Telemetry:
    """shard_map out_specs: every field is replicated — scalars and
    the ``hist_*`` counter planes alike (the Hist subtrees mirror
    their structure so no pytree-prefix resolution is needed)."""
    from jax.sharding import PartitionSpec as P

    return Telemetry(*(
        obs_hist.Hist(counts=P(), total=P())
        if obs_hist.is_hist_field(f) else P()
        for f in Telemetry._fields
    ))


def combine(a: Telemetry, b: Telemetry) -> Telemetry:
    """Fold two runs' telemetry (e.g. across elastic migrations):
    throughput counters add; the final-state gauges (depth, residue,
    pressure) come from the LATER run — they describe where the state
    ended, not a rate."""
    return Telemetry(
        merges=a.merges + b.merges,
        slots_changed=a.slots_changed + b.slots_changed,
        bytes_exchanged=a.bytes_exchanged + b.bytes_exchanged,
        bytes_useful=a.bytes_useful + b.bytes_useful,
        reclaimed_slots=a.reclaimed_slots + b.reclaimed_slots,
        reclaimed_bytes=a.reclaimed_bytes + b.reclaimed_bytes,
        stream_blocks=a.stream_blocks + b.stream_blocks,
        stream_staged_bytes=a.stream_staged_bytes + b.stream_staged_bytes,
        stream_overlap_hit=a.stream_overlap_hit + b.stream_overlap_hit,
        faults_dropped=a.faults_dropped + b.faults_dropped,
        faults_rejected=a.faults_rejected + b.faults_rejected,
        faults_delayed=a.faults_delayed + b.faults_delayed,
        bytes_acked_skipped=a.bytes_acked_skipped + b.bytes_acked_skipped,
        wal_bytes=a.wal_bytes + b.wal_bytes,
        wal_fsyncs=a.wal_fsyncs + b.wal_fsyncs,
        snapshots_written=a.snapshots_written + b.snapshots_written,
        replayed_records=a.replayed_records + b.replayed_records,
        torn_tail_truncated=a.torn_tail_truncated + b.torn_tail_truncated,
        recovery_rounds=a.recovery_rounds + b.recovery_rounds,
        scaleout_admits=a.scaleout_admits + b.scaleout_admits,
        scaleout_drains=a.scaleout_drains + b.scaleout_drains,
        bootstrap_bytes=a.bootstrap_bytes + b.bootstrap_bytes,
        wire_packed_bytes=a.wire_packed_bytes + b.wire_packed_bytes,
        ingest_coalesced_ops=(
            a.ingest_coalesced_ops + b.ingest_coalesced_ops
        ),
        cohorts_per_dispatch=(
            a.cohorts_per_dispatch + b.cohorts_per_dispatch
        ),
        delta_push_bytes=a.delta_push_bytes + b.delta_push_bytes,
        resync_fallbacks=a.resync_fallbacks + b.resync_fallbacks,
        serve_wal_bytes=a.serve_wal_bytes + b.serve_wal_bytes,
        serve_overlap_hit=a.serve_overlap_hit + b.serve_overlap_hit,
        rebalance_moves=a.rebalance_moves + b.rebalance_moves,
        geo_exchanges=a.geo_exchanges + b.geo_exchanges,
        geo_exchange_bytes=a.geo_exchange_bytes + b.geo_exchange_bytes,
        geo_full_mirror_bytes=(
            a.geo_full_mirror_bytes + b.geo_full_mirror_bytes
        ),
        geo_failovers=a.geo_failovers + b.geo_failovers,
        hist_residue=obs_hist.merge(a.hist_residue, b.hist_residue),
        hist_useful_bytes=obs_hist.merge(
            a.hist_useful_bytes, b.hist_useful_bytes
        ),
        hist_ack_depth=obs_hist.merge(a.hist_ack_depth, b.hist_ack_depth),
        hist_packed_bytes=obs_hist.merge(
            a.hist_packed_bytes, b.hist_packed_bytes
        ),
        hist_dispatch_us=obs_hist.merge(
            a.hist_dispatch_us, b.hist_dispatch_us
        ),
        hist_ingest_batch=obs_hist.merge(
            a.hist_ingest_batch, b.hist_ingest_batch
        ),
        hist_push_bytes=obs_hist.merge(
            a.hist_push_bytes, b.hist_push_bytes
        ),
        hist_persist_us=obs_hist.merge(
            a.hist_persist_us, b.hist_persist_us
        ),
        hist_queue_wait_us=obs_hist.merge(
            a.hist_queue_wait_us, b.hist_queue_wait_us
        ),
        hist_dispatch_gap_us=obs_hist.merge(
            a.hist_dispatch_gap_us, b.hist_dispatch_gap_us
        ),
        hist_durable_lag_us=obs_hist.merge(
            a.hist_durable_lag_us, b.hist_durable_lag_us
        ),
        hist_push_lag_us=obs_hist.merge(
            a.hist_push_lag_us, b.hist_push_lag_us
        ),
        hist_ack_lag_us=obs_hist.merge(
            a.hist_ack_lag_us, b.hist_ack_lag_us
        ),
        hist_freshness_us=obs_hist.merge(
            a.hist_freshness_us, b.hist_freshness_us
        ),
        hist_geo_watermark_lag=obs_hist.merge(
            a.hist_geo_watermark_lag, b.hist_geo_watermark_lag
        ),
        deferred_depth=b.deferred_depth,
        residue=b.residue,
        widen_pressure=b.widen_pressure,
        frontier_lag=b.frontier_lag,
        ack_window_depth=b.ack_window_depth,
        live_ranks=b.live_ranks,
        live_tenants=b.live_tenants,
        evicted_tenants=b.evicted_tenants,
        subscribers_live=b.subscribers_live,
        regions_live=b.regions_live,
        geo_home_tenants=b.geo_home_tenants,
    )


# ---- in-kernel reducers ---------------------------------------------------
# All pure lax/jnp on static shapes: safe inside jit AND shard_map.

def device_depth(state) -> jax.Array:
    """In-kernel ``deferred_depth``: max over leading (replica) lanes of
    valid parked slots summed across every ``*dvalid`` buffer level —
    the jit-transparent twin of ``utils.metrics.deferred_depth`` (same
    masked-epoch field convention, uint32 instead of the -1 host
    sentinel)."""
    total = None

    def walk(node):
        nonlocal total
        for name in node._fields:
            child = getattr(node, name)
            if name.endswith("dvalid"):
                d = jnp.sum(child.astype(jnp.uint32), axis=-1)
                total = d if total is None else total + d
            elif hasattr(child, "_fields"):
                walk(child)

    if hasattr(state, "_fields"):
        walk(state)
    if total is None:
        return jnp.zeros((), jnp.uint32)
    return jnp.max(total).astype(jnp.uint32)


def device_pressure(state) -> jax.Array:
    """Max occupancy fraction over the bounded parked buffers (every
    ``*dvalid`` level): 1.0 = some replica's buffer is at capacity —
    the widen-before-overflow signal, in-kernel."""
    worst = None

    def walk(node):
        nonlocal worst
        for name in node._fields:
            child = getattr(node, name)
            if name.endswith("dvalid"):
                cap = max(child.shape[-1], 1)
                frac = jnp.max(
                    jnp.sum(child.astype(jnp.float32), axis=-1) / cap
                )
                worst = frac if worst is None else jnp.maximum(worst, frac)
            elif hasattr(child, "_fields"):
                walk(child)

    if hasattr(state, "_fields"):
        walk(state)
    if worst is None:
        return jnp.zeros((), jnp.float32)
    return worst.astype(jnp.float32)


def generic_slots_changed(a, b) -> jax.Array:
    """Fallback slots-changed counter: entries that differ across EVERY
    state plane. Exact for element-replicated layouts; kinds with a
    sharded content plane use their ops kernel's specialized counter
    (``ops.orswot.changed_members`` etc.) so element-shard psums don't
    double count replicated planes."""
    total = jnp.zeros((), jnp.uint32)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        total = total + jnp.sum(x != y, dtype=jnp.uint32)
    return total


def shipped_bytes(pytree) -> int:
    """STATIC per-exchange byte count of a shipped pytree (shapes are
    static under tracing, so this is a Python int even in-kernel)."""
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(pytree))


def packet_useful_bytes(pkt) -> jax.Array:
    """DYNAMIC post-mask byte count of one δ packet (``bytes_useful``):
    slot lanes weighted by the packet's slot ``valid`` mask, parked
    buffers by their ``*dvalid`` masks. Walks the packet convention the
    δ flavors share — a leaf packet carries ``idx``/``valid`` plus its
    slot planes, wrapper packets nest the core packet first with one
    parked group (``[k|o]?d{cl,mask,keys,valid}``) riding whole per
    level — so every current and future ``nested_delta`` composition is
    covered without per-flavor byte tables. Pure lax on static shapes:
    safe inside jit and shard_map."""
    total = jnp.zeros((), jnp.float32)

    def group(mask, values):
        n = max(mask.shape[0], 1)
        per = sum(
            (leaf.size // n) * leaf.dtype.itemsize
            for v in values
            for leaf in jax.tree.leaves(v)
        )
        return jnp.sum(mask, dtype=jnp.float32) * per

    def walk(node):
        nonlocal total
        names = node._fields
        parked = {}
        for f in names:
            if f.endswith("dvalid"):
                pref = f[: -len("dvalid")]
                parked[pref] = [
                    getattr(node, pref + s)
                    for s in ("dcl", "dmask", "dkeys", "dvalid")
                    if pref + s in names
                ]
        parked_names = {
            pref + s
            for pref in parked
            for s in ("dcl", "dmask", "dkeys", "dvalid")
            if pref + s in names
        }
        if "idx" in names:  # leaf packet: slot planes gated by `valid`
            total = total + group(
                node.valid,
                [getattr(node, f) for f in names if f not in parked_names],
            )
        else:  # wrapper packet: the core packet rides first
            walk(node[0])
        for bufs in parked.values():
            total = total + group(bufs[-1], bufs)  # bufs[-1] is *dvalid

    walk(pkt)
    return total


# ---- host-side drain ------------------------------------------------------

def is_concrete(tel: Telemetry) -> bool:
    return not any(
        isinstance(x, jax.core.Tracer) for x in jax.tree.leaves(tel)
    )


def to_dict(tel: Telemetry) -> Dict[str, Any]:
    """Host ints/floats for a CONCRETE Telemetry (exporter/JSONL form)."""
    return {
        "merges": int(tel.merges),
        "slots_changed": int(tel.slots_changed),
        "deferred_depth": int(tel.deferred_depth),
        "bytes_exchanged": float(tel.bytes_exchanged),
        "bytes_useful": float(tel.bytes_useful),
        "residue": int(tel.residue),
        "widen_pressure": float(tel.widen_pressure),
        "reclaimed_slots": int(tel.reclaimed_slots),
        "reclaimed_bytes": float(tel.reclaimed_bytes),
        "frontier_lag": int(tel.frontier_lag),
        "stream_blocks": int(tel.stream_blocks),
        "stream_staged_bytes": float(tel.stream_staged_bytes),
        "stream_overlap_hit": int(tel.stream_overlap_hit),
        "faults_dropped": int(tel.faults_dropped),
        "faults_rejected": int(tel.faults_rejected),
        "faults_delayed": int(tel.faults_delayed),
        "bytes_acked_skipped": float(tel.bytes_acked_skipped),
        "ack_window_depth": int(tel.ack_window_depth),
        "wal_bytes": float(tel.wal_bytes),
        "wal_fsyncs": int(tel.wal_fsyncs),
        "snapshots_written": int(tel.snapshots_written),
        "replayed_records": int(tel.replayed_records),
        "torn_tail_truncated": int(tel.torn_tail_truncated),
        "recovery_rounds": int(tel.recovery_rounds),
        "live_ranks": int(tel.live_ranks),
        "scaleout_admits": int(tel.scaleout_admits),
        "scaleout_drains": int(tel.scaleout_drains),
        "bootstrap_bytes": float(tel.bootstrap_bytes),
        "wire_packed_bytes": float(tel.wire_packed_bytes),
        "live_tenants": int(tel.live_tenants),
        "evicted_tenants": int(tel.evicted_tenants),
        "ingest_coalesced_ops": int(tel.ingest_coalesced_ops),
        "subscribers_live": int(tel.subscribers_live),
        "cohorts_per_dispatch": int(tel.cohorts_per_dispatch),
        "delta_push_bytes": float(tel.delta_push_bytes),
        "resync_fallbacks": int(tel.resync_fallbacks),
        "serve_wal_bytes": float(tel.serve_wal_bytes),
        "serve_overlap_hit": int(tel.serve_overlap_hit),
        "rebalance_moves": int(tel.rebalance_moves),
        "regions_live": int(tel.regions_live),
        "geo_home_tenants": int(tel.geo_home_tenants),
        "geo_exchanges": int(tel.geo_exchanges),
        "geo_exchange_bytes": float(tel.geo_exchange_bytes),
        "geo_full_mirror_bytes": float(tel.geo_full_mirror_bytes),
        "geo_failovers": int(tel.geo_failovers),
        "hist_residue": obs_hist.to_dict(tel.hist_residue),
        "hist_useful_bytes": obs_hist.to_dict(tel.hist_useful_bytes),
        "hist_ack_depth": obs_hist.to_dict(tel.hist_ack_depth),
        "hist_packed_bytes": obs_hist.to_dict(tel.hist_packed_bytes),
        "hist_dispatch_us": obs_hist.to_dict(tel.hist_dispatch_us),
        "hist_ingest_batch": obs_hist.to_dict(tel.hist_ingest_batch),
        "hist_push_bytes": obs_hist.to_dict(tel.hist_push_bytes),
        "hist_persist_us": obs_hist.to_dict(tel.hist_persist_us),
        "hist_queue_wait_us": obs_hist.to_dict(tel.hist_queue_wait_us),
        "hist_dispatch_gap_us": obs_hist.to_dict(tel.hist_dispatch_gap_us),
        "hist_durable_lag_us": obs_hist.to_dict(tel.hist_durable_lag_us),
        "hist_push_lag_us": obs_hist.to_dict(tel.hist_push_lag_us),
        "hist_ack_lag_us": obs_hist.to_dict(tel.hist_ack_lag_us),
        "hist_freshness_us": obs_hist.to_dict(tel.hist_freshness_us),
        "hist_geo_watermark_lag": obs_hist.to_dict(
            tel.hist_geo_watermark_lag
        ),
    }


# Telemetry fields carrying a Hist subtree (self-describing serialized
# form; the exporter renders these as Prometheus histogram exposition,
# the schema validates them as the `histogram` kind).
HIST_FIELDS = tuple(
    f for f in Telemetry._fields if obs_hist.is_hist_field(f)
)


def time_dispatch(tel: Telemetry, seconds: float) -> Telemetry:
    """Fold one host-timed dispatch wall-clock into
    ``hist_dispatch_us`` (MICROSECONDS — log2 buckets resolve the
    µs..minutes range; a p99 over many dispatches is the ROADMAP
    serving-gate quantity). Host-side, concrete Telemetry only (the
    ``stream_*``/``wal_*`` fill discipline): under an outer jit the
    pytree is traced, host timing is meaningless, and the input is
    returned untouched."""
    if not is_concrete(tel):
        return tel
    return tel._replace(
        hist_dispatch_us=obs_hist.observe(
            tel.hist_dispatch_us, seconds * 1e6
        )
    )


def counter_increments(kind: str, d: Dict[str, Any]) -> Dict[str, int]:
    """The registry COUNTER increments one recorded Telemetry dict
    (:func:`to_dict`) produces — THE single source of truth shared by
    :func:`record` (which applies them) and ``tools/obs_report.py``
    (which re-folds a flight dump's ``telemetry`` events through this
    exact mapping and compares the result bit-exactly against the live
    registry — a drift here would break that audit, never fork the two
    sides). Gauge observations (depth/residue/pressure/lag and the
    histogram quantile summaries) are NOT counters and live in
    :func:`record` only."""
    inc = {
        f"telemetry.{kind}.merges": d["merges"],
        f"telemetry.{kind}.slots_changed": d["slots_changed"],
        f"telemetry.{kind}.bytes_exchanged": int(d["bytes_exchanged"]),
        f"telemetry.{kind}.bytes_useful": int(d["bytes_useful"]),
        f"telemetry.{kind}.reclaimed_slots": d["reclaimed_slots"],
        f"telemetry.{kind}.reclaimed_bytes": int(d["reclaimed_bytes"]),
        f"telemetry.{kind}.stream.blocks": d["stream_blocks"],
        f"telemetry.{kind}.stream.staged_bytes": int(
            d["stream_staged_bytes"]
        ),
        f"telemetry.{kind}.stream.overlap_hit": d["stream_overlap_hit"],
        f"telemetry.{kind}.faults.packets_dropped": d["faults_dropped"],
        f"telemetry.{kind}.faults.packets_rejected": d["faults_rejected"],
        f"telemetry.{kind}.faults.packets_delayed": d["faults_delayed"],
        f"telemetry.{kind}.bytes_acked_skipped": int(
            d["bytes_acked_skipped"]
        ),
        f"telemetry.{kind}.wal_bytes": int(d["wal_bytes"]),
        f"telemetry.{kind}.wal_fsyncs": d["wal_fsyncs"],
        f"telemetry.{kind}.snapshots_written": d["snapshots_written"],
        f"telemetry.{kind}.replayed_records": d["replayed_records"],
        f"telemetry.{kind}.torn_tail_truncated": d["torn_tail_truncated"],
        f"telemetry.{kind}.recovery_rounds": d["recovery_rounds"],
        f"telemetry.{kind}.scaleout.admits": d["scaleout_admits"],
        f"telemetry.{kind}.scaleout.drains": d["scaleout_drains"],
        f"telemetry.{kind}.scaleout.bootstrap_bytes": int(
            d["bootstrap_bytes"]
        ),
        f"telemetry.{kind}.wire.packed_bytes": int(
            d["wire_packed_bytes"]
        ),
        f"telemetry.{kind}.serve.ingest_coalesced_ops": d[
            "ingest_coalesced_ops"
        ],
        f"telemetry.{kind}.fanout.cohorts_per_dispatch": d[
            "cohorts_per_dispatch"
        ],
        f"telemetry.{kind}.fanout.delta_push_bytes": int(
            d["delta_push_bytes"]
        ),
        f"telemetry.{kind}.fanout.resync_fallbacks": d[
            "resync_fallbacks"
        ],
        f"telemetry.{kind}.serve.wal_bytes": int(d["serve_wal_bytes"]),
        f"telemetry.{kind}.serve.overlap_hit": d["serve_overlap_hit"],
        f"telemetry.{kind}.serve.rebalance_moves": d["rebalance_moves"],
        f"telemetry.{kind}.geo.exchanges": d["geo_exchanges"],
        f"telemetry.{kind}.geo.exchange_bytes": int(
            d["geo_exchange_bytes"]
        ),
        f"telemetry.{kind}.geo.full_mirror_bytes": int(
            d["geo_full_mirror_bytes"]
        ),
        f"telemetry.{kind}.geo.failovers": d["geo_failovers"],
    }
    # Histogram per-bucket counters fold bit-exactly across runs —
    # exactly what tools/obs_report.py cross-checks a dump against.
    for field in HIST_FIELDS:
        hd = d[field]
        n = sum(hd["counts"])
        if not n:
            continue
        base = f"telemetry.{kind}.hist.{field[len('hist_'):]}"
        inc[f"{base}.count"] = n
        for i, c in enumerate(hd["counts"]):
            if c:
                inc[f"{base}.bucket{i:02d}"] = c
    return inc


def record(kind: str, tel: Telemetry) -> None:
    """Drain a concrete Telemetry into the host registry under
    ``telemetry.<kind>.*`` (counters for the monotone fields — the
    :func:`counter_increments` mapping — gauges for the final-state
    ones and the histogram p50/p95/p99 summaries). A no-op under
    tracing — the caller then owns the returned pytree (that is the
    whole point of it). With a flight recorder installed
    (crdt_tpu/obs/), each call additionally advances the correlation
    key's round coordinate and records one ``telemetry`` event
    carrying the full dict — the per-round timeline entry
    ``tools/obs_report.py`` re-folds."""
    if not is_concrete(tel):
        return
    d = to_dict(tel)
    for name, n in counter_increments(kind, d).items():
        metrics.count(name, n)
    metrics.observe(
        f"telemetry.{kind}.ack_window_depth", d["ack_window_depth"]
    )
    metrics.observe(f"telemetry.{kind}.live_ranks", d["live_ranks"])
    metrics.observe(f"telemetry.{kind}.live_tenants", d["live_tenants"])
    metrics.observe(
        f"telemetry.{kind}.evicted_tenants", d["evicted_tenants"]
    )
    metrics.observe(
        f"telemetry.{kind}.subscribers_live", d["subscribers_live"]
    )
    metrics.observe(f"telemetry.{kind}.regions_live", d["regions_live"])
    metrics.observe(
        f"telemetry.{kind}.geo_home_tenants", d["geo_home_tenants"]
    )
    metrics.observe(f"telemetry.{kind}.deferred_depth", d["deferred_depth"])
    metrics.observe(f"telemetry.{kind}.residue", d["residue"])
    metrics.observe(f"telemetry.{kind}.widen_pressure", d["widen_pressure"])
    metrics.observe(f"telemetry.{kind}.frontier_lag", d["frontier_lag"])
    for field in HIST_FIELDS:
        hd = d[field]
        if not sum(hd["counts"]):
            continue
        base = f"telemetry.{kind}.hist.{field[len('hist_'):]}"
        s = obs_hist.summary(hd)
        for q in ("p50", "p95", "p99"):
            metrics.observe(f"{base}.{q}", s[q])
    if _rec.get_recorder() is not None:
        # Emit FIRST, advance AFTER: the telemetry drain is the last
        # event of its dispatch, so everything the dispatch emitted
        # earlier (WAL fsyncs, fault counters) shares its round
        # coordinate — advancing first would split one dispatch across
        # two rounds on the postmortem timeline.
        _rec.emit("telemetry", kind=kind, **d)
        _rec.advance_round()


# ---- span tracing ---------------------------------------------------------

_trace_lock = threading.Lock()
_trace_events: list = []
_trace_path: Optional[str] = None
_MAX_BUFFERED_EVENTS = 65536
_SPAN_RING = 4096   # per-name call durations kept by the aggregate
_local = threading.local()
_profiling = jax.profiler.TraceAnnotation.is_enabled
_stats: Dict[str, Dict[str, Any]] = {}


def configure_tracing(path: Optional[str]) -> None:
    """Point span JSONL emission at ``path`` (append mode). A path also
    switches span recording on; ``None`` switches that reason off."""
    global _trace_path
    with _trace_lock:
        _trace_path = path


def drain_events() -> list:
    """Pop and return every buffered span event (oldest first)."""
    with _trace_lock:
        out, _trace_events[:] = list(_trace_events), []
    return out


def span_stats() -> Dict[str, Dict[str, Any]]:
    """Per span name, what the recorded spans add up to: ``n`` calls,
    ``total_s``, ``self_s`` (total less the time child spans cover),
    ``attrs`` (the sum of each numeric attribute) and ``durations_s``
    (the newest 4,096 calls, oldest first)."""
    with _trace_lock:
        return {
            name: {
                "n": a["n"], "total_s": a["total_s"], "self_s": a["self_s"],
                "attrs": dict(a["attrs"]), "durations_s": list(a["durations_s"]),
            }
            for name, a in _stats.items()
        }


def reset_span_stats() -> None:
    """Clear the :func:`span_stats` aggregate."""
    with _trace_lock:
        _stats.clear()


def _aggregate(name: str, dur: float, self_s: float, attrs) -> None:
    with _trace_lock:
        a = _stats.get(name)
        if a is None:
            a = _stats[name] = {"n": 0, "total_s": 0.0, "self_s": 0.0,
                                "attrs": {},
                                "durations_s": deque(maxlen=_SPAN_RING)}
        a["n"] += 1
        a["total_s"] += dur
        a["self_s"] += self_s
        a["durations_s"].append(dur)
        sums = a["attrs"]
        for k, v in attrs.items():
            if isinstance(v, numbers.Real) and not isinstance(v, bool):
                sums[k] = sums.get(k, 0) + v


def _emit(event: Dict[str, Any]) -> None:
    # Stamp the flight recorder's (generation, round, rank) correlation
    # key when one is installed, so spans and flight events line up on
    # one timeline (obs/recorder.py module docstring).
    k = _rec.current_key()
    if k is not None:
        event.setdefault("gen", k[0])
        event.setdefault("round", k[1])
        event.setdefault("rank", k[2])
    with _trace_lock:
        _trace_events.append(event)
        del _trace_events[:-_MAX_BUFFERED_EVENTS]
        path = _trace_path
    if path:
        try:
            # default=str: attrs may carry numpy/jnp scalars; tracing
            # must never take down the traced program.
            line = json.dumps(event, default=str)
            with open(path, "a") as f:
                f.write(line + "\n")
        except (OSError, TypeError, ValueError):
            pass


class _Span:
    __slots__ = ("name", "attrs", "_on", "_ann", "_parent", "_child",
                 "_wall", "_t0")

    def __init__(self, name: str, attrs: Dict[str, Any]):
        self.name = name
        self.attrs = attrs

    def __enter__(self):
        if _trace_path is None and _rec.get_recorder() is None and not _profiling():
            self._on = False
            return self
        self._on = True
        stack = getattr(_local, "stack", None)
        if stack is None:
            stack = _local.stack = []
        self._parent = stack[-1] if stack else None
        stack.append(self)
        self._child = 0.0
        try:
            self._ann = jax.profiler.TraceAnnotation(self.name)
            self._ann.__enter__()
        except Exception:
            self._ann = None  # profiler backend unavailable: the rest still records
        self._wall = time.time()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        if not self._on:
            return False
        dur = time.perf_counter() - self._t0
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        _local.stack.pop()
        parent = self._parent
        if parent is not None:
            parent._child += dur
        _aggregate(self.name, dur, dur - self._child, self.attrs)
        metrics.observe(f"{self.name}_seconds", dur)
        _emit({
            "record": "span",
            "name": self.name,
            "ts": self._wall,
            "dur_s": dur,
            "parent": None if parent is None else parent.name,
            "attrs": self.attrs,
        })
        return False


def span(name: str, **attrs) -> _Span:
    """A named span around a layer boundary. It records only while a
    profiler session runs (``TraceAnnotation.is_enabled()``), a JSONL
    path is configured (:func:`configure_tracing`) or a flight recorder
    is installed (``crdt_tpu.obs.install``); otherwise entering it is
    one flag check. A recorded span is:

    - a ``jax.profiler.TraceAnnotation`` of ``name``: the span on the
      device trace's clock, on the host thread's line;
    - a structured trace event on exit (wall-clock start, duration,
      attrs, parent span), stamped with the flight recorder's
      ``(generation, round, rank)`` key as ``gen``/``round``/``rank``
      when one is installed, so spans interleave with the recorder's
      per-round events in a ``FlightRecorder.dump()`` artifact;
    - the registry gauge ``<name>_seconds``;
    - a sample of the per-name aggregate :func:`span_stats`.

    Attrs must be JSON-serializable; the context manager's value is
    the span, whose ``attrs`` dict may be filled in before exit.
    Program span names never start with ``span.``: that prefix is the
    benchmark's own."""
    return _Span(name, attrs)


def reset_residue_warnings() -> None:
    """Re-arm the δ-ring's once-per-kind residue warning (the dedupe
    lives in parallel.delta_ring; re-exported here because tests and
    operators reach for it next to the telemetry registry — see
    tests/test_residue_warnings.py)."""
    from .parallel.delta_ring import reset_residue_warnings as _reset

    _reset()


__all__ = [
    "HIST_FIELDS", "Telemetry", "combine", "configure_tracing",
    "counter_increments",
    "device_depth", "device_pressure", "drain_events",
    "generic_slots_changed", "is_concrete", "packet_useful_bytes",
    "record", "reset_residue_warnings", "reset_span_stats", "shipped_bytes",
    "span", "span_stats", "specs", "time_dispatch", "to_dict", "zeros",
]
