"""Region planes + federation membership — the mesh-of-meshes layer.

One mesh is one failure domain (ROADMAP item 3): the serving tier
(crdt_tpu/serve/) and the fan-out plane (crdt_tpu/fanout/) both die
with the region hosting them. This module federates N such regions:

- :class:`RegionMap` — rendezvous-hashed tenant→**region** homing,
  the exact :class:`~crdt_tpu.serve.shard.TenantShardMap` discipline
  layered one level up (a distinct splitmix64 salt decorrelates the
  region layer from the per-host layer, so a region's tenant set
  spreads evenly over its hosts). Every tenant has ONE home region —
  the single writer; rendezvous makes region loss a minimal remap.
- :class:`FederationMembership` — generation-stamped membership
  reusing the ``scaleout/mesh_scale.py`` discipline: every
  evict/admit bumps the generation, and every cross-region packet
  carries the generation it was built under. A packet stamped with a
  stale generation is REFUSED loudly (:class:`GeoGenerationError`),
  exactly like a stale :class:`~crdt_tpu.scaleout.mesh_scale
  .DrainCertificate` — the split-brain guard for the federation.
- :class:`RegionPlane` — one region's full serving stack (superblock
  + evictor + WAL-attached ingest queue + optional fan-out plane)
  plus the region-local interest signals that drive PARTIAL
  replication: a region materializes a non-home tenant only when it
  has local subscribers (the fan-out plane's interest table) or
  recent local writes (``local_writes``, stamped at the federation
  front door). Global tenant population × regions must NOT multiply
  device memory — the resident lane count per region is bounded by
  home ∪ local-interest.
- :class:`Federation` — the front door: writes route to the tenant's
  HOME region's ingest queue (the ack point stays the home region's
  :class:`~crdt_tpu.serve.wal.ServeWal` group commit — ``flush`` on
  the home queue, nothing geo-specific), stamping origin-region
  interest so anti-entropy knows which mirrors to feed.

All regions must share one tenant kind and one capacity layout:
cross-region δ lanes are positional (delta_opt/decompose.py), so a
capacity divergence between regions would make reconstruction
meaningless. The constructor enforces it; capacity autoscale under
federation must be coordinated federation-wide (future work — the
exchange fails loudly on drift rather than joining garbage).
"""

from __future__ import annotations

from typing import Dict, Iterable, List, Optional, Sequence, Set

import jax
import numpy as np

from ..obs import hist as obs_hist
from ..utils.metrics import metrics
from .. import telemetry as tele


def _region_weight(tenant: int, region: int) -> int:
    """Deterministic (tenant, region) rendezvous weight — the
    serve/shard.py splitmix64 round under a geo-distinct increment, so
    region homing does not correlate with per-host shard placement."""
    z = (
        (tenant & 0xFFFFFFFF) << 32 | (region & 0xFFFFFFFF)
    ) + 0xD1B54A32D192ED03
    z &= 0xFFFFFFFFFFFFFFFF
    z = ((z ^ (z >> 30)) * 0xBF58476D1CE4E5B9) & 0xFFFFFFFFFFFFFFFF
    z = ((z ^ (z >> 27)) * 0x94D049BB133111EB) & 0xFFFFFFFFFFFFFFFF
    return z ^ (z >> 31)


class GeoGenerationError(RuntimeError):
    """A cross-region operation carried a stale federation generation —
    membership changed under it. Refused loudly (the
    scaleout/mesh_scale.py stale-certificate discipline at federation
    granularity); the caller must re-read membership and rebuild."""


class RegionMap:
    """Rendezvous-hashed tenant→region homing over a live region set."""

    def __init__(self, n_regions: int,
                 live: Optional[Iterable[int]] = None):
        if n_regions < 1:
            raise ValueError("need at least one region")
        self.n_regions = n_regions
        self.live = set(range(n_regions) if live is None else live)
        if not self.live <= set(range(n_regions)):
            raise ValueError(
                f"live regions {self.live} exceed {n_regions}"
            )
        if not self.live:
            raise ValueError("no live regions")
        # Placement overrides (tenant → region), consulted BEFORE the
        # rendezvous hash — the serve/shard.py override discipline, so
        # a future geo rebalancer can pin hot tenants without moving
        # anything else.
        self.overrides: Dict[int, int] = {}

    def home(self, tenant: int) -> int:
        o = self.overrides.get(int(tenant))
        if o is not None and o in self.live:
            return o
        return max(self.live, key=lambda r: _region_weight(tenant, r))

    def homed(self, region: int, tenants: Sequence[int]) -> List[int]:
        return [t for t in tenants if self.home(t) == region]

    def fail_over(self, region: int) -> None:
        """Membership evicted a region: its tenants re-home to
        survivors by rendezvous, every other assignment untouched
        (minimal remap). Overrides pointing at the dead region are
        dropped — those tenants fall back to rendezvous too."""
        if region not in self.live:
            return
        if len(self.live) == 1:
            raise ValueError("cannot fail over the last live region")
        self.live.discard(region)
        for t in [t for t, r in self.overrides.items() if r == region]:
            del self.overrides[t]
        metrics.count("geo.region.failovers")

    def admit(self, region: int) -> None:
        if not 0 <= region < self.n_regions:
            raise ValueError(f"region {region} out of range")
        self.live.add(region)


class FederationMembership:
    """Generation-stamped federation membership (mesh_scale
    discipline): every evict/admit bumps ``generation``; cross-region
    packets stamp the generation they were built under and are refused
    on mismatch."""

    def __init__(self, rmap: RegionMap):
        self.rmap = rmap
        self.generation = 1

    def evict(self, region: int) -> int:
        self.rmap.fail_over(region)
        self.generation += 1
        return self.generation

    def admit(self, region: int) -> int:
        self.rmap.admit(region)
        self.generation += 1
        return self.generation

    def require(self, generation: int, *, op: str = "exchange") -> None:
        if generation != self.generation:
            raise GeoGenerationError(
                f"geo {op} stamped generation {generation} but the "
                f"federation is at {self.generation} — membership "
                f"changed; rebuild against current membership"
            )


class RegionPlane:
    """One region's serving stack plus its local-interest signals.

    ``superblock``/``evictor``/``queue`` are the PR 15/18 tier exactly
    as a single mesh runs them — the queue's attached
    :class:`~crdt_tpu.serve.wal.ServeWal` stays THE ack point for
    writes homed here. ``fanout`` (optional) contributes the
    subscriber half of the interest table; ``local_writes`` is the
    recent-local-writer half, stamped by
    :meth:`Federation.submit` for the ORIGIN region of every op so
    anti-entropy mirrors tenants written through this region even when
    nobody here subscribes."""

    def __init__(self, region: int, superblock, queue, *,
                 evictor=None, wal=None, fanout=None):
        self.region = int(region)
        self.sb = superblock
        self.queue = queue
        self.evictor = evictor
        self.wal = wal
        self.fanout = fanout
        self.alive = True
        self.local_writes = np.zeros(superblock.n_tenants, bool)
        # Receiver-side lockstep state: last anti-entropy round applied
        # per source region (geo/antientropy.py bumps these).
        self.rounds_applied: Dict[int, int] = {}

    def interest_tenants(self) -> Set[int]:
        """Tenants this region must materialize beyond its home set:
        local subscribers (fan-out interest table) ∪ recent local
        writers. This set — not the global tenant population — bounds
        the region's mirror lanes (the partial-replication
        contract)."""
        out: Set[int] = set(
            int(t) for t in np.nonzero(self.local_writes)[0]
        )
        if self.fanout is not None:
            st = self.fanout.sub_tenant[: self.fanout._top]
            out.update(int(t) for t in st[st >= 0])
        return out

    def resident_lanes(self) -> int:
        return int(self.sb.n_resident)


class Federation:
    """The multi-region front door: home-routed writes, shared
    membership, per-tenant home-version counters (the causal
    watermark's numerator — geo/reads.py compares a link's acked
    version against these)."""

    def __init__(self, planes: Dict[int, RegionPlane],
                 rmap: Optional[RegionMap] = None):
        if not planes:
            raise ValueError("a federation needs at least one region")
        self.planes = dict(planes)
        n = max(self.planes) + 1
        self.rmap = rmap or RegionMap(n, live=self.planes.keys())
        self.membership = FederationMembership(self.rmap)
        kinds = {p.sb.kind for p in self.planes.values()}
        capss = {tuple(sorted(p.sb.caps.items()))
                 for p in self.planes.values()}
        if len(kinds) != 1 or len(capss) != 1:
            raise ValueError(
                "federated regions must share one tenant kind and one "
                f"capacity layout (got kinds={kinds})"
            )
        self.kind = kinds.pop()
        self.n_tenants = next(iter(self.planes.values())).sb.n_tenants
        # Per-tenant home version: bumped once per op accepted at the
        # tenant's home region. Monotone by single-writer homing; the
        # read-path watermark certificates are lags against this.
        self.versions = np.zeros(self.n_tenants, np.int64)
        # Anti-entropy links keyed (src, dst) — geo/antientropy.py
        # owns their state; registered here so failover can reset
        # every link touching a re-homed tenant.
        self.links: Dict[tuple, object] = {}
        self.exchanges = 0
        self.exchange_bytes = 0.0
        self.full_mirror_bytes = 0.0
        self.failovers = 0
        self.hist_watermark_lag = obs_hist.zeros()

    # ---- routing --------------------------------------------------------
    def plane(self, region: int) -> RegionPlane:
        p = self.planes.get(int(region))
        if p is None or not p.alive:
            raise KeyError(f"region {region} is not live")
        return p

    def submit(self, origin: int, tenant: int, op) -> int:
        """Route one op (serve.ingest ``AddOp``/``RmOp``) to the
        tenant's HOME region's queue and stamp origin-region interest.
        Returns the home region id. The op is NOT acked here — acks
        stay gated on the home region's ServeWal group commit, i.e.
        the home queue's flush/drain."""
        home = self.rmap.home(tenant)
        self.plane(home).queue.submit(int(tenant), op)
        self.versions[int(tenant)] += 1
        origin_plane = self.planes.get(int(origin))
        if origin_plane is not None and origin_plane.alive:
            origin_plane.local_writes[int(tenant)] = True
        return home

    def add(self, origin: int, tenant: int, actor: int, counter: int,
            member) -> int:
        from ..serve.ingest import AddOp

        return self.submit(
            origin, tenant, AddOp(actor, counter, np.asarray(member))
        )

    def rm(self, origin: int, tenant: int, clock, member) -> int:
        from ..serve.ingest import RmOp

        return self.submit(
            origin, tenant,
            RmOp(np.asarray(clock, np.uint32), np.asarray(member)),
        )

    def drain_all(self) -> int:
        """Drain every live region's queue (each drain is that
        region's own WAL-gated flush loop). Returns ops applied."""
        ops = 0
        for p in self.planes.values():
            if not p.alive:
                continue
            rep, _ = p.queue.drain()
            ops += rep.ops_applied
        return ops

    # ---- telemetry ------------------------------------------------------
    def annotate(self, tel: "tele.Telemetry") -> "tele.Telemetry":
        """Fill the host-owned federation gauges/counters on a
        concrete Telemetry (the ``stream_*``/``wal_*`` fill
        discipline)."""
        if not tele.is_concrete(tel):
            return tel
        live = sum(1 for p in self.planes.values() if p.alive)
        home = sum(
            len(self.rmap.homed(r, range(self.n_tenants)))
            for r, p in self.planes.items() if p.alive
        )
        return tel._replace(
            regions_live=np.uint32(live),
            geo_home_tenants=np.uint32(home),
            geo_exchanges=np.uint32(self.exchanges),
            geo_exchange_bytes=np.float32(self.exchange_bytes),
            geo_full_mirror_bytes=np.float32(self.full_mirror_bytes),
            geo_failovers=np.uint32(self.failovers),
            hist_geo_watermark_lag=jax.tree.map(
                np.asarray, self.hist_watermark_lag
            ),
        )
