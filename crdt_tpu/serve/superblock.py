"""The host-side tenant superblock — a large live-tenant population
served from a bounded pool of device-resident lanes (ISSUE 15
tentpole).

:class:`Superblock` owns the device pytree (``ops/superblock.py``
layout: a LANE axis prepended on a registered kind's planes, sharded
over the replica mesh axis) plus the tenant bookkeeping the kernels
cannot see. The population (``n_tenants``) may EXCEED the device pool
(``n_lanes``): a tenant occupies a lane only while resident, via the
host-side ``tenant → lane`` indirection —

- a never-touched tenant costs NOTHING (no lane, no disk record: its
  state is ⊥ by definition);
- first touch allocates a free lane (⊥ — still no disk);
- cold tenants move to the durable tier and FREE their lane
  (crdt_tpu/serve/evict.py), re-warming on next touch into whatever
  lane is free — which is why the device footprint is
  ``n_lanes × row_bytes`` while the SERVED population is
  ``n_tenants``;
- an exhausted pool raises :class:`LanePressure`; the evictor turns
  that into evict-coldest-then-restore (serving-tier paging).

The elastic overflow→widen→retry loop lifts the PR 1 ``elastic_call``
discipline over the lane axis: overflowed tenants (bounded deferred /
dot capacity) roll back from their pre-gathered rows, the WHOLE
superblock widens by ``policy.factor`` (one repack migrates every
lane), and only the overflowed lanes retry — never re-applying a
non-overflowed tenant, so the elastic path stays bit-identical to a
wide-born superblock. :meth:`autoscale_capacity` debounces the
telemetry ``widen_pressure`` gauge through ``elastic.Hysteresis.vote``
(the PR 11 symmetric governor) for proactive widen/shrink; a shrink
that would drop live lanes is REFUSED by the per-kind ``narrow``
kernel (a no-op, never a data loss).
"""

from __future__ import annotations

import time
from collections import deque
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import telemetry as tele
from ..elastic import DEFAULT_POLICY, ElasticPolicy, Hysteresis
from ..ops import superblock as sb_ops
from ..parallel.mesh import REPLICA_AXIS
from ..parallel.serve_apply import mesh_serve_apply


class CapacityOverflow(RuntimeError):
    """An op slab overflowed a tenant's bounded buffers and the widen
    budget (``policy.max_migrations``) is exhausted. ``tenants`` names
    the overflowed tenants — their rows were ROLLED BACK to the
    pre-slab state (nothing lossy survives) and their ops are the ones
    a loss-free caller must re-queue (the ingest queue does)."""

    def __init__(self, msg: str, tenants=()):
        super().__init__(msg)
        self.tenants = tuple(int(t) for t in tenants)


class LanePressure(RuntimeError):
    """No free device lane for a tenant that needs one — evict a cold
    tenant first (the evictor does this automatically:
    crdt_tpu/serve/evict.py restore-under-pressure)."""


class PendingApply:
    """One in-flight coalesced dispatch: the issued (not yet
    overflow-checked) ``mesh_serve_apply`` plus everything
    :meth:`Superblock.finish` needs to run the overflow→widen→retry
    loop — the rollback base (``pre``), the slab/idx for retries, and
    the issue timestamp the host dispatch timing folds from. Minted by
    :meth:`Superblock.apply_async`; the pipelined serving loop
    (crdt_tpu/serve/loop.py) assembles + WAL-logs the NEXT slab while
    one of these is in flight."""

    __slots__ = ("slab", "idx_local", "tenants", "valid", "glanes",
                 "pre", "of", "tel", "telemetry", "donate", "t0")

    def __init__(self, slab, idx_local, tenants, valid, glanes, pre,
                 of, tel, telemetry, donate, t0):
        self.slab = slab
        self.idx_local = idx_local
        self.tenants = tenants
        self.valid = valid
        self.glanes = glanes
        self.pre = pre
        self.of = of
        self.tel = tel
        self.telemetry = telemetry
        self.donate = donate
        self.t0 = t0

    def ready(self) -> bool:
        """Best-effort 'has the scatter landed' probe (the
        ``parallel/stream.py`` ``_ready`` discipline — feeds the
        ``serve_overlap_hit`` counter only, never correctness)."""
        import jax

        leaf = jax.tree.leaves(self.of)[0]
        fn = getattr(leaf, "is_ready", None)
        if not callable(fn):
            return True
        try:
            return bool(fn())
        except Exception:
            return True


class Superblock:
    """``n_tenants`` live sessions of one registered kind served from
    ``n_lanes`` device-resident rows (default: fully resident,
    ``n_lanes == n_tenants``). ``caps`` is the kind's capacity dict
    (the ops ``empty`` kwargs minus ``batch``); ``n_lanes`` must
    divide the mesh's replica axis."""

    def __init__(
        self,
        n_tenants: int,
        mesh,
        *,
        kind: str = "orswot",
        caps: Optional[Dict[str, int]] = None,
        policy: ElasticPolicy = DEFAULT_POLICY,
        n_lanes: Optional[int] = None,
    ):
        self.kind = kind
        self.tk = sb_ops.tenant_kind(kind)
        self.mesh = mesh
        self.p = mesh.shape[REPLICA_AXIS]
        n_lanes = n_tenants if n_lanes is None else n_lanes
        if n_lanes % self.p:
            raise ValueError(
                f"{n_lanes} lanes do not divide the {self.p}-way "
                f"replica mesh axis"
            )
        if n_lanes > n_tenants:
            raise ValueError(
                f"{n_lanes} lanes exceed the {n_tenants}-tenant "
                f"population"
            )
        self.n_tenants = n_tenants
        self.n_lanes = n_lanes
        self.caps = dict(caps) if caps else self._default_caps(kind)
        self.policy = policy
        self.hysteresis = Hysteresis(policy)
        self.state = self._placed(
            self.tk.empty(**self.caps, batch=(n_lanes,))
        )
        # The indirection: lane_of[tenant] (-1 = not resident),
        # tenant_of[lane] (-1 = free), plus the free-lane pool. Dirt is
        # per TENANT (touched since last durable persist — what the
        # evictor must flush before freeing the lane); was_evicted
        # marks tenants currently parked in the durable tier.
        self.lane_of = np.full(n_tenants, -1, np.int64)
        self.tenant_of = np.full(n_lanes, -1, np.int64)
        # Free pool RANK-INTERLEAVED (lane r*lpr+i is rank r's): a
        # sequential pool would hand the first lanes_per_rank
        # admissions to rank 0 alone, serializing every early slab on
        # one rank's lane block.
        lpr = n_lanes // self.p
        order = np.arange(n_lanes).reshape(self.p, lpr).T.reshape(-1)
        self._free: deque = deque(int(x) for x in order)
        self.dirty = np.zeros(n_tenants, bool)
        self.was_evicted = np.zeros(n_tenants, bool)
        self.widen_events = 0
        self.last_pressure = 0.0

    def _placed(self, state):
        """Commit the lane axis to its mesh sharding up front (replica
        axis partitions lanes). The apply dispatch would resolve the
        same placement on its first output anyway — but starting
        UNplaced costs one full recompile when the second dispatch
        sees the now-sharded layout (found live: a 600 ms p99 outlier
        in the serve bench's measured window)."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        spec = NamedSharding(self.mesh, P(REPLICA_AXIS))
        return jax.tree.map(lambda x: jax.device_put(x, spec), state)

    @staticmethod
    def _default_caps(kind: str) -> Dict[str, int]:
        if kind == "sparse_orswot":
            return dict(dot_cap=16, n_actors=4, deferred_cap=4, rm_width=8)
        return dict(n_elems=16, n_actors=4, deferred_cap=4)

    # ---- layout / residency --------------------------------------------
    @property
    def lanes_per_rank(self) -> int:
        return self.n_lanes // self.p

    def is_resident(self, tenant: int) -> bool:
        return self.lane_of[tenant] >= 0

    @property
    def n_resident(self) -> int:
        return self.n_lanes - len(self._free)

    @property
    def free_lanes(self) -> int:
        return len(self._free)

    def resident_tenants(self) -> np.ndarray:
        return self.tenant_of[self.tenant_of >= 0]

    def ensure_resident(self, tenant: int) -> int:
        """The tenant's lane, allocating a free (⊥) one on first touch.
        Raises :class:`LanePressure` when the pool is exhausted — the
        evictor's restore path converts that into evict-coldest-first.
        NOTE: this is the ⊥ fast path; a tenant with a DURABLE record
        must come back through ``Evictor.restore`` so the record loads.
        """
        lane = self.lane_of[tenant]
        if lane >= 0:
            return int(lane)
        if not self._free:
            raise LanePressure(
                f"all {self.n_lanes} lanes resident; evict a cold "
                f"tenant before admitting tenant {tenant}"
            )
        lane = self._free.popleft()
        self.lane_of[tenant] = lane
        self.tenant_of[lane] = tenant
        return int(lane)

    def release_lane(self, tenant: int) -> int:
        """Return a tenant's lane to the free pool (the evictor calls
        this AFTER persisting + clearing — the freed lane holds ⊥)."""
        lane = int(self.lane_of[tenant])
        if lane < 0:
            raise ValueError(f"tenant {tenant} is not resident")
        self.lane_of[tenant] = -1
        self.tenant_of[lane] = -1
        self._free.append(lane)
        return lane

    def nbytes(self) -> int:
        return sum(
            x.size * x.dtype.itemsize for x in jax.tree.leaves(self.state)
        )

    def row_nbytes(self) -> int:
        return self.nbytes() // max(self.n_lanes, 1)

    # ---- the coalesced apply (with the elastic retry) -------------------
    def apply(
        self,
        slab: sb_ops.OpSlab,
        idx_local,
        tenants,
        *,
        telemetry: bool = False,
        donate: bool = True,
    ):
        """Apply one coalesced slab (``idx_local`` per
        ``mesh_serve_apply``'s lane convention; ``tenants[B]`` the
        tenant id per slab lane, -1 = empty — every listed tenant must
        be resident). Returns the Telemetry sidecar (or None).
        Overflow rolls back ONLY the overflowed tenants, widens the
        superblock, and retries their lanes — bounded by
        ``policy.max_migrations``. ``apply`` == ``finish(apply_async())``
        — the split is the pipelined serving loop's seam
        (crdt_tpu/serve/loop.py overlaps next-slab assembly + WAL
        append with the in-flight scatter)."""
        return self.finish(self.apply_async(
            slab, idx_local, tenants, telemetry=telemetry, donate=donate,
        ))

    def apply_async(
        self,
        slab: sb_ops.OpSlab,
        idx_local,
        tenants,
        *,
        telemetry: bool = False,
        donate: bool = True,
    ) -> PendingApply:
        """Issue one coalesced dispatch WITHOUT waiting for it: gather
        the rollback base, launch ``mesh_serve_apply``, and return the
        :class:`PendingApply` handle :meth:`finish` completes. The
        superblock's device state advances to the in-flight output
        immediately (JAX async dispatch) — but no NEW dispatch may be
        issued and no overflow decision exists until :meth:`finish`
        runs (a widen retry changes every lane's shape)."""
        tenants = np.asarray(tenants)
        valid = tenants >= 0
        # Pre-rows of touched tenants: the rollback base that keeps the
        # overflow→widen→retry loop exact (a lossy overflowed apply —
        # e.g. a dropped parked remove — must never survive).
        glanes = np.where(valid, self.lane_of[np.where(valid, tenants, 0)], 0)
        gidx = jnp.asarray(glanes, jnp.int32)
        pre = sb_ops.gather_rows(self.state, gidx)
        t0 = time.perf_counter()
        out = mesh_serve_apply(
            self.state, slab, idx_local, self.mesh, kind=self.kind,
            donate=donate, telemetry=telemetry, sync=False,
        )
        if telemetry:
            self.state, of, t_raw = out
        else:
            self.state, of = out
            t_raw = None
        return PendingApply(
            slab, idx_local, tenants, valid, glanes, pre, of, t_raw,
            telemetry, donate, t0,
        )

    def finish(self, p: PendingApply):
        """Complete an in-flight dispatch: wait for its overflow flags,
        run the overflow→widen→retry loop (identical to the serial
        :meth:`apply` — the retries themselves are issued and waited
        inline), mark applied tenants dirty, and return the combined
        Telemetry (or None). The host dispatch timing
        (``hist_dispatch_us``) measures issue→completion, so an
        overlapped dispatch's histogram entry covers exactly the
        wall-clock a serial caller would have blocked for."""
        slab, idx_local = p.slab, p.idx_local
        tenants, valid, glanes, pre = p.tenants, p.valid, p.glanes, p.pre
        tel = None
        of, t_raw, t0 = p.of, p.tel, p.t0
        for attempt in range(self.policy.max_migrations + 1):
            if attempt:
                t0 = time.perf_counter()
                out = mesh_serve_apply(
                    self.state, slab, idx_local, self.mesh,
                    kind=self.kind, donate=p.donate,
                    telemetry=p.telemetry, sync=False,
                )
                if p.telemetry:
                    self.state, of, t_raw = out
                else:
                    self.state, of = out
            if p.telemetry:
                jax.block_until_ready((self.state, of, t_raw))
                t = tele.time_dispatch(t_raw, time.perf_counter() - t0)
                tel = t if tel is None else tele.combine(tel, t)
                self.last_pressure = float(t.widen_pressure)
            of_host = np.asarray(of) & valid
            if not of_host.any():
                break
            if attempt == self.policy.max_migrations:
                # Budget exhausted: roll the overflowed tenants back to
                # their pre-slab rows (a lossy overflowed apply — e.g. a
                # dropped parked remove — must never survive), mark the
                # SUCCESSFULLY applied tenants dirty, and name the
                # overflowed ones so the caller can re-queue their ops.
                ovr = np.where(of_host)[0]
                self.state = sb_ops.write_rows(
                    self.state,
                    jnp.asarray(glanes[ovr], jnp.int32),
                    jax.tree.map(lambda x: x[jnp.asarray(ovr)], pre),
                )
                self.dirty[tenants[valid & ~of_host]] = True
                raise CapacityOverflow(
                    f"{int(of_host.sum())} tenants still overflow after "
                    f"{attempt} widen migrations (caps {self.caps})",
                    tenants=tenants[ovr],
                )
            # Roll back overflowed tenants, widen EVERY lane's capacity
            # in one repack, retry only the overflowed slab lanes.
            ovr = np.where(of_host)[0]
            self.state = sb_ops.write_rows(
                self.state,
                jnp.asarray(glanes[ovr], jnp.int32),
                jax.tree.map(lambda x: x[jnp.asarray(ovr)], pre),
            )
            grow = self._widen_step()
            # The rollback base must track the widened layout, or a
            # SECOND overflow's scatter would mix pre-widen rows into
            # the widened state (shape mismatch at max_migrations > 1).
            pre = self.tk.widen(pre, **grow)
            keep = jnp.asarray(of_host)
            slab = slab._replace(
                kind=jnp.where(keep[:, None], slab.kind, sb_ops.NOOP)
            )
            idx_local = jnp.where(keep, jnp.asarray(idx_local), -1)
        self.dirty[tenants[valid]] = True
        return tel

    def _widen_step(self) -> Dict[str, int]:
        grow = {
            "deferred_cap": max(
                int(np.ceil(self.caps["deferred_cap"] * self.policy.factor)),
                self.caps["deferred_cap"] + 1,
            )
        }
        if "dot_cap" in self.caps:
            grow["dot_cap"] = max(
                int(np.ceil(self.caps["dot_cap"] * self.policy.factor)),
                self.caps["dot_cap"] + 1,
            )
        self.widen_capacity(**grow)
        return grow

    def widen_capacity(self, **growth: int) -> None:
        """Widen named capacity axes for EVERY lane (one repack — the
        PR 1 widen kernels with the lane axis as batch)."""
        self.state = self.tk.widen(self.state, **growth)
        self.caps.update(growth)
        self.widen_events += 1

    def narrow_capacity(self, **shrink: int) -> bool:
        """Try to narrow named capacity axes; a refusal (live lanes —
        the PR 5 ``narrow`` precondition) is a False no-op."""
        try:
            self.state = self.tk.narrow(self.state, **shrink)
        except ValueError:
            return False
        self.caps.update(shrink)
        return True

    def autoscale_capacity(self, pressure: Optional[float] = None):
        """One debounced capacity vote on the serving pressure signal
        (default: the last telemetry ``widen_pressure``) through
        ``elastic.Hysteresis.vote``. Returns the fired decision
        (``"widen"`` / ``"shrink"`` / None); shrink steps the deferred
        cap down by ``policy.factor`` to ``policy.shrink_floor`` and
        silently no-ops when lanes are live."""
        p = self.last_pressure if pressure is None else pressure
        vote = self.hysteresis.vote("serve.capacity", p)
        if vote == "widen":
            self._widen_step()
        elif vote == "shrink":
            floor = max(self.policy.shrink_floor, 1)
            target = max(int(self.caps["deferred_cap"] // self.policy.factor),
                         floor)
            if target >= self.caps["deferred_cap"]:
                return None
            if not self.narrow_capacity(deferred_cap=target):
                return None
        return vote

    # ---- per-tenant rows (the eviction tier's device boundary) ----------
    def _lane(self, tenant: int) -> int:
        lane = self.lane_of[tenant]
        if lane < 0:
            raise ValueError(
                f"tenant {tenant} is not resident — restore it first"
            )
        return int(lane)

    def row(self, tenant: int):
        """One resident tenant's state as a HOST pytree (numpy leaves)
        — the durable form the evictor persists."""
        return jax.tree.map(
            lambda x: np.asarray(x),
            sb_ops.unpack(self.state, self._lane(tenant)),
        )

    def write_row(self, tenant: int, row) -> None:
        """Land a full row for a tenant (allocating a lane on first
        touch — writing IS touching)."""
        lane = self.ensure_resident(tenant)
        self.state = sb_ops.write_rows(
            self.state,
            jnp.asarray([lane], jnp.int32),
            jax.tree.map(lambda x: jnp.asarray(x)[None], row),
        )

    def clear_lanes(self, lanes) -> None:
        """Reset device lanes to the join identity in ONE batched
        scatter (the evictor's post-persist clear)."""
        lanes = np.asarray(lanes, np.int32)
        if len(lanes) == 0:
            return
        empty = self.tk.empty(**self.caps, batch=(len(lanes),))
        self.state = sb_ops.write_rows(
            self.state, jnp.asarray(lanes), empty
        )

    def empty_row(self):
        return self.tk.empty(**self.caps)

    def read(self, tenant: int):
        """The resident tenant's observable read (host), via the
        kind's registered observe projection."""
        return jax.tree.map(
            np.asarray,
            self.tk.observe(sb_ops.unpack(self.state, self._lane(tenant))),
        )

    # ---- telemetry ------------------------------------------------------
    def annotate(self, tel: tele.Telemetry) -> tele.Telemetry:
        """Fill the host-owned serving gauges on a concrete Telemetry
        (the ``stream_*``/``wal_*`` fill discipline): ``live_tenants``
        = the served population (every session the front door answers
        for), ``evicted_tenants`` = tenants currently parked in the
        durable tier."""
        if not tele.is_concrete(tel):
            return tel
        n_evicted = int(
            (self.was_evicted & (self.lane_of < 0)).sum()
        )
        return tel._replace(
            live_tenants=jnp.uint32(self.n_tenants),
            evicted_tenants=jnp.uint32(n_evicted),
        )


from ..analysis.registry import register_shared_field as _reg_sf  # noqa: E402

# Shared-state coverage contract for the ``concurrency`` static-check
# section (analysis/effects.py): every field mutated outside __init__
# registers here, or discovery fails.
_reg_sf("state", owner="Superblock", module=__name__,
        kind="packed per-lane device state")
_reg_sf("caps", owner="Superblock", module=__name__,
        kind="per-kind capacity caps (widen/narrow)")
_reg_sf("lane_of", owner="Superblock", module=__name__,
        kind="tenant→lane indirection table")
_reg_sf("tenant_of", owner="Superblock", module=__name__,
        kind="lane→tenant back-pointer table")
_reg_sf("_free", owner="Superblock", module=__name__,
        kind="free-lane pool (deque)")
_reg_sf("dirty", owner="Superblock", module=__name__,
        kind="per-tenant dirty-since-persist flags")
_reg_sf("was_evicted", owner="Superblock", module=__name__,
        kind="per-tenant evicted-at-least-once flags")
_reg_sf("widen_events", owner="Superblock", module=__name__,
        kind="capacity-widen event counter")
_reg_sf("last_pressure", owner="Superblock", module=__name__,
        kind="smoothed lane-pressure telemetry")

__all__ = [
    "CapacityOverflow", "LanePressure", "PendingApply", "Superblock",
]
