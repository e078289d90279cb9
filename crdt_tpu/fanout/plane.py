"""The δ-subscription fan-out plane (ISSUE 16 tentpole).

:class:`FanoutPlane` is the serving tier's egress twin of the ingest
queue: clients register ``(tenant, acked watermark)`` interests and
every push cycle ships each subscriber the join-irreducible δ between
its acked watermark and the served tenant row — Almeida et al.'s
thin-client δ sync (PAPERS.md, arXiv 1410.2803 / 1603.01529) run
against the PR 15 superblock.

**Watermarks are versions of the sender's own shipped copy** — the
``delta_opt/ackwin.py`` discipline host-side. Per tenant the plane
keeps an integer version counter (0 = ⊥) and, per pushed version, the
bit-exact host snapshot of the row it shipped against. A subscriber's
acked watermark names one of those snapshots; promotion happens ONLY
on a positive ack (:meth:`FanoutPlane.ack` — acks are knowledge of
delivered content, never inference), so the encoder's base and the
client's decode base are bit-identical by construction, which is what
makes the biased-u16 wire delta-encoding exact end to end.

**Cohorts**: subscribers sharing ``(tenant, acked version)`` form one
cohort — ONE decomposition and ONE wire payload serve them all. A push
cycle buckets every lagging-or-dirty subscriber, packs cohorts into
``mesh_fanout_push`` dispatches (lane blocks per mesh rank, riding the
superblock's tenant→lane indirection — evicted tenants re-warm through
the evictor first, so the subscription registry survives
eviction/restore by keying on TENANT ids, never lanes), and marks the
shipped version pending per subscriber. Un-acked subscribers simply
re-enter the next cycle's cohorts (the retry loop is the bucketing).

**Slow/dead subscribers degrade, never buffer**: versions older than
``window_cap`` pushes are pruned; a subscriber acked below the window
(or at a pruned snapshot) falls back to the PR 10/11 snapshot+suffix
path — :func:`crdt_tpu.scaleout.bootstrap.bootstrap` against whatever
acked base survives — counted by the ``resync_fallbacks`` telemetry
counter and the ``subscriber_resync`` flight-recorder event. The
``fanout.ack.*`` crashpoints bracket the promote and resync
boundaries; ack promotion is idempotent, so a crash at any point
re-acks to the same watermark (tests/test_fanout.py fuzzes this amid
tenant eviction/restore cycles).

:func:`fanout_covers_cohorts` is the ``fanout`` static-check section's
broken-twin gate: a pusher that skips a watermark bucket (the
``analysis.fixtures.fanout_skips_watermark_bucket`` twin flips the
``_skip_versions`` seam) starves that cohort forever and MUST fail it.

Two of the prose invariants above are declared happens-before
contracts in ``analysis.concur.HB_CONTRACTS``:
``pin_precedes_gather_dispatch`` (a push chunk pins its whole tenant
set via ``_ensure_resident(_exclude=pinned)`` before warming, and
``_snapshot``/``_dispatch`` refuse a lane that lost residency
mid-cycle — the PR 16 lane-eviction race, rebuilt as the explorable
``analysis.fixtures.racy_fanout_world`` twin) and
``ack_clamped_to_window`` (promotion clamps to [watermark, shipped];
``analysis.fixtures.regressing_ack_promoter_cls`` must fail the
probe). ``analysis.interleave.fanout_world`` replays one push cycle
against client acks and a concurrent eviction under every
≤2-preemption schedule (the ``concurrency`` static-check section).
"""

from __future__ import annotations

from typing import Any, Dict, List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import telemetry as tele
from ..analysis.interleave import boundary
from ..durability import crashpoints
from ..obs import recorder as _rec
from ..obs import trace as obs_trace
from ..ops import superblock as sb_ops
from ..ops.fanout_kernels import CohortWire, wire_lane
from ..parallel.fanout_push import mesh_fanout_push

CP_ACK_PRE = crashpoints.register(
    "fanout.ack.pre_promote",
    "about to promote acked watermarks (nothing promoted yet — a kill "
    "here leaves every subscriber at its previous acked version)",
)
CP_ACK_POST = crashpoints.register(
    "fanout.ack.post_promote",
    "acked watermarks promoted, pending marks not yet cleared (the "
    "mid-ack boundary: re-acking promotes to the SAME version — "
    "promotion is idempotent)",
)
CP_RESYNC_PRE = crashpoints.register(
    "fanout.ack.pre_resync",
    "subscriber fell out of the ack window, snapshot+suffix resync not "
    "yet shipped (a kill here re-resyncs from the same live row)",
)


class CohortPush(NamedTuple):
    """One cohort's shipped δ payload: ``wire`` is the lane-sliced
    :class:`~crdt_tpu.ops.fanout_kernels.CohortWire` (batch axis 1)
    every member decodes against its acked base."""

    tenant: int
    base_ver: int     # the cohort's acked watermark version
    to_ver: int       # the version this payload lands the client at
    wire: CohortWire  # host-sliced, leading batch axis 1
    members: np.ndarray  # subscriber ids


class CohortResync(NamedTuple):
    """One cohort's snapshot+suffix fallback (the bootstrap path)."""

    tenant: int
    to_ver: int
    state: Any        # bit-identical to the served row (bootstrap law)
    report: Any       # scaleout.bootstrap.BootstrapReport
    members: np.ndarray


class PushReport(NamedTuple):
    """One push cycle's accounting."""

    pushes: List[CohortPush]
    resyncs: List[CohortResync]
    cohorts: int          # δ cohorts dispatched
    subscribers: int      # subscriber deliveries (δ + resync)
    telemetry: Optional[tele.Telemetry]


class FanoutPlane:
    """The subscription registry + push driver over one superblock
    (module docstring). ``dispatch_lanes`` must divide the mesh's
    replica axis; ``window_cap`` bounds how many un-acked versions a
    subscriber may lag before degrading to resync."""

    def __init__(
        self,
        superblock,
        *,
        evictor=None,
        window_cap: int = 4,
        dispatch_lanes: Optional[int] = None,
        capacity: int = 1024,
    ):
        self.sb = superblock
        self.ev = evictor
        self.kind = superblock.kind
        self.mesh = superblock.mesh
        self.p = superblock.p
        self.window_cap = int(window_cap)
        dl = int(dispatch_lanes) if dispatch_lanes else self.p * 256
        if dl % self.p:
            raise ValueError(
                f"{dl} dispatch lanes do not divide the {self.p}-way "
                f"replica mesh axis"
            )
        self.dispatch_lanes = dl
        # Per-tenant shipped-version counter (0 = ⊥) and the shipped
        # base snapshots: tenant -> {version: (host row, caps)}. Keyed
        # by TENANT id, never lane — eviction/re-warm is invisible.
        self.ver = np.zeros(superblock.n_tenants, np.int64)
        self._bases: Dict[int, Dict[int, tuple]] = {}
        # Plane-owned dirt (the ingest driver calls note_dirty after
        # applies): the superblock's dirty flag means
        # touched-since-persist, which the EVICTOR owns.
        self.dirt = np.zeros(superblock.n_tenants, bool)
        cap = max(int(capacity), 1)
        self.sub_tenant = np.full(cap, -1, np.int64)
        self.sub_ver = np.zeros(cap, np.int64)   # acked watermark
        self.sub_pend = np.full(cap, -1, np.int64)  # shipped, un-acked
        self._top = 0
        self._free_ids: List[int] = []
        self.resyncs_total = 0
        self._empty: Optional[tuple] = None  # (caps, host empty row)

    # ---- subscription registry -----------------------------------------
    @property
    def n_live(self) -> int:
        return int(np.count_nonzero(self.sub_tenant[: self._top] >= 0))

    def _grow(self, need: int) -> None:
        cap = len(self.sub_tenant)
        if need <= cap:
            return
        while cap < need:
            cap *= 2
        for name in ("sub_tenant", "sub_ver", "sub_pend"):
            old = getattr(self, name)
            fill = 0 if name == "sub_ver" else -1
            new = np.full(cap, fill, np.int64)
            new[: len(old)] = old
            setattr(self, name, new)

    def subscribe(self, tenants) -> np.ndarray:
        """Register subscribers (one per entry of ``tenants``) at the
        ⊥ watermark — their first push ships the full content as δ, or
        bootstraps when the tenant's window has moved past ⊥. Returns
        the subscriber ids."""
        tenants = np.atleast_1d(np.asarray(tenants, np.int64))
        n = len(tenants)
        ids = np.empty(n, np.int64)
        take = min(len(self._free_ids), n)
        for i in range(take):
            ids[i] = self._free_ids.pop()
        fresh = n - take
        if fresh:
            self._grow(self._top + fresh)
            ids[take:] = np.arange(self._top, self._top + fresh)
            self._top += fresh
        self.sub_tenant[ids] = tenants
        self.sub_ver[ids] = 0
        self.sub_pend[ids] = -1
        return ids

    def unsubscribe(self, ids) -> None:
        ids = np.atleast_1d(np.asarray(ids, np.int64))
        self.sub_tenant[ids] = -1
        self.sub_pend[ids] = -1
        self._free_ids.extend(int(i) for i in ids)

    def ack(self, ids, versions=None) -> None:
        """Positive confirmation: promote each subscriber's acked
        watermark (promote-on-ack, the ackwin discipline). ``versions``
        is the version the CLIENT says it applied
        (``ClientReplica.ver`` after its own ``ack()``) — pass it
        whenever deliveries can be lost, so a client that missed the
        latest ship promotes the server only to what it actually
        holds; ``None`` trusts the last shipped version (in-order
        synchronous transport). Promotion is monotonic: a reordered
        stale ack is clamped to the current watermark and a claim
        above the last shipped version is clamped down to it.
        Idempotent across the ``fanout.ack.*``
        crashpoints: a kill between promote and clear re-acks to the
        SAME version, and an un-promoted kill leaves the pending mark
        for the re-ack."""
        boundary("ack.promote")
        ids = np.atleast_1d(np.asarray(ids, np.int64))
        crashpoints.hit(CP_ACK_PRE)
        pend = self.sub_pend[ids]
        if versions is None:
            v = pend
        else:
            v = np.broadcast_to(
                np.asarray(versions, np.int64), ids.shape
            )
        ok = (pend >= 0) & (self.sub_tenant[ids] >= 0) & (v >= 0)
        sel = ids[ok]
        # Lossy transports reorder and duplicate acks: clamp each
        # promotion to [current watermark, last shipped version]. A
        # stale ack arriving after a newer one must never regress
        # sub_ver below the base the client actually decodes with (the
        # next push would encode against an older snapshot and the
        # client would silently reconstruct wrong state), and a claim
        # above pend names a version this plane never shipped. max
        # keeps the promote idempotent across the fanout.ack.*
        # crashpoints.
        self.sub_ver[sel] = np.maximum(
            self.sub_ver[sel], np.minimum(v[ok], pend[ok])
        )
        crashpoints.hit(CP_ACK_POST)
        # An ack BELOW the pending ship confirms an older payload only:
        # the latest ship is still outstanding, so keep its pend mark —
        # clearing it would gate out the real ack when a duplicate of
        # an old one sneaks in first (lag-driven re-bucketing still
        # covers the subscriber either way).
        self.sub_pend[sel[v[ok] >= pend[ok]]] = -1
        # Close the freshness loop: each promoted tenant's highest
        # acked watermark completes every open trace pushed at or
        # below it (the submit→client-ack headline metric).
        if obs_trace.get_tracer() is not None and len(sel):
            t_sel = self.sub_tenant[sel]
            v_sel = self.sub_ver[sel]
            for t in np.unique(t_sel):
                obs_trace.stamp(
                    "ack", tenant=int(t),
                    version=int(v_sel[t_sel == t].max()),
                )

    def note_dirty(self, tenants) -> None:
        """Mark tenants changed since their last push (the ingest
        driver's hook — mirrors ``Evictor.note_touch``)."""
        self.dirt[np.atleast_1d(np.asarray(tenants, np.int64))] = True

    # ---- base snapshots --------------------------------------------------
    def _empty_host(self):
        """The host ⊥ row at the superblock's CURRENT caps, cached —
        every ⊥-watermark cohort uses it as its base, so building it
        per cohort would cost a device transfer each."""
        caps = dict(self.sb.caps)
        if self._empty is None or self._empty[0] != caps:
            self._empty = (
                caps, jax.tree.map(np.asarray, self.sb.empty_row())
            )
        return self._empty[1]

    def _base_row(self, tenant: int, version: int):
        """The bit-exact host row shipped as ``version`` of ``tenant``
        (⊥ synthesized for version 0), widened to the superblock's
        CURRENT capacity when an elastic widen landed since the
        snapshot — decompose needs shape-identical operands. None when
        the snapshot was pruned (the caller resyncs)."""
        if version == 0:
            return self._empty_host()
        entry = self._bases.get(int(tenant), {}).get(int(version))
        if entry is None:
            return None
        row, caps = entry
        if caps != self.sb.caps:
            grow = {
                k: v for k, v in self.sb.caps.items()
                if v > caps.get(k, 0)
            }
            row = jax.tree.map(np.asarray, self.sb.tk.widen(row, **grow))
            self._bases[int(tenant)][int(version)] = (row, dict(self.sb.caps))
        return row

    def _snapshot(self, tenants: np.ndarray) -> None:
        """Bump each tenant's version and store the live row as the
        new shipped base — ONE batched device gather for the whole
        cycle, then host slices."""
        if len(tenants) == 0:
            return
        lanes_host = np.asarray(self.sb.lane_of[tenants])
        if np.any(lanes_host < 0):
            lost = tenants[lanes_host < 0]
            raise RuntimeError(
                f"tenants {lost.tolist()} lost residency mid-cycle — a "
                f"-1 lane would gather a wrapped index (another "
                f"tenant's row) as the shipped base snapshot"
            )
        lanes = jnp.asarray(lanes_host, jnp.int32)
        host = jax.tree.map(
            np.asarray, sb_ops.gather_rows(self.sb.state, lanes)
        )
        caps = dict(self.sb.caps)
        for i, t in enumerate(tenants):
            t = int(t)
            self.ver[t] += 1
            row = jax.tree.map(lambda x, i=i: x[i], host)
            vers = self._bases.setdefault(t, {})
            vers[int(self.ver[t])] = (row, caps)
            floor = int(self.ver[t]) - self.window_cap
            for v in [v for v in vers if v < floor]:
                del vers[v]

    def _ensure_resident(self, tenant: int, _exclude=()) -> None:
        """Warm one tenant's lane before the cycle reads it. ``_exclude``
        pins the cycle's whole pushed-tenant set (the ingest slab's
        ``restore(t, _exclude=placed)`` discipline): a lane-pressure
        eviction inside ``restore`` must never free a lane some OTHER
        cohort of this same cycle is about to snapshot or dispatch
        from. A push is also a touch — refreshing recency keeps
        fan-out-restored tenants off the next pressure batch's cold
        list (they would otherwise keep a stale ``last_touch`` and
        thrash restore→evict→restore)."""
        if self.sb.lane_of[tenant] < 0:
            if self.ev is not None:
                self.ev.restore(int(tenant), _exclude=_exclude)
            else:
                self.sb.ensure_resident(int(tenant))
        if self.ev is not None:
            self.ev.note_touch(int(tenant))

    # ---- the push cycle --------------------------------------------------
    def push(
        self,
        tenants=None,
        *,
        telemetry: bool = False,
        _skip_versions=(),
    ) -> PushReport:
        """One fan-out cycle: bucket every lagging-or-dirty subscriber
        into ``(tenant, acked version)`` cohorts, dispatch the δ
        cohorts through ``mesh_fanout_push``, degrade out-of-window
        cohorts to snapshot+suffix resync. ``tenants`` overrides the
        dirty set for this cycle (default: every tenant noted dirty
        since the last push). ``_skip_versions`` is the broken-twin
        seam (``analysis.fixtures.fanout_skips_watermark_bucket``):
        production callers never pass it."""
        with tele.span("fanout.push"):
            top = self._top
            st = self.sub_tenant[:top]
            alive = st >= 0
            safe_t = np.where(alive, st, 0)
            if tenants is None:
                dirty = self.dirt
            else:
                dirty = np.zeros(self.sb.n_tenants, bool)
                dirty[np.atleast_1d(np.asarray(tenants, np.int64))] = True
            lag = alive & (self.sub_ver[:top] < self.ver[safe_t])
            sel = alive & (dirty[safe_t] | lag)
            ids = np.where(sel)[0]
            report = PushReport([], [], 0, 0, None)
            if len(ids) == 0:
                tel = self.annotate(tele.zeros()) if telemetry else None
                return report._replace(telemetry=tel)

            # Residency + version bump for the dirty tenants being pushed
            # (lag-only tenants keep their version: their stored newest
            # base IS the live row — note_dirty is the change contract).
            t_s = st[ids]
            v_s = self.sub_ver[:top][ids]
            pushed_tenants = np.unique(t_s)

            # Residency is the cycle's working-set bound: a chunk's tenants
            # must hold their lanes from the batched snapshot gather through
            # the lane-indexed dispatch, so a push over MORE tenants than
            # the lane pool proceeds in pool-sized chunks. Each chunk pins
            # ONLY its own tenants against the restores' lane-pressure
            # evictions (the ingest slab's ``restore(t, _exclude=placed)``
            # discipline — without the pin a mid-cycle eviction hands an
            # already-warmed cohort's lane to another tenant and its row
            # ships as the wrong δ base); a later chunk is free to page an
            # earlier chunk's lanes out, because that chunk already shipped.
            pushes: List[CohortPush] = []
            resyncs: List[CohortResync] = []
            tel = None
            n_cohorts = 0
            n_subs = 0
            n_resync_subs = 0
            resync_bytes = 0.0
            chunk_cap = max(self.sb.n_lanes, 1)
            for lo in range(0, len(pushed_tenants), chunk_cap):
                chunk = pushed_tenants[lo:lo + chunk_cap]
                pinned = set(map(int, chunk))
                with tele.span("fanout.warm"):
                    for t in chunk:
                        self._ensure_resident(int(t), _exclude=pinned)
                boundary("push.warm")
                bumped = chunk[dirty[chunk]]
                with tele.span("fanout.snapshot"):
                    self._snapshot(bumped)
                boundary("push.snapshot")
                self.dirt[bumped] = False

                with tele.span("fanout.cohorts"):
                    # Cohorts: subscribers sharing (tenant, acked version).
                    in_chunk = np.isin(t_s, chunk)
                    c_ids, c_t, c_v = ids[in_chunk], t_s[in_chunk], v_s[in_chunk]
                    code = c_t * (int(self.ver.max()) + 2) + c_v
                    order = np.argsort(code, kind="stable")
                    c_ids, c_t, c_v = c_ids[order], c_t[order], c_v[order]
                    _, starts, counts = np.unique(
                        code[order], return_index=True, return_counts=True
                    )

                    wire_cohorts: List[tuple] = []
                    for s, c in zip(starts, counts):
                        t, v = int(c_t[s]), int(c_v[s])
                        members = c_ids[s:s + c]
                        target = int(self.ver[t])
                        if v == target:
                            continue  # already current (dirty push raced an ack)
                        if v in _skip_versions:
                            continue  # the broken-twin seam — never taken honestly
                        base = self._base_row(t, v)
                        if (target - v > self.window_cap) or base is None:
                            crashpoints.hit(CP_RESYNC_PRE)
                            from ..scaleout.bootstrap import bootstrap

                            state, rep = bootstrap(
                                self.kind, self.sb.row(t), base=base
                            )
                            resyncs.append(CohortResync(
                                tenant=t, to_ver=target,
                                state=jax.tree.map(np.asarray, state), report=rep,
                                members=members,
                            ))
                            self.sub_pend[members] = target
                            n_resync_subs += len(members)
                            resync_bytes += rep.bytes_shipped * len(members)
                            obs_trace.stamp("push", tenant=t, version=target)
                            _rec.emit(
                                "subscriber_resync", tenant=t,
                                subscribers=len(members),
                            )
                        else:
                            wire_cohorts.append((t, v, target, members, base))

                chunk_pushes, chunk_tel = self._dispatch(wire_cohorts, telemetry)
                pushes.extend(chunk_pushes)
                n_cohorts += len(wire_cohorts)
                n_subs += int(sum(len(m) for *_x, m, _b in wire_cohorts))
                if chunk_tel is not None:
                    tel = (
                        chunk_tel if tel is None
                        else tele.combine(tel, chunk_tel)
                    )

            self.resyncs_total += n_resync_subs
            if telemetry:
                tel = tele.zeros() if tel is None else tel
                tel = self.annotate(tel._replace(
                    resync_fallbacks=(
                        tel.resync_fallbacks + jnp.uint32(n_resync_subs)
                    ),
                    bootstrap_bytes=(
                        tel.bootstrap_bytes + jnp.float32(resync_bytes)
                    ),
                ))
            return PushReport(
                pushes=pushes, resyncs=resyncs, cohorts=n_cohorts,
                subscribers=n_subs + n_resync_subs, telemetry=tel,
            )

    def _dispatch(self, cohorts, telemetry: bool):
        """Pack wire cohorts into ``dispatch_lanes``-wide
        ``mesh_fanout_push`` calls: each cohort lands in the lane block
        of the mesh rank owning its tenant's superblock lane (the
        serve_apply index convention)."""
        boundary("push.dispatch")
        pushes: List[CohortPush] = []
        tel = None
        if not cohorts:
            return pushes, tel
        lpr_disp = self.dispatch_lanes // self.p
        per_rank: List[List[tuple]] = [[] for _ in range(self.p)]
        for co in cohorts:
            lane = int(self.sb.lane_of[co[0]])
            if lane < 0:
                raise RuntimeError(
                    f"tenant {co[0]} lost residency mid-cycle — a -1 "
                    f"lane would dispatch another rank's row as this "
                    f"cohort's delta base"
                )
            per_rank[lane // self.sb.lanes_per_rank].append((lane, co))
        n_disp = max(
            (len(r) + lpr_disp - 1) // lpr_disp for r in per_rank
        )
        empty = self._empty_host()
        for dnum in range(n_disp):
            with tele.span("fanout.dispatch", lanes=self.dispatch_lanes) as sp:
                idx = np.full(self.dispatch_lanes, -1, np.int32)
                wts = np.zeros(self.dispatch_lanes, np.float32)
                rows = [empty] * self.dispatch_lanes
                slots: List[tuple] = []
                for r in range(self.p):
                    chunk = per_rank[r][dnum * lpr_disp:(dnum + 1) * lpr_disp]
                    for j, (lane, (t, v, target, members, base)) in enumerate(
                        chunk
                    ):
                        dl = r * lpr_disp + j
                        idx[dl] = lane % self.sb.lanes_per_rank
                        wts[dl] = len(members)
                        rows[dl] = base
                        slots.append((dl, t, v, target, members))
                sp.attrs["live"] = len(slots)
                with tele.span("fanout.stack"):
                    bases_dev = jax.tree.map(
                        lambda *xs: jnp.asarray(np.stack(xs)), *rows
                    )
                out = mesh_fanout_push(
                    self.sb.state, bases_dev, jnp.asarray(idx), self.mesh,
                    kind=self.kind, weights=jnp.asarray(wts),
                    telemetry=telemetry,
                )
                with tele.span("fanout.wire_fetch"):
                    wire = jax.tree.map(np.asarray, out[0])
                if telemetry:
                    t3 = out[2]
                    tel = t3 if tel is None else tele.combine(tel, t3)
                with tele.span("fanout.deliver"):
                    for dl, t, v, target, members in slots:
                        pushes.append(CohortPush(
                            tenant=t, base_ver=v, to_ver=target,
                            wire=wire_lane(wire, dl), members=members,
                        ))
                        self.sub_pend[members] = target
                        obs_trace.stamp("push", tenant=t, version=target)
                    _rec.emit(
                        "fanout_push", cohorts=len(slots),
                        subscribers=int(wts.sum()),
                    )
        return pushes, tel

    # ---- telemetry -------------------------------------------------------
    def annotate(self, tel: tele.Telemetry) -> tele.Telemetry:
        """Fill the host-owned fan-out gauge (the serve ``annotate``
        discipline): ``subscribers_live`` = the registered population
        the plane answers for."""
        if not tele.is_concrete(tel):
            return tel
        return tel._replace(subscribers_live=jnp.uint32(self.n_live))


def fanout_covers_cohorts(push_fn) -> bool:
    """Detector behind the ``fanout`` static-check section: drive
    ``push_fn(plane)`` over a two-subscriber workload whose acks split
    the subscribers into DIFFERENT watermark buckets, deliver every
    payload, and return True iff both client replicas land
    bit-identical to the served tenant. The honest
    ``FanoutPlane.push`` passes; the committed bucket-skipping twin
    (``analysis.fixtures.fanout_skips_watermark_bucket``) starves the
    stale-watermark cohort and must FAIL here, proving the gate
    catches cohort-selection bugs."""
    from ..parallel import make_mesh
    from ..serve.superblock import Superblock
    from .client import ClientReplica

    mesh = make_mesh(1, 1)
    caps = dict(n_elems=4, n_actors=2, deferred_cap=2)
    sb = Superblock(2, mesh, kind="orswot", caps=caps)
    plane = FanoutPlane(sb, window_cap=8, dispatch_lanes=2)
    ids = plane.subscribe([0, 0])
    clients = {int(i): ClientReplica("orswot", sb.empty_row()) for i in ids}
    m = lambda *on: np.isin(np.arange(4), on)  # noqa: E731

    def touch(adds):
        lane = sb.ensure_resident(0)
        row = sb_ops.unpack(sb.state, lane)
        for actor, c, mask in adds:
            row, _ = sb.tk.apply_add(
                row, jnp.int32(actor), jnp.uint32(c), jnp.asarray(mask)
            )
        sb.state = sb_ops.write_rows(
            sb.state, jnp.asarray([lane], jnp.int32),
            jax.tree.map(lambda x: x[None], row),
        )
        plane.note_dirty([0])

    def deliver(rep):
        for cp in rep.pushes:
            for s in cp.members:
                clients[int(s)].apply_wire(cp.wire, cp.to_ver)
        for rs in rep.resyncs:
            for s in rs.members:
                clients[int(s)].adopt(rs.state, rs.to_ver)

    touch([(0, 1, m(0, 1))])
    deliver(push_fn(plane))
    clients[int(ids[0])].ack()
    plane.ack([ids[0]])  # only subscriber 0 promotes — watermarks split
    touch([(1, 1, m(2)), (0, 2, m(3))])
    deliver(push_fn(plane))  # cohorts (t0, v1) AND (t0, v0)
    for i in ids:
        clients[int(i)].ack()
    plane.ack(ids)
    want = sb.row(0)
    return all(clients[int(i)].equals(want) for i in ids)


# ---- observability registration (crdt_tpu.analysis) -----------------------

from ..analysis.registry import register_obs_event as _reg_ev  # noqa: E402

_reg_ev(
    "fanout_push", subsystem="fanout",
    fields=("cohorts", "subscribers"), module=__name__,
)
_reg_ev(
    "subscriber_resync", subsystem="fanout",
    fields=("tenant", "subscribers"), module=__name__,
)

from ..analysis.registry import register_shared_field as _reg_sf  # noqa: E402

_reg_sf("ver", owner="FanoutPlane", module=__name__,
        kind="per-tenant shipped-version counters")
_reg_sf("dirt", owner="FanoutPlane", module=__name__,
        kind="per-tenant dirty-since-push flags")
_reg_sf("_bases", owner="FanoutPlane", module=__name__,
        kind="retained δ bases keyed (tenant, version)")
_reg_sf("sub_tenant", owner="FanoutPlane", module=__name__,
        kind="subscriber→tenant interest table")
_reg_sf("sub_ver", owner="FanoutPlane", module=__name__,
        kind="per-subscriber acked watermark")
_reg_sf("sub_pend", owner="FanoutPlane", module=__name__,
        kind="per-subscriber shipped-pending version")
_reg_sf("_top", owner="FanoutPlane", module=__name__,
        kind="high-water subscriber id")
_reg_sf("_free_ids", owner="FanoutPlane", module=__name__,
        kind="recycled subscriber-id pool")
_reg_sf("resyncs_total", owner="FanoutPlane", module=__name__,
        kind="lifetime forced-resync counter")
_reg_sf("_empty", owner="FanoutPlane", module=__name__,
        kind="cached host empty-row template")

__all__ = [
    "CohortPush", "CohortResync", "FanoutPlane", "PushReport",
    "fanout_covers_cohorts",
]
