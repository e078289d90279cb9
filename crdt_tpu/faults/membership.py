"""Rank liveness and eviction — the membership half of fault tolerance.

The mesh's safe default for a silent rank is to WAIT: PR 5's stable
frontier pins on a straggler's stale top, which is never unsafe but
lets memory grow without bound exactly when a production mesh is
degraded. This module is the operator-side escape hatch: per-rank miss
accounting fed by the in-kernel :class:`~.inject.FaultCounters` streaks,
a K-consecutive-misses suspicion rule, and an explicit eviction decision
that (a) rebuilds the ring permutation over live ranks only
(``inject.ring_perm`` — still a true bijection, so the PR 7 collective
lint holds) and (b) removes the evicted rank's top from the frontier
``pmin``, unpinning reclamation.

Protocol (the chaos tests walk it end to end):

1. run mesh rounds with ``faults=tracker.plan(base)``;
2. feed the returned counters to :meth:`Membership.observe` — a rank
   whose outbound link delivered nothing for ``k_suspect`` consecutive
   rounds becomes SUSPECT;
3. :meth:`Membership.evict` suspects (policy: automatic via
   ``auto_evict=True`` on observe, or operator-driven);
4. a returning rank calls :meth:`Membership.rejoin` ONLY after
   state-driven resync (Enes et al. 1803.02750) — while it was out,
   the frontier may have advanced past its top and compaction may have
   retired parked slots it never saw, so δ re-entry from its stale
   tracking is forbidden. THREE sound re-entry paths:

   - **full-state resync** (the original contract — always available):
     the rank's state is replaced wholesale by full-state gossip/fold
     over a live replica; ships a whole state, needs no local
     artifacts.
   - **log-suffix rejoin** (ISSUE 10,
     ``crdt_tpu.durability.recover.rejoin``) for a rank that recovered
     locally from its snapshot + write-ahead δ-log: the live peer
     ships only its join-irreducible decomposition over the recovered
     state (reconstruction is positionally bit-exact whatever the
     bound, and the final join keeps recovered-but-unreplicated local
     content) — < 25% of full-state bytes
     (``tests/test_durability.py``).
   - **bootstrap-from-⊥** (ISSUE 11, ``crdt_tpu.scaleout.bootstrap``):
     the rank re-enters as a NEW member through the scale-out admit
     path — its causal lower bound is ⊥ (or a PR 10 snapshot as the
     warm base, which again ships only the log suffix), the wire
     carries segmented, integrity-checked ``decompose(live, base)``
     lanes, and its pre-eviction identity (tracking, marks, window
     state) is simply abandoned. This is the right exit when the
     rank's local artifacts are gone or untrusted; membership-wise it
     is ``ScaleoutMesh.admit``, not ``rejoin``.
   - **inter-mesh re-homing** (ISSUE 20, ``crdt_tpu.geo.failover
     .fail_over_region``) — the FOURTH contract, one level up: here
     the evicted member is a whole REGION (one mesh), and what
     re-enters is not the region but its HOME TENANT SHARDS, re-homed
     onto the surviving regions by minimal rendezvous remap. Each new
     home rebuilds a tenant from the dead region's durable tier
     (snapshot rows + the ServeWal suffix replayed through its own
     ingest queue — acks were gated on that WAL's group commit, so a
     complete tier recovers every acked op) plus peer-region
     divergence lanes (surviving mirrors, δ-decomposed against the
     recovery; adopted wholesale only in the sole-survivor case).
     Membership-wise it is ``FederationMembership.evict`` — a
     generation bump that refuses every pre-failover packet — and
     every ack window touching a re-homed tenant resets to ⊥ with its
     surviving mirrors cleared, so the next cross-region exchange
     re-ships full state against ⊥.

   δ re-entry from stale marks remains forbidden on every path —
   intra-mesh (rank tracking, ack marks) and inter-mesh (geo link
   acked bases) alike.

The liveness signal is receiver-measured: device p's ``miss_streak[p]``
counts consecutive end-of-run rounds with nothing arriving on its
inbound link, and :meth:`observe` maps that back to the SENDER through
the same ``sender_of`` table the kernel used. Streaks that span runs
accumulate (a run fully missed extends the streak by its round count);
any delivery resets.
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import numpy as np

from .. import obs
from ..utils.metrics import metrics
from .inject import FaultPlan, ring_perm, sender_of


def validate_perm(perm: Sequence[Tuple[int, int]], p: int) -> List[str]:
    """Check a ppermute pair list is a TRUE BIJECTION of a size-``p``
    axis — every rank sends exactly once and receives exactly once.
    Returns the violations (empty = valid). This is the standalone
    detector behind the ``faults`` static-check section: the broken
    eviction twin (``analysis.fixtures.eviction_drops_ranks``, which
    omits evicted ranks instead of self-looping them) must fail here,
    exactly as it would fail the PR 7 ppermute lint once traced."""
    errs: List[str] = []
    srcs = [s for s, _ in perm]
    dsts = [d for _, d in perm]
    for name, seen in (("source", srcs), ("destination", dsts)):
        missing = sorted(set(range(p)) - set(seen))
        dupes = sorted({x for x in seen if seen.count(x) > 1})
        if missing:
            errs.append(f"{name}s missing ranks {missing} (axis size {p})")
        if dupes:
            errs.append(f"duplicate {name}s {dupes}")
    out_of_range = sorted(
        {x for x in srcs + dsts if not 0 <= x < p}
    )
    if out_of_range:
        errs.append(f"ranks {out_of_range} outside axis [0, {p})")
    return errs


class Membership:
    """Host-side liveness tracker for one replica mesh axis."""

    def __init__(self, n_ranks: int, k_suspect: int = 3):
        if n_ranks < 1:
            raise ValueError("n_ranks must be >= 1")
        if k_suspect < 1:
            raise ValueError("k_suspect must be >= 1")
        self.n_ranks = n_ranks
        self.k_suspect = k_suspect
        # Consecutive missed rounds per SENDER rank (accumulated across
        # runs; reset by any observed delivery or by rejoin).
        self.streaks = [0] * n_ranks
        self._evicted: set = set()

    # ---- state ------------------------------------------------------------

    @property
    def evicted(self) -> Tuple[int, ...]:
        return tuple(sorted(self._evicted))

    def live(self) -> Tuple[int, ...]:
        return tuple(
            i for i in range(self.n_ranks) if i not in self._evicted
        )

    def suspects(self) -> Tuple[int, ...]:
        """Live ranks whose outbound link has missed ``k_suspect``
        consecutive rounds."""
        return tuple(
            i for i in range(self.n_ranks)
            if i not in self._evicted and self.streaks[i] >= self.k_suspect
        )

    def plan(self, base: Optional[FaultPlan] = None) -> FaultPlan:
        """The base plan with this tracker's current eviction set — what
        the next mesh round should run under."""
        return (base or FaultPlan()).with_evicted(self.evicted)

    # ---- transitions ------------------------------------------------------

    def observe(self, counters, rounds: int,
                auto_evict: bool = False) -> Tuple[int, ...]:
        """Fold one run's :class:`~.inject.FaultCounters` in. ``rounds``
        is the run's exchange-round count (the in-kernel streak
        saturates there — a fully-missed run extends a spanning streak
        rather than resetting it). Returns the post-update suspect set;
        with ``auto_evict=True`` suspects are evicted immediately."""
        streak = np.asarray(counters.miss_streak).reshape(-1)
        if streak.shape[0] != self.n_ranks:
            raise ValueError(
                f"miss_streak has {streak.shape[0]} lanes, tracker "
                f"covers {self.n_ranks} ranks"
            )
        senders = sender_of(self.n_ranks, self.evicted)
        for dst in range(self.n_ranks):
            src = senders[dst]
            if src == dst and src in self._evicted:
                continue  # self-loop of an evicted rank: no liveness info
            s = int(streak[dst])
            if s >= rounds > 0:
                self.streaks[src] += rounds  # whole run missed: spans
            else:
                self.streaks[src] = s
        hot = self.suspects()
        for r in hot:
            metrics.count("faults.rank_suspected")
            obs.emit("rank_suspected", suspect=r, streak=self.streaks[r])
        if auto_evict:
            for r in hot:
                self.evict(r)
        return hot

    def evict(self, rank: int) -> None:
        """Remove ``rank`` from the ring and the frontier ``pmin``. The
        headline consequence: the mesh's stable frontier stops pinning
        on the dead rank's stale top and reclamation resumes
        (reclaim/frontier.py documents why the un-evicted default must
        pin)."""
        self._check_rank(rank)
        if rank in self._evicted:
            return
        if len(self._evicted) + 1 >= self.n_ranks:
            raise ValueError(
                f"evicting rank {rank} would leave fewer than one live "
                f"rank on a {self.n_ranks}-rank axis"
            )
        self._evicted.add(rank)
        metrics.count("faults.rank_evicted")
        obs.emit("rank_evicted", evicted=rank,
                 live=self.n_ranks - len(self._evicted))

    def rejoin(self, rank: int) -> None:
        """Re-admit ``rank``. PRECONDITION (the caller's contract): the
        rank's state has been replaced by state-driven resync against a
        live replica — full-state gossip, or the log-suffix form
        (``durability.recover.rejoin``) when the rank recovered locally
        from snapshot + WAL (module docstring item 4). Its pre-eviction
        δ TRACKING is stale either way (the frontier may have advanced
        past its top; compaction may have retired slots it never saw)
        and must not re-enter the δ ring; a state join is always sound,
        δ re-entry from stale marks is not."""
        self._check_rank(rank)
        self._evicted.discard(rank)
        self.streaks[rank] = 0
        metrics.count("faults.rank_rejoined")
        obs.emit("rank_rejoined", rejoined=rank,
                 live=self.n_ranks - len(self._evicted))

    def _check_rank(self, rank: int) -> None:
        if not 0 <= rank < self.n_ranks:
            raise ValueError(
                f"rank {rank} outside [0, {self.n_ranks})"
            )

    def ring(self) -> List[Tuple[int, int]]:
        """The current live-rank ring permutation (a true bijection)."""
        return ring_perm(self.n_ranks, self.evicted)


# Flight-recorder event schemas for the membership transitions
# (registration is the coverage contract — obs/recorder.py).
from ..analysis.registry import register_obs_event as _reg_ev  # noqa: E402

_reg_ev("rank_suspected", subsystem="faults.membership",
        fields=("suspect", "streak"), module=__name__)
_reg_ev("rank_evicted", subsystem="faults.membership",
        fields=("evicted", "live"), module=__name__)
_reg_ev("rank_rejoined", subsystem="faults.membership",
        fields=("rejoined", "live"), module=__name__)


__all__ = ["Membership", "validate_perm"]
