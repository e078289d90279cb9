"""Elastic capacity manager: live overflow → widen → resume migration.

Every bounded device structure surfaces overflow correctly
(``DeferredOverflow`` / ``DotCapacityOverflow`` / ``SlotOverflow`` /
a full interned universe's ``UniverseFull``) but, before this module, the
only remedy was "rebuild the model with a larger capacity" — a
long-lived replica that hit a cap mid-gossip was dead. This module is
the sanctioned recovery, the capacity analog of lifecycle.py's dtype
widening:

- :func:`widen` — grow named capacity axes (2× by default,
  policy-configurable) and re-encode the live device state into the
  wider layout via the per-kind ``widen`` kernels (``ops/orswot.py``,
  ``ops/sparse_orswot.py``, ``ops/sparse_mvmap.py``,
  ``ops/sparse_nest.py``, ``ops/mvreg.py`` through ``ops/map.py``) —
  pure tail padding for dense slabs, a monotone segment-table repack
  for sparse (no host round-trip either way). Delta-state semantics
  (Almeida et al.; Enes et al., PAPERS.md) guarantee the re-encoded
  state rejoins gossip and converges without replay: the migration is
  bit-identical to a from-scratch model built at the wider capacity,
  so every later join is the same lattice join.
- :func:`recover` / :func:`elastic_call` — the overflow→widen→resume
  loop: map a capacity error to the implicated axes, widen them, retry.
- :func:`widen_dtype` / :func:`migrate` — compose capacity growth with
  lifecycle-style u32→u64 counter widening in ONE migration (every
  uint32 plane of a causal state is a counter plane — ids are int32,
  masks bool — so the dtype migration is one dtype-gated tree map).
- :func:`utilization` / :func:`record_headroom` — per-kind headroom
  gauges (``elastic.<kind>.headroom.<axis>``) so operators see pressure
  BEFORE overflow; :func:`widen` feeds ``elastic.widen_events`` and
  ``elastic.migrated_bytes`` counters.
- :func:`shrink` / :class:`Hysteresis` — the INVERSE migration
  (reclaim/, ISSUE 5): per-kind ``narrow``/``narrow_span`` kernels
  slice dead tail lanes off (refused when occupancy does not fit),
  governed by a low-water hysteresis so widen/shrink cannot thrash;
  feeds ``reclaim.shrink_events`` / ``reclaim.reclaimed_bytes``.
  Run ``reclaim.compact_model`` first so retired parked slots and
  stale payload do not pin lanes.

Like lifecycle.py's migrations, widening is ADMINISTRATIVE: apply it
identically on every host holding the replica set. It commutes with
gossip (the widened state is bit-identical to a wider-born one), so a
replica may pause mid-round, migrate, and rejoin — the ring entry
points' elastic wrappers (parallel/anti_entropy.py ``gossip_elastic``,
parallel/delta_ring.py ``delta_gossip_elastic``) do exactly that.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Callable, Dict, Optional, Tuple

import numpy as np

from .models.orswot import BatchedOrswot, DeferredOverflow
from .models.registers import SlotOverflow
from .models.sparse_orswot import BatchedSparseOrswot, DotCapacityOverflow
from .utils.interner import UniverseFull
from .utils.metrics import metrics, state_nbytes


#: The errors :func:`elastic_call` treats as recoverable capacity
#: pressure. UniverseFull is the interner's full-universe signal
#: (utils/interner.py bounded_intern); a plain IndexError is a bug in
#: the caller's code and re-raises untouched.
CAPACITY_ERRORS = (
    DeferredOverflow, DotCapacityOverflow, SlotOverflow, UniverseFull
)


@dataclass(frozen=True)
class ElasticPolicy:
    """How aggressively to widen — and how cautiously to shrink.

    ``factor`` scales each implicated axis on widen (ceil, never less
    than +1 lane) and divides it on shrink; ``max_migrations`` bounds
    the overflow→widen→retry loop of :func:`elastic_call`.

    The shrink half (reclaim/, ISSUE 5) is deliberately hysteretic so
    widen/shrink cannot thrash: :class:`Hysteresis` shrinks an axis
    only after its occupancy sat below ``low_water`` for
    ``shrink_rounds`` CONSECUTIVE observations, never below
    ``shrink_floor`` lanes, and any widening resets the streak.

    The widen half (``high_water`` / ``widen_rounds``, ISSUE 11) makes
    the debounce SYMMETRIC for policy drivers that decide in both
    directions (``Hysteresis.vote`` — the scaleout Autoscaler's
    admit/drain governor): a pressure signal must sit at or above
    ``high_water`` for ``widen_rounds`` consecutive observations before
    a widen-direction decision fires. The original shrink-only fields
    keep their exact semantics — ``observe`` is unchanged."""

    factor: float = 2.0
    max_migrations: int = 4
    low_water: float = 0.25
    shrink_rounds: int = 4
    shrink_floor: int = 8
    high_water: float = 0.85
    widen_rounds: int = 2


DEFAULT_POLICY = ElasticPolicy()


# ---- per-kind axis tables -------------------------------------------------
# axis -> (capacity, used-thunk) getters; "used" is the live occupancy
# the headroom gauges report (interner length for universes, max live
# slots for device buffers). Occupancy is LAZY — it forces a device →
# host copy of the masks (and, for sparse maps, an O(live cells)
# unique), which capacity-only callers (widen, capacities) never need.

def _max_count(mask) -> int:
    a = np.asarray(mask)
    return int(a.sum(axis=-1).max()) if a.size else 0


def _max_listed(ids) -> int:
    a = np.asarray(ids)
    return int((a >= 0).sum(axis=-1).max()) if a.size else 0


def _axes_orswot(m) -> Dict[str, Tuple[int, Callable[[], int]]]:
    return {
        "n_members": (m.state.ctr.shape[-2], lambda: len(m.members)),
        "n_actors": (m.state.top.shape[-1], lambda: len(m.actors)),
        "deferred_cap": (
            m.state.dvalid.shape[-1], lambda: _max_count(m.state.dvalid)
        ),
    }


def _axes_sparse_orswot(m) -> Dict[str, Tuple[int, Callable[[], int]]]:
    return {
        "dot_cap": (m.state.eid.shape[-1], lambda: _max_count(m.state.valid)),
        "n_actors": (m.state.top.shape[-1], lambda: len(m.actors)),
        "deferred_cap": (
            m.state.dvalid.shape[-1], lambda: _max_count(m.state.dvalid)
        ),
        "rm_width": (
            m.state.didx.shape[-1], lambda: _max_listed(m.state.didx)
        ),
    }


def _axes_map(m) -> Dict[str, Tuple[int, Callable[[], int]]]:
    return {
        "n_keys": (m.state.dkeys.shape[-1], lambda: len(m.keys)),
        "n_actors": (m.state.top.shape[-1], lambda: len(m.actors)),
        "sibling_cap": (
            m.state.child.valid.shape[-1],
            lambda: _max_count(m.state.child.valid),
        ),
        "deferred_cap": (
            m.state.dvalid.shape[-1], lambda: _max_count(m.state.dvalid)
        ),
    }


def _axes_sparse_map(m) -> Dict[str, Tuple[int, Callable[[], int]]]:
    return {
        "cell_cap": (m.state.kid.shape[-1], lambda: _max_count(m.state.valid)),
        "n_keys": (m.n_keys, lambda: len(m.keys)),
        "n_actors": (m.state.top.shape[-1], lambda: len(m.actors)),
        "sibling_cap": (m.sibling_cap, lambda: _max_siblings(m.state)),
        "deferred_cap": (
            m.state.dvalid.shape[-1], lambda: _max_count(m.state.dvalid)
        ),
        "rm_width": (
            m.state.kidx.shape[-1], lambda: _max_listed(m.state.kidx)
        ),
    }


def _axes_sparse_nested(m) -> Dict[str, Tuple[int, Callable[[], int]]]:
    core = m.state.core
    return {
        "cell_cap": (core.kid.shape[-1], lambda: _max_count(core.valid)),
        "span": (m.span, lambda: len(m.keys2)),
        "n_keys1": (m.n_keys1, lambda: len(m.keys1)),
        "n_actors": (core.top.shape[-1], lambda: len(m.actors)),
        "sibling_cap": (m.sibling_cap, lambda: _max_siblings(core)),
        "deferred_cap": (
            core.dvalid.shape[-1], lambda: _max_count(core.dvalid)
        ),
        "rm_width": (core.kidx.shape[-1], lambda: _max_listed(core.kidx)),
        "key_deferred_cap": (
            m.state.kdvalid.shape[-1], lambda: _max_count(m.state.kdvalid)
        ),
        "key_rm_width": (
            m.state.kidx.shape[-1], lambda: _max_listed(m.state.kidx)
        ),
    }


def _max_siblings(core) -> int:
    """Max live cells sharing one (replica, key) — the sibling_cap
    occupancy. One vectorized unique over (row, kid) pairs: O(live
    cells) total with no per-replica Python loop (record_headroom runs
    at op/round cadence over bench-scale replica counts), and no dense
    bincount over the huge virtual key universe."""
    kid = np.asarray(core.kid).reshape(-1, core.kid.shape[-1])
    valid = np.asarray(core.valid).reshape(kid.shape)
    rows, _ = np.nonzero(valid)
    if not rows.size:
        return 0
    packed = rows.astype(np.int64) << 31 | kid[valid].astype(np.int64)
    return int(np.unique(packed, return_counts=True)[1].max())


def _kind_tables():
    from .models.map import BatchedMap
    from .models.sparse_mvmap import BatchedSparseMap
    from .models.sparse_nested_map import BatchedSparseNestedMap

    return {
        BatchedOrswot: ("orswot", _axes_orswot),
        BatchedSparseOrswot: ("sparse_orswot", _axes_sparse_orswot),
        BatchedMap: ("map", _axes_map),
        BatchedSparseMap: ("sparse_map", _axes_sparse_map),
        BatchedSparseNestedMap: ("sparse_nested_map", _axes_sparse_nested),
    }


def _lookup(model):
    for cls, entry in _kind_tables().items():
        if isinstance(model, cls):
            return entry
    raise TypeError(
        f"elastic migrations cover the batched set/map family, got "
        f"{type(model).__name__}"
    )


def kind_of(model) -> str:
    """The metrics namespace for a model (``orswot``, ``sparse_map``, …)."""
    return _lookup(model)[0]


def utilization(model) -> Dict[str, Tuple[int, int]]:
    """Per-axis ``(capacity, used)`` — the raw headroom table (forces
    the occupancy scan; capacity-only callers use :func:`capacities`)."""
    return {
        k: (cap, used()) for k, (cap, used) in _lookup(model)[1](model).items()
    }


def capacities(model) -> Dict[str, int]:
    """Current capacity per elastic axis — shape reads only, no
    device → host occupancy scan."""
    return {k: cap for k, (cap, _) in _lookup(model)[1](model).items()}


def record_headroom(model) -> Dict[str, float]:
    """Record per-axis FREE-fraction gauges
    (``elastic.<kind>.headroom.<axis>``; 0.0 = at capacity, the signal
    to widen before overflow) and return them. Call at op/round cadence
    — host-side only, zero jit impact (utils/metrics.py discipline)."""
    kind = kind_of(model)
    out = {}
    for axis, (cap, used) in utilization(model).items():
        free = 0.0 if cap <= 0 else max(0.0, 1.0 - used / cap)
        out[axis] = free
        metrics.observe(f"elastic.{kind}.headroom.{axis}", free)
    return out


# ---- the migration --------------------------------------------------------

def _grown(cap: int, factor: float) -> int:
    return max(int(math.ceil(cap * factor)), cap + 1)


def widen(
    model,
    axes: Optional[Tuple[str, ...]] = None,
    policy: ElasticPolicy = DEFAULT_POLICY,
    **explicit: int,
) -> Dict[str, int]:
    """Widen ``axes`` of ``model`` by ``policy.factor`` (or to the
    ``explicit`` values) and re-encode the live device state in place
    via the model's ``widen_capacity``. Returns the new capacities of
    the changed axes. Feeds ``elastic.widen_events`` (and the per-kind
    variant) plus ``elastic.migrated_bytes`` — the bytes of the
    re-encoded state — and refreshes the headroom gauges."""
    kind, table = _lookup(model)
    current = {k: cap for k, (cap, _) in table(model).items()}
    new = dict(explicit)
    for axis in axes or ():
        if axis not in current:
            raise ValueError(f"{kind} has no elastic axis {axis!r}")
        new.setdefault(axis, _grown(current[axis], policy.factor))
    if not new:
        raise ValueError("nothing to widen: pass axes and/or explicit caps")
    for axis in new:
        if axis not in current:
            raise ValueError(f"{kind} has no elastic axis {axis!r}")
    if "span" in new and new["span"] % current["span"]:
        # A span widening must keep key ids (aligned offsets).
        new["span"] = current["span"] * int(
            math.ceil(new["span"] / current["span"])
        )
    # Packing interactions (sparse cell keys fit int32, so growing
    # span/n_actors may force the VIRTUAL key-universe bound down) are
    # the model's own business: widen_capacity auto-clamps bounds the
    # caller did not pin and raises — never silently clamps — on
    # explicit ones.
    from .telemetry import span

    with span("elastic.widen", kind=kind, axes=sorted(new)):
        model.widen_capacity(**new)
    metrics.count("elastic.widen_events")
    metrics.count(f"elastic.widen_events.{kind}")
    metrics.count("elastic.migrated_bytes", state_nbytes(model.state))
    record_headroom(model)
    return new


# ---- the inverse migration (reclaim/, ISSUE 5) ----------------------------

def _shrink_target(cap: int, used: int, policy: ElasticPolicy) -> int:
    """Where one shrink step lands: one ``factor`` step down, but never
    below live occupancy or the policy floor."""
    return max(int(math.ceil(cap / policy.factor)), used, policy.shrink_floor)


def _narrowable_axes(model) -> Tuple[str, ...]:
    """The elastic axes this model's ``narrow_capacity`` accepts —
    axes it cannot narrow (e.g. the nested kind's ``n_keys1``, whose
    ids are pinned by packing) are simply not shrink candidates."""
    import inspect

    try:
        params = inspect.signature(model.narrow_capacity).parameters
    except (AttributeError, TypeError, ValueError):
        return ()
    return tuple(a for a in capacities(model) if a in params)


def shrink(
    model,
    axes: Optional[Tuple[str, ...]] = None,
    policy: ElasticPolicy = DEFAULT_POLICY,
    **explicit: int,
) -> Dict[str, int]:
    """The inverse of :func:`widen` — narrow ``axes`` by one
    ``policy.factor`` step (or to the ``explicit`` values), re-encoding
    the live device state in place via the model's ``narrow_capacity``
    (which REFUSES when occupancy does not fit — compaction first,
    ``reclaim.compact_model``, frees retired parked slots so they do
    not pin lanes). Axes already at occupancy/floor are skipped, not
    errors — steady-state callers ask every round. Returns the new
    capacities of the axes actually narrowed and feeds
    ``reclaim.shrink_events`` + ``reclaim.reclaimed_bytes``.

    Like widening, shrinking is ADMINISTRATIVE: apply it identically on
    every host holding the replica set. It commutes with gossip for the
    same reason widening does — the narrowed state is bit-identical to
    a narrower-born model holding the same dots (the tail lanes sliced
    off were dead), so every later join is the same lattice join."""
    kind, table = _lookup(model)
    util = {k: (cap, used()) for k, (cap, used) in table(model).items()}
    for axis in tuple(axes or ()) + tuple(explicit):
        if axis not in util:
            raise ValueError(f"{kind} has no elastic axis {axis!r}")
    new: Dict[str, int] = {}
    for axis in axes or ():
        cap, used = util[axis]
        target = _shrink_target(cap, used, policy)
        if target < cap:
            new[axis] = target
    for axis, target in explicit.items():
        cap, used = util[axis]
        if target > cap:
            # Same error surface as the ops narrow kernels: an explicit
            # target is the caller's claim, not a steady-state poll.
            raise ValueError(
                f"shrink cannot grow {axis}: {cap} -> {target}"
            )
        if target < cap:
            new[axis] = target  # narrow_capacity enforces occupancy fit
    if not new:
        return {}
    from .telemetry import span

    before = state_nbytes(model.state)
    with span("elastic.shrink", kind=kind, axes=sorted(new)):
        model.narrow_capacity(**new)
    freed = max(before - state_nbytes(model.state), 0)
    metrics.count("reclaim.shrink_events")
    metrics.count(f"reclaim.shrink_events.{kind}")
    metrics.count("reclaim.reclaimed_bytes", freed)
    record_headroom(model)
    return new


class Hysteresis:
    """The symmetric widen/shrink governor.

    The shrink half (reclaim/, the original contract): call
    :meth:`observe` once per gossip round and it narrows an axis only
    after occupancy sat below ``policy.low_water`` for
    ``policy.shrink_rounds`` CONSECUTIVE rounds — a single quiet round
    after a burst reclaims nothing, and a widening (capacity grew
    between observations) resets every streak, so the widen loop and
    the shrink loop cannot chase each other. Composes with
    ``gossip_elastic``/``delta_gossip_elastic`` via their ``reclaim=``
    parameter the same way widening composes via overflow recovery.

    The widen half (ISSUE 11): :meth:`vote` is the direction-symmetric
    debouncer over an arbitrary named pressure signal in [0, 1] —
    ``high_water``/``widen_rounds`` gate the widen direction exactly as
    ``low_water``/``shrink_rounds`` gate shrink. The scaleout
    Autoscaler (crdt_tpu/scaleout/autoscaler.py) keys admit/drain
    decisions on it; ``observe`` keeps its original shrink-only
    behavior bit-for-bit (pinned by tests/test_elastic.py)."""

    def __init__(self, policy: ElasticPolicy = DEFAULT_POLICY):
        self.policy = policy
        self._streak: Dict[str, int] = {}
        self._caps: Dict[str, int] = {}
        self._hot: Dict[str, int] = {}
        self._cold: Dict[str, int] = {}

    def observe(
        self, model, policy: Optional[ElasticPolicy] = None
    ) -> Dict[str, int]:
        """Record one round's occupancy; shrink and return the narrowed
        axes when the hysteresis clears (usually ``{}``)."""
        policy = policy or self.policy
        candidates = []
        narrowable = _narrowable_axes(model)
        for axis, (cap, used) in utilization(model).items():
            prev = self._caps.get(axis)
            if prev is not None and cap > prev:
                self._streak[axis] = 0  # widened since last round
            self._caps[axis] = cap
            shrinkable = (
                axis in narrowable
                and cap > 0
                and used / cap < policy.low_water
                and _shrink_target(cap, used, policy) < cap
            )
            if shrinkable:
                self._streak[axis] = self._streak.get(axis, 0) + 1
            else:
                self._streak[axis] = 0
            if self._streak[axis] >= policy.shrink_rounds:
                candidates.append(axis)
        if not candidates:
            return {}
        shrunk = shrink(model, tuple(candidates), policy)
        for axis in shrunk:
            self._streak[axis] = 0
            self._caps[axis] = capacities(model)[axis]
        return shrunk

    def vote(
        self,
        name: str,
        pressure: float,
        policy: Optional[ElasticPolicy] = None,
    ) -> Optional[str]:
        """One debounced decision on a named pressure signal in [0, 1]:
        returns ``"widen"`` after ``pressure >= high_water`` held for
        ``widen_rounds`` CONSECUTIVE calls, ``"shrink"`` after
        ``pressure < low_water`` held for ``shrink_rounds``, else
        ``None``. A mid-band or opposite-direction observation resets
        BOTH streaks, and a fired vote resets its own — the debounce
        re-arms, so a driver acting on the vote (the Autoscaler's
        admit/drain) is never retriggered within the same debounce
        window, while a plateau that PERSISTS past another full window
        fires again (the driver absorbed one capacity move and the
        pressure still stands — more moves are warranted). Signals are
        independent per ``name`` (one governor can debounce several
        meshes/axes)."""
        policy = policy or self.policy
        if not 0.0 <= pressure <= 1.0:
            raise ValueError(f"pressure {pressure} not in [0, 1]")
        hot = self._hot.get(name, 0)
        cold = self._cold.get(name, 0)
        if pressure >= policy.high_water:
            hot, cold = hot + 1, 0
        elif pressure < policy.low_water:
            hot, cold = 0, cold + 1
        else:
            hot = cold = 0
        decision = None
        if hot >= policy.widen_rounds:
            decision, hot = "widen", 0
        elif cold >= policy.shrink_rounds:
            decision, cold = "shrink", 0
        self._hot[name], self._cold[name] = hot, cold
        if decision is not None:
            from . import obs

            obs.emit("elastic_vote", name=name, decision=decision,
                     pressure=float(pressure))
        return decision


def axes_for(model, exc: BaseException) -> Tuple[str, ...]:
    """The capacity axes a surfaced overflow implicates — the
    exception-type → axis mapping of the recovery loop. Empty tuple
    means the error is NOT elastic pressure (re-raise it)."""
    kind, table = _lookup(model)
    axes = table(model)  # caps + lazy occupancy; forced only below
    if isinstance(exc, DotCapacityOverflow):
        return ("dot_cap",) if "dot_cap" in axes else ("cell_cap",)
    if isinstance(exc, SlotOverflow):
        return ("sibling_cap",)
    if isinstance(exc, DeferredOverflow):
        # Slot-count overflows AND too-narrow parked keylist lanes
        # (rm_width) raise the same type; the message names the buffer,
        # but widening every parked axis the kind has is always sound
        # (monotone tail padding, bounded by max_migrations) and keeps
        # recovery independent of message text. The nested kind adds
        # its outer-level pair for the same reason.
        return tuple(
            a for a in (
                "deferred_cap", "rm_width",
                "key_deferred_cap", "key_rm_width",
            )
            if a in axes
        )
    if isinstance(exc, UniverseFull):
        # bounded_intern: implicate exactly the full universes.
        full = tuple(
            axis for axis in (
                "n_members", "n_actors", "n_keys", "n_keys1", "span"
            )
            if axis in axes and axes[axis][1]() >= axes[axis][0]
        )
        return full
    return ()


def recover(
    model, exc: BaseException, policy: ElasticPolicy = DEFAULT_POLICY
) -> Dict[str, int]:
    """Widen the axes ``exc`` implicates. Re-raises ``exc`` when it is
    not recoverable capacity pressure."""
    axes = axes_for(model, exc)
    if not axes:
        raise exc
    return widen(model, axes, policy)


def elastic_call(
    fn: Callable[[], object],
    model,
    policy: ElasticPolicy = DEFAULT_POLICY,
):
    """The overflow→widen→resume loop: run ``fn`` (an op application, a
    merge, a fold — any closure over ``model``), and on a capacity
    error widen the implicated axes and retry, up to
    ``policy.max_migrations`` migrations. Sound because every rejected
    operation is side-effect free (the validation.py contract: ops roll
    back interner allocations; joins raise without committing), so the
    retry replays against an unchanged — merely wider — state."""
    for _ in range(policy.max_migrations):
        try:
            return fn()
        except CAPACITY_ERRORS as exc:
            recover(model, exc, policy)
    return fn()


# ---- dtype composition (lifecycle.py's widening, generalized) -------------

def widen_dtype(model, dtype: str = "uint64") -> None:
    """u32 → u64 counter-plane widening for the causal set/map family —
    the lifecycle.py ``widen_counters`` analog (same x64 guard, same
    bit-identical contract: every counter VALUE is preserved, only the
    ceiling lifts). Every uint32 plane of a causal state is a counter
    plane (top/birth/write clocks and witness counters; ids are int32,
    masks bool), so the migration is one dtype-gated tree map."""
    import jax
    import jax.numpy as jnp

    target = jnp.dtype(dtype)
    if target == jnp.dtype("uint64") and not jax.config.jax_enable_x64:
        raise RuntimeError(
            "uint64 lanes require x64 mode: call "
            "configure(counter_dtype='uint64') before widening"
        )
    _lookup(model)  # covered-family check
    model.state = jax.tree.map(
        lambda x: x.astype(target) if x.dtype == jnp.dtype("uint32") else x,
        model.state,
    )


def migrate(
    model,
    counter_dtype: Optional[str] = None,
    axes: Optional[Tuple[str, ...]] = None,
    policy: ElasticPolicy = DEFAULT_POLICY,
    **explicit: int,
) -> Dict[str, int]:
    """One administrative migration composing both widenings: grow
    capacity axes AND (optionally) the counter dtype — e.g. u32→u64 +
    capacity 2× in one step. Order matters only for efficiency: dtype
    first, so the capacity padding allocates wide lanes once."""
    if counter_dtype is not None:
        widen_dtype(model, counter_dtype)
    if axes or explicit:
        return widen(model, axes, policy, **explicit)
    record_headroom(model)
    return {}


from .analysis.registry import register_obs_event as _reg_ev  # noqa: E402

_reg_ev("elastic_vote", subsystem="elastic",
        fields=("name", "decision", "pressure"), module=__name__)


__all__ = [
    "CAPACITY_ERRORS", "DEFAULT_POLICY", "ElasticPolicy", "Hysteresis",
    "axes_for", "capacities", "elastic_call", "kind_of", "migrate",
    "record_headroom", "recover", "shrink", "utilization", "widen",
    "widen_dtype",
]
