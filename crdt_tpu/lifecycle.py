"""Actor lifecycle mechanics: counter widening and retirement
(SURVEY.md §7.3 overflow discipline).

The reference sidesteps saturation by being u64 end to end
(src/vclock.rs ``BTreeMap<A, u64>``); the device lattice defaults to
u32 lanes for bandwidth, and strict mode traps an approaching overflow
with ``CounterSaturation`` — whose message prescribes "widen
counter_dtype or retire the actor". This module is those two remedies
as CODE, for the clock/counter family:

- :func:`widen_counters` — u32 → u64 state migration in place
  (bit-identical at the oracle level: every lane value is preserved
  exactly; only the dtype grows). Requires
  ``configure(counter_dtype="uint64")`` first (which enables x64 — see
  config.py).
- :func:`retire_actor` — fold a retired actor's CONVERGED contribution
  into the shared ``RETIRED`` aggregate lane and zero its own lane.
  Sound for GCounter/PNCounter because their read is a lane SUM; the
  migration demands lane convergence across the model's replicas (and,
  operationally, must be applied identically on every host holding the
  replica set — it is an administrative migration, not a CRDT op).
  VClock retirement is deliberately NOT offered: clock comparisons are
  per-actor, so lanes cannot be merged without changing the partial
  order — causal types (VClock, Orswot, MVReg, Map) retire an actor
  via ``Causal::reset_remove`` on their models instead (forget the
  departed actor's causal history; see tests/test_reset_remove.py).
- :func:`compact_actors` — rebuild the interner/lane universe without
  all-zero lanes (retired or never-used actors), shrinking device
  state. Reads are preserved exactly; freed lanes make room for new
  actors in the fixed-width universe.

:func:`compact_actors` is the counter family's host-side reclamation
path and reports through the same ``reclaim.*`` counters as the
causal-stability subsystem (crdt_tpu/reclaim/ — frontier-driven
compaction + ``elastic.shrink`` for the set/map family): freed lanes
count as ``reclaim.reclaimed_slots``, and a run that actually freed
lanes counts one ``reclaim.shrink_events`` (the live universe shrank
into the fixed width — the freed tail is reclaimed headroom, exactly
what a capacity shrink reclaims for the causal kinds).
"""

from __future__ import annotations

from typing import Tuple

import jax.numpy as jnp
import numpy as np

from .utils import Interner

RETIRED = "__retired__"


def _vclock_models(model) -> Tuple:
    """The BatchedVClock leaves of a counter-family model."""
    from .models.counters import BatchedGCounter, BatchedPNCounter
    from .models.vclock import BatchedVClock

    if isinstance(model, BatchedVClock):
        return (model,)
    if isinstance(model, BatchedGCounter):
        return (model.inner,)
    if isinstance(model, BatchedPNCounter):
        return (model.p, model.n)
    raise TypeError(
        f"lifecycle operations cover the clock/counter family, got "
        f"{type(model).__name__}"
    )


def widen_counters(model) -> None:
    """Widen a counter-family model's device lanes u32 → u64 in place.

    Bit-identical migration: every lane VALUE is unchanged; only the
    dtype doubles, lifting the saturation ceiling from 2^32-1 to
    2^64-1 (reference width, src/vclock.rs). Enable x64 first via
    ``configure(counter_dtype="uint64")`` — without it jax silently
    truncates uint64 arrays back to uint32, which this refuses to do."""
    if not jnp.zeros((), jnp.uint64).dtype == jnp.dtype("uint64"):
        raise RuntimeError(
            "uint64 lanes require x64 mode: call "
            "configure(counter_dtype='uint64') before widening"
        )
    for vc in _vclock_models(model):
        vc.clocks = vc.clocks.astype(jnp.uint64)


def retire_actor(model, actor) -> None:
    """Retire ``actor`` from a GCounter/PNCounter model: fold its
    converged count into the shared ``RETIRED`` aggregate lane and zero
    its own lane. The actor must never mint again (its lane is now
    dead weight until :func:`compact_actors`).

    Demands convergence: every replica row must hold the SAME value in
    the actor's lane (otherwise moving the count would lose or double
    increments depending on later merges) — converge first
    (``fold``/anti-entropy), then retire, then resume. Raises
    ValueError when rows diverge."""
    from .models.counters import BatchedGCounter, BatchedPNCounter

    if not isinstance(model, (BatchedGCounter, BatchedPNCounter)):
        raise TypeError(
            "retire_actor is a counter migration (reads are lane sums); "
            "VClock lanes cannot be merged without changing the partial "
            "order — causal types retire via model.reset_remove(...) "
            f"instead; got {type(model).__name__}"
        )
    clocks = _vclock_models(model)
    actors = clocks[0].actors
    aid = actors.id_of(actor)
    rid = actors.intern(RETIRED)
    if rid == aid:
        raise ValueError("cannot retire the RETIRED aggregate lane")
    # The aggregate may need a lane the fixed universe doesn't have —
    # growing width by one is part of the migration (administrative,
    # applied identically everywhere like the rest of this function).
    for vc in clocks:
        grow = rid + 1 - vc.clocks.shape[-1]
        if grow > 0:
            vc.clocks = jnp.pad(vc.clocks, ((0, 0), (0, grow)))
    for vc in clocks:
        col = np.asarray(vc.clocks[:, aid])
        if col.size and not (col == col[0]).all():
            raise ValueError(
                f"actor {actor!r} lane diverges across replicas "
                f"({sorted(set(col.tolist()))}); converge before retiring"
            )
        moved = vc.clocks.at[:, rid].add(vc.clocks[:, aid])
        vc.clocks = moved.at[:, aid].set(0)


def compact_actors(model) -> None:
    """Drop all-zero lanes (retired or never-used actors) from a
    counter-family model and rebuild its interner with the survivors —
    device state shrinks, reads are untouched, and the freed width is
    available for new actors after a rebuild.

    PNCounter compacts on the UNION of p/n liveness (both share one
    interner, so both must keep the same lanes). The LANE WIDTH is
    preserved — live lanes move to the front and the freed tail becomes
    zero headroom for new actors (shrinking to the live count would
    leave a full universe and defeat the point of retiring).

    Reclamation accounting rides the shared ``reclaim.*`` namespace
    (crdt_tpu/reclaim/compaction.py ``record_reclaim``): freed lanes →
    ``reclaimed_slots``; a run that freed any lane → one
    ``shrink_events`` (see the module docstring)."""
    from .reclaim.compaction import record_reclaim
    from .utils.metrics import metrics

    clocks = _vclock_models(model)
    live = None
    for vc in clocks:
        lanes = np.asarray(vc.clocks).any(axis=0)
        live = lanes if live is None else (live | lanes)
    actors = clocks[0].actors
    keep = [a for a in range(min(len(live), len(actors))) if live[a]]
    freed = len(actors) - len(keep)
    if freed > 0:
        record_reclaim("actors", freed, 0)
        metrics.count("reclaim.shrink_events")
        metrics.count("reclaim.shrink_events.actors")
    new_actors = Interner(actors[a] for a in keep)
    idx = jnp.asarray(np.asarray(keep, np.int64))
    for vc in clocks:
        width = vc.clocks.shape[-1]
        packed = (
            vc.clocks[:, idx]
            if keep
            else jnp.zeros((vc.clocks.shape[0], 0), vc.clocks.dtype)
        )
        vc.clocks = jnp.pad(packed, ((0, 0), (0, width - packed.shape[-1])))
        vc.actors = new_actors
    # Counter wrappers expose .actors via their inner clock(s); the
    # shared-interner invariant (PNCounter) is restored by assigning the
    # same object everywhere above.


__all__ = ["RETIRED", "widen_counters", "retire_actor", "compact_actors"]
