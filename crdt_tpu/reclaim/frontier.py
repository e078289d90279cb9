"""The mesh-wide stable frontier.

A dot ``(actor, c)`` is **causally stable** once every replica's top
clock covers it — from then on no replica can ever treat it as unseen,
so metadata whose only job is to decide seen-vs-unseen for dots at or
below it is dead weight (Almeida et al., "Delta State Replicated Data
Types"; Enes et al., "Efficient Synchronization of State-based CRDTs"
— both bound metadata by exactly this stability argument). The frontier
is therefore the per-actor MINIMUM over all replicas' top clocks:

    frontier[a] = min over replicas r of top_r[a]

Safety shape: the min is monotone in each input, so a straggler or a
partitioned replica simply PINS the frontier at its stale top — the
frontier stops advancing (compaction reclaims less) but never claims
stability for a dot some replica has not seen. Degradation is graceful,
never unsafe. By the same token ``frontier <= top_r`` for every
participant, which is what keeps frontier-gated compaction
read-invariant (see reclaim/compaction.py).

Three computation paths:

- :func:`stable_frontier` — pure jnp over a batched state's leading
  replica axes (host or traced; lax-only so it survives jit/shard_map).
- in-kernel, piggybacked on gossip: the ``stability=`` flag on the mesh
  entry points (parallel/anti_entropy.py) computes
  ``lax.pmin(min over local rows, replica_axis)`` on the PRE-fold input
  tops — the knowledge each replica ENTERED the round with — and
  returns it as an extra replicated output. Flag off traces nothing
  (HLO-identical program, the ``telemetry=`` discipline).
- :func:`host_frontier` — the host-side fallback for the pure-oracle
  and multihost paths: hand it every participant's top (gather across
  processes first — e.g. ``multihost._allgather_host``) and it reduces
  in numpy.
"""

from __future__ import annotations

from typing import Iterable, Optional

import numpy as np


def top_of(state):
    """The replica's top clock ``[..., A]`` of any registered state
    pytree: the outermost ``top`` field, found by walking wrapper
    levels inward (nested kinds store ONE shared top on the innermost
    slab — the causal-composition rule pins every child top to it).
    Returns None for kinds without a clock (gset, lwwreg)."""
    seen = set()
    node = state
    while hasattr(node, "_fields") and id(node) not in seen:
        seen.add(id(node))
        if "top" in node._fields:
            return node.top
        node = node[0]  # wrapper convention: the core slab rides first
    return None


def stable_frontier(state_or_top, n_lead: Optional[int] = None):
    """Per-actor min over a batched state's replica axes: accepts a
    state pytree (top found via :func:`top_of`) or a top array
    ``[R, ..., A]`` directly. ``n_lead`` pins how many leading axes are
    replica axes (default: all but the last). Pure jnp — safe under
    jit/shard_map (the in-kernel path composes this with ``lax.pmin``
    across the mesh axis). Returns ``[A]`` (or None for clockless
    kinds)."""
    import jax.numpy as jnp

    top = state_or_top if hasattr(state_or_top, "ndim") else top_of(state_or_top)
    if top is None:
        return None
    lead = top.ndim - 1 if n_lead is None else n_lead
    return jnp.min(top, axis=tuple(range(lead))) if lead else top


def host_frontier(tops: Iterable) -> Optional[np.ndarray]:
    """Host-side frontier over an explicit collection of top clocks
    (one per replica, each ``[A]`` or a batch ``[R, A]``) — the
    fallback for the pure-oracle and multihost paths, where the
    participants are not one device batch. Multihost callers gather
    every process's local tops first (the DCN analog of the in-kernel
    pmin); a missing/stale participant's old top pins the result.
    Ragged actor widths are right-padded with 0 (an actor a participant
    never saw has min 0 — maximally conservative)."""
    mats = [np.atleast_2d(np.asarray(t)) for t in tops]
    if not mats:
        return None
    width = max(m.shape[-1] for m in mats)
    padded = [
        np.pad(m.reshape(-1, m.shape[-1]), ((0, 0), (0, width - m.shape[-1])))
        for m in mats
    ]
    return np.concatenate(padded, axis=0).min(axis=0)


def model_frontier(model) -> Optional[np.ndarray]:
    """The frontier of one batched model's OWN replica rows — the
    self-contained form checkpoint compact-on-save and
    :func:`..reclaim.compact_model` use when the device batch IS the
    replica set. For a model that is one shard of a larger mesh, use
    :func:`host_frontier` over every shard's tops instead (a local min
    over a subset may claim stability for dots remote replicas lack)."""
    top = top_of(model.state)
    if top is None:
        return None
    return np.asarray(top).reshape(-1, top.shape[-1]).min(axis=0)


def frontier_lag(top, frontier):
    """How far knowledge has run ahead of stability: the max over
    replicas and actor lanes of ``top - frontier`` (0 = fully stable
    mesh). The in-jit gauge behind the ``frontier_lag`` telemetry
    field; a growing lag under steady traffic means some replica is
    pinning the frontier (straggler/partition) and reclamation is
    stalled — the operator's signal. Pure jnp; lanes
    BEHIND the frontier (an identity-padded row, a restored straggler)
    clamp to 0 rather than wrapping the unsigned difference."""
    import jax.numpy as jnp

    t = jnp.asarray(top)
    f = jnp.asarray(frontier).astype(t.dtype)
    return jnp.max(jnp.maximum(t, f) - f).astype(jnp.uint32)


# Kinds whose frontier-stall warning already fired this process —
# repeats only count in the registry (the _warn_residue dedupe pattern,
# parallel/delta_ring.py).
_STALL_WARNED: set = set()


def reset_stall_warnings() -> None:
    """Re-arm the once-per-kind frontier-stall warning (tests; or after
    an operator evicted the straggler and wants fresh signal)."""
    _STALL_WARNED.clear()


def watch_lag(kind: str, lag: int, lag_threshold) -> None:
    """The alert the docstring above promises: ``frontier_lag`` is "the
    stall signal", and this is what watches it. Called host-side by the
    gossip entry points when ``lag_threshold=`` is set (needs
    ``stability=``): a lag at or past the threshold counts
    ``reclaim.frontier_stalled`` on EVERY occurrence — the rate an
    operator can alert on — and warns once per kind per process (the
    ``_warn_residue`` dedupe discipline: a stalled mesh in a gossip
    loop must not emit one warning per round). A sustained stall means
    some replica is pinning the frontier — investigate the straggler,
    or evict it (crdt_tpu/faults/membership.py) to unpin."""
    from ..utils.metrics import metrics

    if lag_threshold is None or lag < lag_threshold:
        return
    metrics.count("reclaim.frontier_stalled")
    if kind in _STALL_WARNED:
        return
    _STALL_WARNED.add(kind)
    import warnings

    warnings.warn(
        f"{kind}: frontier_lag={lag} >= lag_threshold={lag_threshold} — "
        f"a straggler is pinning the stable frontier and reclamation is "
        f"stalled; investigate or evict the rank "
        f"(crdt_tpu.faults.Membership). Warned once per kind; repeats "
        f"count in reclaim.frontier_stalled",
        stacklevel=3,
    )


__all__ = [
    "frontier_lag", "host_frontier", "model_frontier",
    "reset_stall_warnings", "stable_frontier", "top_of", "watch_lag",
]
