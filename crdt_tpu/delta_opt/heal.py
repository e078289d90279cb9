"""Post-heal state-driven sync via join decomposition.

The degraded-mesh contract (crdt_tpu/faults/, PR 8): a lossy δ run
voids the residue certificate and returns every rank's rows as valid
partial states; heal is **state-driven resync** — historically
full-state gossip over the returned rows, which ships P whole states to
re-converge a mesh that usually diverged by a handful of rows during
the drop window. :func:`resync` is the bandwidth-optimal form Enes
et al. §4 prescribes: each rank decomposes its state over ``since`` —
the last mutually-known state, e.g. the pre-partition certified
fixpoint the operator snapshotted — and ships only the irredundant
divergence lanes; reconstruction plus the kind's own join then lands
bit-identically on the full-state fixpoint (the reconstruction law,
pinned per kind by the ``decomp`` static-check section).

``since`` must be a lower bound of every rank's state (all divergence
after the snapshot is join-/op-inflationary, so any pre-divergence
converged state qualifies; the join identity always does — at the price
of shipping everything, which is exactly full-state resync). The driver
does not verify the bound: a wrong ``since`` still reconstructs each
rank's state bit-exactly (the positional diff is unconditional), it
just stops being minimal.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..utils.metrics import metrics, state_nbytes
from .decompose import decompose, decomposition_bytes, reconstruct


@functools.lru_cache(maxsize=None)
def _fold_and_broadcast(kind: str):
    """One jitted program per kind for the resync fold: the sequential
    join chain keeps the eager loop's left-to-right order (bit-identity
    preserved), but the P-1 per-join dispatches and deferred-replay
    lowerings collapse into a single scan — the heal path must not
    become dispatch-bound at mega-mesh P. jit re-traces per new
    ``[P, ...]`` shape; the lru keyes the kind's join closure."""
    from ..analysis.registry import get_merge_kind

    mk = get_merge_kind(kind)

    def norm_join(a, b):
        out = mk.join(a, b)
        return out[0] if isinstance(out, tuple) and len(out) == 2 else out

    @jax.jit
    def fold(batch):
        def body(acc, row):
            return norm_join(acc, row), None

        first = jax.tree.map(lambda x: x[0], batch)
        rest = jax.tree.map(lambda x: x[1:], batch)
        folded, _ = jax.lax.scan(body, first, rest)
        p = jax.tree.leaves(batch)[0].shape[0]
        return jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (p,) + x.shape), folded
        )

    return fold


class ResyncReport(NamedTuple):
    """Byte accounting for one decomposition resync."""

    ranks: int
    lanes_shipped: int        # valid δ lanes across every rank
    bytes_shipped: float      # decomposition payload (bytes_useful form)
    bytes_full_state: float   # what full-state resync would have shipped
    ratio: float              # shipped / full — the headline quantity


def resync(kind: str, states, since):
    """Decomposition-based state-driven resync over a ``[P, ...]`` rank
    batch: decompose every rank over ``since``, "ship" the lanes
    (counted under the ``bytes_useful`` convention — valid lanes plus
    residuals), reconstruct, and fold with the kind's registered join.
    Returns ``(healed [P, ...], ResyncReport)`` — ``healed`` is the
    full-join fixpoint broadcast to every rank, bit-identical to
    full-state gossip over the same rows (tests/test_delta_opt.py pins
    it).

    Counters: ``delta_opt.resync_runs``,
    ``delta_opt.resync_bytes_shipped`` / ``_full`` (plus per-kind
    ``delta_opt.resync_bytes_shipped.<kind>``)."""
    from ..analysis.registry import get_decomposer

    dec = get_decomposer(kind)
    p = jax.tree.leaves(states)[0].shape[0]
    one = jax.tree.map(lambda x: x[0], states)

    decs = jax.vmap(lambda s: decompose(dec, s, since))(states)
    shipped = float(
        jnp.sum(jax.vmap(decomposition_bytes)(decs))
    )
    lanes = int(jnp.sum(decs.valid))
    recon = jax.vmap(lambda d: reconstruct(dec, since, d))(decs)

    healed = _fold_and_broadcast(kind)(recon)

    full = float(p * state_nbytes(one))
    report = ResyncReport(
        ranks=p,
        lanes_shipped=lanes,
        bytes_shipped=shipped,
        bytes_full_state=full,
        ratio=shipped / full if full else 0.0,
    )
    metrics.count("delta_opt.resync_runs")
    metrics.count("delta_opt.resync_bytes_shipped", int(shipped))
    metrics.count(f"delta_opt.resync_bytes_shipped.{kind}", int(shipped))
    metrics.count("delta_opt.resync_bytes_full", int(full))
    return healed, report
