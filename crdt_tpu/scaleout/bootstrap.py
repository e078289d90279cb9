"""Newcomer bootstrap: the rejoin path generalized to empty bases.

Delta State Replicated Data Types (Almeida et al., PAPERS.md
1603.01529) make dynamic membership safe by construction — a newcomer
is just a replica whose causal lower bound is ⊥ — and the PR 9/10
machinery already ships exactly the right thing for a rank re-entering
with SOME lower bound: ``durability.recover.rejoin`` decomposes the
live state over the recovered one and ships only the divergence lanes.
This module is that path with the base generalized:

- **cold start** (``base=None``) — the lower bound is ⊥ (the join
  identity, all-zero planes: the ``mesh.pad_replicas`` padding
  convention). ``decompose(live, ⊥)`` emits every live row — a
  structured full-state ship, segmented and integrity-checked instead
  of one blind state copy.
- **warm start** (``base=`` a PR 10 snapshot state) — the newcomer (or
  a rejoining-as-new rank) restores the snapshot locally first, and
  the wire carries only ``decompose(live, snapshot)``: the log suffix.
  ``tests/test_scaleout.py`` pins this at < 25% of full-state bytes.

The wire is REAL in the degraded sense: under a ``faults=``
:class:`~crdt_tpu.faults.inject.FaultPlan` every shipped segment
crosses the same drop/corrupt draws + checksum lane the streaming
fold's upload wire uses (``faults.block_wire``, keyed on the plan seed
and an absolute segment index so a chaos bootstrap replays
deterministically). A dropped segment never arrived — it re-ships. A
corrupt segment is REJECTED by the checksum verify and re-ships —
corrupted lanes never join (the broken twin
``analysis.fixtures.bootstrap_skips_checksum`` skips the verify and
must fail :func:`bootstrap_rejects_corruption`). Once every valid lane
and the residual have landed, ``reconstruct`` lands the live state
**bit-exactly** (the reconstruction law — positional diff is
unconditional, so even a non-lower-bound ``base`` reconstructs
exactly; it just stops being minimal).
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from ..utils.metrics import metrics, state_nbytes


class BootstrapFailed(RuntimeError):
    """Segments still pending after ``max_attempts`` ship rounds — the
    wire is too lossy for the budget; raise the budget or heal the
    links first."""


class BootstrapReport(NamedTuple):
    """One newcomer bootstrap's accounting."""

    lanes: int                # valid δ lanes shipped (the divergence set)
    segments: int             # distinct wire segments (incl. the residual)
    reshipped: int            # segments that needed another attempt
    dropped: int              # segment ships lost on the wire
    rejected: int             # segment ships refused by the checksum lane
    bytes_shipped: float      # wire bytes including every re-ship
    bytes_payload: float      # the decomposition payload (bytes_useful form)
    bytes_full_state: float   # what a blind full-state ship would cost
    ratio: float              # payload / full — the headline quantity


def _seg_bytes(tree) -> float:
    return float(sum(
        x.size * x.dtype.itemsize for x in jax.tree.leaves(tree)
    ))


def bootstrap(
    kind: str,
    live,
    base=None,
    *,
    faults=None,
    segment_cap: int = 64,
    max_attempts: int = 64,
    verify_checksums: bool = True,
) -> Tuple[object, BootstrapReport]:
    """Bootstrap one newcomer to ``live`` (a single un-batched state of
    registered merge ``kind``) by shipping ``decompose(live, base-or-⊥)``
    in ``segment_cap``-lane segments over an optionally faulted wire
    (module docstring). Returns ``(state, BootstrapReport)`` with
    ``state`` bit-identical to ``live``.

    ``verify_checksums=False`` is the broken-twin seam
    (``analysis.fixtures.bootstrap_skips_checksum``): production
    callers never pass it — a corrupt-blind receiver joins wire-flipped
    lanes and :func:`bootstrap_rejects_corruption` catches it."""
    from ..delta_opt.decompose import (
        decompose, decomposition_bytes, reconstruct,
    )
    from ..faults.inject import block_wire

    if segment_cap < 1:
        raise ValueError("segment_cap must be >= 1")
    ident = (
        base if base is not None
        else jax.tree.map(jnp.zeros_like, live)
    )
    d = decompose(kind, live, ident)
    n_lanes = int(d.valid.shape[-1])
    n_segs = max((n_lanes + segment_cap - 1) // segment_cap, 1)

    # Receive-side assembly buffers: lanes land positionally (absolute
    # lane indices — the stream driver's absolute-block-index
    # convention at δ granularity), the residual rides whole as its own
    # segment.
    lanes_rx = jax.tree.map(jnp.zeros_like, d.lanes)
    residual_rx = None

    # Pending queue: segment -1 is the residual (+ validity mask),
    # 0..n_segs-1 the lane slices.
    pending = [-1] + list(range(n_segs))
    dropped = rejected = reshipped = 0
    bytes_shipped = 0.0
    attempt = 0
    while pending:
        if attempt >= max_attempts:
            raise BootstrapFailed(
                f"{len(pending)} bootstrap segments still pending after "
                f"{max_attempts} attempts (dropped={dropped}, "
                f"rejected={rejected}) — raise max_attempts or heal the "
                f"links first"
            )
        still = []
        for seg in pending:
            if seg < 0:
                payload = (d.residual, d.valid)
            else:
                sl = slice(seg * segment_cap, (seg + 1) * segment_cap)
                payload = jax.tree.map(lambda x: x[sl], d.lanes)
            bytes_shipped += _seg_bytes(payload)
            if faults is not None:
                # Absolute wire index: (attempt, segment) — replayable
                # under the plan's seed like every other injected draw.
                bix = jnp.int32(attempt * (n_segs + 1) + (seg + 1))
                payload, code = block_wire(faults, bix, payload)
                code = int(code)
                if code == 1:
                    dropped += 1
                    reshipped += 1
                    still.append(seg)
                    continue
                if code == 2 and verify_checksums:
                    rejected += 1
                    reshipped += 1
                    still.append(seg)
                    continue
                # code == 0 — or the corrupt-blind twin seam joining a
                # rejected payload anyway (what the detector catches).
            if seg < 0:
                residual_rx = payload
            else:
                sl = slice(seg * segment_cap, (seg + 1) * segment_cap)
                lanes_rx = jax.tree.map(
                    lambda x, p: x.at[sl].set(p), lanes_rx, payload
                )
        pending = still
        attempt += 1

    res_rx, valid_rx = residual_rx
    got = reconstruct(
        kind, ident, type(d)(lanes=lanes_rx, valid=valid_rx, residual=res_rx)
    )
    payload_bytes = float(decomposition_bytes(d))
    full = float(state_nbytes(live))
    report = BootstrapReport(
        lanes=int(jnp.sum(d.valid)),
        segments=n_segs + 1,
        reshipped=reshipped,
        dropped=dropped,
        rejected=rejected,
        bytes_shipped=bytes_shipped,
        bytes_payload=payload_bytes,
        bytes_full_state=full,
        ratio=payload_bytes / full if full else 0.0,
    )
    metrics.count("scaleout.bootstrap_lanes", report.lanes)
    metrics.count("scaleout.bootstrap_reships", reshipped)
    return got, report


def bootstrap_rejects_corruption(bootstrap_fn) -> bool:
    """Detector behind the ``scaleout`` static-check section: run
    ``bootstrap_fn`` over a corrupt-heavy wire and return True iff the
    newcomer's state lands BIT-IDENTICAL to the live peer's AND at
    least one segment was checksum-rejected (the wire really fired).
    The honest :func:`bootstrap` passes — rejected segments re-ship
    until clean copies land; the committed corrupt-blind twin
    (``analysis.fixtures.bootstrap_skips_checksum``) joins a
    wire-flipped lane and must FAIL here, proving the integrity gate
    fires."""
    from ..analysis.registry import get_merge_kind
    from ..faults.inject import FaultPlan

    live = get_merge_kind("orswot").states()[-1]
    plan = FaultPlan(seed=23, corrupt=0.7)
    try:
        got, rep = bootstrap_fn(
            "orswot", live, faults=plan, segment_cap=2, max_attempts=256,
        )
    except BootstrapFailed:
        return False
    identical = all(
        bool(jnp.array_equal(a, b))
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(live))
    )
    return identical and rep.rejected > 0


# ---- static-analysis registration (crdt_tpu.analysis) ---------------------

from ..analysis.registry import register_scaleout_surface as _reg_so  # noqa: E402

_reg_so("bootstrap", module=__name__)
_reg_so("bootstrap_rejects_corruption", module=__name__)

__all__ = [
    "BootstrapFailed", "BootstrapReport", "bootstrap",
    "bootstrap_rejects_corruption",
]
