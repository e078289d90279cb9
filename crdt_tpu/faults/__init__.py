"""crdt_tpu.faults — degraded-mesh fault tolerance.

Four cooperating pieces (see each module's docstring):

- :mod:`.inject` — seeded, jit-compatible fault injection: a
  :class:`FaultPlan` of per-round × per-link drop/corrupt/delay draws
  minted from ``jax.random`` INSIDE the traced program, accepted via a
  ``faults=`` flag on ``run_delta_ring``, the ``mesh_gossip*`` family,
  and ``mesh_stream_fold*`` (flag off = byte-identical pre-flag trace,
  the ``telemetry=`` discipline).
- :mod:`.integrity` — an in-kernel checksum lane on every shipped
  payload; mismatches REJECT (local state kept,
  ``faults.packets_rejected`` counted) and state-driven resync heals.
- :mod:`.membership` — rank liveness from the in-kernel miss streaks,
  K-consecutive-miss suspicion, eviction (ring rebuilt over live ranks,
  frontier pmin unpinned) and the full-state-resync rejoin contract.
- :mod:`.retry` — host-side DCN resilience: timeout + exponential
  backoff with jitter around ``multihost.sync_list`` /
  ``_allgather_host``, failing into :class:`DcnExchangeFailed` with
  the last-good resume state.

Plus :mod:`.scenarios` (the shared host-side fault-schedule generators
the test suites draw from) and :func:`static_checks` — the ``faults``
section of tools/run_static_checks.py: fault-surface registry coverage
and the broken-fixture detector gates.

**Healing a degraded run.** A lossy ring returns every rank's rows as
valid partial states with the certificate voided; two state-driven
resync modes re-converge them (both land bit-identical on the
fault-free fixpoint):

- full-state gossip over the returned rows (``mesh_gossip(rows,
  mesh)``) — no prerequisites, ships P whole states; the historical
  path and still the REJOIN contract for an evicted rank (its
  divergence has no usable lower bound);
- decomposition resync (:func:`resync`, re-exported from
  ``crdt_tpu.delta_opt.heal``) — each rank ships only its minimal
  irredundant join decomposition over a pre-divergence snapshot
  ``since`` (any mutually-known lower bound, e.g. the last certified
  fixpoint), so a partition that diverged by a handful of rows heals
  for a fraction of full-state bytes (tests/test_delta_opt.py pins
  the ratio; the reconstruction law pins exactness per kind).
"""

from __future__ import annotations

from typing import List

from .inject import (
    FaultCounters,
    FaultPlan,
    accumulate_counters,
    block_wire,
    combine_counters,
    corrupt_tree,
    counters_specs,
    evicted_mask,
    inv_ring_perm,
    receive_wire,
    record,
    ring_perm,
    round_faults,
    sender_of,
    tick_counters,
    tree_select,
)
from .integrity import checksum, checksum_detects, verify
from .membership import Membership, validate_perm
from .retry import DcnExchangeFailed, RetryPolicy, with_retries
from . import scenarios  # noqa: F401  (re-export the schedule generators)

# The bandwidth-optimal heal path (module docstring): decomposition
# resync lives in crdt_tpu/delta_opt/ (it is pure δ machinery), but the
# operator reaches for it from here, next to the fault plans that made
# it necessary.
from ..delta_opt.heal import ResyncReport, resync


def static_checks() -> List:
    """The ``faults`` static-check section (Finding list, empty =
    clean):

    1. **fault-surface coverage** — every public ``crdt_tpu.parallel``
       callable exposing a ``faults=`` parameter must have called
       ``analysis.registry.register_fault_surface``; an unregistered
       fault-capable entry fails discovery (the same
       registration-is-the-coverage-contract rule as joins/entries).
    2. **checksum detector** — ``integrity.checksum`` must detect every
       single-lane perturbation class the injector mints; the broken
       twin (``analysis.fixtures.checksum_ignores_corruption``) must
       FAIL the same detector — proving the gate fires.
    3. **eviction bijection** — ``inject.ring_perm`` must stay a true
       bijection for every eviction subset on the gate axis (and reduce
       to the standard ring when nothing is evicted); the broken twin
       (``analysis.fixtures.eviction_drops_ranks``) must fail
       ``membership.validate_perm``.
    """
    from ..analysis import fixtures
    from ..analysis.registry import unregistered_fault_surfaces
    from ..analysis.report import Finding

    findings: List[Finding] = []

    for name in unregistered_fault_surfaces():
        findings.append(Finding(
            "fault-surface-coverage", name,
            "public entry exposes a faults= parameter but never called "
            "register_fault_surface — the faults gate cannot see it",
        ))

    if not checksum_detects(checksum):
        findings.append(Finding(
            "checksum-detects", "integrity.checksum",
            "checksum failed to change under a single-lane perturbation "
            "— corrupted packets would be silently joined",
        ))
    if checksum_detects(fixtures.checksum_ignores_corruption):
        findings.append(Finding(
            "broken-fixture-missed", "checksum_ignores_corruption",
            "the corruption-blind checksum twin PASSED the detector — "
            "the integrity gate is not actually firing",
        ))

    p = 8
    for evicted in ((), (3,), (0, 5), tuple(range(1, p))):
        perm = ring_perm(p, evicted)
        errs = validate_perm(perm, p)
        if errs:
            findings.append(Finding(
                "eviction-bijection", f"ring_perm(p={p}, evicted={evicted})",
                "; ".join(errs),
            ))
    if ring_perm(p, ()) != sorted((i, (i + 1) % p) for i in range(p)):
        findings.append(Finding(
            "eviction-bijection", "ring_perm(p=8, evicted=())",
            "empty eviction set must reproduce the standard unit-shift "
            "ring exactly",
        ))
    if not validate_perm(fixtures.eviction_drops_ranks(p, (3,)), p):
        findings.append(Finding(
            "broken-fixture-missed", "eviction_drops_ranks",
            "the bijection-breaking eviction twin PASSED validate_perm — "
            "the membership gate is not actually firing",
        ))
    return findings


__all__ = [
    "DcnExchangeFailed", "FaultCounters", "FaultPlan", "Membership",
    "ResyncReport", "RetryPolicy", "accumulate_counters", "block_wire",
    "checksum", "checksum_detects", "combine_counters", "corrupt_tree",
    "counters_specs", "evicted_mask", "inv_ring_perm", "receive_wire",
    "record", "resync", "ring_perm", "round_faults", "scenarios",
    "sender_of", "static_checks", "tick_counters", "tree_select",
    "validate_perm", "verify", "with_retries",
]
