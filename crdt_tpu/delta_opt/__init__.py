"""crdt_tpu.delta_opt — optimal δ synchronization (Enes et al.,
"Efficient Synchronization of State-based CRDTs", arXiv 1803.02750).

Three cooperating pieces (see each module's docstring):

- :mod:`.decompose` — per-kind **join-irreducible decomposition**:
  ``decompose(state, since)`` splits a state's inflation over a known
  lower bound into an irredundant set of row-lane δs plus a minimal
  residual; every op kind registers a split/unsplit pair next to its
  ``compact()`` (``analysis.registry.register_decomposition`` — the
  coverage contract, 12/12 or discovery fails), and two new lattice
  laws pin every registration (reconstruction + irredundancy,
  analysis/laws.py).
- :mod:`.ackwin` — **ack-window back-propagation** for the δ rings:
  a per-link acked-interval watermark fed by one bool-per-slot ack on
  the inverse-ring channel, masking every δ the peer has positively
  confirmed joining — the digest gate's generalization to arbitrary
  covered intervals INCLUDING removals (``ack_window=True`` on
  ``run_delta_ring`` and all four ``mesh_delta_gossip*`` flavors).
- :mod:`.heal` — the **post-heal state-driven sync mode**: a healed
  partition resyncs by shipping each rank's decomposition over the
  pre-divergence snapshot instead of full states, bit-identical to
  full-state gossip (tests/test_delta_opt.py pins it under 25% of the
  full-state bytes).

Plus :func:`static_checks` — the ``decomp`` section of
tools/run_static_checks.py: decomposition registry coverage and the
broken-twin detector gates (lossy and non-irredundant fixtures must
fire the respective law).
"""

from __future__ import annotations

from typing import List

from .ackwin import (
    AckWindow,
    AckWindowKey,
    ack_bits,
    gate_window,
    init_window,
    update_window,
    window_depth,
)
from .decompose import (
    Decomposition,
    decompose,
    decompose_rows,
    decomposition_bytes,
    drop_lane,
    reconstruct,
    reconstruct_rows,
)
from .heal import ResyncReport, resync


def static_checks() -> List:
    """The ``decomp`` static-check section (Finding list, empty =
    clean):

    1. **decomposition coverage** — every registered merge kind must
       have called ``analysis.registry.register_decomposition``
       (12/12); an unregistered δ-bearing kind fails discovery, the
       same registration-is-the-coverage-contract rule as joins /
       compactors / entry points.
    2. **decomposition laws** — reconstruction
       (``join(decompose(s, since)) ⊔ since == s``) and irredundancy
       (no δ lane covered by the join of the others) over every kind's
       registered small domain, bit-exact on canonical forms
       (analysis/laws.py ``check_decomposition_all``).
    3. **broken twins fire** — the committed lossy twin
       (``analysis.fixtures.LOSSY_DECOMPOSER`` drops a changed lane)
       must fail reconstruction, and the non-irredundant twin
       (``analysis.fixtures.REDUNDANT_DECOMPOSER`` emits unchanged
       lanes) must fail irredundancy — proving both detectors have
       teeth.
    """
    from ..analysis import fixtures, laws
    from ..analysis.registry import get_merge_kind
    from ..analysis.report import Finding

    # Coverage and laws share one walk: check_decomposition_all emits
    # the decomp-coverage Finding itself for any merge kind with no
    # registered decomposer (the get_decomposer KeyError branch), so an
    # unregistered kind is reported exactly once.
    findings: List[Finding] = list(laws.check_decomposition_all())

    orswot = get_merge_kind("orswot")
    lossy = laws.check_decomposition_kind(
        orswot, dec=fixtures.LOSSY_DECOMPOSER
    )
    if not any(f.check == "decomp-reconstruction" for f in lossy):
        findings.append(Finding(
            "broken-fixture-missed", "LOSSY_DECOMPOSER",
            "the lane-dropping decomposition twin PASSED the "
            "reconstruction law — the decomp gate is not actually "
            "firing",
        ))
    redundant = laws.check_decomposition_kind(
        orswot, dec=fixtures.REDUNDANT_DECOMPOSER
    )
    if not any(f.check == "decomp-irredundancy" for f in redundant):
        findings.append(Finding(
            "broken-fixture-missed", "REDUNDANT_DECOMPOSER",
            "the unchanged-lane-emitting decomposition twin PASSED the "
            "irredundancy law — the minimality gate is not actually "
            "firing",
        ))
    return findings


__all__ = [
    "AckWindow", "AckWindowKey", "Decomposition", "ResyncReport",
    "ack_bits", "decompose", "decompose_rows", "decomposition_bytes",
    "drop_lane", "gate_window", "init_window", "reconstruct",
    "reconstruct_rows", "resync", "static_checks", "update_window",
    "window_depth",
]
