"""The static cost/residency budget gate.

Benchmarks catch regressions that are big enough to notice on a noisy
wall clock; everything below that threshold compounds silently. This
pass walks every registered mesh entry point's jaxpr (the traces the
jit-lint already memoised — :func:`..jit_lint.entry_jaxprs`) and
derives three deterministic program metrics per entry:

- **peak_bytes** — estimated peak live bytes: a def-use liveness scan
  over the eqns (inputs live from entry, each var dies at its last
  use; an eqn carrying a sub-program contributes its own peak on top
  of the caller's live set). An extra pad, a dropped donation, or a
  widened temp shows up here immediately.
- **collective_bytes** — bytes moved across cross-device collectives
  per invocation: the summed output bytes of every ``ppermute`` /
  ``psum`` / ``all_gather`` / … eqn, multiplied through enclosing
  ``scan`` trip counts (the δ-ring's ``fori_loop`` lowers to scan, so
  ring rounds are priced in). A digest-gate regression or an
  accidentally-widened packet moves this number.
- **eqns** — total eqn count, recursively: the dispatch/program-size
  proxy. A fusion-defeating refactor or an accidentally unrolled loop
  moves this number even when bytes stay flat.

These are ESTIMATES of the traced program, not XLA's allocator — their
value is drift detection, which only needs determinism: the same jaxpr
always prices the same. Each metric is compared against the committed
table ``tools/cost_budgets.json``; exceeding a budget by more than
``tol`` (10%) fails the gate. Intentional regressions re-baseline
explicitly::

    python tools/run_static_checks.py --only cost                  # the gate
    python tools/run_static_checks.py --only cost --write-budgets  # re-baseline

(the same committed-table flow as ``tools/tile_sweep.py --write-table``
— the reviewer sees the new numbers in the diff, not a silently slower
bench three PRs later).
"""

from __future__ import annotations

import json
import os
from typing import Dict, List, Optional, Sequence

import numpy as np

from .jit_lint import _CLOBBER_PRIMS, _sub_jaxprs, entry_jaxprs
from .report import Finding

BUDGET_PATH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    "tools", "cost_budgets.json",
)

METRICS = ("peak_bytes", "collective_bytes", "eqns")
TOL = 0.10


def _aval_bytes(aval) -> int:
    shape = getattr(aval, "shape", ())
    dtype = getattr(aval, "dtype", None)
    if dtype is None:
        return 0
    return int(np.prod(shape, dtype=np.int64)) * np.dtype(dtype).itemsize


def _var_key(v):
    return id(v)


def _walk(jaxpr):
    """(peak_bytes, collective_bytes, eqns) for one (open) jaxpr."""
    import jax
    from jax.extend import core as jcore

    last_use: Dict[int, int] = {}
    n_eqns = len(jaxpr.eqns)
    for i, eqn in enumerate(jaxpr.eqns):
        for v in eqn.invars:
            if not isinstance(v, jcore.Literal):
                last_use[_var_key(v)] = i
    for v in jaxpr.outvars:
        if not isinstance(v, jcore.Literal):
            last_use[_var_key(v)] = n_eqns  # outputs outlive the body

    live: Dict[int, int] = {}
    for v in list(jaxpr.invars) + list(jaxpr.constvars):
        live[_var_key(v)] = _aval_bytes(getattr(v, "aval", None))
    live_bytes = sum(live.values())
    peak = live_bytes
    coll = 0
    eqns = 0

    for i, eqn in enumerate(jaxpr.eqns):
        eqns += 1
        prim = eqn.primitive.name
        trip = int(eqn.params.get("length", 1)) if prim == "scan" else 1

        sub_peak = 0
        for _, sub in _sub_jaxprs(eqn):
            sp, sc, sn = _walk(sub)
            sub_peak = max(sub_peak, sp)
            coll += sc * trip
            eqns += sn

        out_bytes = sum(
            _aval_bytes(v.aval) for v in eqn.outvars
            if not isinstance(v, jax.core.DropVar)
        )
        if prim in _CLOBBER_PRIMS:
            coll += out_bytes * trip

        for v in eqn.outvars:
            if isinstance(v, jax.core.DropVar):
                continue
            k = _var_key(v)
            if k not in live:
                live[k] = _aval_bytes(v.aval)
                live_bytes += live[k]
        peak = max(peak, live_bytes + sub_peak)

        for v in list(eqn.invars) + list(eqn.outvars):
            if isinstance(v, (jcore.Literal, jax.core.DropVar)):
                continue
            k = _var_key(v)
            if last_use.get(k, -1) <= i and k in live:
                live_bytes -= live.pop(k)

    return peak, coll, eqns


def cost_of_jaxpr(closed) -> Dict[str, int]:
    """The three committed metrics for one closed jaxpr."""
    peak, coll, eqns = _walk(closed.jaxpr)
    return {"peak_bytes": peak, "collective_bytes": coll, "eqns": eqns}


def measure_entry_points(
    mesh=None, names: Optional[Sequence[str]] = None
) -> Dict[str, Dict[str, int]]:
    """``{entry name: metrics}`` over the registered fleet (reusing the
    jit-lint's memoised traces). Entries that failed to trace are
    omitted — the jit-lint section already reports them."""
    out = {}
    for name, (ep, closed, _donated) in entry_jaxprs(mesh, names).items():
        if isinstance(closed, Exception):
            continue
        out[name] = cost_of_jaxpr(closed)
    return out


def _mesh_shape(mesh=None) -> Dict[str, int]:
    from .jit_lint import _default_mesh

    mesh = _default_mesh() if mesh is None else mesh
    return {k: int(v) for k, v in mesh.shape.items()}


def load_budgets(path: str = BUDGET_PATH) -> dict:
    """The full committed doc: ``{"mesh": {...}, "entries": {...}}``."""
    if not os.path.exists(path):
        return {}
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def write_budgets(path: str = BUDGET_PATH, mesh=None,
                  measured: Optional[dict] = None) -> Dict[str, Dict[str, int]]:
    """Re-baseline: measure the fleet and commit the table (the
    ``tile_sweep --write-table`` flow). The measuring mesh shape is
    committed alongside — jaxpr shapes (and so every metric) depend on
    it, and the gate refuses to compare across shapes."""
    measured = measure_entry_points(mesh) if measured is None else measured
    doc = {
        "comment": (
            "Static cost budgets per registered mesh entry point "
            "(crdt_tpu/analysis/cost.py): estimated peak live bytes, "
            "collective bytes moved per invocation, and recursive eqn "
            "count of the traced jaxpr at the shared gate geometry on "
            "the committed mesh shape. The gate fails on >10% "
            "regression. Regenerate EXPLICITLY after an intentional "
            "cost change: python tools/run_static_checks.py --only "
            "cost --write-budgets"
        ),
        "mesh": _mesh_shape(mesh),
        "entries": {k: measured[k] for k in sorted(measured)},
    }
    with open(path, "w", encoding="utf-8") as f:
        json.dump(doc, f, indent=2, sort_keys=True)
        f.write("\n")
    return measured


def check_budgets(
    measured: Optional[dict] = None,
    budgets: Optional[dict] = None,
    path: str = BUDGET_PATH,
    tol: float = TOL,
    mesh=None,
) -> List[Finding]:
    """Compare measured metrics against the committed table; >tol
    regression on any metric is an error, as is an entry with no
    committed budget (new entries must be priced in the same PR that
    adds them). Budget rows for entries no longer registered are stale
    — a warning, so table hygiene cannot mask a real failure. A mesh
    shape differing from the committed one refuses the comparison
    outright (error): every metric is a function of the traced shapes,
    so cross-shape numbers would fail (or worse, pass) meaninglessly."""
    if budgets is None:
        doc = load_budgets(path)
        budgets = doc.get("entries", {})
        want_mesh = doc.get("mesh")
        if want_mesh is not None and want_mesh != _mesh_shape(mesh):
            return [Finding(
                "cost-mesh-mismatch", "cost",
                f"measuring mesh {_mesh_shape(mesh)} != committed "
                f"budget mesh {want_mesh} — metrics are shape-dependent "
                "and cannot be compared; run under the committed "
                "topology (tools/run_static_checks.py pins an 8-device "
                "CPU mesh) or re-baseline with --write-budgets",
            )]
    findings: List[Finding] = []
    failed: Dict[str, str] = {}
    if measured is None:
        # Measure inline (rather than via measure_entry_points) so a
        # registered entry that fails to invoke/trace is an ERROR here
        # too — under `--only cost` the jit-lint section that would
        # otherwise report it never runs, and the entry must not
        # masquerade as a stale budget row.
        measured = {}
        for name, (ep, closed, _d) in entry_jaxprs(mesh).items():
            if isinstance(closed, Exception):
                failed[name] = f"{type(closed).__name__}: {closed}"
            else:
                measured[name] = cost_of_jaxpr(closed)
        for name in sorted(failed):
            findings.append(Finding(
                "cost-entry-error", name,
                "registered entry failed to invoke/trace — cannot "
                f"price it: {failed[name]}",
            ))

    for name in sorted(measured):
        got = measured[name]
        want = budgets.get(name)
        if want is None:
            findings.append(Finding(
                "cost-budget-missing", name,
                "entry has no committed cost budget — price it in: "
                "python tools/run_static_checks.py --only cost "
                "--write-budgets",
            ))
            continue
        for metric in METRICS:
            if metric not in want:
                findings.append(Finding(
                    "cost-budget-missing", name,
                    f"committed budget lacks the {metric!r} metric — "
                    "regenerate with --write-budgets",
                ))
                continue
            g, w = int(got[metric]), int(want[metric])
            if g > w * (1.0 + tol):
                pct = (g / w - 1.0) * 100 if w else float("inf")
                findings.append(Finding(
                    "cost-budget", name,
                    f"{metric} regressed {pct:.1f}% over budget "
                    f"({g} vs {w}, tol {tol:.0%}) — if intentional, "
                    "re-baseline with --write-budgets",
                ))
    for name in sorted(set(budgets) - set(measured) - set(failed)):
        findings.append(Finding(
            "cost-budget-stale", name,
            "committed budget row has no registered entry — drop it "
            "with --write-budgets", severity="warning",
        ))
    return findings
