#!/usr/bin/env python
"""THE static-check suite — one fast tier-1 command chaining every gate.

Sections (each timed, each independently skippable):

- ``lint``      — ``ruff check .`` against the committed ``ruff.toml``
  when a ruff binary/module exists; otherwise the built-in fallback
  linter (F401 unused imports, E722 bare except, E999 syntax errors —
  the highest-signal subset, honoring ``# noqa``) so the gate never
  silently vanishes on images without ruff.
- ``schema``    — the telemetry export contract
  (tools/check_telemetry_schema.py) against a live registry snapshot.
- ``laws``      — the lattice-law engine (crdt_tpu.analysis.laws) over
  every registered merge kind: commutativity / associativity /
  idempotence / identity / δ-inflation, bit-exact on canonical forms.
- ``schedules`` — the bounded SEC model checker
  (crdt_tpu.analysis.schedules): every registered kind converges
  bit-exactly under every delivery schedule up to the bound (reorder,
  duplication, drop-with-resync; causal interleavings for op-based
  kinds), with minimized counterexamples on violation — plus the
  generator-degeneracy gate (a one-point domain vacuates every law).
- ``faults``    — the degraded-mesh fault-tolerance gates
  (crdt_tpu.faults.static_checks): fault-surface registry coverage
  (every public entry exposing ``faults=`` must have registered —
  crdt_tpu.analysis.registry.register_fault_surface), the checksum
  detector (integrity.checksum must catch every injected perturbation
  class), and the eviction-bijection gate (ring_perm stays a true
  bijection under every eviction subset) — each with a committed broken
  twin in analysis/fixtures.py proving the detector fires.
- ``durability``— the crash-consistent durability gates
  (crdt_tpu.durability.static_checks): crashpoint registry coverage
  (every registered durability I/O boundary must be crossed by the
  canonical workload), the kill-then-recover contract at EVERY
  crashpoint (recovery lands the last durable record bit-identically),
  and the broken-twin detector gates — the no-fsync WAL
  (``analysis.fixtures.wal_skips_fsync``) must fail the fsync-policy
  detector and the checksum-ignoring snapshot loader
  (``fixtures.snapshot_load_unchecked``) must fail the corruption
  detector.
- ``decomp``    — the join-irreducible decomposition gates
  (crdt_tpu.delta_opt.static_checks): registry coverage (every merge
  kind must have registered a decomposition —
  crdt_tpu.analysis.registry.register_decomposition, 12/12), the two
  decomposition laws per kind (reconstruction:
  ``join(decompose(s, since)) ⊔ since == s``; irredundancy: no δ lane
  covered by the join of the others — analysis/laws.py), and the
  broken-twin detectors (the lossy and non-irredundant fixtures must
  each fire their law).
- ``wire``     — the fused δ wire gates
  (crdt_tpu.parallel.wire_checks): wire-surface registry coverage
  (every δ ring kind must have a registered codec know function —
  crdt_tpu.analysis.registry.register_wire_surface), the fused-gate
  removal-preservation detector on the committed three-slot fixture
  (the PR 3 wider-gate unsoundness rebuilt IN-KERNEL by
  ``analysis.fixtures.fused_mask_drops_removals`` must fire it), and
  the wire round-trip + checksum-parity + bitmap detectors (the
  word-dropping ``fixtures.bitmap_truncates_lanes`` twin must fire
  the truncation gate).
- ``obs``      — the observability-plane gates
  (crdt_tpu.obs.static_checks): flight-recorder event-type coverage
  (every literal ``emit("...")`` site under ``crdt_tpu/`` must have a
  registered schema — crdt_tpu.analysis.registry.register_obs_event —
  so an event-emitting subsystem cannot ship events a dump header
  cannot describe), the recorder ring-conformance detector (newest
  ``capacity`` events kept in order, every drop counted), and the
  in-kernel histogram conformance detector (jit-folded bucket counts
  bit-exact vs the host reference) — each with a committed broken twin
  in analysis/fixtures.py (``recorder_drops_events``,
  ``histogram_miscounts``) proving the detector fires.
- ``scaleout`` — the elastic mesh scale-out gates
  (crdt_tpu.scaleout.static_checks): scaleout-surface registry
  coverage (every public operational symbol must have registered —
  crdt_tpu.analysis.registry.register_scaleout_surface), the
  generation/bijection membership walk (every admit/drain ring rebuild
  stays a true bijection, generations strictly increase, full
  membership composes NO fault plan), and the broken-twin detector
  gates — the corrupt-blind bootstrap
  (``analysis.fixtures.bootstrap_skips_checksum``) must fail the
  corruption detector and the unacked-blind drain certifier
  (``fixtures.drain_ignores_unacked``) must fail the refusal detector.
- ``serve``    — the multi-tenant serving gates
  (crdt_tpu.serve.static_checks): serve-surface registry coverage
  (every public operational symbol must have registered —
  crdt_tpu.analysis.registry.register_serve_surface), the
  coalesced==sequential-oracle micro A/B + pack/unpack round-trip,
  the rendezvous minimal-remap failover property, and the broken-twin
  detector gate — the dirt-dropping evictor
  (``analysis.fixtures.evictor_drops_dirt``) must fail the
  evict/restore preservation detector.
- ``fanout``   — the δ-subscription fan-out gates
  (crdt_tpu.fanout.static_checks): fanout-surface registry coverage
  (every public operational symbol must have registered —
  crdt_tpu.analysis.registry.register_fanout_surface), the cohort
  wire encode/decode bit-exact round-trip + keep∪defer partition,
  the split-watermark push/replay property, and the broken-twin
  detector gate — the watermark-bucket-skipping pusher
  (``analysis.fixtures.fanout_skips_watermark_bucket``) must fail the
  cohort coverage detector.
- ``federation`` — the geo-federation gates (ISSUE 20,
  crdt_tpu.geo.static_checks): geo-surface registry coverage (every
  public operational symbol must have registered —
  crdt_tpu.analysis.registry.register_geo_surface), the two-region
  convergence micro A/B (mirrors bit-identical to home rows after one
  anti-entropy sweep, δ wire bytes strictly under the full-state
  mirroring baseline, a corrupted packet rejected by the checksum
  lane then healed by the retry re-ship), the watermark-monotonicity
  detector (``crdt_tpu.geo.reads.watermark_reads_sound`` — stale
  local reads labeled stale, certificates monotone, caught-up mirrors
  bit-equal to home), and the broken-twin detector gate — the
  always-fresh read path
  (``analysis.fixtures.region_serves_unwatermarked_read``) must fail
  the watermark detector.
- ``pipeline`` — the pipelined-serving-loop gates (ISSUE 18): the
  skew-aware rebalance minimal-move property (balanced fleet → zero
  moves; every move sheds from an over-threshold host and strictly
  shrinks the gap) on a synthetic zipf load.
- ``concurrency`` — the host-concurrency analysis plane (ISSUE 19):
  effect inference over the serving surface with TOTAL shared-field
  coverage (crdt_tpu.analysis.effects — a mutated-but-unregistered
  field fails discovery), the declared happens-before contracts
  (crdt_tpu.analysis.concur.HB_CONTRACTS — WAL≺dispatch, now migrated
  here from ``pipeline``; the settled persist window; persist≺clear;
  pin≺gather…dispatch; the ack clamp; requeue seq preservation;
  touch≺pick), the cross-thread conflict gate (every conflicting
  effect pair on a shared field ordered by a contract or lock guard),
  the retry-timeout-reaches-collective and thread-discipline lints,
  and the deterministic interleaving explorer
  (crdt_tpu.analysis.interleave — every ≤2-preemption schedule of the
  serve and fanout worlds bit-identical to the serial oracle). Five
  committed broken twins (``UnorderedWalLoop``, ``PersistFreesLanes``,
  ``regressing_ack_promoter_cls``, ``RogueCounterMutator``,
  ``racy_fanout_world`` — the rebuilt PR 16 lane-eviction race) are
  each proven to fire.
- ``jit-lint``  — the jaxpr walker (crdt_tpu.analysis.jit_lint) over
  every registered mesh entry point: traced-branch, unstable-sort,
  float-accum, dtype-overflow, donation-alias, PLUS the collective-
  semantics checks (ppermute bijection, collective axis-name vs the
  entry's registered mesh axes, donated-read-after-collective) and the
  δ digest-gate removal-preservation fixtures — plus registry
  discovery (an unregistered public ``mesh_*`` entry is a failure).
- ``cost``      — the static cost/residency budget gate
  (crdt_tpu.analysis.cost): estimated peak live bytes / collective
  bytes moved / eqn count per entry vs the committed
  ``tools/cost_budgets.json``; >10% regression fails.
  ``--write-budgets`` re-baselines the table instead of checking.
- ``slo``       — the trace-plane/SLO gates (crdt_tpu.obs.trace +
  crdt_tpu.analysis.slo): trace-stage registry coverage (every literal
  ``stamp("...")`` site under ``crdt_tpu/`` must have registered —
  crdt_tpu.analysis.registry.register_trace_stage), the tracer
  conformance detector (canonical journey completes, stamps monotonic,
  latencies bit-equal to ``derive_latencies``) with its two committed
  broken twins (``analysis.fixtures.tracer_skips_stage``,
  ``fixtures.tracer_clock_regresses``) proving it fires, and the
  committed ``tools/slo_budgets.json`` freshness regression gate over
  the deterministic canonical serve+fanout workload (counts exact,
  latency quantiles >10% regression fails; ``--write-budgets``
  re-baselines).
- ``aliasing``  — the compiled-HLO input_output_alias gate
  (tools/check_aliasing.py) over every registered donating entry.

CLI::

    python tools/run_static_checks.py              # everything, rc != 0 on any error
    python tools/run_static_checks.py --only laws,jit-lint
    python tools/run_static_checks.py --skip lint
    python tools/run_static_checks.py --json-out static_checks.json
    python tools/run_static_checks.py --only cost --write-budgets

``--json-out`` writes the machine-readable per-section summary
(pass/fail, finding counts, wall-clock — crdt_tpu.analysis.report) so
CI can trend the gates instead of parsing text.

The jax-heavy sections share one process (and the repo's persistent XLA
compilation cache at .jax_cache/), so a warm run of the whole suite
stays inside the 120 s budget in ISSUE 7's acceptance criteria.
"""

from __future__ import annotations

import argparse
import ast
import os
import subprocess
import sys
import time
from typing import List, Tuple

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

SECTIONS = (
    "lint", "schema", "laws", "schedules", "faults", "decomp",
    "durability", "scaleout", "obs", "wire", "serve", "fanout",
    "federation", "pipeline", "concurrency", "jit-lint", "cost",
    "slo", "aliasing",
)

# Directories the fallback linter walks (ruff takes its own config).
LINT_TARGETS = ("crdt_tpu", "tools", "tests", "examples")


# ---- section: lint -------------------------------------------------------

def _noqa_lines(src: str) -> dict:
    """line number -> set of noqa'd codes ('*' = bare noqa). Codes may
    be followed by free-text commentary (``# noqa: F401  (reason)``)."""
    import re

    out = {}
    for i, line in enumerate(src.splitlines(), 1):
        if "# noqa" not in line:
            continue
        tail = line.split("# noqa", 1)[1]
        codes = set(re.findall(r"[A-Z]+[0-9]+", tail)) if (
            tail.lstrip().startswith(":")
        ) else set()
        out[i] = codes or {"*"}
    return out


def _mini_lint_file(path: str) -> List[str]:
    with open(path) as f:
        src = f.read()
    try:
        tree = ast.parse(src, filename=path)
    except SyntaxError as exc:
        return [f"{path}:{exc.lineno}: E999 {exc.msg}"]
    noqa = _noqa_lines(src)

    def quiet(lineno: int, code: str) -> bool:
        codes = noqa.get(lineno, ())
        return "*" in codes or code in codes

    errs: List[str] = []
    imports: List[Tuple[str, int]] = []
    used = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for al in node.names:
                imports.append(
                    (al.asname or al.name.split(".")[0], node.lineno)
                )
        elif isinstance(node, ast.ImportFrom):
            if node.module == "__future__":
                continue
            for al in node.names:
                if al.name == "*":
                    continue
                imports.append((al.asname or al.name, node.lineno))
        elif isinstance(node, ast.Name):
            used.add(node.id)
        elif isinstance(node, ast.ExceptHandler) and node.type is None:
            if not quiet(node.lineno, "E722"):
                errs.append(f"{path}:{node.lineno}: E722 bare except")
    # Names exported via __all__ count as used.
    for node in ast.walk(tree):
        if (isinstance(node, ast.Assign)
                and any(getattr(t, "id", "") == "__all__"
                        for t in node.targets)):
            for c in ast.walk(node.value):
                if isinstance(c, ast.Constant) and isinstance(c.value, str):
                    used.add(c.value)
    if os.path.basename(path) != "__init__.py":  # __init__ = re-export surface
        for name, lineno in imports:
            if name not in used and not quiet(lineno, "F401"):
                errs.append(f"{path}:{lineno}: F401 unused import '{name}'")
    return errs


def mini_lint(targets=LINT_TARGETS) -> List[str]:
    errs: List[str] = []
    for target in targets:
        target = os.path.join(ROOT, target)
        if os.path.isfile(target):
            errs += _mini_lint_file(target)
            continue
        for dirpath, dirnames, filenames in os.walk(target):
            dirnames[:] = [d for d in dirnames if d != "__pycache__"]
            for fn in sorted(filenames):
                if fn.endswith(".py"):
                    errs += _mini_lint_file(os.path.join(dirpath, fn))
    return errs


def _ruff_cmd():
    import shutil

    if shutil.which("ruff"):
        return ["ruff"]
    try:
        import ruff  # noqa: F401

        return [sys.executable, "-m", "ruff"]
    except ImportError:
        return None


def run_lint() -> List[str]:
    cmd = _ruff_cmd()
    if cmd is not None:
        proc = subprocess.run(
            cmd + ["check", ROOT], capture_output=True, text=True
        )
        if proc.returncode == 0:
            return []
        return (proc.stdout + proc.stderr).strip().splitlines()
    return [f"(ruff unavailable — built-in F401/E722/E999 subset) {e}"
            for e in mini_lint()] or []


# ---- section: schema -----------------------------------------------------

def run_schema() -> List[str]:
    from crdt_tpu.utils.metrics import metrics

    from check_telemetry_schema import validate_snapshot

    metrics.count("static_checks.runs")
    metrics.observe("static_checks.heartbeat", 1.0)
    return validate_snapshot(metrics.snapshot())


# ---- sections: laws / schedules / jit-lint / cost / aliasing --------------

def run_laws():
    from crdt_tpu.analysis import laws

    return laws.check_all()


def run_schedules():
    from crdt_tpu.analysis import schedules

    return schedules.check_all_schedules()


def run_faults():
    from crdt_tpu.faults import static_checks

    return static_checks()


def run_decomp():
    from crdt_tpu.delta_opt import static_checks

    return static_checks()


def run_durability():
    from crdt_tpu.durability import static_checks

    return static_checks()


def run_scaleout():
    from crdt_tpu.scaleout import static_checks

    return static_checks()


def run_obs():
    from crdt_tpu.obs import static_checks

    return static_checks()


def run_wire():
    from crdt_tpu.parallel.wire_checks import static_checks

    return static_checks()


def run_serve():
    from crdt_tpu.serve import static_checks

    return static_checks()


def run_fanout():
    from crdt_tpu.fanout import static_checks

    return static_checks()


def run_federation():
    from crdt_tpu.geo import static_checks

    return static_checks()


def run_pipeline():
    """The pipelined-serving-loop section (ISSUE 18): the skew-aware
    rebalance minimal-move property on a synthetic zipf load (balanced
    fleet plans zero moves; every planned move sheds from an
    over-threshold host and strictly shrinks the src/dst gap). The
    WAL-before-dispatch ordering gate that used to live here is now
    the first ``HB_CONTRACTS`` entry of the ``concurrency`` section.
    """
    from crdt_tpu.analysis.report import Finding
    from crdt_tpu.serve import (
        TenantShardMap, host_loads, rebalance_plan,
    )

    findings = []

    # Rebalance minimal-move property on a synthetic zipf load:
    # 64 tenants, zipf-ish weights, rendezvous placement over 4 hosts.
    sm = TenantShardMap(4)
    tenants = list(range(64))
    weights = {t: 1.0 / (t + 1) ** 1.0 for t in tenants}  # zipf α=1
    loads0 = host_loads(sm, tenants, weights)
    mean = sum(loads0.values()) / len(loads0)
    plan = rebalance_plan(sm, tenants, weights, threshold=1.5)
    loads = dict(loads0)
    for mv in plan:
        if loads[mv.src] <= 1.5 * mean:
            findings.append(Finding(
                "pipeline-rebalance-minimal", f"tenant {mv.tenant}",
                f"move sheds from host {mv.src} whose load "
                f"{loads[mv.src]:.3f} is already under threshold — "
                "not a minimal-move plan",
            ))
        if loads[mv.dst] + mv.load >= loads[mv.src]:
            findings.append(Finding(
                "pipeline-rebalance-minimal", f"tenant {mv.tenant}",
                "move does not strictly shrink the src/dst gap",
            ))
        loads[mv.src] -= mv.load
        loads[mv.dst] += mv.load
    # A balanced fleet (uniform weights) must plan ZERO moves... unless
    # rendezvous itself landed it lopsided, in which case every move
    # still obeys the shed-from-hot rule checked above.
    flat = {t: 1.0 for t in tenants}
    lf = host_loads(sm, tenants, flat)
    if max(lf.values()) <= 1.5 * (sum(lf.values()) / len(lf)):
        if rebalance_plan(sm, tenants, flat, threshold=1.5):
            findings.append(Finding(
                "pipeline-rebalance-minimal", "uniform load",
                "a balanced fleet planned moves — the planner churns "
                "placements it cannot improve",
            ))
    return findings


def run_concurrency():
    """The host-concurrency section (ISSUE 19 tentpole): effect
    inference over the serving surface with total shared-field
    coverage (a mutated-but-unregistered field fails discovery), the
    ``analysis.concur.HB_CONTRACTS`` checker (every declared
    happens-before edge proven executable — WAL≺dispatch, the settled
    persist window, persist≺clear, pin≺gather…dispatch, the ack
    clamp, requeue seq preservation, touch≺pick), the cross-thread
    conflict gate (every conflicting effect pair on a shared field
    ordered by a contract or lock guard), the retry-timeout and
    thread-discipline lints, and the deterministic interleaving
    explorer: bit-identity to the serial oracle on every
    ≤2-preemption schedule of the serve and fanout worlds. Each
    committed broken twin must fire its detector; the rebuilt PR 16
    lane-eviction race must yield a counterexample."""
    from crdt_tpu.analysis import concur, effects, fixtures, interleave
    from crdt_tpu.analysis.report import Finding

    findings = []

    # 1. Coverage: every shared-state mutation on the host surface is
    # registered...
    for field, site in effects.unregistered_shared_mutations():
        findings.append(Finding(
            "concurrency-coverage", field,
            f"shared-state mutation at {site} has no "
            "register_shared_field declaration — its cross-thread "
            "conflicts are invisible to the HB checker",
        ))
    # ...and the unregistered-mutator twin must fail discovery.
    if not effects.unregistered_shared_mutations(
        extra=(fixtures.RogueCounterMutator,)
    ):
        findings.append(Finding(
            "broken-fixture-missed", "RogueCounterMutator",
            "an unregistered shared-field mutation PASSED discovery — "
            "the coverage contract is not actually total",
        ))

    # 2. Declared happens-before contracts, each an executable proof.
    for cname, viol in concur.check_hb_contracts():
        findings.append(Finding("concurrency-hb", cname, viol))
    # Broken twins per contract family: ordering, ack clamp.
    if not concur.call_order_violations(
        fixtures.UnorderedWalLoop, ("_log",), ("_issue",)
    ):
        findings.append(Finding(
            "broken-fixture-missed", "UnorderedWalLoop",
            "the dispatch-before-WAL loop twin PASSED the generalized "
            "call-order scan",
        ))
    if not concur.ack_window_probe(fixtures.regressing_ack_promoter_cls()):
        findings.append(Finding(
            "broken-fixture-missed", "regressing_ack_promoter",
            "an unclamped ack promotion PASSED the ack-window probe",
        ))

    # 3. Conflict gate: every cross-thread conflicting effect pair on
    # a shared field is ordered...
    for viol in concur.uncovered_conflicts():
        findings.append(Finding("concurrency-conflict", "effects", viol))
    # ...and the off-thread lane-freeing twin must be reported.
    if not concur.uncovered_conflicts(
        extra=(fixtures.PersistFreesLanes,),
        extra_threads={"PersistFreesLanes": ("persist",)},
    ):
        findings.append(Finding(
            "broken-fixture-missed", "PersistFreesLanes",
            "a persist-thread lane-table write with no ordering "
            "contract PASSED the conflict gate",
        ))

    # 4. Host lints: no timed retry may reach a collective; every
    # thread is daemon, named, and a registered effect source.
    for viol in concur.retry_timeout_collective_violations():
        findings.append(Finding("concurrency-retry", "retry", viol))
    for viol in concur.thread_lint_violations():
        findings.append(Finding("concurrency-thread", "threads", viol))

    # 5. The interleaving explorer: serve world (dense; the sparse
    # kind and the heavier matrices run in tests/test_concur.py) and
    # fanout world, all ≤2-preemption schedules bit-identical to the
    # serial oracle.
    for mk, preempt in (
        (lambda: interleave.serve_world("orswot"), 1),
        (interleave.fanout_world, 2),
    ):
        r = interleave.explore(mk, preemptions=preempt)
        if not r.ok:
            cx = r.counterexample
            findings.append(Finding(
                "concurrency-interleave", r.world,
                f"schedule {list(cx.schedule)} diverged: "
                + "; ".join(cx.reasons[:2]),
            ))
    # The rebuilt PR 16 lane-eviction race must produce a
    # counterexample within 2 preemptions.
    r = interleave.explore(fixtures.racy_fanout_world, preemptions=2)
    if r.ok:
        findings.append(Finding(
            "broken-fixture-missed", "racy_fanout_world",
            "the lane-eviction-race twin PASSED every explored "
            "schedule — the explorer is not catching the PR 16 race",
        ))
    return findings


def run_jit_lint():
    from crdt_tpu.analysis.jit_lint import check_gates, lint_entry_points

    return lint_entry_points() + check_gates()


def run_cost(write_budgets: bool = False):
    from crdt_tpu.analysis import cost

    if write_budgets:
        measured = cost.write_budgets()
        print(f"     wrote {len(measured)} entry budgets -> "
              f"{os.path.relpath(cost.BUDGET_PATH, ROOT)}")
        return []
    return cost.check_budgets()


def run_slo(write_budgets: bool = False):
    """The trace-plane/SLO section: stamp-site registry coverage
    (every literal ``stamp("...")`` stage under crdt_tpu/ must be
    registered), tracer conformance with both committed broken twins
    proven to fire, and the committed ``tools/slo_budgets.json``
    freshness regression gate."""
    from crdt_tpu.analysis import fixtures, slo
    from crdt_tpu.analysis.registry import unregistered_trace_stages
    from crdt_tpu.analysis.report import Finding
    from crdt_tpu.obs import trace

    findings = []
    for name, where in unregistered_trace_stages():
        findings.append(Finding(
            "slo-stage-coverage", name,
            f"trace stage stamped at {where} has no registration "
            "(register_trace_stage) — the SLO waterfall cannot place "
            "the leg it bounds",
        ))
    if not trace.tracer_conformant(trace.Tracer):
        findings.append(Finding(
            "slo-tracer-conformance", "Tracer",
            "the tracer orphaned, double-completed, or mis-derived a "
            "canonical two-tenant journey (conformance probe)",
        ))
    if trace.tracer_conformant(fixtures.tracer_skips_stage):
        findings.append(Finding(
            "slo-tracer-conformance", "fixtures.tracer_skips_stage",
            "the durable-stamp-dropping broken twin PASSED the tracer "
            "conformance detector — the detector has no teeth",
        ))
    if trace.tracer_conformant(fixtures.tracer_clock_regresses):
        findings.append(Finding(
            "slo-tracer-conformance", "fixtures.tracer_clock_regresses",
            "the regressing-clock broken twin PASSED the tracer "
            "conformance detector — the detector has no teeth",
        ))
    if write_budgets:
        measured = slo.write_budgets()
        print(f"     wrote {len(measured)} SLO baselines -> "
              f"{os.path.relpath(slo.SLO_BUDGET_PATH, ROOT)}")
        return findings
    return findings + slo.check_budgets()


def run_aliasing() -> List[str]:
    import check_aliasing

    return [
        f"{kind}: {detail}"
        for kind, ok, detail in check_aliasing.check_all()
        if not ok
    ]


RUNNERS = {
    "lint": run_lint,
    "schema": run_schema,
    "laws": run_laws,
    "schedules": run_schedules,
    "faults": run_faults,
    "decomp": run_decomp,
    "durability": run_durability,
    "scaleout": run_scaleout,
    "obs": run_obs,
    "wire": run_wire,
    "serve": run_serve,
    "fanout": run_fanout,
    "federation": run_federation,
    "pipeline": run_pipeline,
    "concurrency": run_concurrency,
    "jit-lint": run_jit_lint,
    "cost": run_cost,
    "slo": run_slo,
    "aliasing": run_aliasing,
}

_JAX_SECTIONS = (
    "laws", "schedules", "faults", "decomp", "durability", "scaleout",
    "obs", "wire", "serve", "fanout", "federation", "pipeline",
    "concurrency", "jit-lint", "cost", "slo", "aliasing",
)


def _as_findings(section: str, result):
    """Normalize a runner's result (Finding list or legacy string list)
    into Findings so every section reports uniformly."""
    from crdt_tpu.analysis.report import Finding

    out = []
    for item in result:
        if isinstance(item, Finding):
            out.append(item)
        else:
            out.append(Finding(section, section, str(item)))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--only", default="", help="comma-separated sections")
    ap.add_argument("--skip", default="", help="comma-separated sections")
    ap.add_argument(
        "--json-out", default="",
        help="write the machine-readable per-section summary "
        "(crdt_tpu.analysis.report) to this path",
    )
    ap.add_argument(
        "--write-budgets", action="store_true",
        help="re-baseline tools/cost_budgets.json and "
        "tools/slo_budgets.json instead of checking (the cost/slo "
        "sections' tile_sweep --write-table flow)",
    )
    args = ap.parse_args(argv)

    only = {s for s in args.only.split(",") if s}
    skip = {s for s in args.skip.split(",") if s}
    unknown = (only | skip) - set(SECTIONS)
    if unknown:
        ap.error(f"unknown sections {sorted(unknown)}; know {SECTIONS}")
    chosen = [
        s for s in SECTIONS
        if (not only or s in only) and s not in skip
    ]

    if any(s in chosen for s in _JAX_SECTIONS):
        # One CPU pin + one persistent compile cache for every jax
        # section (mirrors tests/conftest.py) — this is what keeps the
        # warm full suite inside the 120 s budget. The two vars default
        # INDEPENDENTLY: an ambient JAX_PLATFORMS=cpu (common in CI
        # images) must not silently collapse the virtual mesh to one
        # device — the gates would then lint/price a 1×1 program while
        # the committed budgets and HLO pins assume the 4×2 gate mesh.
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        os.environ.setdefault(
            "XLA_FLAGS", "--xla_force_host_platform_device_count=8"
        )
        from crdt_tpu.utils.compile_cache import enable_compile_cache

        enable_compile_cache()

    from crdt_tpu.analysis.report import (
        Finding, SectionResult, errors, write_summary,
    )

    rc = 0
    results: List[SectionResult] = []
    t_all = time.perf_counter()
    for section in chosen:
        t0 = time.perf_counter()
        try:
            if section == "cost":
                found = run_cost(write_budgets=args.write_budgets)
            elif section == "slo":
                found = run_slo(write_budgets=args.write_budgets)
            else:
                found = RUNNERS[section]()
            findings = _as_findings(section, found)
        except Exception as exc:  # a crashed section is a failed gate
            findings = [Finding(
                "section-crash", section,
                f"section crashed: {type(exc).__name__}: {exc}",
            )]
        dt = time.perf_counter() - t0
        res = SectionResult(name=section, findings=findings, seconds=dt)
        results.append(res)
        bad = errors(findings)
        status = "PASS" if not bad else "FAIL"
        print(f"{status} {section:<10} ({dt:5.1f}s)")
        for f in findings:
            print(f"     {f}")
        if bad:
            rc = 1
    if args.json_out:
        write_summary(results, args.json_out)
        print(f"summary -> {args.json_out}")
    print(f"{'OK' if rc == 0 else 'FAILED'} static checks "
          f"({time.perf_counter() - t_all:.1f}s)")
    return rc


if __name__ == "__main__":
    sys.exit(main())
