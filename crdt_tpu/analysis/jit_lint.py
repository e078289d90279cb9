"""The jit-safety lint — jaxpr-level purity checks for mesh entry points.

Walks the jaxpr of every registered mesh entry point (and any fixture
callable) and flags the hazards that break determinism or zero-copy on
an accelerator, each reported with the offending eqn:

- **traced-branch** — host Python control flow on a traced value: the
  trace itself aborts (``TracerBoolConversionError``); the lint turns
  the crash into a finding with the source frame.
- **unstable-sort** — a ``sort`` primitive lowered with
  ``is_stable=False``: tie order then depends on backend/tile schedule,
  so converged replicas stop being bit-identical.
- **float-accum** — an additive reduction (``reduce_sum``,
  ``dot_general``, ``cumsum``, float ``psum``/``reduce_window_sum``)
  over floating operands whose values are NOT provably exact 0/1
  (i.e. derived from booleans): float addition is non-associative, so
  the reduction order XLA picks changes the bits. The provenance walk
  follows bool-preserving ops (converts, broadcasts, reshapes,
  transposes, boolean logic, 0/1 products) through nested call jaxprs —
  the ORSWOT dedupe matmul (bf16 0/1 masks, f32 accumulator) passes,
  a genuine float accumulation fails.
- **dtype-overflow** — counter/clock-lane hazards: arithmetic on
  sub-32-bit unsigned integers (saturates in thousands of ops) and
  unsigned-narrowing ``convert_element_type`` (a u64→u32 or u32→u16
  truncation silently reorders dot comparisons).
- **donation-alias** — a donated input leaf whose (shape, dtype) has no
  matching output leaf: XLA cannot alias it, the donation silently
  degrades to a copy (the jaxpr-level shadow of tools/check_aliasing.py's
  compiled-HLO gate).

**Collective semantics** (the wiring the dataflow checks are blind to —
an invalid ppermute permutation or axis-name mismatch compiles fine and
silently exchanges the wrong data):

- **ppermute-perm** — a ``ppermute`` whose (src, dst) pairs are not a
  true permutation of the axis: duplicate sources/destinations race,
  and missing pairs leave ranks holding zeros — either way the δ ring
  stops being a bijection and replicas silently diverge.
- **collective-axis** — a collective naming a mesh axis outside the
  entry's registered ``mesh_axes``: under a mesh that happens to bind
  the name it reduces over the wrong ranks; under any other it is a
  trace error only reached on that code path.
- **donated-read-after-collective** — a donated input var consumed by a
  collective and then read by a later eqn (or returned): donation lets
  XLA alias the collective's output onto the input buffer, so the later
  read sees overwritten data — a zero-copy-only corruption invisible in
  undonated tests.

**δ digest-gate soundness** (:func:`check_gates`): the registered gate
flavors (``delta.gate_delta``, ``delta_map.gate_delta_map``, and the
``delta_nest.nested_gate`` lift) are proven removal-preserving on
committed gate fixtures — a slot whose context carries removal
knowledge (ctx lane above its content's witness dots) must ship even
when the receiver's digest covers the content, and a covered add-only
slot must actually be masked (an always-keep gate is dead weight).
This pins statically the exact unsoundness PR 3's wider gate hit by
runtime test.

Entry-point driver: :func:`lint_entry_points` builds each registered
entry's example args, runs it once so the memoised jit exists, then
lints the cached function's jaxpr (:func:`entry_jaxprs` memoises the
traces per mesh shape — the cost gate reuses them for free). Fixture
driver: :func:`lint_callable` takes any callable + example args
(tests/test_analysis.py proves every detector fires on
crdt_tpu/analysis/fixtures.py).
"""

from __future__ import annotations

from typing import Any, List, Optional, Sequence, Set

import jax
import numpy as np
from jax.extend import core as jcore

from .report import Finding, slice_jaxpr

# Additive (order-sensitive when floating) accumulations.
_ACCUM_PRIMS = {
    "reduce_sum", "cumsum", "dot_general", "psum", "reduce_window_sum",
}
# Cross-device collectives: axis names must match the entry's
# registered mesh axes (axis_index included — a wrong name there
# misroutes ring arithmetic even though no bytes move).
_COLLECTIVE_PRIMS = {
    "psum", "pmax", "pmin", "ppermute", "pbroadcast", "all_gather",
    "all_to_all", "reduce_scatter", "pgather", "axis_index",
}
# The subset that moves/overwrites buffers — reading a donated operand
# after one of these races the alias.
_CLOBBER_PRIMS = _COLLECTIVE_PRIMS - {"axis_index"}


def _collective_axis_names(eqn) -> list:
    """String axis names a collective eqn touches (positional ints from
    axis_index_groups etc. are not names and not checked)."""
    names = []
    for pname in ("axes", "axis_name"):
        if pname in eqn.params:
            v = eqn.params[pname]
            vs = v if isinstance(v, (list, tuple)) else (v,)
            names += [x for x in vs if isinstance(x, str)]
    return names
# Integer arithmetic that can wrap a narrow counter lane.
_INT_ARITH_PRIMS = {"add", "sub", "mul", "reduce_sum", "cumsum"}
# Value-preserving ops through which 0/1-ness survives.
_SHAPE_PRIMS = {
    "broadcast_in_dim", "reshape", "transpose", "squeeze", "slice",
    "dynamic_slice", "rev", "concatenate", "expand_dims", "copy",
    "convert_element_type", "stop_gradient", "gather", "select_n",
    "and", "or", "xor", "not", "reduce_or", "reduce_and", "reduce_max",
    "reduce_min", "max", "min", "mul", "pad",
}


def _is_float(aval) -> bool:
    return np.issubdtype(aval.dtype, np.floating)


def _sub_jaxprs(eqn):
    """(param_name, Jaxpr) pairs nested under an eqn (pjit, shard_map,
    scan, while, cond, custom_* — anything carrying a sub-program)."""
    for name, val in eqn.params.items():
        vals = val if isinstance(val, (list, tuple)) else (val,)
        for v in vals:
            if isinstance(v, jcore.ClosedJaxpr):
                yield name, v.jaxpr
            elif isinstance(v, jcore.Jaxpr):
                yield name, v


class _Walker:
    """One pass over a closed jaxpr tracking 0/1 provenance, donated
    buffers, and collective wiring. ``axis_sizes`` maps mesh axis names
    to sizes (for the ppermute bijection check); ``allowed_axes`` is
    the entry's registered mesh-axis set (None = any axis name passes —
    fixture callables carry no registration)."""

    def __init__(self, label: str, axis_sizes=None, allowed_axes=None):
        self.label = label
        self.axis_sizes = dict(axis_sizes or {})
        self.allowed_axes = (
            None if allowed_axes is None else set(allowed_axes)
        )
        self.findings: List[Finding] = []

    def _finding(self, check: str, eqn, detail: str, path: str) -> None:
        self.findings.append(Finding(
            check, self.label,
            f"{detail} (at {path or 'top level'})",
            jaxpr_slice=slice_jaxpr(eqn, max_lines=6),
        ))

    def _check_collective(self, eqn, donated: Set[Any], clobbered: dict,
                          path: str) -> None:
        prim = eqn.primitive.name
        if prim not in _COLLECTIVE_PRIMS:
            return
        if self.allowed_axes is not None:
            bad = [
                n for n in _collective_axis_names(eqn)
                if n not in self.allowed_axes
            ]
            if bad:
                self._finding(
                    "collective-axis", eqn,
                    f"{prim} touches axis {bad} outside the entry's "
                    f"registered mesh axes {sorted(self.allowed_axes)} — "
                    "a stale/typo'd axis name exchanges over the wrong "
                    "ranks", path,
                )
        if prim == "ppermute":
            perm = [tuple(p) for p in eqn.params.get("perm", ())]
            srcs = [s for s, _ in perm]
            dsts = [d for _, d in perm]
            size = None
            for n in _collective_axis_names(eqn):
                size = self.axis_sizes.get(n, size)
            dup = (len(set(srcs)) != len(srcs)
                   or len(set(dsts)) != len(dsts))
            partial = (
                size is not None
                and (set(srcs) != set(range(size))
                     or set(dsts) != set(range(size)))
            )
            if dup or partial:
                why = ("duplicate sources/destinations race"
                       if dup else
                       f"pairs do not cover the full axis of size {size} "
                       "— uncovered ranks receive zeros")
                self._finding(
                    "ppermute-perm", eqn,
                    f"ppermute perm {perm} is not a true permutation of "
                    f"the axis: {why}", path,
                )
        if prim in _CLOBBER_PRIMS:
            for v in eqn.invars:
                if not isinstance(v, jcore.Literal) and v in donated:
                    clobbered.setdefault(v, prim)

    def walk(self, jaxpr: jcore.Jaxpr, exact: Set[Any], path: str = "",
             donated: Optional[Set[Any]] = None) -> None:
        """``exact`` holds vars whose runtime values are provably all in
        {0, 1} (bool inputs/constants and anything value-preserving
        derived from them); ``donated`` holds input vars whose buffers
        the caller donated (alias-clobber tracking)."""
        donated = donated or set()
        clobbered: dict = {}  # donated var -> collective prim that consumed it

        def is_exact(v) -> bool:
            if isinstance(v, jcore.Literal):
                val = np.asarray(v.val)
                return bool(np.isin(val, (0, 1)).all())
            if v.aval.dtype == np.bool_:
                return True
            return v in exact

        reported_clobber: Set[Any] = set()
        for eqn in jaxpr.eqns:
            prim = eqn.primitive.name
            ins_exact = all(is_exact(v) for v in eqn.invars)

            # Read-after-clobber first (before this eqn can register its
            # own collective consumption — the collective itself is the
            # legitimate last read of a donated operand).
            for v in eqn.invars:
                if (not isinstance(v, jcore.Literal) and v in clobbered
                        and v not in reported_clobber):
                    reported_clobber.add(v)
                    self._finding(
                        "donated-read-after-collective", eqn,
                        f"donated input read by {prim} AFTER a "
                        f"{clobbered[v]} consumed it — donation lets XLA "
                        "alias the collective's output onto this buffer, "
                        "so the read sees overwritten data", path,
                    )
            self._check_collective(eqn, donated, clobbered, path)

            if prim == "sort" and not eqn.params.get("is_stable", True):
                self._finding(
                    "unstable-sort", eqn,
                    "sort lowered with is_stable=False — tie order is "
                    "backend-dependent", path,
                )

            if prim in _ACCUM_PRIMS:
                float_ins = [v for v in eqn.invars if _is_float(v.aval)]
                if float_ins and not all(is_exact(v) for v in float_ins):
                    self._finding(
                        "float-accum", eqn,
                        f"{prim} accumulates floating values not provably "
                        "0/1 — float addition is non-associative, bits "
                        "depend on reduction order", path,
                    )

            if prim in _INT_ARITH_PRIMS:
                for v in eqn.invars:
                    dt = v.aval.dtype
                    if (np.issubdtype(dt, np.unsignedinteger)
                            and dt.itemsize < 4):
                        self._finding(
                            "dtype-overflow", eqn,
                            f"{prim} on {dt} counter lane — sub-32-bit "
                            "unsigned arithmetic wraps under realistic op "
                            "counts", path,
                        )
                        break

            if prim == "convert_element_type":
                src = eqn.invars[0].aval.dtype
                dst = eqn.params.get("new_dtype")
                if (dst is not None
                        and np.issubdtype(src, np.unsignedinteger)
                        and np.issubdtype(np.dtype(dst), np.unsignedinteger)
                        and np.dtype(dst).itemsize < np.dtype(src).itemsize):
                    self._finding(
                        "dtype-overflow", eqn,
                        f"narrowing convert {src} -> {np.dtype(dst)} "
                        "truncates counter/clock lanes", path,
                    )

            # Propagate 0/1 provenance.
            if eqn.outvars:
                out_exact = False
                if prim in _SHAPE_PRIMS:
                    if prim == "pad":
                        out_exact = ins_exact  # padding value is an invar
                    elif prim == "select_n":
                        out_exact = all(is_exact(v) for v in eqn.invars[1:])
                    else:
                        out_exact = ins_exact
                elif all(
                    not isinstance(v, jcore.Literal)
                    and v.aval.dtype == np.bool_
                    for v in eqn.outvars
                ):
                    out_exact = True  # comparisons etc. produce bools
                if out_exact:
                    exact.update(
                        v for v in eqn.outvars
                        if not isinstance(v, jax.core.DropVar)
                    )

            # Recurse into sub-programs, mapping provenance positionally
            # where the calling convention is 1:1 (pjit/closed_call/
            # shard_map/scan prefix); unknown conventions start cold.
            for pname, sub in _sub_jaxprs(eqn):
                sub_exact: Set[Any] = set()
                sub_donated: Set[Any] = set()
                if len(sub.invars) == len(eqn.invars):
                    sub_exact = {
                        sv for sv, ov in zip(sub.invars, eqn.invars)
                        if is_exact(ov)
                    }
                    sub_donated = {
                        sv for sv, ov in zip(sub.invars, eqn.invars)
                        if not isinstance(ov, jcore.Literal)
                        and ov in donated
                    }
                for cv in sub.constvars:
                    av = getattr(cv, "aval", None)
                    if av is not None and av.dtype == np.bool_:
                        sub_exact.add(cv)
                self.walk(sub, sub_exact,
                          f"{path}/{prim}" if path else prim,
                          donated=sub_donated)

        # Returning a donated var a collective already consumed is the
        # same stale read, at the output boundary.
        for v in jaxpr.outvars:
            if (not isinstance(v, jcore.Literal) and v in clobbered
                    and v not in reported_clobber):
                reported_clobber.add(v)
                self._finding(
                    "donated-read-after-collective", jaxpr,
                    f"donated input returned AFTER a {clobbered[v]} "
                    "consumed it — the output may alias the overwritten "
                    "buffer", path,
                )


def lint_jaxpr(
    closed: jcore.ClosedJaxpr,
    label: str,
    donated_avals: Sequence[Any] = (),
    axis_sizes=None,
    allowed_axes=None,
) -> List[Finding]:
    """All detectors over one closed jaxpr. ``donated_avals`` are the
    (shape, dtype) pairs of donated input leaves for the aliasing
    check — by the flattening convention they are the FIRST
    ``len(donated_avals)`` invars, which seeds the alias-clobber
    tracking. ``axis_sizes``/``allowed_axes`` feed the collective
    checks (None skips the axis-membership check)."""
    w = _Walker(label, axis_sizes=axis_sizes, allowed_axes=allowed_axes)
    w.walk(closed.jaxpr, set(),
           donated=set(closed.jaxpr.invars[:len(donated_avals)]))

    if donated_avals:
        outs = [(tuple(v.aval.shape), np.dtype(v.aval.dtype))
                for v in closed.jaxpr.outvars]
        for shape, dtype in donated_avals:
            key = (tuple(shape), np.dtype(dtype))
            if key in outs:
                outs.remove(key)
            else:
                w.findings.append(Finding(
                    "donation-alias", label,
                    f"donated input {dtype}{list(shape)} has no "
                    "shape/dtype-matching output leaf — XLA cannot alias "
                    "it and will silently copy",
                ))
    return w.findings


def lint_callable(
    fn,
    args: tuple,
    label: Optional[str] = None,
    n_donated_leaves: int = 0,
    axis_sizes=None,
    allowed_axes=None,
) -> List[Finding]:
    """Trace ``fn`` on ``args`` and lint the jaxpr. A trace abort on a
    host branch over a traced value becomes a ``traced-branch``
    finding. ``n_donated_leaves`` marks the first N flattened input
    leaves donated (for the aliasing check)."""
    label = label or getattr(fn, "__name__", repr(fn))
    try:
        closed = jax.make_jaxpr(fn)(*args)
    except (jax.errors.TracerBoolConversionError,
            jax.errors.ConcretizationTypeError,
            jax.errors.TracerArrayConversionError,
            jax.errors.TracerIntegerConversionError) as exc:
        return [Finding(
            "traced-branch", label,
            "host Python control flow on a traced value aborts the "
            f"trace: {str(exc).splitlines()[0]}",
        )]
    donated = [
        (np.shape(leaf), np.asarray(leaf).dtype)
        for leaf in jax.tree.leaves(args)[:n_donated_leaves]
    ]
    return lint_jaxpr(closed, label, donated,
                      axis_sizes=axis_sizes, allowed_axes=allowed_axes)


def _cached_entry_fn(kind: str, n_donated: int, mesh=None):
    """The memoised jit the entry's run populated
    (parallel.anti_entropy._FN_CACHE: key = (kind, mesh, sig,
    donate_argnums, *extra)). The lookup keys on (kind, n_donated,
    mesh shape) — matching on (kind, donation) alone returned whichever
    mesh was invoked LAST, so re-linting under a different mesh could
    silently reuse a jaxpr traced for the wrong axis sizes. Keys
    carrying a FaultPlan OR an AckWindowKey are skipped: a faulted or
    acked program is a DIFFERENT program (extra args / an extra ack
    ppermute per round), and the analysis gates must always see the
    flags-off one — the PR 8 cache-poisoning class, pinned for the ack
    flavor by tests/test_delta_opt.py."""
    from ..delta_opt.ackwin import AckWindowKey
    from ..faults import FaultPlan
    from ..parallel import anti_entropy as ae
    from ..parallel.wire import WireKey

    def mesh_matches(key_mesh) -> bool:
        if mesh is None:
            return True
        return (getattr(key_mesh, "shape", None) is not None
                and tuple(key_mesh.shape.items())
                == tuple(mesh.shape.items()))

    hits = [
        fn for key, fn in ae._FN_CACHE.items()
        if key[0] == kind and key[3] == tuple(range(n_donated))
        and mesh_matches(key[1])
        and not any(
            # A faulted / acked / fused-OFF run is a DIFFERENT traced
            # program; reading it back here would poison the gates'
            # view of the default entry (the PR 8/9 class — WireKey is
            # the fused-wire pin, tests/test_wire.py).
            isinstance(x, (FaultPlan, AckWindowKey, WireKey))
            for x in key[4:]
        )
    ]
    return hits[-1] if hits else None


def _default_mesh():
    from ..parallel import make_mesh

    n = len(jax.devices())
    p = max(n // 2, 1)
    return make_mesh(p, n // p)


# Memoised entry traces, keyed on mesh shape: the jit-lint and the cost
# gate both walk every entry's jaxpr — trace the fleet once per process.
_TRACE_CACHE: dict = {}


def entry_jaxprs(mesh=None, names: Optional[Sequence[str]] = None):
    """``{name: (entry, closed_jaxpr, donated_avals)}`` for the
    registered mesh entry points, invoking each once so the memoised
    jit exists, then tracing the cached fn. Entries that fail to
    invoke/trace map to ``(entry, exception, ())`` — callers turn those
    into findings. Results are memoised per (mesh shape, name)."""
    from .registry import entry_points

    if mesh is None:
        mesh = _default_mesh()
    mesh_key = tuple(mesh.shape.items())

    out = {}
    for ep in entry_points():
        if names is not None and ep.name not in names:
            continue
        key = (mesh_key, ep.name)
        if key not in _TRACE_CACHE:
            try:
                ep.invoke(mesh, ep.make_args(mesh))
                fn = _cached_entry_fn(ep.kind, ep.n_donated, mesh)
                if fn is None:
                    raise LookupError(
                        f"no cached jit for kind {ep.kind!r} after "
                        "invoking — registration out of sync with the "
                        "entry's cache key"
                    )
                args = ep.make_args(mesh)
                donated = tuple(
                    (np.shape(leaf), np.asarray(leaf).dtype)
                    for a in args[:ep.n_donated]
                    for leaf in jax.tree.leaves(a)
                )
                closed = jax.make_jaxpr(fn)(*args)
                _TRACE_CACHE[key] = (ep, closed, donated)
            except Exception as exc:  # broken entry -> finding, loudly
                _TRACE_CACHE[key] = (ep, exc, ())
        out[ep.name] = _TRACE_CACHE[key]
    return out


def lint_entry_points(mesh=None, names: Optional[Sequence[str]] = None
                      ) -> List[Finding]:
    """Lint every registered mesh entry point's jaxpr (running each once
    so the memoised jit exists). Unregistered-but-discoverable entry
    points are findings too — the registry is the coverage contract."""
    from .registry import unregistered_entry_points

    findings: List[Finding] = []
    for name in unregistered_entry_points():
        findings.append(Finding(
            "unregistered-entry", name,
            "public mesh entry point is not registered with "
            "crdt_tpu.analysis.registry — the static gates cannot see it",
        ))

    if mesh is None:
        mesh = _default_mesh()
    axis_sizes = dict(mesh.shape)

    for name, (ep, closed, donated) in entry_jaxprs(mesh, names).items():
        if isinstance(closed, Exception):
            check = ("entry-cache" if isinstance(closed, LookupError)
                     else "entry-error")
            findings.append(Finding(
                check, name, f"{type(closed).__name__}: {closed}",
            ))
            continue
        findings += lint_jaxpr(
            closed, name, donated,
            axis_sizes=axis_sizes, allowed_axes=ep.mesh_axes,
        )
    return findings


# ---- δ digest-gate soundness (the gate fixtures) --------------------------
#
# Three committed packet slots per flavor, spanning the decision table:
#
#   slot 0  removal-carrying, digest-covered  -> MUST ship (soundness:
#           a top digest can never prove the receiver knows a removal —
#           the unsoundness PR 3's wider gate hit by runtime test)
#   slot 1  add-only, digest-covered          -> MUST be masked (an
#           always-keep gate is dead weight — the efficiency half)
#   slot 2  add-only, NOT covered             -> MUST ship (masking
#           undelivered content is silent data loss)

def _gate_verdicts(label: str, kept, masked_detail: str) -> List[Finding]:
    kept = np.asarray(kept)
    findings: List[Finding] = []
    if not bool(kept[0]):
        findings.append(Finding(
            "gate-removal-dropped", label,
            "a removal-carrying slot (context above its content's "
            "witness dots) was masked by a top digest — a digest can "
            "never prove the receiver knows a removal; this gate "
            "resurrects removed entries under partition/replay",
        ))
    if bool(kept[1]):
        findings.append(Finding(
            "gate-mask-ineffective", label, masked_detail,
        ))
    if not bool(kept[2]):
        findings.append(Finding(
            "gate-overmask", label,
            "an uncovered add-only slot (content above the receiver's "
            "digest) was masked — undelivered content dropped on the "
            "wire, replicas cannot converge",
        ))
    return findings


def check_orswot_gate(gate, label: str = "delta.gate_delta"
                      ) -> List[Finding]:
    """Prove one orswot-flavor δ digest gate removal-preserving (and
    actually masking) on the committed three-slot fixture."""
    import jax.numpy as jnp

    from ..ops.orswot import DTYPE
    from ..parallel.delta import DeltaPacket

    pkt = DeltaPacket(
        idx=jnp.arange(3, dtype=jnp.int32),
        rows=jnp.array([[1, 0], [1, 0], [7, 0]], DTYPE),
        ctxs=jnp.array([[2, 0], [1, 0], [7, 0]], DTYPE),
        valid=jnp.ones((3,), bool),
        dcl=jnp.zeros((2, 2), DTYPE),
        dmask=jnp.zeros((2, 4), bool),
        dvalid=jnp.zeros((2,), bool),
    )
    digest = jnp.array([5, 5], DTYPE)
    out = gate(pkt, digest)
    return _gate_verdicts(
        label, out.valid,
        "a digest-covered add-only slot (ctx == rows <= digest) was NOT "
        "masked — the gate never strips redundant payload, so digest "
        "gating is dead weight on the wire",
    )


def check_map_gate(gate, label: str = "delta_map.gate_delta_map"
                   ) -> List[Finding]:
    """The map-flavor twin: knowledge is the content slots' witness
    dots (`delta_map._key_knowledge`), not raw rows."""
    import jax.numpy as jnp

    from ..ops.mvreg import empty as mv_empty
    from ..ops.orswot import DTYPE
    from ..parallel.delta_map import MapDeltaPacket

    child = mv_empty(2, 2, batch=(3,))
    wctr = jnp.array([1, 1, 7], DTYPE)
    child = child._replace(
        wctr=child.wctr.at[:, 0].set(wctr),
        clk=child.clk.at[:, 0, 0].set(wctr),
        valid=child.valid.at[:, 0].set(True),
    )  # per-key knowledge: [[1,0], [1,0], [7,0]]
    pkt = MapDeltaPacket(
        idx=jnp.arange(3, dtype=jnp.int32),
        child=child,
        ctxs=jnp.array([[2, 0], [1, 0], [7, 0]], DTYPE),
        valid=jnp.ones((3,), bool),
        dcl=jnp.zeros((2, 2), DTYPE),
        dkeys=jnp.zeros((2, 4), bool),
        dvalid=jnp.zeros((2,), bool),
    )
    out = gate(pkt, jnp.array([5, 5], DTYPE))
    return _gate_verdicts(
        label, out.valid,
        "a digest-covered add-only key (ctx == witness knowledge <= "
        "digest) was NOT masked — the map gate never strips redundant "
        "payload",
    )


def check_nested_lift(label: str = "delta_nest.nested_gate"
                      ) -> List[Finding]:
    """The nested lift must gate ONLY the core packet and pass the
    level's parked-keyset buffer through bit-identically — parked rm
    clocks are their own context; gating them would drop removal
    knowledge mid-ring."""
    import jax.numpy as jnp

    from ..ops.orswot import DTYPE
    from ..parallel.delta import DeltaPacket, gate_delta
    from ..parallel.delta_nest import NestedDeltaPacket, nested_gate

    core = DeltaPacket(
        idx=jnp.arange(3, dtype=jnp.int32),
        rows=jnp.array([[1, 0], [1, 0], [7, 0]], DTYPE),
        ctxs=jnp.array([[2, 0], [1, 0], [7, 0]], DTYPE),
        valid=jnp.ones((3,), bool),
        dcl=jnp.zeros((2, 2), DTYPE),
        dmask=jnp.zeros((2, 4), bool),
        dvalid=jnp.zeros((2,), bool),
    )
    dcl = jnp.array([[3, 1], [0, 2]], DTYPE)
    dkeys = jnp.array([[True, False], [False, True]])
    dvalid = jnp.array([True, True])
    pkt = NestedDeltaPacket(core, dcl, dkeys, dvalid)
    digest = jnp.array([5, 5], DTYPE)

    out = nested_gate(gate_delta)(pkt, digest)
    findings = _gate_verdicts(
        label, out.core.valid,
        "the lifted core gate stopped masking covered add-only slots",
    )
    want = gate_delta(core, digest)
    if bool(np.any(np.asarray(out.core.valid)
                   != np.asarray(want.valid))):
        findings.append(Finding(
            "gate-nested-core", label,
            "the lift changed the core gate's verdicts — nested_gate "
            "must be semantics-preserving on the core packet",
        ))
    for name, got, wanted in (
        ("dcl", out.dcl, dcl), ("dkeys", out.dkeys, dkeys),
        ("dvalid", out.dvalid, dvalid),
    ):
        if bool(np.any(np.asarray(got) != np.asarray(wanted))):
            findings.append(Finding(
                "gate-nested-buffer", label,
                f"the parked-keyset buffer leaf {name!r} was modified "
                "by the lift — parked rm clocks must ride whole",
            ))
    return findings


def check_gates() -> List[Finding]:
    """All registered δ digest-gate flavors, proven on the committed
    gate fixtures (tools/run_static_checks.py `collectives`)."""
    from ..parallel.delta import gate_delta
    from ..parallel.delta_map import gate_delta_map

    return (
        check_orswot_gate(gate_delta)
        + check_map_gate(gate_delta_map)
        + check_nested_lift()
    )
