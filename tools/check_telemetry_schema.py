#!/usr/bin/env python
"""Validate observability exports against the committed schema.

``tools/telemetry_schema.json`` is the contract for everything the
exporter emits: JSONL lines from ``crdt_tpu.exporter.drain_jsonl``
(snapshot / telemetry / span records) and bare registry snapshots
(``metrics.snapshot()``). This checker is
deliberately dependency-free (no jsonschema on the CI image) and runs
as a fast tier-1 test (tests/test_telemetry_schema.py), so exporter
drift — a renamed field, a stringly-typed counter, a NaN smuggled into
a gauge — fails CI instead of silently corrupting trajectories.

CLI::

    python tools/check_telemetry_schema.py out.jsonl [more.jsonl ...]

exits non-zero listing every violating line. Importable surface:
``validate_record`` / ``validate_snapshot`` / ``validate_jsonl``.
"""

from __future__ import annotations

import json
import math
import os
import sys
from typing import Any, List

SCHEMA_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "telemetry_schema.json")


def load_schema(path: str = SCHEMA_PATH) -> dict:
    with open(path) as f:
        return json.load(f)


def _is_int(v: Any) -> bool:
    return isinstance(v, int) and not isinstance(v, bool)


def _is_number(v: Any) -> bool:
    # Strict JSON numbers only: bools are ints in Python but not
    # numbers here, and NaN/inf do not survive strict JSON round-trips.
    return (
        (_is_int(v) or isinstance(v, float))
        and not isinstance(v, bool)
        and math.isfinite(v)
    )


def _check(value: Any, kind: str, where: str, schema: dict) -> List[str]:
    errs: List[str] = []
    if kind == "string":
        if not isinstance(value, str):
            errs.append(f"{where}: expected string, got {type(value).__name__}")
    elif kind == "int":
        if not _is_int(value):
            errs.append(f"{where}: expected int, got {value!r}")
    elif kind == "number":
        if not _is_number(value):
            errs.append(f"{where}: expected finite number, got {value!r}")
    elif kind == "string_or_null":
        if value is not None and not isinstance(value, str):
            errs.append(f"{where}: expected string or null, got {value!r}")
    elif kind == "object":
        if not isinstance(value, dict):
            errs.append(f"{where}: expected object, got {type(value).__name__}")
    elif kind == "histogram":
        errs += _check_histogram(value, where)
    elif kind.startswith("array:"):
        inner = kind.split(":", 1)[1]
        if not isinstance(value, list):
            errs.append(
                f"{where}: expected array, got {type(value).__name__}"
            )
        else:
            for i, v in enumerate(value):
                errs += _check(v, inner, f"{where}[{i}]", schema)
    elif kind == "gauge":
        if not isinstance(value, dict):
            errs.append(f"{where}: expected gauge object, got {value!r}")
        else:
            for field, fkind in schema["gauge"].items():
                if field not in value:
                    errs.append(f"{where}.{field}: missing")
                else:
                    errs += _check(value[field], fkind, f"{where}.{field}", schema)
    elif kind.startswith("map:"):
        inner = kind.split(":", 1)[1]
        if not isinstance(value, dict):
            errs.append(f"{where}: expected object, got {type(value).__name__}")
        else:
            for k, v in value.items():
                if not isinstance(k, str):
                    errs.append(f"{where}: non-string key {k!r}")
                errs += _check(v, inner, f"{where}[{k!r}]", schema)
    else:  # schema bug, not data bug — still surface it
        errs.append(f"{where}: unknown schema kind {kind!r}")
    return errs


def _check_histogram(value: Any, where: str) -> List[str]:
    """Structural validation of one serialized histogram (the
    ``hist_*`` Telemetry fields — crdt_tpu/obs/hist.py ``to_dict``
    form): strictly-ascending finite edges, non-negative int counts
    EXACTLY one longer than the edges (the trailing count is the
    unbounded +Inf bucket), finite total. Stricter than the generic
    ``array:`` kinds because a shape-valid histogram with mismatched
    lengths silently mis-renders every quantile downstream."""
    errs: List[str] = []
    if not isinstance(value, dict):
        return [f"{where}: expected histogram object, got "
                f"{type(value).__name__}"]
    edges = value.get("edges")
    counts = value.get("counts")
    total = value.get("total")
    if not isinstance(edges, list) or not all(
        _is_number(e) for e in edges
    ):
        errs.append(f"{where}.edges: expected array of finite numbers")
    elif any(b <= a for a, b in zip(edges, edges[1:])):
        errs.append(f"{where}.edges: must be strictly ascending")
    if not isinstance(counts, list) or not all(
        _is_int(c) and c >= 0 for c in counts
    ):
        errs.append(
            f"{where}.counts: expected array of non-negative ints"
        )
    elif isinstance(edges, list) and len(counts) != len(edges) + 1:
        errs.append(
            f"{where}.counts: expected {len(edges) + 1} buckets "
            f"(len(edges) + 1, the last unbounded), got {len(counts)}"
        )
    if not _is_number(total):
        errs.append(f"{where}.total: expected finite number")
    return errs


def validate_record(rec: Any, schema: dict = None) -> List[str]:
    """Errors for one JSONL record (empty list = valid)."""
    schema = schema or load_schema()
    if not isinstance(rec, dict):
        return [f"record is {type(rec).__name__}, expected object"]
    rtype = rec.get("record")
    fields = schema["records"].get(rtype)
    if fields is None:
        return [
            f"unknown record type {rtype!r} "
            f"(schema knows {sorted(schema['records'])})"
        ]
    errs: List[str] = []
    for field, kind in fields.items():
        if field not in rec:
            errs.append(f"{rtype}.{field}: missing")
        else:
            errs += _check(rec[field], kind, f"{rtype}.{field}", schema)
    return errs


def validate_snapshot(snap: Any, schema: dict = None) -> List[str]:
    """Errors for a bare ``metrics.snapshot()`` dict (the bench
    headline's ``metrics`` field) — the snapshot record's payload
    without the envelope."""
    schema = schema or load_schema()
    if not isinstance(snap, dict):
        return [f"snapshot is {type(snap).__name__}, expected object"]
    errs: List[str] = []
    errs += _check(snap.get("counters", None), "map:int",
                   "snapshot.counters", schema)
    errs += _check(snap.get("gauges", None), "map:gauge",
                   "snapshot.gauges", schema)
    return errs


def validate_jsonl(path: str, schema: dict = None) -> List[str]:
    """Errors for a whole export file, prefixed ``line N:``."""
    schema = schema or load_schema()
    errs: List[str] = []
    with open(path) as f:
        for n, line in enumerate(f, 1):
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except ValueError as exc:
                errs.append(f"line {n}: not JSON ({exc})")
                continue
            errs += [f"line {n}: {e}" for e in validate_record(rec, schema)]
    return errs


def main(argv: List[str]) -> int:
    if not argv:
        print(__doc__)
        return 2
    schema = load_schema()
    failed = False
    for path in argv:
        errs = validate_jsonl(path, schema)
        if errs:
            failed = True
            print(f"{path}: {len(errs)} schema violation(s)")
            for e in errs[:50]:
                print(f"  {e}")
        else:
            print(f"{path}: OK")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
