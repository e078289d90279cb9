"""From a profiler trace (``.xplane.pb``) to the numbers the per-layer
readers take: device busy time, per-op device time, collective time
that no compute hides, and what the host was doing in the longest idle
gaps.

The trace is read with ``jax.profiler.ProfileData`` into plain
``Event``s, and the arithmetic works on those alone, so
``benchmark/tests/test_trace_reduce.py`` can check it on events whose
answer is known by hand.
"""

from __future__ import annotations

import re
from typing import Dict, List, NamedTuple, Optional, Tuple

WINDOW = "bench_window"
SHORT = 160   # characters of an op's HLO text kept in the breakdown
# Ops that move data between chips. Async pairs show as *-start/*-done.
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|collective-permute|reduce-scatter|all-to-all"
    r"|ppermute|psum|send|recv", re.I)
# Device op lines, by the names the TPU profiler gives them.
OP_LINES = ("XLA Ops",)


class Event(NamedTuple):
    name: str
    start_ns: float
    end_ns: float
    detail: str = ""   # the op's long name / HLO text, where the trace has it


def _clip(ev: List[Event], lo: float, hi: float) -> List[Tuple[float, float]]:
    out = []
    for e in ev:
        a, b = max(e.start_ns, lo), min(e.end_ns, hi)
        if b > a:
            out.append((a, b))
    return out


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def length(intervals) -> float:
    return sum(b - a for a, b in intervals)


def subtract(xs, ys) -> List[Tuple[float, float]]:
    """Parts of the (merged) intervals ``xs`` that no (merged) interval
    of ``ys`` covers."""
    out, j = [], 0
    for a, b in xs:
        cur = a
        while j < len(ys) and ys[j][1] <= cur:
            j += 1
        k = j
        while k < len(ys) and ys[k][0] < b:
            if ys[k][0] > cur:
                out.append((cur, ys[k][0]))
            cur = max(cur, ys[k][1])
            k += 1
        if cur < b:
            out.append((cur, b))
    return out


def reduce_events(host: List[Event], devices: List[List[Event]],
                  window: Optional[Tuple[float, float]] = None) -> dict:
    """``host``: the host's annotation events (the ``bench_window`` and
    the benchmark's spans); ``devices``: each chip's op events. Returns
    seconds, averaged over chips where the name says so."""
    if window is None:
        w = [e for e in host if e.name == WINDOW]
        if not w:
            raise ValueError(f"no {WINDOW!r} annotation in the trace")
        window = (w[0].start_ns, w[0].end_ns)
    lo, hi = window
    busy, exposed, op_s, op_n, detail = [], [], {}, {}, {}
    gaps: Dict[str, float] = {}
    spans = [e for e in host if e.name != WINDOW]
    for ev in devices:
        ops = union(_clip(ev, lo, hi))
        busy.append(length(ops))
        coll = union(_clip([e for e in ev if COLLECTIVE.search(e.name)], lo, hi))
        comp = union(_clip([e for e in ev if not COLLECTIVE.search(e.name)], lo, hi))
        exposed.append(length(subtract(coll, comp)))
        for e in ev:
            d = length(_clip([e], lo, hi))
            if d:
                op_s[e.name] = op_s.get(e.name, 0.0) + d
                op_n[e.name] = op_n.get(e.name, 0) + 1
                detail.setdefault(e.name, e.detail)
        # Idle gaps, each put down to the host span that covers most of it.
        for a, b in subtract([(lo, hi)], ops):
            best, cover = "no_span", 0.0
            for s in spans:
                c = min(b, s.end_ns) - max(a, s.start_ns)
                if c > cover:
                    best, cover = s.name, c
            gaps[best] = gaps.get(best, 0.0) + (b - a)
    n = max(len(devices), 1)
    ns = 1e-9
    return {
        "window_s": (hi - lo) * ns,
        "devices": len(devices),
        "busy_s": sum(busy) / n * ns,
        "collective_exposed_s": sum(exposed) / n * ns,
        "op_s": {k: v * ns for k, v in op_s.items()},
        "op_n": op_n,
        "op_detail": detail,
        "idle_gaps_s": {k: v / n * ns for k, v in gaps.items()},
    }


def _stat_text(ev) -> str:
    return " ".join(f"{k}={v}" for k, v in ev.stats if isinstance(v, str))[:400]


def load(path: str) -> Tuple[List[Event], List[List[Event]]]:
    """Host annotation events and per-chip op events of one trace."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    host: List[Event] = []
    devices: List[List[Event]] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and "Core" not in plane.name:
            evs = []
            for line in plane.lines:
                if line.name in OP_LINES:
                    evs += [Event(e.name, e.start_ns, e.end_ns, _stat_text(e))
                            for e in line.events]
            devices.append(evs)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW or e.name.startswith("span."):
                        host.append(Event(e.name, e.start_ns, e.end_ns))
    return host, devices


def reduce(path: str) -> dict:
    host, devices = load(path)
    return reduce_events(host, devices)


def breakdown(red: dict, k: int = 10) -> dict:
    """The contract's ``breakdown``: the device ops that took most time
    and the longest idle gaps by what the host was doing."""
    ops = sorted(red["op_s"].items(), key=lambda kv: -kv[1])[:k]
    gaps = sorted(red["idle_gaps_s"].items(), key=lambda kv: -kv[1])[:k]
    return {"device_ops": [[n[:SHORT], s] for n, s in ops],
            "idle_gaps": [[n, s] for n, s in gaps]}


def op_time(red: dict, pattern: str) -> Optional[Tuple[float, int]]:
    """Device seconds and events (summed over chips) of the ops whose name
    or long name matches ``pattern``; None when the trace holds none."""
    rx = re.compile(pattern)
    hits = [n for n in red["op_s"]
            if rx.search(n) or rx.search(red["op_detail"].get(n, ""))]
    if not hits:
        return None
    return sum(red["op_s"][n] for n in hits), sum(red["op_n"][n] for n in hits)
