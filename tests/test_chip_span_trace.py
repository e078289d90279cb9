"""A profiler trace recorded on a v5e chip with the program's spans on
(``tools/record_span_trace.py``: two ``ServeLoop.step`` calls under the
benchmark's ``span.step``, one ``FanoutPlane.push`` under ``span.push``
and one ``mesh_fold`` under ``span.converge``, in a ``bench_window``).
The program's spans sit on the host plane, nested inside the
benchmark's ``span.*`` events, on the clock of the device ops."""

import os

import pytest

PATH = os.path.join(os.path.dirname(__file__), "data", "v5e_spans.xplane.pb")
PROGRAM = ("serve.", "fanout.", "ae.")


@pytest.fixture(scope="module")
def trace():
    """Host events as ``(line, name, start, end)`` and the chip's op
    events as ``(name, start, end)``, in nanoseconds."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(PATH)
    host, ops = [], []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [(line.name, e.name, e.start_ns, e.end_ns)
                         for e in line.events]
        elif plane.name.startswith("/device:TPU:") and "Core" not in plane.name:
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops += [(e.name, e.start_ns, e.end_ns) for e in line.events]
    return host, ops


def _named(host, name):
    return [e for e in host if e[1] == name]


def _inside(inner, outer):
    return outer[2] <= inner[2] and inner[3] <= outer[3]


def test_recording_is_small():
    assert os.path.getsize(PATH) <= 100 * 1024


def test_program_spans_nest_inside_the_benchmarks_spans(trace):
    host, _ = trace
    program = [e for e in host if e[1].startswith(PROGRAM)]
    bench = [e for e in host if e[1].startswith("span.")]
    assert program and bench
    for e in program:
        assert any(b[0] == e[0] and _inside(e, b) for b in bench), e
    where = {"serve.step": "span.step", "fanout.push": "span.push",
             "ae.fold": "span.converge"}
    for name, outer in where.items():
        assert [any(_inside(e, b) for b in _named(host, outer))
                for e in _named(host, name)] == [True] * (2 if name == "serve.step" else 1)
    # Every child lies inside one parent of its layer.
    for child, parent in [("serve.wal_commit", "serve.step"),
                          ("serve.finish", "serve.step"),
                          ("fanout.snapshot", "fanout.push"),
                          ("fanout.dispatch", "fanout.push"),
                          ("fanout.wire_fetch", "fanout.dispatch"),
                          ("ae.fold.wait", "ae.fold")]:
        kids = _named(host, child)
        assert kids, child
        assert all(any(_inside(k, p) for p in _named(host, parent))
                   for k in kids), child


def test_program_spans_share_the_device_ops_clock(trace):
    """The profiler puts the chip's ops on the host's clock, aligned to
    within a millisecond in this recording: the fold's 10 µs device
    program reads 0.58 ms before its dispatch span opens."""
    host, ops = trace
    skew = 1e6

    def during(op, span):
        return span[2] - skew <= op[1] and op[2] <= span[3] + skew

    [window] = _named(host, "bench_window")
    assert ops and all(during(o, window) for o in ops)
    # The wire kernel, named in the trace, runs inside the push dispatch
    # that waits for its wire.
    wire = [o for o in ops if o[0].startswith("%wire_pack")]
    [dispatch] = _named(host, "fanout.dispatch")
    assert wire and all(during(o, dispatch) for o in wire)
    fold = [o for o in ops if o[0].startswith("%_fold_fused_jit")]
    [entry] = _named(host, "ae.fold")
    assert fold and all(during(o, entry) for o in fold)
    # The wire kernel runs after the push has stacked its base rows and
    # ends before the fetch of its wire does.
    [stack], [fetch] = _named(host, "fanout.stack"), _named(host, "fanout.wire_fetch")
    assert all(stack[3] < o[1] and o[2] < fetch[3] for o in wire)
