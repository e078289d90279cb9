"""Record a small profiler trace of the serving path and one fold with the
program's spans on, as a traced benchmark run records its window.

    python tools/record_span_trace.py OUT.xplane.pb

A store of 512 tenants at the ``orswot_tenants_resident`` widths (256
elements x 4 actors, deferred cap 2), every tenant resident, runs two
``ServeLoop.step`` calls under the benchmark's ``span.step`` and one
``FanoutPlane.push`` under ``span.push``; then ``mesh_fold`` folds 16
replicas of 1,024 x 8 under ``span.converge``. Everything compiles
before the profiler starts, so the trace holds the measured calls and
nothing else. The file keeps what the test reads (:func:`trim`): the
annotations on the host plane, and the chip's op and module lines with
each op under its HLO instruction name. ``tests/test_chip_span_trace.py``
reads the copy recorded on a v5e chip.
"""

from __future__ import annotations

import glob
import importlib.util
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "benchmark"), ROOT]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from common import Spans, Window  # noqa: E402  (the benchmark's own)

TENANTS, ELEMS, ACTORS = 512, 256, 4
ANNOTATIONS = ("bench_window", "span.", "serve.", "fanout.", "ae.")
DEVICE_LINES = ("XLA Ops", "XLA Modules")


def _xplane_pb2():
    """The ``XSpace`` protobuf classes, loaded from the copy TensorFlow
    installs without importing TensorFlow itself."""
    tf = importlib.util.find_spec("tensorflow")
    path = os.path.join(os.path.dirname(tf.origin), "tsl", "profiler",
                        "protobuf", "xplane_pb2.py")
    spec = importlib.util.spec_from_file_location("xplane_pb2", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def trim(data: bytes) -> bytes:
    """A recorded ``.xplane.pb`` cut to the host annotations and the
    chip's op and module lines. Kept events are unchanged, on their
    recorded clock; a device op's metadata keeps its HLO instruction
    name (``%wire_pack.1``) without the instruction's text."""
    pb = _xplane_pb2()
    space = pb.XSpace()
    space.ParseFromString(data)
    keep = [p for p in space.planes
            if p.name.startswith("/host:CPU") or p.name == "/device:TPU:0"]
    del space.planes[:]
    for plane in keep:
        meta = plane.event_metadata
        if plane.name.startswith("/host:"):
            wanted = {k for k, m in meta.items() if m.name.startswith(ANNOTATIONS)}
            lines = [line for line in plane.lines
                     if any(e.metadata_id in wanted for e in line.events)]
            for line in lines:
                evs = [e for e in line.events if e.metadata_id in wanted]
                del line.events[:]
                line.events.extend(evs)
        else:
            lines = [line for line in plane.lines if line.name in DEVICE_LINES]
            wanted = {e.metadata_id for line in lines for e in line.events}
            for k in wanted:
                m = meta[k]
                m.name = m.name.split(" = ")[0]
                m.display_name = m.display_name.split(" = ")[0]
                m.metadata = b""
                del m.stats[:]
        for k in [k for k in meta if k not in wanted]:
            del meta[k]
        del plane.lines[:]
        plane.lines.extend(lines)
        space.planes.add().CopyFrom(plane)
    return space.SerializeToString()


def _store(scratch):
    from crdt_tpu.fanout import FanoutPlane
    from crdt_tpu.parallel import make_mesh
    from crdt_tpu.serve import Evictor, IngestQueue, ServeLoop, Superblock
    from crdt_tpu.serve.wal import ServeWal

    caps = dict(n_elems=ELEMS, n_actors=ACTORS, deferred_cap=2)
    sb = Superblock(TENANTS, make_mesh(1, 1), kind="orswot", caps=caps,
                    n_lanes=TENANTS)
    ev = Evictor(sb, os.path.join(scratch, "snap"))
    wal = ServeWal(os.path.join(scratch, "wal"))
    q = IngestQueue(sb, lanes=256, depth=4, evictor=ev, wal=wal)
    plane = FanoutPlane(sb, evictor=ev, dispatch_lanes=256, capacity=TENANTS)
    plane.subscribe(np.arange(TENANTS))
    return q, ServeLoop(q), plane, wal


def _round(q, loop, plane, spans, rng, ctr):
    for t in rng.choice(TENANTS, 200, replace=False):
        ctr[t] += 1
        q.add(int(t), int(t) % ACTORS, int(ctr[t]), rng.random(ELEMS) < 0.03)
    for _ in range(2):
        with spans.span("span.step"):
            loop.step()
    with spans.span("span.push"):
        plane.push(tenants=np.arange(128))


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        print(__doc__, file=sys.stderr)
        return 2
    from crdt_tpu.ops import orswot as ops
    from crdt_tpu.parallel import make_mesh, mesh_fold

    scratch = tempfile.mkdtemp(prefix="span-trace-")
    try:
        rng = np.random.default_rng(23)
        q, loop, plane, wal = _store(scratch)
        mesh = make_mesh(1, 1)
        reps = ops.empty(1024, 8, 2, batch=(16,))
        reps = reps._replace(ctr=jnp.asarray(
            rng.integers(0, 3, reps.ctr.shape), reps.ctr.dtype))
        ctr = np.zeros(TENANTS, np.int64)
        warm = Spans(traced=False)
        for _ in range(2):   # compile every program the recording runs
            _round(q, loop, plane, warm, rng, ctr)
            jax.block_until_ready(mesh_fold(reps, mesh))
        spans = Spans(traced=True)
        trace_dir = os.path.join(scratch, "trace")
        with Window(True, trace_dir):
            _round(q, loop, plane, spans, rng, ctr)
            with spans.span("span.converge"):
                jax.block_until_ready(mesh_fold(reps, mesh))
        wal.close()
        [path] = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                        "*.xplane.pb"))
        with open(path, "rb") as f:
            data = trim(f.read())
        with open(argv[0], "wb") as f:
            f.write(data)
        print(f"{argv[0]}: {len(data)} bytes")
        return 0
    finally:
        shutil.rmtree(scratch, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
