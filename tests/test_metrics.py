"""The host observability registry (crdt_tpu/utils/metrics.py): thread
safety, snapshot serializability, the deferred-depth walker across the
state families, and the two blindness-visibility satellites (traced
depth skips and profile_trace start failures are COUNTED, not silent).
"""

import json
import threading

import jax
import jax.numpy as jnp

from crdt_tpu.ops import map_map as mm_ops
from crdt_tpu.ops import orswot as ops
from crdt_tpu.ops import sparse_orswot as sp
from crdt_tpu.utils.metrics import (
    Metrics,
    deferred_depth,
    metrics,
    observe_depth,
    profile_trace,
    state_nbytes,
)


def test_registry_thread_safety():
    m = Metrics()
    n_threads, n_iter = 8, 500

    def work(tid):
        for i in range(n_iter):
            m.count("t.counter")
            m.count("t.counter_by", 3)
            m.observe("t.gauge", float(tid * n_iter + i))

    threads = [
        threading.Thread(target=work, args=(t,)) for t in range(n_threads)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    snap = m.snapshot()
    assert snap["counters"]["t.counter"] == n_threads * n_iter
    assert snap["counters"]["t.counter_by"] == 3 * n_threads * n_iter
    g = snap["gauges"]["t.gauge"]
    assert g["n"] == n_threads * n_iter
    assert g["min"] == 0.0
    assert g["max"] == float(n_threads * n_iter - 1)


def test_snapshot_json_serializable_and_detached():
    m = Metrics()
    m.count("a.b.c", 2)
    m.observe("d.e", 1.5)
    with m.time("f"):
        pass
    snap = m.snapshot()
    rt = json.loads(json.dumps(snap))  # strict JSON round trip
    assert rt["counters"]["a.b.c"] == 2
    assert rt["gauges"]["d.e"]["last"] == 1.5
    assert "f_seconds" in rt["gauges"]
    # The snapshot is a copy, not a live view.
    m.count("a.b.c")
    assert snap["counters"]["a.b.c"] == 2


def test_deferred_depth_dense():
    state = ops.empty(4, 2, deferred_cap=4, batch=(3,))
    assert deferred_depth(state) == 0.0
    dvalid = jnp.asarray(
        [[True, False, False, False],
         [True, True, False, False],
         [False, False, False, False]]
    )
    assert deferred_depth(state._replace(dvalid=dvalid)) == 2.0


def test_deferred_depth_sparse():
    state = sp.empty(8, 2, deferred_cap=4, rm_width=2, batch=(2,))
    assert deferred_depth(state) == 0.0
    dvalid = jnp.asarray([[True, False, False, False],
                          [True, True, True, False]])
    assert deferred_depth(state._replace(dvalid=dvalid)) == 3.0


def test_deferred_depth_nested_sums_buffer_levels():
    # Map<K1, Map<K2, MVReg>>: inner-map dvalid + outer odvalid both
    # end in "dvalid", so the walker sums ACROSS levels per replica.
    state = mm_ops.empty(2, 2, 2, 2, 3, batch=(2,))
    inner = jnp.asarray([[True, True, False], [True, False, False]])
    outer = jnp.asarray([[True, False, False], [False, False, False]])
    state = state._replace(
        m=state.m._replace(dvalid=inner), odvalid=outer
    )
    assert deferred_depth(state) == 3.0  # replica 0: 2 inner + 1 outer


def test_traced_depth_skip_is_counted():
    state = ops.empty(4, 2, batch=(2,))
    key = "anti_entropy.depth_skipped_traced"
    before = metrics.snapshot()["counters"].get(key, 0)
    seen = {}

    @jax.jit
    def step(s):
        seen["depth"] = deferred_depth(s)  # trace-time host call
        observe_depth("test_traced", s)    # must record nothing
        return s.top

    step(state)
    assert seen["depth"] == -1.0  # the documented traced sentinel
    after = metrics.snapshot()
    # Two skips: deferred_depth directly + via observe_depth.
    assert after["counters"].get(key, 0) == before + 2
    assert "test_traced.deferred_depth" not in after["gauges"]


def test_concrete_depth_still_recorded():
    state = ops.empty(4, 2, batch=(2,))
    observe_depth("test_concrete", state)
    g = metrics.snapshot()["gauges"]["test_concrete.deferred_depth"]
    assert g["last"] == 0.0


def test_profile_trace_start_failure_is_counted(monkeypatch, tmp_path):
    def boom(logdir):
        raise RuntimeError("no profiler backend")

    monkeypatch.setattr(jax.profiler, "start_trace", boom)
    key = "profile_trace.start_failed"
    before = metrics.snapshot()["counters"].get(key, 0)
    ran = False
    with profile_trace(str(tmp_path)):
        ran = True  # the block must still run
    assert ran
    assert metrics.snapshot()["counters"].get(key, 0) == before + 1
    # Second failure counts again (only the log line is once-only).
    with profile_trace(str(tmp_path)):
        pass
    assert metrics.snapshot()["counters"].get(key, 0) == before + 2


def test_state_nbytes_matches_numpy():
    state = ops.empty(4, 2, deferred_cap=4, batch=(3,))
    import numpy as np

    assert state_nbytes(state) == sum(
        np.asarray(x).nbytes for x in jax.tree.leaves(state)
    )


def test_deferred_depth_counts_all_buffer_levels():
    from crdt_tpu.ops import map3 as map3_ops

    st = map3_ops.empty(2, 2, 2, 4, deferred_cap=3, batch=(5,))
    assert deferred_depth(st) == 0.0
    # Mark parked slots at two different nesting levels of one replica
    # and one at another: max-per-replica must see the 2-slot replica.
    st = st._replace(odvalid=st.odvalid.at[1, 0].set(True))
    st = st._replace(
        mo=st.mo._replace(kdvalid=st.mo.kdvalid.at[1, 1].set(True))
    )
    st = st._replace(
        mo=st.mo._replace(
            core=st.mo.core._replace(
                dvalid=st.mo.core.dvalid.at[3, 2].set(True)
            )
        )
    )
    assert deferred_depth(st) == 2.0  # replica 1 holds two live slots

    metrics.reset()
    observe_depth("t", st)
    snap = metrics.snapshot()
    assert snap["gauges"]["t.deferred_depth"]["last"] == 2.0


def test_anti_entropy_records_depth_and_merges():

    from crdt_tpu.models import BatchedOrswot
    from crdt_tpu.parallel.anti_entropy import mesh_fold
    from crdt_tpu.parallel.mesh import make_mesh

    metrics.reset()
    mesh = make_mesh(4, 2)
    m = BatchedOrswot(4, 16, 4, 2)
    mesh_fold(m.state, mesh)
    snap = metrics.snapshot()
    assert snap["counters"]["anti_entropy.merges"] >= 3
    assert "anti_entropy.orswot_fold.deferred_depth" in snap["gauges"]
