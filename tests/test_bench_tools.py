"""Format tests for bench.py's host-side tooling: the automerge-perf
trace loader (BASELINE config 5 — the REAL trace format, loadable
whenever a copy of ``edit-by-index/trace.json`` is dropped on the box)
and the metrics deferred-depth gauge (SURVEY §6.5's missing metric)."""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


import pytest

import bench
from crdt_tpu.native import DELETE, INSERT
from crdt_tpu.pure.list import List
from crdt_tpu.utils.metrics import deferred_depth, metrics, observe_depth


def test_automerge_trace_loader_format(tmp_path):
    # The published format: [position, n_deleted, inserted_string...].
    edits = [
        [0, 0, "h", "i"],       # insert "hi"
        [2, 0, " there"],       # append a multi-char chunk
        [0, 1],                 # delete the "h"
        [1, 2, "X"],            # replace two chars with "X"
    ]
    p = tmp_path / "trace.json"
    p.write_text(json.dumps(edits))
    kinds, idxs, vals, actors = bench.load_automerge_trace(str(p), n_actors=3)

    # Replay through the oracle: the loader's flattening must reproduce
    # the document the edit script describes.
    doc = List()
    for k, ix, v, a in zip(kinds, idxs, vals, actors):
        op = (
            doc.insert_index(ix, v, a)
            if k == INSERT
            else doc.delete_index(ix, a)
        )
        doc.apply(op)
    text = "".join(chr(v) for v in doc.read())
    assert text == "iX" + "here"  # "hi there" -> "i there" -> "iXhere"

    assert set(actors) <= {0, 1, 2}
    assert all(k in (INSERT, DELETE) for k in kinds)


def test_automerge_trace_loader_limit(tmp_path):
    p = tmp_path / "trace.json"
    p.write_text(json.dumps([[0, 0, "abcdefgh"]]))
    kinds, idxs, vals, actors = bench.load_automerge_trace(str(p), limit=3)
    assert len(kinds) == len(idxs) == len(vals) == len(actors) == 3
    assert vals == [ord("a"), ord("b"), ord("c")]


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


def test_bench_runs_on_cpu_only_when_asked(monkeypatch):
    # JAX_PLATFORMS=cpu: the CPU run, labeled as such.
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.delenv("BENCH_CPU_FALLBACK", raising=False)
    dev = bench.device_info()
    assert dev["platform"] == "cpu" and dev["device_count"] == 8
    assert os.environ["BENCH_CPU_FALLBACK"] == "1"
    # No TPU and no request for the CPU: exit non-zero, no fallback.
    monkeypatch.delenv("JAX_PLATFORMS")
    with pytest.raises(SystemExit) as exc:
        bench.device_info()
    assert exc.value.code != 0


@pytest.mark.parametrize(
    "flag", ["--quick-comms", "--reclaim", "--chaos", "--heal", "--recovery",
             "--scaleout"],
)
def test_single_leg_mode_on_one_device_prints_skipped(flag, monkeypatch,
                                                        capsys):
    # One chip: these legs need a ring (>= 2 devices, scaleout >= 4) and
    # return no record; the mode still exits cleanly with one line.
    import jax

    one = jax.devices()[:1]
    dev = {"platform": "cpu", "device_kind": one[0].device_kind,
           "device_count": 1}
    monkeypatch.setattr(jax, "devices", lambda *a, **k: one)
    monkeypatch.setattr(bench, "device_info", lambda: dev)
    bench.main([flag])
    rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rec["skipped"] is True and rec["device"] == dev
    want = "comms" if flag == "--quick-comms" else flag[2:]
    assert rec["config"] == want


def test_compile_cache_placement(monkeypatch, tmp_path):
    import jax

    from crdt_tpu.utils import compile_cache

    # Set from outside: used as is, and no path is set in code.
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.setattr(jax.config, "update", lambda k, v: updates.append(k))
    updates = []
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert "jax_compilation_cache_dir" not in updates
    # Unset: the fixed path under the checkout, exported to children.
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    want = os.path.join(root, ".jax_cache")
    assert compile_cache.enable_compile_cache() == want
    assert os.environ["JAX_COMPILATION_CACHE_DIR"] == want
    assert "jax_compilation_cache_dir" in updates


def test_bench_configs_contract():
    """BENCH_CONFIGS.json is the COMMITTED shape source of truth the
    sparse/flagship legs and chip_smoke.py share — this pins
    the keys those consumers read, so an edit that drops one fails here
    instead of at replay time on hardware."""
    cfgs = bench.bench_configs()
    for leg, keys in (
        ("sparse", ("replicas", "dot_cap", "universe", "passes")),
        ("sparse_map",
         ("replicas", "cell_cap", "universe", "sibling_cap", "passes")),
        ("flagship",
         ("replicas", "universe", "segment_cap", "block_rows", "actors",
          "mesh")),
    ):
        assert leg in cfgs, leg
        for key in keys:
            assert key in cfgs[leg], f"{leg}.{key}"
    # the flagship entry IS the metric-of-record shape — and every
    # shape knob it declares must actually be read by bench_flagship
    # (the replay-verbatim contract), actors included
    assert cfgs["flagship"]["replicas"] == 10240
    assert cfgs["flagship"]["universe"] == 1_000_000
    assert cfgs["flagship"]["actors"] == 8
    # the CPU stand-in must scale the replica count too, or the default
    # no-TPU bench run streams all 10,240 replicas through ~13 passes
    assert cfgs["flagship"]["cpu_fallback"]["replicas"] <= 2048
    # env > cpu_fallback > committed value precedence
    assert bench._cfg("sparse", "dot_cap", "NOPE_UNSET_ENV") == 4096
    assert bench._cfg(
        "sparse", "dot_cap", "NOPE_UNSET_ENV", cpu_fallback=True
    ) == 512
    os.environ["NOPE_SET_ENV"] = "77"
    try:
        assert bench._cfg(
            "sparse", "dot_cap", "NOPE_SET_ENV", cpu_fallback=True
        ) == 77
    finally:
        del os.environ["NOPE_SET_ENV"]
