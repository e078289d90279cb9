"""Compile the replica cells' programs for a described v5e at their real
sizes, without a chip, and check they fit one chip's memory: the check
that fixed 1,280 rows per chip for the four-chip cell (PERF.md)."""

import json
import os

import pytest

HBM = 16 * 1024**3


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _used(compiled):
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


@pytest.mark.parametrize("cell", ["replicas_fold_1chip", "replicas_ring_4chip"])
def test_replica_cell_fits_one_chip(topo, monkeypatch, cell):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from crdt_tpu.ops import orswot as ops
    from crdt_tpu.ops import pallas_kernels
    from crdt_tpu.parallel import make_mesh, mesh_fold
    from crdt_tpu.parallel.delta import mesh_delta_gossip
    from crdt_tpu.parallel.mesh import ELEMENT_AXIS, REPLICA_AXIS, orswot_specs
    from tiny import bench

    monkeypatch.setattr(pallas_kernels, "_fused_backend", lambda: True)
    b = bench()
    work = next(w for w in b["workloads"] if w["name"] == cell)
    conf = next(c for c in b["configs"] if c["name"] == work["config"])
    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    cfg = json.load(open(os.path.join(root, conf["file"])))
    tr = json.load(open(os.path.join(root, "benchmark", "traffic", f"{cell}.json")))
    n = work["chips"]
    mesh = make_mesh(n, 1, devices=topo.devices[:n])
    r, e, a, d = cfg["replicas"] * n, cfg["elems"], cfg["actors"], cfg["deferred_cap"]
    shard = lambda spec: NamedSharding(mesh, spec)  # noqa: E731
    st = jax.tree.map(
        lambda s, p: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=shard(p)),
        jax.eval_shape(lambda: ops.empty(e, a, d, batch=(r,))), orswot_specs(),
        is_leaf=lambda x: isinstance(x, P))
    if tr["entry"] == "mesh_fold":
        compiled = jax.jit(lambda s: mesh_fold(s, mesh)[0]).lower(st).compile()
    else:
        dirty = jax.ShapeDtypeStruct((r, e), jnp.bool_,
                                     sharding=shard(P(REPLICA_AXIS, ELEMENT_AXIS)))
        fctx = jax.ShapeDtypeStruct((r, e, a), jnp.uint32,
                                    sharding=shard(P(REPLICA_AXIS, ELEMENT_AXIS, None)))
        compiled = jax.jit(lambda s, dm, f: mesh_delta_gossip(
            s, dm, f, mesh, rounds=tr["ring_rounds"], cap=tr["cap"],
            pipeline=tr["pipeline"], fused=True)[0]).lower(st, dirty, fctx).compile()
    used = _used(compiled)
    print(f"{cell}: {used} bytes per chip")
    assert "tpu_custom_call" in compiled.as_text()
    assert used < HBM, used
