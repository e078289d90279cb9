"""Compile the main path's Pallas kernels for a described v5e, at the
widths the deployments run. Nothing runs and no chip is needed: the TPU
compiler is installed here and refuses what the chip would refuse (an
unsupported primitive in a kernel, a block past VMEM, a program past
HBM). The topology is described inside a fixture, never at import, so
every test worker collects the same tests; only the worker that runs
this file loads the TPU library."""

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, SingleDeviceSharding

HBM_BYTES = 16 * 1024**3  # one v5e chip


@pytest.fixture(scope="module")
def topo():
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off meanwhile.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def tpu_dispatch(monkeypatch):
    """Steer the library's backend dispatch to its TPU branch (the
    process's own backend is the CPU)."""
    from crdt_tpu.ops import pallas_kernels

    monkeypatch.setattr(pallas_kernels, "_fused_backend", lambda: True)


def _shapes(make, sharding):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        jax.eval_shape(make),
    )


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert used < HBM_BYTES, used
    return compiled


def test_fold_fused_config3_chunk(one_chip):
    from crdt_tpu.ops import orswot as ops
    from crdt_tpu.ops.pallas_kernels import fold_fused

    st = _shapes(lambda: ops.empty(102400, 8, 4, batch=(512,)), one_chip)
    _compile(lambda s: fold_fused(s, interpret=False), st)


def test_fold_fused_map_config4(one_chip):
    """The ``map_mvreg_fold_1chip`` cell's fold: 64 replicas x 2^20 keys x
    8 actors x 2 siblings. It decodes one replica chunk at a time, so its
    temporaries do not grow with the batch: doubling R from 32 to 64 adds
    less to them than one replica's state."""
    from crdt_tpu.ops import map as map_ops
    from crdt_tpu.ops.pallas_kernels import fold_fused_map

    temp = {}
    for r in (32, 64):
        st = _shapes(
            lambda: map_ops.empty(1 << 20, 8, sibling_cap=2, deferred_cap=4, batch=(r,)),
            one_chip,
        )
        mem = _compile(lambda s: fold_fused_map(s, interpret=False), st).memory_analysis()
        temp[r] = mem.temp_size_in_bytes
        one_replica = mem.argument_size_in_bytes // r
    assert temp[64] - temp[32] < one_replica, (temp, one_replica)


def test_nested_level_folds(one_chip):
    from crdt_tpu.ops import map3 as m3
    from crdt_tpu.ops import map_orswot as mo
    from crdt_tpu.ops.pallas_kernels import fold_fused_level

    st = _shapes(lambda: mo.empty(1024, 64, 8, 4, batch=(64,)), one_chip)
    _compile(lambda s: fold_fused_level(mo.LEVEL, s, interpret=False), st)
    st3 = _shapes(lambda: m3.empty(32, 32, 64, 8, 4, batch=(64,)), one_chip)
    _compile(lambda s: fold_fused_level(m3.LEVEL, s, interpret=False), st3)


@pytest.mark.parametrize("flavor", ["fanout", "gated", "acked", "parked"])
def test_wire_pack(one_chip, flavor):
    """The fused wire kernel per flavor. ``fanout`` is one dispatch of the
    ``fanout`` entry: 4,096 lanes x 256 elements of 4-actor clock lanes;
    the δ-ring flavors run at a 1M-row packet."""
    from crdt_tpu.ops.wire_kernels import WireLaneSpec, wire_pack

    n = 4096 * 256
    spec = {
        "fanout": WireLaneSpec(lc=4),
        "gated": WireLaneSpec(lc=12, ctx_lo=4, ctx_hi=12, gated=True),
        "acked": WireLaneSpec(lc=12, ctx_lo=4, ctx_hi=12, gated=True,
                              acked=True),
        "parked": WireLaneSpec(lc=12, parked=True),
    }[flavor]
    k = spec.ctx_hi - spec.ctx_lo

    def arg(*shape, dtype=jnp.uint32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = [arg(n, spec.lc), arg(n, spec.lc), arg(n, dtype=jnp.bool_)]
    kw = {}
    if spec.gated:
        kw.update(know=arg(n, k), dig=arg(n, k))
    if spec.acked:
        kw.update(winc=arg(n, spec.lc), ack_ok=arg(n, dtype=jnp.bool_))
    names = list(kw)

    def pack(c, b, v, *extra):
        return wire_pack(spec, c, b, v, interpret=False,
                         **dict(zip(names, extra)))

    _compile(pack, *args, *kw.values())


def test_mesh_fold_four_chips(topo, tpu_dispatch):
    from crdt_tpu.ops import orswot as ops
    from crdt_tpu.parallel import make_mesh, mesh_fold
    from crdt_tpu.parallel.mesh import orswot_specs
    from jax.sharding import PartitionSpec as P

    mesh = make_mesh(4, 1, devices=topo.devices)
    specs = orswot_specs()
    shapes = jax.eval_shape(lambda: ops.empty(102400, 8, 4, batch=(2048,)))
    st = jax.tree.map(
        lambda s, p: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=NamedSharding(mesh, p)),
        shapes, specs, is_leaf=lambda x: isinstance(x, P),
    )
    compiled = _compile(lambda s: mesh_fold(s, mesh)[0], st)
    assert "collective-permute" in compiled.as_text()


def test_list_apply_config5(one_chip):
    """The ``list_automerge_1chip`` cell's apply: 1,024 replicas of the
    182,315-identifier universe take one 1,024-lane chunk each from a
    254-chunk device op log. The state is donated, so the program's
    output aliases it; its layout makes the compiler's flattened scatter
    a bitcast, so no copy of the state is made (temporaries under a
    hundredth of it); and the round fits the chip."""
    from crdt_tpu.models.list import LANES, _donated, _list_apply_chunks, slot_width

    r, n, chunks, width = 1024, 182315, 254, 1024
    state = (r, slot_width(n) // LANES, LANES)

    def arg(*shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = _donated(_list_apply_chunks).lower(
        arg(*state), arg(*state, dtype=jnp.bool_), arg(chunks, width),
        arg(chunks, width, dtype=jnp.uint8), arg(chunks, width), arg(r),
    ).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= r * n * 5, mem
    assert mem.temp_size_in_bytes < r * n * 5 // 100, mem
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert used < HBM_BYTES, used
