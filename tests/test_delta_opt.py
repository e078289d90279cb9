"""Optimal δ synchronization gates (crdt_tpu/delta_opt/, Enes et al.
arXiv 1803.02750):

1. **Decomposition coverage + laws** — every registered merge kind has
   a join-irreducible decomposition (``register_decomposition``, the
   registration-is-the-coverage-contract rule), and each registration
   satisfies reconstruction (``join(decompose(s, since)) ⊔ since == s``)
   and irredundancy (no δ lane covered by the join of the others),
   bit-exact over the kind's law domain. The committed broken twins
   (lossy / non-irredundant) must each fire their law.
2. **Ack-window back-propagation** — ``ack_window=True`` on the δ ring
   converges bit-identical to flags-off while ``bytes_useful`` drops
   strictly below the digest-only baseline (the Enes back-propagation
   claim); the flag gates the trace (off == the default program — the
   deep pre-flag reconstruction pin lives in test_zero_copy_ring.py);
   an acked run must NOT poison the flags-off jit-cache lookup the
   analysis gates read (the PR 8 poisoning class, AckWindowKey edition).
3. **Decomposition resync** — the post-heal state-driven sync mode
   ships only the divergence set and lands bit-identical on the
   full-join fixpoint, per kind.
"""

import random

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from crdt_tpu import telemetry as tele
from crdt_tpu.analysis import fixtures, laws
from crdt_tpu.analysis.registry import (
    decomposers,
    get_merge_kind,
    merge_kinds,
    undecomposable_kinds,
)
from crdt_tpu.models.orswot import BatchedOrswot
from crdt_tpu.parallel import make_mesh, mesh_delta_gossip, mesh_fold, shard_orswot
from crdt_tpu.utils.metrics import metrics

from test_delta import _rand_states, _rows_equal, _tracking

MEMBERS = ["a", "b", "c", "d"]


def _norm_join(mk):
    def j(a, b):
        out = mk.join(a, b)
        return out[0] if isinstance(out, tuple) and len(out) == 2 else out

    return j


def _trees_equal(a, b) -> bool:
    return all(
        np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))
    )


# ---- 1. registry coverage + the two decomposition laws --------------------

def test_every_merge_kind_registers_a_decomposition():
    """Total coverage by contract: 12/12 (the ``decomp`` static-check
    section enforces the same — registration IS the coverage gate)."""
    assert undecomposable_kinds() == []
    assert len(decomposers()) == len(merge_kinds())


def test_unregistered_kind_fails_discovery():
    """A merge kind without a decomposition shows up in the gap list —
    the negative half of the coverage contract."""
    from crdt_tpu.analysis import registry as reg

    fake = reg.register_merge(
        "___fake_decompless", module=__name__,
        join=lambda a, b: a, states=lambda: [jnp.zeros((2,))],
    )
    try:
        assert "___fake_decompless" in undecomposable_kinds()
    finally:
        del reg._MERGE[fake.name]
    assert undecomposable_kinds() == []


@pytest.mark.parametrize(
    "kind_name", [k.name for k in merge_kinds()]
)
def test_decomposition_laws_clean(kind_name):
    """Reconstruction + irredundancy, bit-exact over the kind's law
    domain paired as (S_i ∨ S_j, S_i) — every ``since`` a genuine lower
    bound, the shape the resync path sees. The 5 heaviest kinds ride
    the curated slow tier (conftest); run_static_checks ``decomp``
    covers all 12 per chain regardless."""
    findings = laws.check_decomposition_kind(get_merge_kind(kind_name))
    assert findings == [], [f.detail for f in findings]


def test_lossy_twin_fires_reconstruction_law():
    """The lane-dropping broken decomposer must fail reconstruction —
    the law has teeth."""
    findings = laws.check_decomposition_kind(
        get_merge_kind("orswot"), dec=fixtures.LOSSY_DECOMPOSER
    )
    assert any(f.check == "decomp-reconstruction" for f in findings)


def test_redundant_twin_fires_irredundancy_law():
    """The everything-valid broken decomposer must fail irredundancy —
    an unchanged lane drops harmlessly, which the law must catch."""
    findings = laws.check_decomposition_kind(
        get_merge_kind("orswot"), dec=fixtures.REDUNDANT_DECOMPOSER
    )
    assert any(f.check == "decomp-irredundancy" for f in findings)


def test_decomp_section_is_chained():
    """tools/run_static_checks.py runs the ``decomp`` section (the
    broken-twin + coverage checks above are its substance; this pins
    the wiring so the chain cannot silently drop it)."""
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "run_static_checks",
        os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "tools", "run_static_checks.py",
        ),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert "decomp" in mod.SECTIONS
    assert "decomp" in mod.RUNNERS
    assert "decomp" in mod._JAX_SECTIONS  # it traces jax programs


# ---- 2. ack-window back-propagation on the δ ring -------------------------

def _dense_workload(seed, p):
    rng = random.Random(seed)
    states, applied = _rand_states(rng, 8, MEMBERS)
    batched = BatchedOrswot.from_pure(states)
    mesh = make_mesh(p, 8 // p)
    sharded = shard_orswot(batched.state, mesh)
    dirty, fctx = _tracking(batched, applied)
    return mesh, sharded, dirty, fctx


@pytest.mark.parametrize("pipeline", [True, False])
def test_acked_ring_bit_identical_and_fewer_useful_bytes(pipeline):
    """The acceptance triple on the dense flavor: (a) converged states
    bit-identical to flags-off AND to the full fold, (b) residue still
    certifies, (c) ``bytes_useful`` strictly below the digest-only
    baseline with ``bytes_acked_skipped > 0`` — the window masks real
    re-circulated knowledge the frozen-top digest cannot."""
    mesh, sharded, dirty, fctx = _dense_workload(9 if pipeline else 17, 8)
    folded, _ = mesh_fold(sharded, mesh)
    rounds = 24
    g0, _, of0, r0, t0 = mesh_delta_gossip(
        sharded, dirty, fctx, mesh, rounds=rounds, cap=64,
        pipeline=pipeline, telemetry=True,
    )
    g1, _, of1, r1, t1 = mesh_delta_gossip(
        sharded, dirty, fctx, mesh, rounds=rounds, cap=64,
        pipeline=pipeline, telemetry=True, ack_window=True,
    )
    assert _trees_equal(g0, g1)
    _rows_equal(g1, folded)
    assert int(r1) == 0
    assert float(t1.bytes_acked_skipped) > 0
    assert float(t1.bytes_useful) < float(t0.bytes_useful)
    assert int(t1.ack_window_depth) > 0
    assert float(t0.bytes_acked_skipped) == 0  # off path reports nothing
    assert int(t0.ack_window_depth) == 0


def test_acked_registry_twins_recorded():
    """The ``delta_opt.acked_skipped[.kind]`` registry twins drain from
    the telemetry pytree on a concrete acked run."""
    mesh, sharded, dirty, fctx = _dense_workload(3, 4)
    before = metrics.snapshot()["counters"].get("delta_opt.acked_skipped", 0)
    _, _, _, _, t = mesh_delta_gossip(
        sharded, dirty, fctx, mesh, rounds=12, cap=64,
        telemetry=True, ack_window=True,
    )
    counters = metrics.snapshot()["counters"]
    assert counters.get("delta_opt.acked_skipped", 0) - before == int(
        float(t.bytes_acked_skipped)
    )
    assert "delta_opt.acked_skipped.delta_gossip" in counters
    assert counters.get("delta_opt.ack_window_runs", 0) >= 1


def test_acked_map_ring_bit_identical():
    """The map flavor (removal-carrying packets — exactly what the
    digest gate can never mask and the ack window can)."""
    from crdt_tpu.models.map import BatchedMap
    from crdt_tpu.parallel import mesh_delta_gossip_map, mesh_fold_map
    from crdt_tpu.parallel.mesh import shard_map_state

    from test_delta_map import _interners, _site_run, _tracking as _trk

    rng = random.Random(11)
    sites, applied = _site_run(rng)
    batched = BatchedMap.from_pure(sites, **_interners())
    mesh = make_mesh(4, 2)
    sharded = shard_map_state(batched.state, mesh)
    folded, _ = mesh_fold_map(sharded, mesh)
    dirty, fctx = _trk(batched, applied)
    g0 = mesh_delta_gossip_map(sharded, dirty, fctx, mesh, rounds=16, cap=64)
    g1 = mesh_delta_gossip_map(
        sharded, dirty, fctx, mesh, rounds=16, cap=64, ack_window=True
    )
    assert _trees_equal(g0[0], g1[0])
    _rows_equal(g1[0], folded)


@pytest.mark.parametrize("flavor", ["map3", "map_orswot"])
def test_acked_nested_flavors_bit_identical(flavor):
    """The two doubly-nested flavors (Map3DeltaPacket /
    MapOrswotDeltaPacket — the deepest packet layouts the generic
    ackwin core/content traversal must navigate): ``ack_window=True``
    converges bit-identical to flags-off and to the mesh fold, closing
    the per-flavor pin README claims for all four ``mesh_delta_gossip*``
    entries, not just the dense and map ones."""
    if flavor == "map3":
        import test_delta_map3 as td
        from crdt_tpu.models import BatchedMap3 as Batched
        from crdt_tpu.parallel import (
            mesh_delta_gossip_map3 as gossip,
            mesh_fold_map3 as fold,
            shard_map3 as shard,
        )
        kw = dict(deferred_cap=12)
    else:
        import test_delta_map_orswot as td
        from crdt_tpu.models import BatchedMapOrswot as Batched
        from crdt_tpu.parallel import (
            mesh_delta_gossip_map_orswot as gossip,
            mesh_fold_map_orswot as fold,
            shard_map_orswot as shard,
        )
        kw = {}

    rng = random.Random(13)
    sites, applied = td._site_run(rng)
    batched = Batched.from_pure(sites, **kw, **td._interners())
    mesh = make_mesh(4, 2)
    sharded = shard(batched.state, mesh)
    folded, _ = fold(sharded, mesh)
    dirty, fctx = td._tracking(batched, applied)
    g0 = gossip(sharded, dirty, fctx, mesh, rounds=12, cap=32)
    g1 = gossip(
        sharded, dirty, fctx, mesh, rounds=12, cap=32, ack_window=True
    )
    assert _trees_equal(g0[0], g1[0])
    _rows_equal(g1[0], folded)


def test_acked_ring_under_faults_still_heals():
    """ack_window= composes with faults=: lost/rejected packets are
    never acked (the data packet's fate decides the bits), so the
    masking stays sound under sustained corruption — the degraded rows
    still resync to the fault-free fixpoint."""
    from crdt_tpu.faults import FaultPlan
    from crdt_tpu.parallel import mesh_gossip

    mesh, sharded, dirty, fctx = _dense_workload(7, 8)
    ref, _ = mesh_gossip(sharded, mesh, local_fold="tree")
    ref0 = jax.tree.map(lambda x: x[0], ref)
    rows, _, _, residue, fc = mesh_delta_gossip(
        sharded, dirty, fctx, mesh, rounds=24, cap=64,
        ack_window=True, faults=FaultPlan(seed=5, drop=0.15, corrupt=0.1),
    )
    assert int(residue) >= 1  # loss voids the certificate, acked or not
    assert int(fc.packets_dropped) + int(fc.packets_rejected) > 0
    healed, _ = mesh_gossip(rows, mesh, local_fold="tree")
    for i in range(8):
        assert _trees_equal(jax.tree.map(lambda x: x[i], healed), ref0)


def test_ack_window_flag_gates_the_trace():
    """``ack_window=False`` lowers the exact default program (the
    pre-flag reconstruction pin lives in test_zero_copy_ring.py and
    still holds); ``ack_window=True`` is a genuinely different program
    — one extra ack ppermute per round."""
    mesh, sharded, dirty, fctx = _dense_workload(1, 4)

    def low(**kw):
        return jax.jit(
            lambda s, d, f: mesh_delta_gossip(
                s, d, f, mesh, rounds=3, cap=8, local_fold="tree", **kw
            )
        ).lower(sharded, dirty, fctx).as_text()

    default_txt = low()
    assert low(ack_window=False) == default_txt
    assert low(ack_window=True) != default_txt


def test_acked_run_does_not_poison_flags_off_lookup():
    """Regression (the PR 8 jit-cache poisoning class): an acked run
    memoises a DIFFERENT program under the same (kind, donation, mesh)
    key family; ``analysis._cached_entry_fn`` must keep returning the
    flags-off program the aliasing/cost/lint gates read — AckWindowKey
    rides the cache key and is skipped like FaultPlan."""
    from crdt_tpu.analysis.jit_lint import _cached_entry_fn
    from crdt_tpu.analysis.registry import entry_points

    mesh = make_mesh(4, 2)
    ep = next(
        e for e in entry_points(donatable=True) if e.kind == "delta_gossip"
    )
    ep.invoke(mesh, ep.make_args(mesh))  # flags-off donating program cached
    fn_before = _cached_entry_fn(ep.kind, ep.n_donated, mesh)
    assert fn_before is not None
    s, d, f = ep.make_args(mesh)
    mesh_delta_gossip(
        s, d, f, mesh, local_fold="tree", donate=True, ack_window=True
    )  # acked program cached LAST under the same (kind, donation, mesh)
    fn_after = _cached_entry_fn(ep.kind, ep.n_donated, mesh)
    assert fn_after is fn_before  # the acked entry was skipped


def test_elastic_wrapper_forwards_ack_window():
    """delta_gossip_elastic threads ack_window= into every attempt;
    converged rows stay bit-identical to the unacked wrapper."""
    from crdt_tpu.parallel.delta_ring import delta_gossip_elastic

    rng = random.Random(21)
    states, applied = _rand_states(rng, 8, MEMBERS)
    mesh = make_mesh(4, 2)

    b0 = BatchedOrswot.from_pure(states)
    dirty, fctx = _tracking(b0, applied)
    out0 = delta_gossip_elastic(b0, dirty, fctx, mesh, rounds=12, cap=64)
    b1 = BatchedOrswot.from_pure(states)
    out1 = delta_gossip_elastic(
        b1, dirty, fctx, mesh, rounds=12, cap=64, ack_window=True
    )
    assert _trees_equal(out0[0], out1[0])
    assert out0[4] == out1[4] == {}  # no widen either way


# ---- 3. telemetry pytree fields -------------------------------------------

def test_telemetry_ack_fields_roundtrip():
    z = tele.zeros()
    assert float(z.bytes_acked_skipped) == 0.0
    assert int(z.ack_window_depth) == 0
    d = tele.to_dict(z)
    assert d["bytes_acked_skipped"] == 0.0
    assert d["ack_window_depth"] == 0
    a = z._replace(
        bytes_acked_skipped=jnp.float32(64.0),
        ack_window_depth=jnp.uint32(3),
    )
    b = z._replace(
        bytes_acked_skipped=jnp.float32(16.0),
        ack_window_depth=jnp.uint32(1),
    )
    c = tele.combine(a, b)
    # the skipped counter is a rate (adds); the depth a final-state
    # gauge (later run wins) — the telemetry.combine convention.
    assert float(c.bytes_acked_skipped) == 80.0
    assert int(c.ack_window_depth) == 1


# ---- 4. decomposition resync (the post-heal state-driven sync mode) -------

@pytest.mark.parametrize(
    "kind_name",
    ["orswot", "map", "sparse_orswot", "sparse_mvmap", "sparse_nested_map"],
)
def test_resync_bit_identical_to_full_join(kind_name):
    """Each rank decomposes over a pre-divergence ``since`` and the
    reconstruction + registered join land bit-identically on the
    full-join fixpoint — the reconstruction law, end-to-end through the
    resync driver, for dense, map, and every segment-sparse kind."""
    from crdt_tpu.delta_opt import resync

    mk = get_merge_kind(kind_name)
    join = _norm_join(mk)
    seeds = mk.states()
    since = seeds[0]
    ranks = [join(since, s) for s in seeds[1:5]]
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *ranks)
    healed, report = resync(kind_name, stacked, since)
    ref = ranks[0]
    for r in ranks[1:]:
        ref = join(ref, r)
    for i in range(len(ranks)):
        assert _trees_equal(jax.tree.map(lambda x: x[i], healed), ref), (
            f"rank {i} diverged from the full-join fixpoint"
        )
    assert report.ranks == len(ranks)
    assert report.bytes_shipped <= report.bytes_full_state


def test_resync_ships_only_the_divergence_set():
    """The headline bandwidth claim at a realistic shape: 8 ranks
    diverge by a handful of rows over a large synced base — the
    decomposition resync ships a small fraction of what full-state
    resync would (< 25%)."""
    from crdt_tpu.delta_opt import resync

    # A wide synced base: 256 members all present everywhere, then each
    # of 8 replicas touches ONE member (the row planes must dominate
    # the whole-riding residual for the ratio to mean anything — at toy
    # widths the bounded parked buffers are most of the state).
    members = [f"m{i}" for i in range(256)]
    from crdt_tpu.pure.orswot import Orswot

    base = Orswot()
    for m in members:
        base.apply(base.add(m, base.read().derive_add_ctx("s0")))
    import copy

    reps = []
    for i in range(8):
        r = copy.deepcopy(base)
        r.apply(r.add(f"m{i}", r.read().derive_add_ctx(f"s{i + 1}")))
        reps.append(r)
    batched = BatchedOrswot.from_pure([base] + reps)
    since = jax.tree.map(lambda x: x[0], batched.state)
    states = jax.tree.map(lambda x: x[1:], batched.state)
    healed, report = resync("orswot", states, since)
    assert report.lanes_shipped == 8  # exactly the touched rows
    assert report.ratio < 0.25, report
    # Bit-identity vs the registered join's own full fold.
    join = _norm_join(get_merge_kind("orswot"))
    ref = jax.tree.map(lambda x: x[0], states)
    for i in range(1, 8):
        ref = join(ref, jax.tree.map(lambda x: x[i], states))
    for i in range(8):
        assert _trees_equal(jax.tree.map(lambda x: x[i], healed), ref)


def test_resync_reexported_from_faults():
    """The heal path is reached from crdt_tpu.faults (the operator
    stands next to the FaultPlan that made resync necessary)."""
    from crdt_tpu import faults
    from crdt_tpu.delta_opt import heal

    assert faults.resync is heal.resync
    assert faults.ResyncReport is heal.ResyncReport
