"""δ-state anti-entropy (parallel/delta.py): bounded delta packets on
the ring must reach the same converged state as the full-state fold —
delta-CRDT semantics (PAPERS.md, Almeida et al.) on the dense slabs.

Tracking is accumulated at op granularity per the module contract: each
applied op marks its element rows dirty and folds its dots/clock into
the per-row forwarding context (what the replica can attest about that
element's dots)."""

import random

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from crdt_tpu.models.orswot import BatchedOrswot
from crdt_tpu.parallel import make_mesh, mesh_delta_gossip, mesh_fold, shard_orswot
from crdt_tpu.pure.orswot import Add, Orswot


def _rand_states(rng, n, members):
    """n oracle replicas from a shared op history with random delivery
    (causal per-actor prefix delivery, as in test_parallel). Also
    returns each replica's applied-op log for delta tracking."""
    reps = [Orswot() for _ in range(n)]
    applied = [[] for _ in range(n)]
    got = [[0] * n for _ in range(n)]
    seq = [0] * n
    for _ in range(rng.randint(8, 25)):
        origin = rng.randrange(n)
        m = rng.choice(members)
        if rng.random() < 0.6 or not reps[origin].read().val:
            op = reps[origin].add(
                m, reps[origin].read().derive_add_ctx(f"s{origin}")
            )
        else:
            victim = rng.choice(sorted(reps[origin].read().val))
            op = reps[origin].rm(
                victim, reps[origin].contains(victim).derive_rm_ctx()
            )
        for i in range(n):
            if i == origin:
                reps[i].apply(op)
                applied[i].append(op)
            elif got[i][origin] == seq[origin] and rng.random() < 0.5:
                reps[i].apply(op)
                applied[i].append(op)
                got[i][origin] += 1
        seq[origin] += 1
    return reps, applied


def _tracking(batched, applied):
    """(dirty, fctx) from per-replica op logs: adds contribute their dot
    at their members, removes their clock — op-granularity accumulation
    per the delta module's contract."""
    r = batched.n_replicas
    e, a = batched.state.ctr.shape[-2], batched.state.ctr.shape[-1]
    dirty = np.zeros((r, e), bool)
    fctx = np.zeros((r, e, a), np.uint32)
    for i, ops_i in enumerate(applied):
        for op in ops_i:
            if isinstance(op, Add):
                aid = batched.actors.id_of(op.dot.actor)
                for m in op.members:
                    eid = batched.members.id_of(m)
                    dirty[i, eid] = True
                    fctx[i, eid, aid] = max(fctx[i, eid, aid], op.dot.counter)
            else:
                for m in op.members:
                    eid = batched.members.id_of(m)
                    dirty[i, eid] = True
                    for actor, c in op.clock.dots.items():
                        aid = batched.actors.id_of(actor)
                        fctx[i, eid, aid] = max(fctx[i, eid, aid], c)
    return jnp.asarray(dirty), jnp.asarray(fctx)


def _rows_equal(gossiped, folded):
    for leaf_g, leaf_f in zip(jax.tree.leaves(gossiped), jax.tree.leaves(folded)):
        g, f = np.asarray(leaf_g), np.asarray(leaf_f)
        for row in range(g.shape[0]):
            np.testing.assert_array_equal(g[row], f)


@pytest.mark.parametrize("mesh_shape", [(4, 2), (8, 1), (2, 4)])
@pytest.mark.parametrize("seed", [1, 9, 17])
def test_delta_gossip_matches_fold(mesh_shape, seed):
    """Replicas diverge from genesis under op-granularity tracking:
    δ-gossip must reproduce the full fold bit-for-bit."""
    rng = random.Random(seed)
    states, applied = _rand_states(rng, 8, ["a", "b", "c", "d"])
    batched = BatchedOrswot.from_pure(states)
    mesh = make_mesh(*mesh_shape)
    sharded = shard_orswot(batched.state, mesh)

    folded, of_f = mesh_fold(sharded, mesh)
    assert not bool(of_f)

    dirty, fctx = _tracking(batched, applied)
    # extra rounds: forwarded rows take P-1 hops after local drain
    p = mesh_shape[0]
    gossiped, _, of, _ = mesh_delta_gossip(
        sharded, dirty, fctx, mesh, rounds=2 * p, cap=64
    )
    assert not bool(of)
    _rows_equal(gossiped, folded)


def test_delta_gossip_tracks_changes_since_sync():
    """Synced base + per-replica local ops: only the touched rows are
    dirty; δ rounds converge to the full fold while shipping a bounded
    packet per link per round."""
    from crdt_tpu.utils import Interner

    rng = random.Random(5)
    members = [f"m{i}" for i in range(24)]
    interners = dict(
        members=Interner(members),
        actors=Interner([f"s{i}" for i in range(8)]),
    )

    # Phase 1: every replica adds a few members, everything delivered
    # everywhere (a fully synced base — tracking starts AFTER this).
    sites = [Orswot() for _ in range(8)]
    minted = []
    for i, site in enumerate(sites):
        for _ in range(3):
            m = rng.choice(members)
            op = site.add(m, site.read().derive_add_ctx(f"s{i}"))
            site.apply(op)
            minted.append((i, op))
    for j, site in enumerate(sites):
        for i, op in minted:
            if i != j:
                site.apply(op)

    # Phase 2: diverge locally — each replica adds one and maybe removes
    # one member; only these ops enter the tracking.
    phase2 = [[] for _ in range(8)]
    for i, site in enumerate(sites):
        m = rng.choice(members)
        op = site.add(m, site.read().derive_add_ctx(f"s{i}"))
        site.apply(op)
        phase2[i].append(op)
        if rng.random() < 0.5:
            victims = sorted(site.read().val)
            if victims:
                v = rng.choice(victims)
                rm = site.rm(v, site.contains(v).derive_rm_ctx())
                site.apply(rm)
                phase2[i].append(rm)
    diverged = BatchedOrswot.from_pure(sites, **interners)
    dirty, fctx = _tracking(diverged, phase2)
    n_dirty = int(dirty.sum())
    assert 0 < n_dirty < dirty.size  # genuinely sparse

    mesh = make_mesh(4, 2)
    sharded = shard_orswot(diverged.state, mesh)
    folded, _ = mesh_fold(sharded, mesh)
    gossiped, _, of, _ = mesh_delta_gossip(
        sharded, dirty, fctx, mesh, rounds=10, cap=8
    )
    assert not bool(of)
    _rows_equal(gossiped, folded)


def test_interval_accumulate_tracking_converges():
    """Tracking built with interval_accumulate (per-op endpoint diffs,
    the contract-documented API) must drive δ-gossip to the full fold
    like the op-log builder does."""
    from crdt_tpu.parallel import interval_accumulate

    rng = random.Random(11)
    states, applied = _rand_states(rng, 8, ["a", "b", "c"])
    batched = BatchedOrswot.from_pure(states)

    # Rebuild each replica's device state op by op, accumulating
    # (dirty, fctx) from the endpoint states of every step.
    e, a = batched.state.ctr.shape[-2], batched.state.ctr.shape[-1]
    dirty = jnp.zeros((8, e), bool)
    fctx = jnp.zeros((8, e, a), jnp.uint32)
    replay = BatchedOrswot(
        8, e, a, batched.state.dcl.shape[-2],
        members=batched.members, actors=batched.actors,
    )
    for i, ops_i in enumerate(applied):
        for op in ops_i:
            old = jax.tree.map(lambda x: x[i], replay.state)
            replay.apply(i, op)
            new = jax.tree.map(lambda x: x[i], replay.state)
            d_i, f_i = interval_accumulate(dirty[i], fctx[i], old, new)
            dirty, fctx = dirty.at[i].set(d_i), fctx.at[i].set(f_i)
    np.testing.assert_array_equal(
        np.asarray(replay.state.ctr), np.asarray(batched.state.ctr)
    )

    mesh = make_mesh(4, 2)
    sharded = shard_orswot(replay.state, mesh)
    folded, _ = mesh_fold(sharded, mesh)
    gossiped, _, of, _ = mesh_delta_gossip(
        sharded, dirty, fctx, mesh, rounds=8, cap=32
    )
    assert not bool(of)
    _rows_equal(gossiped, folded)


@pytest.mark.parametrize("cap", [1, 2, 3, 5])
@pytest.mark.parametrize("seed", [7, 29])
def test_delta_converges_for_any_cap(cap, seed):
    """Convergence is cap-independent given the drain budget: rounds =
    P ring latencies of the worst-case per-device backlog."""
    rng = random.Random(seed)
    states, applied = _rand_states(rng, 8, ["a", "b", "c", "d"])
    batched = BatchedOrswot.from_pure(states)
    mesh = make_mesh(4, 2)
    sharded = shard_orswot(batched.state, mesh)
    folded, _ = mesh_fold(sharded, mesh)

    dirty, fctx = _tracking(batched, applied)
    e_local = sharded.ctr.shape[-2] // 2
    rounds = 4 * 4 * (-(-e_local // cap) + 2)
    gossiped, _, of, _ = mesh_delta_gossip(
        sharded, dirty, fctx, mesh, rounds=rounds, cap=cap
    )
    assert not bool(of)
    _rows_equal(gossiped, folded)


@pytest.mark.parametrize("seed", [7])
def test_residue_reports_underbudgeted_run(seed):
    """A capped backlog with the default P-1 rounds
    must REPORT non-convergence at runtime (residue > 0) instead of
    silently returning an under-converged ring — and a properly budgeted
    run of the same workload must report residue == 0."""
    import warnings

    rng = random.Random(seed)
    states, applied = _rand_states(rng, 8, ["a", "b", "c", "d"])
    batched = BatchedOrswot.from_pure(states)
    mesh = make_mesh(4, 2)
    sharded = shard_orswot(batched.state, mesh)
    folded, _ = mesh_fold(sharded, mesh)

    dirty, fctx = _tracking(batched, applied)
    assert int(dirty.sum()) > 4  # backlog genuinely exceeds cap=1

    # Under-budgeted: cap=1 starves the backlog within the default
    # round budget — the runtime indicator must fire (and warn ONCE per
    # kind: repeats only count in the metrics registry).
    from crdt_tpu.parallel.delta_ring import reset_residue_warnings
    from crdt_tpu.utils.metrics import metrics

    reset_residue_warnings()
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        _, _, _, residue = mesh_delta_gossip(
            sharded, dirty, fctx, mesh, cap=1
        )
    assert int(residue) > 0
    assert any("residue" in str(w.message) for w in caught)

    # The SAME under-budgeted run again: deduped to silence, but the
    # registry counter keeps the rate.
    runs_before = metrics.snapshot()["counters"].get(
        "anti_entropy.delta_gossip.residue_runs", 0
    )
    with warnings.catch_warnings(record=True) as again:
        warnings.simplefilter("always")
        mesh_delta_gossip(sharded, dirty, fctx, mesh, cap=1)
    assert not any("residue" in str(w.message) for w in again)
    runs_after = metrics.snapshot()["counters"][
        "anti_entropy.delta_gossip.residue_runs"
    ]
    assert runs_after == runs_before + 1

    # Properly budgeted — enough rounds AND a cap that clears the
    # steady-state circulating-mark load: residue must certify
    # convergence, and the result must equal the fold. (At cap=1 the
    # indicator could never certify: forwarding marks circulate
    # indefinitely, so some device stays slot-starved forever — the
    # one-sidedness run_delta_ring documents.)
    gossiped, _, of, residue = mesh_delta_gossip(
        sharded, dirty, fctx, mesh, rounds=8, cap=64
    )
    assert int(residue) == 0
    assert not bool(of)
    _rows_equal(gossiped, folded)
