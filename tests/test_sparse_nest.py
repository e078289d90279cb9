"""Sparse (segment-encoded) Map<K, Orswot> vs the oracle — the A/B
gates for sparse nesting (Map<K, Orswot> at huge key universes; reference: src/map.rs
``Map<K, V: Val<A>, A>`` at unbounded key spaces). Mirrors the dense
suite (tests/test_models_map_nested.py) so the two backends are pinned
to the same oracle behavior, plus sparse-specific pins: the
dense/sparse cross-check and the newly-bottomed-child scrub ordering."""

import random

import jax
import numpy as np
from hypothesis import given, settings

from crdt_tpu import Map, VClock
from crdt_tpu.models import BatchedMapOrswot, BatchedSparseMapOrswot
from crdt_tpu.utils import Interner

from strategies import ACTORS, seeds
from test_map import drop, sadd, set_map
from test_models_map_nested import srm, _site_run_set

KEYS = list("pq")
MEMBERS = list("xyz")


def _interners():
    return (
        Interner(KEYS),
        Interner(MEMBERS),
        Interner(ACTORS + ["A", "B", "C"]),
    )


def _batched(states, deferred_cap=12, span=4, dot_cap=64):
    keys, members, actors = _interners()
    return BatchedSparseMapOrswot.from_pure(
        states, span=span, dot_cap=dot_cap,
        deferred_cap=deferred_cap, rm_width=16,
        key_deferred_cap=deferred_cap, key_rm_width=8,
        keys=keys, members=members, actors=actors,
    )


@given(seeds)
@settings(max_examples=15)
def test_sparse_join_bit_identical_to_oracle_merge(seed):
    rng = random.Random(seed)
    states = _site_run_set(rng)
    batched = _batched(states)

    expect = states[0].clone()
    expect.merge(states[1].clone())
    batched.merge_from(0, 1)
    assert batched.to_pure(0) == expect

    # round-trip of untouched replicas is lossless
    assert batched.to_pure(2) == states[2]


@given(seeds)
@settings(max_examples=10)
def test_sparse_fold_bit_identical_to_oracle_fold(seed):
    rng = random.Random(seed)
    states = _site_run_set(rng, n_cmds=16)
    batched = _batched(states)

    expect = states[0].clone()
    for s in states[1:]:
        expect.merge(s.clone())
    assert batched.fold() == expect


@given(seeds)
@settings(max_examples=10)
def test_sparse_op_path_bit_identical(seed):
    rng = random.Random(seed)
    site = set_map()
    stream = []
    for _ in range(14):
        key = rng.choice(KEYS)
        member = rng.choice(MEMBERS)
        roll = rng.random()
        if roll < 0.45:
            stream.append(sadd(site, rng.choice(ACTORS), key, member))
        elif roll < 0.7:
            stream.append(srm(site, rng.choice(ACTORS), key, member))
        else:
            stream.append(drop(site, key))
    oracle = set_map()
    device = _batched([set_map()])
    for op in stream:
        oracle.apply(op)
        device.apply(0, op)
        assert device.to_pure(0) == oracle


@given(seeds)
@settings(max_examples=8)
def test_sparse_matches_dense_backend(seed):
    """The two backends are the same CRDT: identical op streams must
    fold to identical oracle states."""
    rng = random.Random(seed)
    states = _site_run_set(rng, n_cmds=14)
    keys, members, actors = _interners()
    dense = BatchedMapOrswot.from_pure(
        [s.clone() for s in states], deferred_cap=12,
        keys=keys, members=members, actors=actors,
    )
    sparse = _batched(states)
    assert sparse.fold() == dense.fold()


@given(seeds)
@settings(max_examples=8)
def test_sparse_join_laws(seed):
    """Commutativity + idempotence at the raw-array level (canonical
    segment order makes equal states bit-equal)."""
    rng = random.Random(seed)
    states = _site_run_set(rng, n_cmds=10)
    batched = _batched(states)
    lvl = batched.level
    a = jax.tree.map(lambda x: x[0], batched.state)
    b = jax.tree.map(lambda x: x[1], batched.state)
    ab, _ = lvl.join(a, b)
    ba, _ = lvl.join(b, a)
    for x, y in zip(jax.tree.leaves(ab), jax.tree.leaves(ba)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))

    aa, _ = lvl.join(ab, ab)
    for x, y in zip(jax.tree.leaves(aa), jax.tree.leaves(ab)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_scrub_drops_parked_state_of_newly_bottomed_child():
    """A key-remove that lands during a join can newly bottom a child;
    the child's parked member-removes must die with it (the dense
    failure mode tests/test_models_map3.py pins, sparse flavor)."""
    a, b = set_map(), set_map()
    # Child "p" gets a member on site a; site b sees it too (sync).
    op1 = sadd(a, "alpha", "p", "x")
    b.apply(op1)
    # b parks a member-remove inside "p" from a clock it hasn't seen
    # (ahead), so b holds parked state inside child "p".
    ahead = VClock({"alpha": 5})
    from crdt_tpu.pure.orswot import Rm as ORm

    rm_inner = b.update(
        "p", b.len().derive_add_ctx("beta"),
        lambda s, c: ORm(clock=ahead.clone(), members=("x",)),
    )
    b.apply(rm_inner)
    # a removes the whole key "p" (covers the only live dot).
    op2 = drop(a, "p")

    sparse = _batched([a, b])
    dense_oracle = a.clone()
    dense_oracle.merge(b.clone())
    sparse.merge_from(0, 1)
    assert sparse.to_pure(0) == dense_oracle
    # And the oracle indeed dropped the child entirely.
    st = jax.device_get(jax.tree.map(lambda x: x[0], sparse.state))
    alive_keys = {int(e) // sparse.span for e in st.core.eid[st.core.valid]}
    dead_parked = [
        int(e)
        for s in np.nonzero(st.core.dvalid)[0]
        for e in st.core.didx[s]
        if e >= 0 and int(e) // sparse.span not in alive_keys
    ]
    assert dead_parked == []


@given(seeds)
@settings(max_examples=6)
def test_sparse_convergence_random_delivery(seed):
    """N replicas, random op delivery in random per-replica orders →
    all replicas converge to the oracle fold after pairwise merges."""
    rng = random.Random(seed)
    states = _site_run_set(rng, n_cmds=12)
    batched = _batched(states)
    order = list(range(1, len(states)))
    rng.shuffle(order)
    for src in order:
        batched.merge_from(0, src)
    expect = states[0].clone()
    for s in states[1:]:
        expect.merge(s.clone())
    assert batched.to_pure(0) == expect


def test_huge_universe_smoke():
    """The point of sparse mode: a key universe the dense slab could
    never hold (10k keys × 4k members = 40M cells) with a handful of
    live dots — state is segments, not cubes."""
    keys = Interner([f"k{i}" for i in range(6)])
    members = Interner([f"m{i}" for i in range(8)])
    actors = Interner(["a", "b"])
    m = BatchedSparseMapOrswot(
        2, span=4096, dot_cap=64, n_actors=2,
        keys=keys, members=members, actors=actors,
    )
    # Mint adds through the oracle so dots are contiguous per actor.
    site = set_map()
    for i, (k, mem) in enumerate(
        [("k0", "m0"), ("k1", "m1"), ("k5", "m7"), ("k3", "m2")]
    ):
        op = sadd(site, "a", k, mem)
        m.apply(0, op)
    assert m.to_pure(0) == site
    nbytes = sum(x.nbytes for x in jax.tree.leaves(m.state)) // 2
    assert nbytes < 10_000  # vs 40M cells * 2 actors * 4B dense


def test_sparse_map_checkpoint_round_trip(tmp_path):
    """Device checkpoint of the sparse map model: save -> load -> states
    and interners identical; resumed model still merges correctly."""
    from crdt_tpu import checkpoint

    rng = random.Random(5)
    states = _site_run_set(rng, n_cmds=10)
    m = _batched(states)
    p = tmp_path / "sm.npz"
    checkpoint.save(p, m)
    back = checkpoint.load(p)
    assert back.span == m.span
    for x, y in zip(jax.tree.leaves(back.state), jax.tree.leaves(m.state)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    assert list(back.keys) == list(m.keys)
    back.merge_from(0, 1)
    m.merge_from(0, 1)
    assert back.to_pure(0) == m.to_pure(0)


def test_sparse_map_factory():
    from crdt_tpu.config import configured, replicaset
    from crdt_tpu.models import BatchedSparseMapOrswot
    from crdt_tpu.pure.map import Map

    with configured(backend="xla"):
        m = replicaset("sparse_map_orswot", 4, n_members=16, n_keys2=64)
        assert isinstance(m, BatchedSparseMapOrswot)
        assert m.span == 16 and m.dot_cap == 64
    with configured(backend="pure"):
        ps = replicaset("sparse_map_orswot", 2)
        assert len(ps) == 2 and isinstance(ps[0], Map)
