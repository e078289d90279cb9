"""Element-sharded sparse folds vs the unsharded path — the SP analog
for the segment-encoded backend: segment tables sharded across the
element axis. Restriction commutes with the join,
so the sharded mesh fold must reproduce the unsharded fold exactly on
content, tops, and the parked-remove SET (slot packing may differ per
shard — each shard is its own restricted CRDT)."""

import random

import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings

from crdt_tpu.models import BatchedSparseMapOrswot, BatchedSparseOrswot
from crdt_tpu.parallel import (
    make_mesh,
    mesh_fold_sparse_map,
    mesh_fold_sparse_sharded,
    split_nested,
    split_segments,
)
from crdt_tpu.ops import sparse_orswot as sp_ops
from crdt_tpu.pure.orswot import Orswot

from strategies import seeds
from test_sparse_nest import _batched as _nest_batched, _site_run_set


def _rand_orswots(rng, n=8):
    members = [f"m{i}" for i in range(16)]
    sites = [Orswot() for _ in range(n)]
    ops = []
    for i, site in enumerate(sites):
        for _ in range(4):
            m = rng.choice(members)
            op = site.add(m, site.read().derive_add_ctx(f"s{i}"))
            site.apply(op)
            ops.append(op)
        if rng.random() < 0.5:
            live = sorted(site.read().val)
            if live:
                op = site.rm(rng.choice(live), site.read().derive_rm_ctx())
                site.apply(op)
    return sites


def _parked_set(st: "jax.Array", batched):
    """The set of (clock-tuple, element) parked pairs of a device state
    (slot packing is not canonical across shardings; the SET is)."""
    st = jax.device_get(st)
    out = set()
    for s in np.nonzero(st.dvalid)[0]:
        clock = tuple(int(c) for c in st.dcl[s])
        for e in st.didx[s]:
            if e >= 0:
                out.add((clock, int(e)))
    return out


@given(seeds)
@settings(max_examples=8, deadline=None)
def test_sharded_flat_fold_matches_unsharded(seed):
    rng = random.Random(seed)
    sites = _rand_orswots(rng)
    b = BatchedSparseOrswot.from_pure(sites, dot_cap=64)
    mesh = make_mesh(4, 2)

    sharded = split_segments(b.state, 2)
    out, of = mesh_fold_sparse_sharded(sharded, mesh)
    assert not bool(jnp.any(of))

    expect = b.fold()  # oracle-form converged state

    # Reassemble: per-shard live cells union + shared top.
    got = Orswot()
    from crdt_tpu.vclock import VClock

    st = jax.device_get(out)
    top0 = st.top[0]
    np.testing.assert_array_equal(st.top[0], st.top[1])  # replicated
    got.clock = VClock(
        {b.actors[a]: int(c) for a, c in enumerate(top0) if c > 0}
    )
    for shard in range(2):
        row = jax.tree.map(lambda x: x[shard], st)
        for s in np.nonzero(row.valid)[0]:
            m = b.members[int(row.eid[s])]
            entry = got.entries.setdefault(m, VClock())
            entry.dots[b.actors[int(row.act[s])]] = int(row.ctr[s])
    assert got.clock == expect.clock
    assert got.entries == expect.entries

    # Parked sets: union of shard sets == unsharded set.
    folded_un, _ = sp_ops.fold(b.state)
    un_set = _parked_set(folded_un, b)
    sh_set = set()
    for shard in range(2):
        sh_set |= _parked_set(jax.tree.map(lambda x: x[shard], out), b)
    assert sh_set == un_set


@given(seeds)
@settings(max_examples=6, deadline=None)
def test_sharded_nested_fold_matches_oracle(seed):
    """Sharded sparse Map<K, Orswot> mesh fold == the oracle fold (the
    scrub's cross-shard key-liveness psum is what this exercises: a
    key's members split across shards must count as one live child)."""
    rng = random.Random(seed)
    states = _site_run_set(rng, n_cmds=14)
    b = _nest_batched(states)
    mesh = make_mesh(4, 2)

    sharded = split_nested(b.state, 2)
    out, of = mesh_fold_sparse_map(sharded, mesh, span=b.span)
    assert not bool(jnp.any(of))

    expect = states[0].clone()
    for s in states[1:]:
        expect.merge(s.clone())

    # Rebuild oracle state from the sharded device fold.
    recon = BatchedSparseMapOrswot(
        1, b.span, b.dot_cap, b.state.core.top.shape[-1],
        b.state.core.dcl.shape[-2], b.state.core.didx.shape[-1],
        b.state.kcl.shape[-2], b.state.kidx.shape[-1],
        keys=b.keys, members=b.members, actors=b.actors,
    )
    got_parts = []
    for shard in range(2):
        recon.state = jax.tree.map(lambda x: x[shard][None], out)
        got_parts.append(recon.to_pure(0))
    # Reassembly is a plain UNION of the element-disjoint restrictions —
    # NOT an oracle merge (both parts carry the full top, so a merge
    # would read the other shard's absent members as observed-and-
    # removed and kill them).
    merged = got_parts[0]
    other = got_parts[1]
    assert merged.clock == other.clock  # tops replicated
    for k, child in other.entries.items():
        mine = merged.entries.get(k)
        if mine is None:
            merged.entries[k] = child
        else:
            mine.entries.update(child.entries)
            for clock, ms in child.deferred.items():
                mine.deferred.setdefault(clock, set()).update(ms)
    for clock, ks in other.deferred.items():
        merged.deferred.setdefault(clock, set()).update(ks)
    assert merged == expect


def test_split_preserves_state_and_respects_residue_classes():
    rng = random.Random(3)
    sites = _rand_orswots(rng, n=4)
    b = BatchedSparseOrswot.from_pure(sites, dot_cap=64)
    sharded = split_segments(b.state, 2)
    st = jax.device_get(sharded)
    for shard in range(2):
        eids = st.eid[:, shard][st.valid[:, shard]]
        assert np.all(eids % 2 == shard)
        didx = st.didx[:, shard]
        assert np.all((didx < 0) | (didx % 2 == shard))
    # Tops replicated across shards.
    np.testing.assert_array_equal(st.top[:, 0], st.top[:, 1])
    # No dot lost: per-shard live counts sum to the original.
    assert int(st.valid.sum()) == int(jax.device_get(b.state.valid).sum())


def test_cross_shard_key_liveness_keeps_parked_state():
    """A parked member-remove whose elements land in one shard while the
    key's only live dots land in the OTHER shard: the scrub's liveness
    test must see across shards (all-gathered queries, not a positional
    psum) or the parked entry is wrongly dropped and the removed member
    resurrects."""
    from crdt_tpu.vclock import VClock
    from test_sparse_nest import _batched as _nest_batched, set_map

    # Oracle: key "p" holds live member id 1 (odd -> shard 1) and a
    # PARKED remove for member id 0 (even -> shard 0) under an ahead
    # clock.
    m = set_map()
    op = m.update(
        "p", m.len().derive_add_ctx("alpha"), lambda s, c: s.add("x", c)
    )
    m.apply(op)
    from crdt_tpu.pure.orswot import Rm as ORm

    ahead = VClock({"alpha": 9})
    rm = m.update(
        "p", m.len().derive_add_ctx("beta"),
        lambda s, c: ORm(clock=ahead.clone(), members=("w",)),
    )
    m.apply(rm)
    b = BatchedSparseMapOrswot.from_pure(
        [m], span=4, dot_cap=16, rm_width=8, key_rm_width=8,
        keys=None, members=None, actors=None,
    )
    # Sanity on the shard split premise: the live dot and the parked
    # entry sit in different residue classes.
    st = jax.device_get(jax.tree.map(lambda x: x[0], b.state))
    live_eids = st.core.eid[st.core.valid].tolist()
    parked = [int(e) for e in st.core.didx[st.core.dvalid].ravel() if e >= 0]
    assert parked and live_eids
    assert {e % 2 for e in live_eids} != {e % 2 for e in parked}

    mesh = make_mesh(4, 2)
    sharded = split_nested(b.state, 2)
    out, of = mesh_fold_sparse_map(sharded, mesh, span=b.span)
    assert not bool(jnp.any(of))
    o = jax.device_get(out)
    surviving = [
        int(e)
        for shard in range(2)
        for e in o.core.didx[shard][o.core.dvalid[shard]].ravel()
        if e >= 0
    ]
    assert surviving == parked, (surviving, parked)


@given(seeds)
@settings(max_examples=6, deadline=None)
def test_sparse_ring_gossip_converges_to_fold(seed):
    """mesh_gossip_sparse: P-1 unit-shift rounds leave every device row
    equal to the full join (bounded per-link traffic, segment-encoded)."""
    from crdt_tpu.parallel import mesh_gossip_sparse

    rng = random.Random(seed)
    sites = _rand_orswots(rng)
    b = BatchedSparseOrswot.from_pure(sites, dot_cap=64)
    mesh = make_mesh(4, 2)

    folded, _ = sp_ops.fold(b.state)
    gossiped, of = mesh_gossip_sparse(b.state, mesh)
    assert not bool(jnp.any(of))
    f = jax.device_get(folded)
    g = jax.device_get(gossiped)
    for row in range(np.asarray(g.top).shape[0]):
        for leaf_g, leaf_f in zip(jax.tree.leaves(g), jax.tree.leaves(f)):
            np.testing.assert_array_equal(np.asarray(leaf_g)[row], leaf_f)
