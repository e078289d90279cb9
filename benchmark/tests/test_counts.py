"""The copied generators and the roofline byte counts, checked by hand at
a tiny size."""

import numpy as np

import roofline
from gen import tenant_ops


def test_roofline_bytes_by_hand():
    # 2 replicas x 3 elements x 2 actors: each state 2 + 6 = 8 words.
    assert roofline.fold_fused_bytes(2, 3, 2) == 2 * 8 * 4 + 8 * 4
    # 5 slots of 4 clocks: read 4+4 words and a flag, write 2 words and 3 flags.
    assert roofline.wire_pack_bytes(5, 4) == 5 * ((8 * 4 + 1) + (2 * 4 + 3))
    assert roofline.wire_pack_bytes(1, 3) == (6 * 4 + 1) + (2 * 4 + 3)


def test_fnv_matches_ycsb():
    # YCSB Utils.fnvhash64(0): eight zero octets through FNV-1a 64.
    h = 0xCBF29CE484222325
    for _ in range(8):
        h = (h * 1099511628211) % (1 << 64)
    want = h - (1 << 64) if h >= 1 << 63 else h
    assert tenant_ops.fnv1a64(np.array([0]))[0] == abs(want)


def test_zipfian_head_share():
    """Rank 0 takes 1/zeta(n) of the draws (YCSB's first branch)."""
    ranks = tenant_ops.zipfian_ranks(np.random.default_rng(0), 200_000)
    share = np.mean(ranks == 0)
    assert abs(share - 1 / tenant_ops.YCSB_ZETAN) < 0.003
    assert ranks.min() >= 0 and ranks.max() < tenant_ops.YCSB_ITEMS


def test_schedule_same_seed_same_ops():
    kw = dict(n_keys=1000, elems=16, theta=0.99, add_share=0.85,
              elems_per_op=6, rate=100.0)
    a = tenant_ops.schedule(5, 500, **kw)
    b = tenant_ops.schedule(5, 500, **kw)
    c = tenant_ops.schedule(6, 500, **kw)
    assert np.array_equal(a.tenant, b.tenant) and np.array_equal(a.mask, b.mask)
    assert np.array_equal(a.due, b.due)
    assert not np.array_equal(a.tenant, c.tenant)
    assert len(a) == len(c) == 500
    assert 0.8 < a.is_add.mean() < 0.9


def test_writer_rows_by_hand():
    import jax.numpy as jnp

    from gen.replicas import Population

    gen = Population(1, replicas=8, elems=64, actors=4, deferred_cap=2,
                     counter_max=100, writer_elems=2)
    base = gen.base()
    adds, rms = gen.targets(3)
    assert int(adds.sum()) == 8 and int(rms.sum()) == 8
    assert not bool(jnp.any(adds & rms))
    w = gen.writer_rows(base, 3)
    for a in range(4):
        assert int(w.top[a, a]) == int(base.top[a]) + 1
        for e in np.nonzero(np.asarray(adds[a]))[0]:
            assert int(w.ctr[a, e, a]) == int(base.top[a]) + 1
        for e in np.nonzero(np.asarray(rms[a]))[0]:
            assert not bool(jnp.any(w.ctr[a, e]))
    rows = gen.block(base, 0, 8, 3)
    assert np.array_equal(np.asarray(rows.ctr[2]), np.asarray(w.ctr[1]))  # writer of actor 1
    assert np.array_equal(np.asarray(rows.ctr[1]), np.asarray(base.ctr))


def test_schedule_is_the_seeds_and_scrambled_zipfian():
    kw = dict(n_keys=1000, elems=16, theta=0.99, add_share=0.85, elems_per_op=6)
    a = tenant_ops.schedule(5, 20000, **kw)
    b = tenant_ops.schedule(5, 20000, **kw)
    assert np.array_equal(a.tenant, b.tenant) and np.array_equal(a.mask, b.mask)
    assert a.tenant.max() < 1000 and a.due is None
    # YCSB's hottest item takes 1/zeta(10^10, 0.99) of the draws, about 3.8%.
    top = np.bincount(a.tenant).max() / len(a.tenant)
    assert 0.03 < top < 0.05
