"""Fused pallas fold vs the jnp tree fold — must be bit-identical
(the kernel runs in interpreter mode on CPU; same program on TPU)."""

import random

import pytest

import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings

from crdt_tpu.models.orswot import BatchedOrswot
from crdt_tpu.ops import orswot as ops
from crdt_tpu.ops.pallas_kernels import fold_fused
from crdt_tpu.pure.orswot import Orswot
from crdt_tpu.utils.metrics import metrics

from strategies import seeds
from test_fault_injection import _mint_streams


@given(seeds)
@settings(max_examples=10, deadline=None)
def test_fused_fold_matches_tree_fold(seed):
    rng = random.Random(seed)
    n = rng.randint(2, 7)
    sites, _ = _mint_streams(rng, n, 16)
    model = BatchedOrswot.from_pure(sites)

    tree, of_tree = ops.fold(model.state)
    fused, of_fused = fold_fused(model.state, tile_e=4)
    assert bool(of_tree) == bool(of_fused)
    for name in ("top", "ctr", "dvalid"):
        np.testing.assert_array_equal(
            np.asarray(getattr(tree, name)), np.asarray(getattr(fused, name)),
            err_msg=name,
        )
    # deferred slots: same live set (slot order may differ)
    def live(s):
        out = set()
        for i in np.nonzero(np.asarray(s.dvalid))[0]:
            out.add((tuple(np.asarray(s.dcl)[i]), tuple(np.asarray(s.dmask)[i])))
        return out
    assert live(tree) == live(fused)


@given(seeds)
@settings(max_examples=8, deadline=None)
def test_fused_fold_matches_oracle(seed):
    rng = random.Random(seed)
    n = rng.randint(2, 6)
    sites, _ = _mint_streams(rng, n, 14)
    model = BatchedOrswot.from_pure(sites)
    fused, of = fold_fused(model.state, tile_e=8)
    assert not bool(of)

    out = BatchedOrswot(
        1, fused.ctr.shape[-2], fused.ctr.shape[-1], fused.dcl.shape[-2],
        members=model.members, actors=model.actors,
    )
    out.state = jax.tree.map(lambda x: x[None], fused)
    oracle = sites[0].clone()
    for s in sites[1:]:
        oracle.merge(s.clone())
    assert out.to_pure(0) == oracle


def test_fused_fold_with_parked_removes():
    # A remove parked ahead of every top must replay against the folded
    # entries exactly as the tree fold does.
    a = Orswot()
    op_add = a.add("m", a.read().derive_add_ctx("x"))
    a.apply(op_add)
    b = Orswot()
    rm = a.rm("m", a.contains("m").derive_rm_ctx())
    # also cover dots b never saw: bump the clock past b's view
    a.apply(a.add("m2", a.read().derive_add_ctx("x")))
    b.apply(rm)  # parked on b
    model = BatchedOrswot.from_pure([a, b])
    tree, _ = ops.fold(model.state)
    fused, _ = fold_fused(model.state, tile_e=2)
    np.testing.assert_array_equal(np.asarray(tree.ctr), np.asarray(fused.ctr))
    np.testing.assert_array_equal(np.asarray(tree.top), np.asarray(fused.top))


# ---- the epilogue's skip when no parked slot is valid --------------------

_PR, _PD, _PE, _PA = 32, 4, 16, 4  # R·D = 128 slots


def _parked_population(seed, n_valid, live):
    """R = 32 replicas of one causal history (actor a's counter c born on
    element ``born[a, c]``; a replica holds the newest dot of each actor
    on each element up to its top) with ``n_valid`` valid parked slots of
    D = 4. Their clocks repeat across replicas (dedupe): ``live`` clocks
    ahead of every top, and two that the folded top covers (replayed,
    then dropped). The tree fold's root joins the even replicas' fold
    with the odd ones', so each parity draws from at most D clocks: no
    join below the root truncates, and ``live`` = D + 1 overflows at the
    root alone. Invalid slots hold stale payload."""
    rng = np.random.default_rng(seed)
    c = np.arange(17)
    top = rng.integers(4, 17, (_PR, _PA))
    top[0] = 16
    born = rng.integers(0, _PE, (_PA, 17))
    seen = ((born[None, None] == np.arange(_PE)[None, :, None, None])
            & (c > 0) & (c <= top[:, None, :, None]))
    ctr = np.where(seen, c, 0).max(axis=-1)
    dcl = rng.integers(0, 17, (_PR, _PD, _PA))
    dmask = rng.random((_PR, _PD, _PE)) < 0.2
    dvalid = np.zeros((_PR, _PD), bool)
    ahead = rng.integers(0, 9, (live, _PA))
    ahead[np.arange(live), rng.integers(0, _PA, live)] = 17 + np.arange(live)
    caught = list(rng.integers(0, 9, (2, _PA)))
    if live >= _PD:
        pools = (list(ahead[:_PD - 1]), list(ahead[live - _PD + 1:]))
    else:
        pools = (list(ahead), list(ahead))
    slots = np.arange(_PR * _PD).reshape(_PR, _PD)
    for parity, count in ((0, n_valid // 2), (1, n_valid - n_valid // 2)):
        pool = pools[parity] + [caught[parity]]
        for i, p in enumerate(rng.permutation(slots[parity::2].ravel())[:count]):
            dcl[p // _PD, p % _PD] = pool[i % len(pool)]
            dvalid[p // _PD, p % _PD] = True
    u32 = lambda x: jnp.asarray(x, jnp.uint32)  # noqa: E731
    return ops.OrswotState(top=u32(top), ctr=u32(ctr), dcl=u32(dcl),
                           dmask=jnp.asarray(dmask), dvalid=jnp.asarray(dvalid))


def _full_epilogue(states):
    """The fused fold's epilogue over every R·D slot, never skipped, on
    the tree fold's join of the entries alone."""
    from crdt_tpu.ops.orswot import _apply_parked, _compact_deferred, _dedupe_deferred

    r, d = states.dvalid.shape
    e, a = states.ctr.shape[-2:]
    entries, _ = ops.fold(states._replace(dvalid=jnp.zeros_like(states.dvalid)))
    dcl, dmask, dvalid = _dedupe_deferred(
        states.dcl.reshape(r * d, a), states.dmask.reshape(r * d, e),
        states.dvalid.reshape(r * d))
    ctr = _apply_parked(entries.ctr, dcl, dmask, dvalid)
    dvalid = dvalid & ~jnp.all(dcl <= entries.top[None, :], axis=-1)
    dcl, dmask, dvalid, over = _compact_deferred(dcl, dmask, dvalid, d)
    return ops.OrswotState(entries.top, ctr, dcl, dmask, dvalid), over


def _slot_rows(state):
    """The parked slots as a sorted list of (valid, clock, mask) rows."""
    return sorted(
        (bool(v), tuple(c), tuple(m)) for v, c, m in zip(
            np.asarray(state.dvalid), np.asarray(state.dcl).tolist(),
            np.asarray(state.dmask).tolist()))


@pytest.mark.parametrize("n_valid,live", [
    (0, 0), (1, 1), (63, 4), (64, 4), (64, 5), (65, 4), (65, 5), (128, 4), (128, 5),
])
def test_fused_fold_epilogue_matches_tree_fold(n_valid, live):
    """From no valid slot (the skipped epilogue) to all R·D of them,
    ``fold_fused`` equals the tree fold on top, ctr, dvalid and the
    overflow flag, and equals the epilogue run over all R·D slots bit for
    bit, slot order included. ``live`` D or D + 1 clocks survive the
    catch-up drop; at D + 1 the flag is up, and which D slots stay is the
    flat order's (the tree's order differs)."""
    for seed in (n_valid, 1000 + n_valid):
        states = _parked_population(seed, n_valid, live)
        got, over = fold_fused(states, tile_e=8, interpret=True)
        tree, tree_over = ops.fold(states)
        full, full_over = _full_epilogue(states)
        assert bool(over) == bool(tree_over) == bool(full_over) == (live > _PD)
        assert int(np.asarray(got.dvalid).sum()) == min(live, _PD)
        for name in got._fields:
            np.testing.assert_array_equal(
                np.asarray(getattr(got, name)), np.asarray(getattr(full, name)),
                err_msg=name)
        for name in ("top", "ctr", "dvalid"):
            np.testing.assert_array_equal(
                np.asarray(getattr(got, name)), np.asarray(getattr(tree, name)),
                err_msg=name)
        if live <= _PD:
            assert _slot_rows(got) == _slot_rows(tree)


def _eqns(jaxpr):
    """Every equation of a jaxpr and of its sub-jaxprs, outside any
    ``pallas_call`` (the kernel body is not the epilogue)."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call":
            continue
        for param in eqn.params.values():
            for sub in param if isinstance(param, (tuple, list)) else (param,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub)


@pytest.mark.parametrize("r", [32, 8])
def test_fused_fold_epilogue_lowers_to_one_conditional(r):
    """The epilogue lowers for the chip to one conditional beside the
    kernel: its skip branch holds no dot, and every dot of the settling
    branch spans the R·D slot axis."""
    from crdt_tpu.ops.pallas_kernels import _fold_fused_jit

    d = 4
    states = jax.eval_shape(lambda: ops.empty(256, 4, d, batch=(r,)))
    text = _fold_fused_jit.trace(states, 128, 8, False).lower(
        lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" in text
    assert text.count("stablehlo.case") == 1

    jaxpr = jax.make_jaxpr(lambda s: _fold_fused_jit(s, 128, 8, False))(states)
    conds = [e for e in _eqns(jaxpr.jaxpr) if e.primitive.name == "cond"]
    assert len(conds) == 1
    skip, settle = conds[0].params["branches"]  # lax.cond(p, settle, skip)
    dots = lambda j: [e for e in _eqns(j.jaxpr) if e.primitive.name == "dot_general"]  # noqa: E731
    assert not dots(skip)
    assert dots(settle)
    assert all(any(r * d in v.aval.shape for v in e.invars) for e in dots(settle))


@pytest.mark.parametrize("n_valid,live", [(0, 0), (128, 4)])
def test_mesh_fold_fused_settles_parked_removes(n_valid, live):
    """``mesh_fold`` with the fused local fold on a 1×1 mesh answers as
    the tree fold does, with no valid slot and with every slot valid."""
    from crdt_tpu.parallel import anti_entropy, make_mesh

    states = _parked_population(7, n_valid, live)
    out, over = anti_entropy.mesh_fold(states, make_mesh(1, 1), local_fold="fused")
    tree, tree_over = ops.fold(states)
    assert bool(over) == bool(tree_over)
    for name in ("top", "ctr", "dvalid"):
        np.testing.assert_array_equal(
            np.asarray(getattr(out, name)), np.asarray(getattr(tree, name)),
            err_msg=name)
    assert _slot_rows(out) == _slot_rows(tree)


def test_fold_auto_rejects_unknown_prefer():
    from crdt_tpu.ops import orswot as oo
    from crdt_tpu.ops.pallas_kernels import fold_auto

    state = oo.empty(4, 2, deferred_cap=2, batch=(2,))
    with pytest.raises(ValueError):
        fold_auto(state, prefer="pallas")


# ---- fused folds for the composition layer (pallas_kernels.fold_fused_*) --

from crdt_tpu.models import BatchedMap, BatchedMapOrswot
from crdt_tpu.ops import map as map_ops
from crdt_tpu.ops import map3 as m3
from crdt_tpu.ops import map_map as mm
from crdt_tpu.ops import map_orswot as mo
from crdt_tpu.ops.pallas_kernels import fold_fused_level, fold_fused_map
from crdt_tpu.utils import Interner

from strategies import ACTORS
from test_map import _site_run as _map_site_run, mv_map
from test_models_map3 import _batched as _m3_batched, _site_run as _m3_site_run
from test_models_map_nested import (
    KEYS,
    MEMBERS,
    _nbatched,
    _site_run_nested,
    _site_run_set,
)

def _tree_eq(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@given(seeds)
@settings(max_examples=8, deadline=None)
def test_fused_map_fold_matches_tree_fold(seed):
    """Map<K, MVReg>: the dense cell-granular kernel + winner-select
    payload epilogue == the slot-table log-tree fold, on reachable
    states (incl. parked keyset-removes)."""
    rng = random.Random(seed)
    states = _map_site_run(rng, mv_map, n_cmds=14)
    model = BatchedMap.from_pure(
        states, keys=Interner(list("pq")),
        actors=Interner(ACTORS + ["A", "B", "C"]),
        sibling_cap=12, deferred_cap=12,
    )
    tree, oft = map_ops._tree_fold(model.state)
    fused, off = fold_fused_map(model.state, tile_e=2)
    assert bool(oft.any()) == bool(off.any())
    _tree_eq(tree, fused)


@given(seeds)
@settings(max_examples=8, deadline=None)
def test_fused_map_orswot_fold_matches_tree_fold(seed):
    """Map<K, Orswot>: the generic level-fused fold == the tree fold on
    reachable states (both deferred levels carried)."""
    rng = random.Random(seed)
    states = _site_run_set(rng, n_cmds=14)
    model = BatchedMapOrswot.from_pure(
        states, deferred_cap=12,
        keys=Interner(KEYS), members=Interner(MEMBERS),
        actors=Interner(ACTORS + ["A", "B", "C"]),
    )
    tree, oft = mo.LEVEL.fold(model.state)
    fused, off = fold_fused_level(mo.LEVEL, model.state, tile_e=2)
    assert bool(oft.any()) == bool(off.any())
    _tree_eq(tree, fused)


@given(seeds)
@settings(max_examples=6, deadline=None)
def test_fused_map3_fold_matches_tree_fold(seed):
    """Depth-3: the level-fused fold settles all THREE deferred levels
    identically to the tree fold."""
    rng = random.Random(seed)
    states = _m3_site_run(rng, n_cmds=14)
    model = _m3_batched(states)
    tree, oft = m3.LEVEL.fold(model.state)
    fused, off = fold_fused_level(m3.LEVEL, model.state, tile_e=2)
    assert bool(oft.any()) == bool(off.any())
    _tree_eq(tree, fused)


@given(seeds)
@settings(max_examples=5, deadline=None)
def test_fused_nested_map_fold_matches_tree_fold(seed):
    """Map<K1, Map<K2, MVReg>>: the MVReg-leaf level-fused fold == the
    tree fold (dense leaf kernel + outer settle)."""
    rng = random.Random(seed)
    states = _site_run_nested(rng, n_cmds=12)
    model = _nbatched(states)
    tree, oft = mm.LEVEL.fold(model.state)
    fused, off = fold_fused_level(mm.LEVEL, model.state, tile_e=2)
    assert bool(oft.any()) == bool(off.any())
    _tree_eq(tree, fused)


# ---- the chunked Map<K, MVReg> fold against the tree fold and the plain
# reference (benchmark/reference/map_mvreg.py) ------------------------------

def _bench_module(sub: str, name: str):
    """``benchmark/<sub>/<name>.py``, loaded by path (the benchmark's
    directories are not packages)."""
    import importlib.util
    import os

    path = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                        "benchmark", sub, f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_{sub}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


MAP_K, MAP_A, MAP_S, MAP_D = 64, 4, 2, 4


def _sim_rows(ref, rng, r):
    """``r`` replica states of a random causal history: one site per
    actor puts (its next dot, its clock), removes keys under its clock,
    receives other sites' removes late (they park while ahead) and merges
    other sites. Rows are snapshots. Two actors write each key and two
    removes (minted halfway) travel late, so no union of rows holds more siblings than the
    slots or more parked clocks than the cap: the tree fold's per-join
    capacity checks then cut nothing (the overflow cases are their own)."""
    sites = [ref.Row(MAP_K, MAP_A) for _ in range(MAP_A)]
    minted, snaps = [], []
    for step in range(160):
        i = int(rng.integers(MAP_A))
        site = sites[i]
        roll = rng.random()
        if roll < 0.45:
            c = int(site.top[i]) + 1
            clock = site.top.copy()
            clock[i] = c
            key = 2 * int(rng.integers(MAP_K // 4)) + i // 2
            site.put(i, c, key, clock, int(rng.integers(1 << 20)))
        elif roll < 0.6:
            keys = rng.choice(MAP_K // 2, size=int(rng.integers(1, 4)), replace=False)
            site.rm(site.top.copy(), keys)
            if step >= 80 and len(minted) < 2:
                minted.append((site.top.copy(), keys))
        elif roll < 0.75 and minted:
            site.rm(*minted[int(rng.integers(len(minted)))])
        else:
            site.merge(sites[int(rng.integers(MAP_A))].clone())
        snaps.append(site.clone())
    rows = [snaps[int(j)].clone() for j in rng.integers(len(snaps), size=r)]
    parked = [s for s in snaps if s.parked]
    if parked:   # the first row holds parked removes
        rows[0] = parked[int(rng.integers(len(parked)))].clone()
    return rows


def _siblings_over_rows(ref, rng, r):
    """Three actors put concurrently on key 0; rows hold one or two of
    the siblings (at the cap), so their union overflows it."""
    sites = [ref.Row(MAP_K, MAP_A) for _ in range(3)]
    for i, site in enumerate(sites):
        for key in (0, 1 + i):
            c = int(site.top[i]) + 1
            clock = site.top.copy()
            clock[i] = c
            site.put(i, c, key, clock, 10 * i + key)
    pair = sites[0].clone()
    pair.merge(sites[1])
    rows = [sites[0], sites[1], sites[2], pair]
    return [rows[j % 4].clone() if j % 5 != 4 else ref.Row(MAP_K, MAP_A) for j in range(r)]


def _parked_over_rows(ref, rng, r):
    """Five removes park under distinct clocks ahead of every top, three
    in the first row and two in the last, on keys nobody writes."""
    rows = [ref.Row(MAP_K, MAP_A) for _ in range(r)]
    for j, row in enumerate(rows[1:-1]):
        c = int(row.top[j % MAP_A]) + 1
        clock = row.top.copy()
        clock[j % MAP_A] = c
        row.put(j % MAP_A, c, j % 8, clock, j)
    for n, row in ((0, rows[0]), (3, rows[0]), (1, rows[0]), (4, rows[-1]), (2, rows[-1])):
        clock = np.zeros(MAP_A, np.uint32)
        clock[3] = 50 + n
        row.rm(clock, [MAP_K - 1 - n])
    return rows


def _map_state(ref, rows):
    """Host reference rows as one batched ``MapState``."""
    from crdt_tpu.ops.mvreg import MVRegState

    tops, cols, dcl, dkeys, dvalid = [], [], [], [], []
    for row in rows:
        top, c, parked, _ = row.canonical(MAP_S)
        tops.append(top)
        cols.append(c)
        cl = np.zeros((MAP_D, MAP_A), np.uint32)
        ks = np.zeros((MAP_D, MAP_K), bool)
        for s, (clock, keys) in enumerate(sorted(parked, key=lambda p: p[0])):
            cl[s] = clock
            ks[s, sorted(keys)] = True
        dcl.append(cl)
        dkeys.append(ks)
        dvalid.append(np.arange(MAP_D) < len(parked))
    stack = lambda xs: jnp.asarray(np.stack(xs))  # noqa: E731
    child = MVRegState(*[stack([c[i] for c in cols]) for i in range(5)])
    return map_ops.MapState(top=stack(tops), child=child, dcl=stack(dcl),
                            dkeys=stack(dkeys), dvalid=stack(dvalid))


@pytest.mark.parametrize("r,rows,flavor,seed", [
    (1, 1, "history", 0), (5, 4, "history", 1), (13, 4, "history", 2),
    (64, 16, "history", 3), (5, 2, "siblings_over", 0), (13, 8, "parked_over", 0),
])
def test_chunked_map_fold_matches_tree_fold_and_reference(monkeypatch, r, rows, flavor, seed):
    """The map fold in replica chunks of ``rows`` (the HBM budget set so
    that a chunk is that many replicas) is bit-identical to the slot-table
    tree fold, both overflow flags included, and agrees with the plain
    reference's merge of every replica."""
    from crdt_tpu.ops import pallas_kernels as pk

    ref = _bench_module("reference", "map_mvreg")
    rng = np.random.default_rng(seed)
    make = {"history": _sim_rows, "siblings_over": _siblings_over_rows,
            "parked_over": _parked_over_rows}[flavor]
    host = make(ref, rng, r)
    state = _map_state(ref, host)
    row_bytes = 8 * 128 * 4   # one replica's wide counters at A = 4, K = 64
    monkeypatch.setattr(pk, "_MAP_CHUNK_BUDGET", rows * row_bytes)
    assert pk._map_chunk_rows(r, MAP_A, MAP_K) == min(rows, r)

    fused, of_fused = fold_fused_map(state, tile_e=32, interpret=True)
    gauges = metrics.snapshot()["gauges"]
    assert gauges["pallas.map_fold.chunk_rows"]["last"] == min(rows, r)
    assert gauges["pallas.map_fold.chunks"]["last"] == -(-r // rows)
    tree, of_tree = map_ops._tree_fold(state)
    np.testing.assert_array_equal(np.asarray(of_fused), np.asarray(of_tree))
    _tree_eq(tree, fused)

    want = host[0].clone()
    for row in host[1:]:
        want.merge(row)
    top, cols, parked, sib_over = want.canonical(MAP_S)
    assert [bool(x) for x in np.asarray(of_fused)] == [sib_over, len(parked) > MAP_D]
    np.testing.assert_array_equal(np.asarray(fused.top), top)
    for got, exp in zip(fused.child, cols):
        np.testing.assert_array_equal(np.asarray(got), exp)
    got_parked = {(tuple(int(x) for x in cl), frozenset(np.nonzero(np.asarray(ks))[0].tolist()))
                  for cl, ks, v in zip(np.asarray(fused.dcl), np.asarray(fused.dkeys),
                                       np.asarray(fused.dvalid)) if v}
    if len(parked) <= MAP_D:
        assert got_parked == parked
    else:
        assert len(got_parked) == MAP_D and got_parked <= parked
    at_cap = np.asarray(fused.child.valid).all(axis=-1)
    assert {"history": r == 1 or (at_cap.any() and not at_cap.all()
                                  and any(row.parked for row in host)),
            "siblings_over": sib_over and at_cap[0],
            "parked_over": len(parked) > MAP_D}[flavor]
    if not any(row.parked for row in host):
        # The device reference fold the chip check runs agrees too.
        (rtop, rcols), over = ref.fold_rows(state.top, tuple(state.child))
        np.testing.assert_array_equal(np.asarray(rtop), top)
        assert (int(over) > 0) == sib_over
        for got, exp in zip(rcols, cols):
            np.testing.assert_array_equal(np.asarray(got), exp)


def test_mesh_fold_map_fused_matches_reference_on_a_traffic_round(monkeypatch):
    """``mesh_fold_map`` on a (1, 1) mesh, its local fold forced onto the
    chunked fused path, against the plain reference on one round of the
    ``map_mvreg_fold_1chip`` cell's traffic at a tiny size."""
    from crdt_tpu.ops import pallas_kernels as pk
    from crdt_tpu.ops.mvreg import MVRegState
    from crdt_tpu.parallel import anti_entropy, make_mesh

    gen_mod = _bench_module("gen", "map_replicas")
    ref = _bench_module("reference", "map_mvreg")

    fold = map_ops.fold
    monkeypatch.setattr(map_ops, "fold", lambda st, prefer="auto": fold(st, prefer="fused"))
    monkeypatch.setattr(anti_entropy, "_FN_CACHE", {})
    monkeypatch.setattr(pk, "_MAP_CHUNK_BUDGET", 4 * 8 * 2048 * 4)   # 4 replicas a chunk
    gen = gen_mod.Population((1 << 33) + 9, replicas=16, keys=2048, actors=8,
                             sibling_cap=2, deferred_cap=4, counter_max=100,
                             writer_puts=4, writer_removes=4)
    rows = gen.block(gen.base(), 0, gen.r, 3)
    cols = (rows.wact, rows.wctr, rows.clk, rows.val, rows.valid)
    state = map_ops.MapState(top=rows.top, child=MVRegState(*cols), dcl=rows.dcl,
                             dkeys=rows.dkeys, dvalid=rows.dvalid)
    out, of = anti_entropy.mesh_fold_map(state, make_mesh(1, 1))
    (top, want), over = ref.fold_rows(rows.top, cols)
    assert not np.asarray(of).any() and int(over) == 0
    np.testing.assert_array_equal(np.asarray(out.top), np.asarray(top))
    for got, exp in zip(out.child, want):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(exp))
    assert not np.asarray(out.dvalid).any() and not np.asarray(out.dkeys).any()
    # The round's writes show: each pair's shared keys hold both siblings,
    # a removed-only key is gone, a removed-while-put key holds the put.
    puts, removes = (np.asarray(x) for x in gen.targets(3))
    valid = np.asarray(out.child.valid)
    wact = np.asarray(out.child.wact)
    assert valid[puts[0, :gen.shared]].all()
    np.testing.assert_array_equal(wact[puts[0, :gen.shared]], [[0, 1]] * gen.shared)
    assert not valid[removes[0, gen.rm_put:]].any()
    kept = removes[0, :gen.rm_put]
    assert valid[kept, 0].all() and not valid[kept, 1].any() and (wact[kept, 0] == 2).all()


def _slot_order_by_sort(folded_w, s):
    """The canonical slot order by a stable argsort of the dead flags and
    ``take_along_axis`` gathers: the reference for ``_slot_order``."""
    a, k = folded_w.shape
    present = folded_w > 0
    order = jnp.argsort(~present, axis=0, stable=True)
    valid = jnp.take_along_axis(present, order, axis=0)
    wact = jnp.where(valid, order.astype(jnp.int32), 0)
    wctr = jnp.where(valid, jnp.take_along_axis(folded_w, order, axis=0), 0)
    if a >= s:
        return wact[:s], wctr[:s], valid[:s]
    pad = lambda x: jnp.pad(x, ((0, s - a), (0, 0)))  # noqa: E731
    return pad(wact), pad(wctr), pad(valid)


@pytest.mark.parametrize("density", [0.0, 0.2, 0.7, 1.0])
@pytest.mark.parametrize("a,s", [(8, 2), (8, 8), (3, 12), (1, 2), (8, 1), (5, 3)])
def test_slot_order_matches_sort_form(a, s, density):
    """The running-count slot order equals the argsort + gather form bit
    for bit (shapes, dtypes, zeros in dead slots), through the pad (A < S)
    and truncate (A > S) branches and from no live cell to all live."""
    from crdt_tpu.ops.pallas_kernels import _slot_order

    rng = np.random.default_rng(a * 100 + s * 10 + int(density * 10))
    k = 257
    live = rng.random((a, k)) < density
    folded_w = jnp.asarray(np.where(live, rng.integers(1, 1 << 31, (a, k)), 0)
                           .astype(np.uint32))
    got = _slot_order(folded_w, s)
    want = _slot_order_by_sort(folded_w, s)
    for g, w in zip(got, want):
        assert g.shape == w.shape == (s, k) and g.dtype == w.dtype
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    assert int(np.asarray(got[2]).sum()) == int(np.minimum(live.sum(0), s).sum())


def test_slot_order_lowers_without_sort_or_gather():
    """At the map cell's width ([8, 2^20] counters, S = 2) the slot order
    lowers to elementwise work: no sort and no gather, which the TPU runs
    one element at a time. The sort form, lowered the same way, has both."""
    from crdt_tpu.ops.pallas_kernels import _slot_order

    spec = jax.ShapeDtypeStruct((8, 1 << 20), jnp.uint32)
    text = lambda f: jax.jit(lambda w: f(w, 2)).lower(spec).as_text().lower()  # noqa: E731
    new = text(_slot_order)
    assert "sort" not in new and "gather" not in new
    old = text(_slot_order_by_sort)
    assert "sort" in old and "gather" in old
