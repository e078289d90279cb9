"""Element sharding for the sparse (segment-encoded) backend.

Round 4's ``mesh_fold_sparse`` reduced the replica axis but left every
segment table replicated across the element axis — the one
representation built for huge universes didn't scale by elements. This module is the missing SP analog: partition each
replica's segment table by ``eid % n_shards``. The restriction of a
sparse ORSWOT to an element subuniverse is itself a sparse ORSWOT, and
every join rule is per-element (cell matching, top subsumption, parked
replay, dedupe-by-clock), so

    restrict(join(a, b), s)  ==  join(restrict(a, s), restrict(b, s))

— shard-local joins are exact, no cross-shard traffic for the flat
type. Per-shard state: the shard's dot lanes, the shard's parked
member-remove entries, and a REPLICATED top clock [A] (tiny; every
shard computes the same max, so it stays consistent).

For the NESTED sparse type (ops/sparse_nest.py) the parked KEY lists
stay replicated across shards (a key's members span all shards) and the
only cross-shard coupling is the scrub's key-liveness test — a psum
over the element axis (``sparse_nest._ids_alive(element_axis=...)``),
mirroring the dense ``ops/nest._any_slots``. Everything else remains
shard-local.

Layout convention: axis 0 = replicas, axis 1 = element shards. Both
mesh axes shard (``P(REPLICA_AXIS, ELEMENT_AXIS)`` on every leaf; the
replicated pieces ride as per-shard copies, which the uniform layout
keeps trivially consistent).
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from .. import telemetry as tele
from ..ops import sparse_nest as nest
from ..ops import sparse_orswot as sp
from ..ops.sparse_nest import SparseNestState
from ..ops.sparse_orswot import SparseOrswotState, _canon, _canon_rmlist
from ..utils.metrics import metrics, observe_depth, state_nbytes
from .anti_entropy import _cached
from .mesh import ELEMENT_AXIS, REPLICA_AXIS


def split_segments(
    state: SparseOrswotState,
    n_shards: int,
    dot_cap: Optional[int] = None,
) -> SparseOrswotState:
    """Partition a (batched) segment table by ``eid % n_shards`` into
    per-shard restrictions: ``[R, ...] -> [R, S, ...]``. ``dot_cap``
    sizes the per-shard lane count (default: the full cap, conservative
    against skew; a uniform universe can safely use ~C/S + slack)."""
    cap = dot_cap or state.eid.shape[-1]

    def restrict(shard: int) -> SparseOrswotState:
        keep = state.valid & (state.eid % n_shards == shard)
        eid, act, ctr, valid, overflow = _canon(
            jnp.where(keep, state.eid, -1),
            jnp.where(keep, state.act, 0),
            jnp.where(keep, state.ctr, 0),
            keep,
            cap,
        )
        if bool(jnp.any(overflow)):
            raise ValueError(
                f"shard {shard}: live dots exceed the per-shard cap {cap}"
            )
        didx = _canon_rmlist(
            jnp.where(
                (state.didx >= 0) & (state.didx % n_shards == shard),
                state.didx,
                -1,
            )
        )
        dvalid = state.dvalid & jnp.any(didx >= 0, axis=-1)
        return SparseOrswotState(
            top=state.top,  # replicated per shard
            eid=eid, act=act, ctr=ctr, valid=valid,
            dcl=jnp.where(dvalid[..., None], state.dcl, 0),
            didx=jnp.where(dvalid[..., None], didx, -1),
            dvalid=dvalid,
        )

    shards = [restrict(s) for s in range(n_shards)]
    return jax.tree.map(lambda *xs: jnp.stack(xs, axis=1), *shards)


def split_nested(
    state: SparseNestState, n_shards: int, dot_cap: Optional[int] = None
) -> SparseNestState:
    """Partition a (batched) nested sparse state: the leaf table splits
    by ``id % n_shards`` (segment ``eid`` for the orswot leaf, cell
    ``kid`` for the register-map leaf), parked KEY lists replicated to
    every shard (``[R, ...] -> [R, S, ...]`` on every leaf)."""
    if isinstance(state.core, SparseNestState):
        core = split_nested(state.core, n_shards, dot_cap)
    elif hasattr(state.core, "kid"):  # sparse register-map leaf
        core = split_cells(state.core, n_shards, dot_cap)
    else:
        core = split_segments(state.core, n_shards, dot_cap)
    rep = lambda x: jnp.repeat(x[:, None], n_shards, axis=1)
    return SparseNestState(
        core=core, kcl=rep(state.kcl), kidx=rep(state.kidx),
        kdvalid=rep(state.kdvalid),
    )


def mesh_fold_sparse_nested_sharded(states, mesh: Mesh, level):
    """Converge a leaf-SHARDED sparse NESTED replica batch ``[R, S, ...]``
    (from ``split_nested``; works for any SparseNestLevel composition —
    orswot or register-map leaf) over the mesh. Shard-local joins are
    exact except the scrub's key-liveness test, which psums across the
    element axis. Returns ``(state [S, ...], flags[L+1])``."""
    spans, core = [], level
    while hasattr(core, "core"):
        spans.append(str(core.span))
        core = core.core
    return _sharded_fold(
        f"sparse_nested_sharded_{'x'.join(spans)}"
        f"_s{getattr(core, 'sibling_cap', 0)}",
        states, mesh,
        partial(level.join, element_axis=ELEMENT_AXIS),
        partial(level.fold, element_axis=ELEMENT_AXIS),
        nest._sparse_identity_like,
    )


def _all_specs(state, lead=(REPLICA_AXIS, ELEMENT_AXIS)):
    return jax.tree.map(lambda _: P(*lead), state)


def _pad_replica_axis(state, rsize: int, make_identity):
    lead = jax.tree.leaves(state)[0].shape[0]
    pad = (-lead) % rsize
    if not pad:
        return state
    ident = make_identity(pad)
    return jax.tree.map(
        lambda x, p: jnp.concatenate([x, p.astype(x.dtype)], axis=0),
        state, ident,
    )


def _sharded_fold(
    kind: str,
    states,
    mesh: Mesh,
    join_fn,
    fold_fn,
    identity_fix,
    cache_extra: tuple = (),
):
    """Shared scaffold for every element-sharded mesh fold: replica-axis
    identity padding, shard-axis check, shard-local fold + replica-axis
    lattice all-reduce inside shard_map, overflow psum over BOTH axes,
    metrics. ``identity_fix(tree)`` repairs -1 id-lane conventions on a
    zeros-built padding batch; ``join_fn``/``fold_fn`` may close over an
    ``element_axis`` for cross-shard scrubs."""
    s_axis = jax.tree.leaves(states)[0].shape[1]
    if s_axis != mesh.shape[ELEMENT_AXIS]:
        raise ValueError(
            f"state has {s_axis} element shards, mesh axis is "
            f"{mesh.shape[ELEMENT_AXIS]}"
        )
    states = _pad_replica_axis(
        states, mesh.shape[REPLICA_AXIS],
        lambda pad: identity_fix(jax.tree.map(
            lambda x: jnp.zeros((pad, *x.shape[1:]), x.dtype), states
        )),
    )

    def build():
        @partial(
            jax.shard_map,
            mesh=mesh,
            in_specs=(_all_specs(states),),
            out_specs=(_all_specs(states, (ELEMENT_AXIS,)), P()),
            check_vma=False,
        )
        def fold_fn_mesh(local):
            local = jax.tree.map(lambda x: x[:, 0], local)  # drop shard axis
            folded, of_local = fold_fn(local)
            joined, of_cross = _lattice_allreduce(folded, join_fn, fold_fn)
            of = (
                lax.psum(of_local.astype(jnp.int32), REPLICA_AXIS) > 0
            ) | of_cross
            of = lax.psum(of.astype(jnp.int32), ELEMENT_AXIS) > 0
            return jax.tree.map(lambda x: x[None], joined), of

        return fold_fn_mesh

    metrics.count(f"anti_entropy.{kind}_rounds")
    metrics.observe("anti_entropy.state_bytes", state_nbytes(states))
    observe_depth(f"anti_entropy.{kind}", states)
    with tele.span(f"ae.{kind}"):
        out = _cached(kind, states, mesh, build, *cache_extra)(states)
        jax.block_until_ready(out)
    return out


def mesh_fold_sparse_sharded(
    states: SparseOrswotState, mesh: Mesh
) -> Tuple[SparseOrswotState, jax.Array]:
    """Converge an element-SHARDED sparse replica batch ``[R, S, ...]``
    (from ``split_segments``; S must equal the mesh's element-axis size)
    over the mesh. Shard-local joins are exact (restriction commutes
    with join), so the only collective is the replica-axis lattice
    all-reduce — per-device state and join cost drop by S. Returns
    ``(state [S, ...], overflow[2])`` with the element axis preserved."""
    return _sharded_fold(
        "sparse_sharded_fold", states, mesh, sp.join, sp.fold,
        nest._sparse_identity_like,
    )


def split_cells(
    states, n_shards: int, cell_cap: Optional[int] = None
):
    """Partition a (batched) sparse ``Map<K, MVReg>`` cell table
    (ops/sparse_mvmap.SparseMVMapState) by ``kid % n_shards``:
    ``[R, ...] -> [R, S, ...]``. Keys are wholly within one shard, so
    restriction commutes with the cellwise join — per-cell matching,
    payload winner-select, per-key sibling ranks, and parked keyset
    replay are all key-local; the top clock replicates per shard (every
    shard computes the same max). Parked key LISTS partition with their
    keys (an entry k only ever kills cells with kid == k)."""
    from ..ops import sparse_mvmap as smv

    cap = cell_cap or states.kid.shape[-1]

    def restrict(shard: int):
        keep = states.valid & (states.kid % n_shards == shard)
        kid, act, ctr, val, clk, valid, overflow = smv._canon(
            jnp.where(keep, states.kid, -1),
            jnp.where(keep, states.act, 0),
            jnp.where(keep, states.ctr, 0),
            jnp.where(keep, states.val, 0),
            jnp.where(keep[..., None], states.clk, 0),
            keep,
            cap,
        )
        if bool(jnp.any(overflow)):
            raise ValueError(
                f"shard {shard}: live cells exceed the per-shard cap {cap}"
            )
        kidx = _canon_rmlist(
            jnp.where(
                (states.kidx >= 0) & (states.kidx % n_shards == shard),
                states.kidx,
                -1,
            )
        )
        dvalid = states.dvalid & jnp.any(kidx >= 0, axis=-1)
        return smv.SparseMVMapState(
            top=states.top,  # replicated per shard
            kid=kid, act=act, ctr=ctr, val=val, clk=clk, valid=valid,
            dcl=jnp.where(dvalid[..., None], states.dcl, 0),
            kidx=jnp.where(dvalid[..., None], kidx, -1),
            dvalid=dvalid,
        )

    shards = [restrict(s_) for s_ in range(n_shards)]
    return jax.tree.map(lambda *xs: jnp.stack(xs, axis=1), *shards)


def mesh_fold_sparse_mvmap_sharded(
    states, mesh: Mesh, sibling_cap: int = 4
):
    """Converge a key-SHARDED sparse ``Map<K, MVReg>`` replica batch
    ``[R, S, ...]`` (from ``split_cells``) over the mesh — the SP
    analog for the register family. Shard-local joins are exact, so the
    only collective is the replica-axis lattice all-reduce; per-device
    state and join cost drop by S. Returns ``(state [S, ...],
    overflow[3])``."""
    from ..ops import sparse_mvmap as smv

    return _sharded_fold(
        f"sparse_mvmap_sharded_fold_s{sibling_cap}", states, mesh,
        partial(smv.join, sibling_cap=sibling_cap),
        partial(smv.fold, sibling_cap=sibling_cap),
        nest._sparse_identity_like,
    )


def mesh_fold_sparse_map(
    states: SparseNestState, mesh: Mesh, span: int
) -> Tuple[SparseNestState, jax.Array]:
    """Converge an element-sharded SPARSE ``Map<K, Orswot>`` replica
    batch ``[R, S, ...]`` (from ``split_nested``) over the mesh. The
    nested join runs shard-local except the scrub's key-liveness psum
    across the element axis. ``span`` is the level's static leaf-ids-
    per-key constant (``BatchedSparseMapOrswot.span``). Returns
    ``(state [S, ...], overflow[3])``."""
    level = nest.level_map_orswot(span)
    return _sharded_fold(
        "sparse_map_fold", states, mesh,
        partial(level.join, element_axis=ELEMENT_AXIS),
        partial(level.fold, element_axis=ELEMENT_AXIS),
        nest._sparse_identity_like,
        cache_extra=(span,),
    )


def _lattice_allreduce(local, join_fn, fold_fn):
    """all_reduce_lattice with array-valued overflow flags."""
    from .collectives import all_reduce_lattice

    return all_reduce_lattice(local, REPLICA_AXIS, join_fn, fold_fn)


# ---- static-analysis registration (crdt_tpu.analysis) --------------------

def _register():
    from ..analysis import gate_states as gs
    from ..analysis.registry import register_entry_point

    def shards(mesh):
        return mesh.shape[ELEMENT_AXIS]

    def reg(name, kind, make_args, invoke):
        register_entry_point(
            name, kind=kind, make_args=make_args, invoke=invoke, n_donated=0
        )

    reg(
        "mesh_fold_sparse_sharded", "sparse_sharded_fold",
        lambda mesh: (split_segments(gs.mk_sparse(gs.replicas(mesh)), shards(mesh)),),
        lambda mesh, args: mesh_fold_sparse_sharded(args[0], mesh),
    )
    reg(
        "mesh_fold_sparse_mvmap_sharded", "sparse_mvmap_sharded_fold_s4",
        lambda mesh: (split_cells(gs.mk_sparse_mvmap(gs.replicas(mesh)), shards(mesh)),),
        lambda mesh, args: mesh_fold_sparse_mvmap_sharded(args[0], mesh),
    )
    reg(
        "mesh_fold_sparse_nested_sharded", f"sparse_nested_sharded_{gs.GM}_s0",
        lambda mesh: (split_nested(gs.mk_sparse_nested(gs.replicas(mesh)), shards(mesh)),),
        lambda mesh, args: mesh_fold_sparse_nested_sharded(
            args[0], mesh, nest.level_map_orswot(gs.GM)
        ),
    )
    reg(
        "mesh_fold_sparse_map", "sparse_map_fold",
        lambda mesh: (split_nested(gs.mk_sparse_nested(gs.replicas(mesh)), shards(mesh)),),
        lambda mesh, args: mesh_fold_sparse_map(args[0], mesh, span=gs.GM),
    )


_register()
