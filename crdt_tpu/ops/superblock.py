"""Tenant-packed superblock kernels — many independent CRDTs, one
dispatch (ROADMAP item 1, ISSUE 15).

Everything before this module batches *replicas of one object*; the
production workload the north star names is millions of SMALL
INDEPENDENT objects (per-user carts, presence sets, doc cursors), each
a few dots wide. Dispatching one kernel per tenant would drown the
device in launch overhead, so the superblock prepends a TENANT axis to
an existing per-kind state layout — ``T`` independent ORSWOTs live in
one device-resident pytree of ``[T, ...]`` planes — and applies a whole
coalesced batch of per-tenant CmRDT ops as ONE program:

    gather touched rows -> scan S sequential op slots, each a vmapped
    per-tenant apply -> scatter rows back (conflict-free by the ingest
    contract below).

The op container is :class:`OpSlab`: ``B`` tenant lanes × ``S``
sequential slots. Within one slab a tenant occupies AT MOST ONE lane
(the host-side ingest queue — crdt_tpu/serve/ingest.py — enforces it),
so the row scatter has unique targets; a lane's ``S`` slots apply in
submission order, which is exactly why the coalesced apply is
bit-identical to the per-tenant sequential oracle (tests/test_serve.py
pins it for the dense AND sparse kinds). Tenants are INDEPENDENT —
no cross-tenant lattice traffic exists, so the tenant axis shards
embarrassingly over the replica mesh axis
(crdt_tpu/parallel/serve_apply.py).

Per-kind support rides a small adapter table (:data:`TENANT_KINDS`)
over the already-registered op kernels — the superblock is a PRODUCT
of registered lattices, not a new lattice, so it registers no new
merge kind (the per-tenant joins are the registered ``orswot`` /
``sparse_orswot`` kinds the law engine and SEC checker already cover);
its own coverage contract is the ``serve`` static-check section plus
the ``mesh_serve_apply`` entry-point registration.

Capacity is elastic PER SUPERBLOCK: ``widen``/``narrow`` lift the
per-kind elastic kernels (PR 1/5) over the tenant axis — one repack
migrates every tenant at once. Causal-stability compaction lifts the
same way (:func:`compact_tenants`).
"""

from __future__ import annotations

from typing import Callable, Dict, NamedTuple, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from . import orswot as dense_ops
from . import sparse_orswot as sparse_ops

# Op slot kinds. NOOP lanes/slots apply the identity — padding never
# touches state, so a partially-filled slab is sound by construction.
NOOP, ADD, RM = 0, 1, 2


class OpSlab(NamedTuple):
    """One coalesced batch of per-tenant ops: ``B`` tenant lanes × ``S``
    sequential slots (slot axis applies in order; the per-tenant
    submission order). ``member`` is the kind's op member descriptor:
    a ``bool[B, S, E]`` element mask for the dense kind, an
    ``int32[B, S, W]`` element-id list (-1 = pad) for the sparse kind.
    """

    kind: jax.Array    # [B, S] uint8 — NOOP / ADD / RM
    actor: jax.Array   # [B, S] int32 — add mint site
    ctr: jax.Array     # [B, S] uint32 — add counter
    clock: jax.Array   # [B, S, A] uint32 — rm clock
    member: jax.Array  # [B, S, *] — per-kind member descriptor


class TenantKind(NamedTuple):
    """One superblock-capable kind: the per-kind kernels the slab apply
    composes, normalized so ADD and RM both return
    ``(state, overflow)``. ``member_plane(caps)`` gives the op member
    descriptor's trailing shape / dtype / pad fill; ``caps`` is the
    kind's capacity dict (the ``empty`` kwargs minus ``batch``)."""

    name: str
    empty: Callable          # (**caps, batch=...) -> state
    apply_add: Callable      # (state, actor, ctr, member) -> (state, of)
    apply_rm: Callable       # (state, clock, member) -> (state, of)
    member_plane: Callable   # caps -> (shape tuple, dtype, fill)
    changed: Callable        # (a, b) -> uint32 changed-lane count
    join: Callable           # (a, b) -> (state, overflow)
    compact: Callable        # (state, frontier) -> (state, n, bytes)
    widen: Callable
    narrow: Callable
    observe: Callable        # state -> observable read pytree
    n_actors_of: Callable    # state -> A (clock lane width)
    caps_of: Callable        # state -> its capacity dict (empty kwargs)


def _dense_add(state, actor, ctr, member):
    return dense_ops.apply_add(state, actor, ctr, member), jnp.zeros((), bool)


TENANT_KINDS: Dict[str, TenantKind] = {
    "orswot": TenantKind(
        name="orswot",
        empty=dense_ops.empty,
        apply_add=_dense_add,
        apply_rm=dense_ops.apply_rm,
        member_plane=lambda caps: ((caps["n_elems"],), jnp.bool_, False),
        changed=dense_ops.changed_members,
        join=dense_ops.join,
        compact=dense_ops.compact,
        widen=dense_ops.widen,
        narrow=dense_ops.narrow,
        observe=lambda s: jnp.any(s.ctr > 0, axis=-1),
        n_actors_of=lambda s: s.top.shape[-1],
        caps_of=lambda s: dict(
            n_elems=s.ctr.shape[-2], n_actors=s.top.shape[-1],
            deferred_cap=s.dvalid.shape[-1],
        ),
    ),
    "sparse_orswot": TenantKind(
        name="sparse_orswot",
        empty=sparse_ops.empty,
        apply_add=sparse_ops.apply_add,
        apply_rm=sparse_ops.apply_rm,
        # One list width for ADD and RM: the rm width bounds both, so a
        # parked remove's element list always fits its didx lanes.
        member_plane=lambda caps: ((caps["rm_width"],), jnp.int32, -1),
        changed=sparse_ops.changed_dots,
        join=sparse_ops.join,
        compact=sparse_ops.compact,
        widen=sparse_ops.widen,
        narrow=sparse_ops.narrow,
        observe=lambda s: (s.eid, s.act, s.ctr, s.valid),
        n_actors_of=lambda s: s.top.shape[-1],
        caps_of=lambda s: dict(
            dot_cap=s.eid.shape[-1], n_actors=s.top.shape[-1],
            deferred_cap=s.dvalid.shape[-1], rm_width=s.didx.shape[-1],
        ),
    ),
}


def tenant_kind(name: str) -> TenantKind:
    if name not in TENANT_KINDS:
        raise KeyError(
            f"no superblock adapter for kind {name!r} "
            f"(know {sorted(TENANT_KINDS)})"
        )
    return TENANT_KINDS[name]


# ---- pack / unpack --------------------------------------------------------

def pack(states: Sequence):
    """Stack per-tenant states (uniform shapes) into one superblock —
    tenant axis prepended on every plane. Exact inverse of
    :func:`unpack` row-wise (the round-trip property in
    tests/test_serve.py)."""
    states = list(states)
    if not states:
        raise ValueError("pack() of zero tenants")
    shapes = {
        tuple(x.shape for x in jax.tree.leaves(s)) for s in states
    }
    if len(shapes) != 1:
        raise ValueError(
            f"pack() needs uniform per-tenant shapes, got {len(shapes)} "
            "distinct layouts — widen the narrow tenants first"
        )
    return jax.tree.map(lambda *xs: jnp.stack(xs, axis=0), *states)


def unpack(superblock, tenant: int):
    """One tenant's state, sliced off the tenant axis."""
    return jax.tree.map(lambda x: x[tenant], superblock)


@jax.jit
def gather_rows(superblock, idx: jax.Array):
    """Rows ``idx`` of the superblock (out-of-range indices clamp —
    callers mask separately; the slab path routes invalid lanes to
    NOOP ops, so a clamped gather is never observable)."""
    return jax.tree.map(lambda x: x[idx], superblock)


@jax.jit
def write_rows(superblock, idx: jax.Array, rows):
    """Scatter per-tenant rows back (unique ``idx`` by the ingest
    contract; negative indices drop via the out-of-range lane)."""
    t = jax.tree.leaves(superblock)[0].shape[0]
    safe = jnp.where(idx >= 0, idx, t)
    return jax.tree.map(
        lambda x, r: x.at[safe].set(r, mode="drop"), superblock, rows
    )


# ---- the coalesced slab apply --------------------------------------------

def empty_slab(tk: TenantKind, caps: dict, lanes: int, depth: int) -> OpSlab:
    """An all-NOOP slab of ``lanes`` × ``depth`` for capacity dict
    ``caps`` — the fill target the ingest queue writes into."""
    a = caps["n_actors"]
    mshape, mdtype, mfill = tk.member_plane(caps)
    return OpSlab(
        kind=jnp.zeros((lanes, depth), jnp.uint8),
        actor=jnp.zeros((lanes, depth), jnp.int32),
        ctr=jnp.zeros((lanes, depth), jnp.uint32),
        clock=jnp.zeros((lanes, depth, a), jnp.uint32),
        member=jnp.full((lanes, depth, *mshape), mfill, mdtype),
    )


def apply_slab_rows(tk: TenantKind, rows, slab: OpSlab):
    """Apply one slab to its gathered tenant rows: ``S`` sequential
    steps (lax.scan), each step one VMAPPED per-tenant op across all
    ``B`` lanes. NOOP slots keep the row bit-identical. Returns
    ``(rows, overflow[B])`` — overflow is the per-tenant deferred /
    dot-capacity pressure signal the serve layer widens on."""

    def one(state, k, actor, ctr, clock, member):
        added, of_a = tk.apply_add(state, actor, ctr, member)
        removed, of_r = tk.apply_rm(state, clock, member)
        is_add, is_rm = k == ADD, k == RM

        def pick(a, r, s):
            return jnp.where(is_add, a, jnp.where(is_rm, r, s))

        new = jax.tree.map(pick, added, removed, state)
        return new, (is_add & of_a) | (is_rm & of_r)

    def step(rows, sl):
        return jax.vmap(one)(
            rows, sl.kind, sl.actor, sl.ctr, sl.clock, sl.member
        )

    slab_s = jax.tree.map(lambda x: jnp.moveaxis(x, 1, 0), slab)
    rows, of = lax.scan(step, rows, slab_s)
    return rows, jnp.any(of, axis=0)


def compact_tenants(tk: TenantKind, superblock, frontier):
    """Causal-stability compaction lifted over the tenant axis: every
    tenant's registered compact kernel in one vmapped pass.
    ``frontier[T, A]`` is per-tenant (each tenant is its own causal
    domain — a single-replica tenant's own top IS its stable frontier).
    Returns ``(superblock, freed_slots, freed_bytes)`` summed over
    tenants."""
    out, freed, freed_b = jax.vmap(tk.compact)(superblock, frontier)
    return (
        out,
        jnp.sum(freed).astype(jnp.uint32),
        jnp.sum(freed_b).astype(jnp.float32),
    )


def sequential_oracle(tk: TenantKind, state, ops_list):
    """The per-tenant SEQUENTIAL oracle: apply one tenant's op stream
    one dispatch at a time on its unbatched state — the bit-identity
    reference for the coalesced slab apply (tests/test_serve.py gates
    on it). ``ops_list`` entries are
    ``(kind, actor, ctr, clock, member)`` host tuples."""
    for k, actor, ctr, clock, member in ops_list:
        if k == ADD:
            state, _ = tk.apply_add(
                state, jnp.int32(actor), jnp.uint32(ctr), jnp.asarray(member)
            )
        elif k == RM:
            state, _ = tk.apply_rm(
                state, jnp.asarray(clock, jnp.uint32), jnp.asarray(member)
            )
    return state


__all__ = [
    "ADD", "NOOP", "OpSlab", "RM", "TENANT_KINDS", "TenantKind",
    "apply_slab_rows", "compact_tenants", "empty_slab", "gather_rows",
    "pack", "sequential_oracle", "tenant_kind", "unpack", "write_rows",
]
