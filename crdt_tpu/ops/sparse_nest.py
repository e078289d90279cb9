"""Sparse (segment-encoded) map nesting — the NestLevel induction for
the compressed representation.

Round 4 left the sparse backend flat: the
segment-encoded ORSWOT scaled to huge member universes, but the map
family was dense-only, so ``Map<K, Orswot>`` at 1M keys could not fit
the E×A slab. This module is the sparse counterpart of ops/nest.py's
``NestLevel``: one induction step that wraps any sparse slab with one
more outer parked-keyset buffer — with LISTS where the dense level uses
masks, so state stays proportional to live content at every level.

Reference semantics: src/map.rs ``Map<K, V: Val<A>, A>`` (SURVEY.md §3
r11) under the causal-composition rule of pure/map.py — every child's
top clock equals the outer map clock, so the whole nest flattens onto
ONE leaf dot-segment table over the product key space, and each map
level adds only its parked keyset-removes. Flattening convention:

    leaf element id  e = key_id * span + member_id

where ``span`` (a static per-level constant) is the number of LEAF ids
per key of that level. A dot's level-ℓ key is ``e // span_ℓ`` — so a
parked (clock, key-list) replays against the leaf segments by integer
division, and per-key liveness is a range query [k·span, (k+1)·span) on
the canonically sorted segment table. No dense K-wide mask is ever
materialized; the universe bound is the packed int32 key of
ops/sparse_orswot._match_other (K · span · A < 2^31).

Key liveness facts the scrub relies on (oracle: pure/map.py — a key is
present iff its child holds any live dot, and a bottomed child dies
with ALL parked state inside it):

- deadness is monotone up the nest: an outer key's leaf range contains
  its inner keys' ranges, so outer-dead ⟹ inner-dead — each level's
  parked entries only need checking against their IMMEDIATELY enclosing
  level's key;
- a newly-dead key can appear whenever a replay kills dots, so (as in
  ops/nest.py ``settle_outer``) the scrub must run AFTER the replay and
  must recurse into inner levels (a replayed outer remove can newly
  bottom an inner child — tests/test_models_map3.py pins the dense
  failure mode; tests/test_sparse_nest.py pins it sparse).
"""

from __future__ import annotations

from functools import partial
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from . import sparse_orswot as sp
from .sparse_orswot import (
    SparseOrswotState,
    _canon,
    _canon_rmlist,
    _compact_parked,
    _dedupe_parked,
    _pad_tail,
    _replay_parked,
)

DTYPE = jnp.uint32
_INT32_MAX = jnp.iinfo(jnp.int32).max


class SparseNestState(NamedTuple):
    """One more level around any sparse slab: the core plus this level's
    parked keyset-removes (key LISTS, -1 = empty lane)."""

    core: Any          # SparseOrswotState or an inner SparseNestState
    kcl: jax.Array     # [..., D, A]  parked rm clocks
    kidx: jax.Array    # [..., D, Q]  key ids (-1 pad)
    kdvalid: jax.Array  # [..., D]


def _bsearch_count(key: jax.Array, lo: jax.Array, hi: jax.Array) -> jax.Array:
    """How many entries of the ascending ``key`` fall in [lo, hi) — the
    range-liveness primitive. Batched over leading axes (key [..., C],
    lo/hi [..., N])."""
    if key.ndim > 1:
        return jax.vmap(_bsearch_count)(key, lo, hi)
    return jnp.searchsorted(key, hi) - jnp.searchsorted(key, lo)


def _sorted_key(leaf) -> jax.Array:
    """The leaf table's ascending search key (invalid lanes sort last —
    canonical order guarantees the valid prefix is id-ascending). Works
    for any leaf slab whose first id plane is canonically sorted: the
    ORSWOT segment table (``eid``) and the sparse register-map cell
    table (``kid``, ops/sparse_mvmap.py)."""
    ids = leaf.eid if hasattr(leaf, "eid") else leaf.kid
    return jnp.where(leaf.valid, ids, _INT32_MAX)


def _ids_alive(
    leaf: SparseOrswotState, ids: jax.Array, span: int, element_axis=None
) -> jax.Array:
    """For each id list entry (level-local key ids, -1 = pad): does the
    key have any live leaf dot? Dead pads report False.

    Under element sharding (``element_axis`` set, inside shard_map) a
    key's dots spread across ALL shards (eid % S partitioning), and the
    query lists themselves may be shard-local (the leaf's parked didx
    entries are restricted per shard) — so a plain psum of per-position
    counts would add up answers for DIFFERENT keys. Instead every shard
    all-gathers the query keys, answers every shard's queries against
    its local table, and the answer matrix is psum-reduced; each shard
    then reads its own row. Sound for both shard-local lists (leaf) and
    replicated lists (outer levels, where all rows coincide)."""
    shape = ids.shape
    flat = ids.reshape(*shape[:-2], -1) if ids.ndim > 1 else ids
    key = _sorted_key(leaf)
    if element_axis is None:
        lo = jnp.where(flat >= 0, flat * span, _INT32_MAX)
        hi = jnp.where(flat >= 0, (flat + 1) * span, _INT32_MAX)
        return (_bsearch_count(key, lo, hi) > 0).reshape(shape)

    from jax import lax

    qk = lax.all_gather(flat, element_axis)        # [S, ...same as flat]
    lo = jnp.where(qk >= 0, qk * span, _INT32_MAX)
    hi = jnp.where(qk >= 0, (qk + 1) * span, _INT32_MAX)
    if flat.ndim > 1:
        # Batched states: fold the shard axis into the query width so the
        # batched bsearch maps over the leading batch only.
        s = qk.shape[0]
        lo2 = jnp.moveaxis(lo, 0, -2).reshape(*flat.shape[:-1], -1)
        hi2 = jnp.moveaxis(hi, 0, -2).reshape(*flat.shape[:-1], -1)
        counts = _bsearch_count(key, lo2, hi2)
        counts = jnp.moveaxis(
            counts.reshape(*flat.shape[:-1], s, flat.shape[-1]), -2, 0
        )
    else:
        counts = jax.vmap(lambda l, h: _bsearch_count(key, l, h))(lo, hi)
    counts = lax.psum(counts, element_axis)        # [S, ...]
    me = lax.axis_index(element_axis)
    return (counts[me] > 0).reshape(shape)


def widen_level(
    state: SparseNestState,
    core_widen,
    key_deferred_cap: int = 0,
    key_rm_width: int = 0,
    n_actors: int = 0,
) -> SparseNestState:
    """Widen one nest level's parked-keylist buffer (and, via
    ``core_widen``, everything inside it) — the elastic capacity
    migration for nested sparse states (elastic.py). ``core_widen`` maps
    the core slab to its widened form (compose ``sparse_orswot.widen``/
    ``sparse_mvmap.widen``/a nested ``widen_level``); 0 keeps a width;
    shrinking is refused."""
    d, a = state.kcl.shape[-2:]
    q = state.kidx.shape[-1]
    nd, nq = key_deferred_cap or d, key_rm_width or q
    na = n_actors or a
    if nd < d or nq < q or na < a:
        raise ValueError(
            f"widen cannot shrink: ({d}, {q}, {a}) -> ({nd}, {nq}, {na})"
        )
    lead = state.kdvalid.ndim - 1
    pad = partial(_pad_tail, lead=lead)
    return type(state)(
        core_widen(state.core),
        pad(state.kcl, (0, nd - d), (0, na - a)),
        pad(state.kidx, (0, nd - d), (0, nq - q), fill=-1),
        pad(state.kdvalid, (0, nd - d), fill=False),
    )


def narrow_level(
    state: SparseNestState,
    core_narrow,
    key_deferred_cap: int = 0,
    key_rm_width: int = 0,
    n_actors: int = 0,
) -> SparseNestState:
    """The inverse of :func:`widen_level` — slice one nest level's
    parked-keylist buffer (and, via ``core_narrow``, everything inside
    it) down to a narrower layout (elastic.shrink drives this). Live
    data in a dropped lane REFUSES with ValueError; run the kind's
    ``compact`` first so retired slots do not pin lanes. 0 keeps a
    width."""
    d, a = state.kcl.shape[-2:]
    q = state.kidx.shape[-1]
    nd, nq = key_deferred_cap or d, key_rm_width or q
    na = n_actors or a
    if nd > d or nq > q or na > a:
        raise ValueError(
            f"narrow cannot grow: ({d}, {q}, {a}) -> ({nd}, {nq}, {na})"
        )
    live = []
    if nd < d and bool(jnp.any(state.kdvalid[..., nd:])):
        live.append(f"key_deferred_cap {d}->{nd}")
    if nq < q and bool(jnp.any(state.kidx[..., nq:] >= 0)):
        live.append(f"key_rm_width {q}->{nq}")
    if na < a and bool(jnp.any(state.kcl[..., na:])):
        live.append(f"n_actors {a}->{na}")
    if live:
        raise ValueError(
            f"narrow refused — dropped lanes hold live state: {live} "
            f"(compact first, or shrink less)"
        )
    return type(state)(
        core_narrow(state.core),
        state.kcl[..., :nd, :na],
        state.kidx[..., :nd, :nq],
        state.kdvalid[..., :nd],
    )


def narrow_span(state: SparseNestState, old_span: int, new_span: int) -> SparseNestState:
    """The inverse of :func:`widen_span` — re-encode a depth-2 nest
    under a NARROWER per-key span. Preconditions: the old span must be
    a multiple of the new (aligned offsets preserve key ids) and every
    live flat id's offset must fit the new span — a live offset beyond
    it REFUSES with ValueError (the occupancy-fits contract of every
    narrow kernel)."""
    if new_span > old_span:
        raise ValueError(f"narrow_span cannot grow: {old_span} -> {new_span}")
    if old_span % new_span:
        raise ValueError(
            f"old span {old_span} must be a multiple of the new {new_span} "
            f"(key-id preservation needs aligned offsets)"
        )
    leaf = state.core
    if isinstance(leaf, SparseNestState):
        raise TypeError(
            "narrow_span covers depth-2 nests; rekey deeper nests level "
            "by level with rekey_flat"
        )
    id_planes = ("eid", "didx") if hasattr(leaf, "eid") else ("kid", "kidx")
    for plane in id_planes:
        ids = getattr(leaf, plane)
        if bool(jnp.any((ids >= 0) & (ids % old_span >= new_span))):
            raise ValueError(
                f"narrow_span refused — {plane} holds offsets >= "
                f"{new_span} (occupancy does not fit the narrower span)"
            )
    new_leaf = leaf._replace(**{
        plane: rekey_flat(getattr(leaf, plane), old_span, new_span)
        for plane in id_planes
    })
    return type(state)(new_leaf, state.kcl, state.kidx, state.kdvalid)


def rekey_flat(ids: jax.Array, old_span: int, new_span: int) -> jax.Array:
    """Remap flat leaf ids ``key·old_span + off`` → ``key·new_span +
    off`` (the segment-table repack of a span widening). Monotone for
    ``new_span >= old_span`` with offsets < old_span, so canonical
    segment order survives without a re-sort; negative pads pass
    through."""
    return jnp.where(ids >= 0, (ids // old_span) * new_span + ids % old_span, ids)


def widen_span(state: SparseNestState, old_span: int, new_span: int) -> SparseNestState:
    """Re-encode a depth-2 nest under a wider per-key span (more leaf
    ids per key of THIS level): flat ids in the leaf slab's id plane AND
    the leaf's own parked lists remap via :func:`rekey_flat`; this
    level's parked lists hold level-local key ids and are untouched.
    Keys keep their ids, so the result is bit-identical to a
    from-scratch nest built at the wider span over the same content.
    Deeper nests must compose the remap level by level (every
    intermediate level's lists would need its own rekey) — refused
    here."""
    if new_span < old_span:
        raise ValueError(f"widen_span cannot shrink: {old_span} -> {new_span}")
    if new_span % old_span:
        raise ValueError(
            f"new span {new_span} must be a multiple of the old {old_span} "
            f"(key-id preservation needs aligned offsets)"
        )
    leaf = state.core
    if isinstance(leaf, SparseNestState):
        raise TypeError(
            "widen_span covers depth-2 nests; rekey deeper nests level "
            "by level with rekey_flat"
        )
    if hasattr(leaf, "eid"):
        new_leaf = leaf._replace(
            eid=rekey_flat(leaf.eid, old_span, new_span),
            didx=rekey_flat(leaf.didx, old_span, new_span),
        )
    else:  # the sparse register-map cell table (ops/sparse_mvmap.py)
        new_leaf = leaf._replace(
            kid=rekey_flat(leaf.kid, old_span, new_span),
            kidx=rekey_flat(leaf.kidx, old_span, new_span),
        )
    return type(state)(new_leaf, state.kcl, state.kidx, state.kdvalid)


class SparseLeaf:
    """Protocol adapter: the flat segment slab (ops/sparse_orswot.py) as
    the innermost level. Its ids are leaf element ids (span 1); its own
    buffer parks member-removes as element lists."""

    span = 1

    def leaf(self, s: SparseOrswotState) -> SparseOrswotState:
        return s

    def top(self, s):
        return s.top

    def witness(self, s, actor, counter):
        return s._replace(top=s.top.at[..., actor].max(counter.astype(s.top.dtype)))

    def join(self, a, b, element_axis=None):
        return sp.join(a, b)  # flags [dot-cap, deferred]

    def replay_keylist(self, s, kcl, kidx, kdvalid, span: int):
        # (shard-oblivious: kills only dots present in THIS table)
        """Kill dots whose level-key (eid // span) a valid parked slot
        lists with a covering clock — the sparse analog of the dense
        expanded-mask replay. Re-canonicalizes (kills open holes)."""
        key_of = jnp.where(s.valid, s.eid // span, -2)
        listed = jnp.any(
            key_of[..., None, :, None] == kidx[..., :, None, :], axis=-1
        )  # [..., D, C]
        cl_at = jnp.take_along_axis(
            kcl, jnp.broadcast_to(s.act[..., None, :], listed.shape), axis=-1
        )
        covered = listed & (s.ctr[..., None, :] <= cl_at) & kdvalid[..., None]
        valid = s.valid & ~jnp.any(covered, axis=-2)
        eid, act, ctr, valid, _ = _canon(
            s.eid, s.act, jnp.where(valid, s.ctr, 0), valid, s.eid.shape[-1]
        )
        return s._replace(eid=eid, act=act, ctr=ctr, valid=valid)

    def scrub_enclosing(self, s, span: int, element_axis=None):
        """Drop parked member-remove entries whose enclosing span-key is
        dead (the oracle deletes a bottomed child WITH its deferred
        buffer); emptied slots die."""
        entry_key = jnp.where(s.didx >= 0, s.didx // span, -1)
        alive = _ids_alive(self.leaf(s), entry_key, span, element_axis)
        didx = _canon_rmlist(jnp.where(alive, s.didx, -1))
        dvalid = s.dvalid & jnp.any(didx >= 0, axis=-1)
        return s._replace(
            didx=jnp.where(dvalid[..., None], didx, -1),
            dcl=jnp.where(dvalid[..., None], s.dcl, 0),
            dvalid=dvalid,
        )

    def scrub_self(self, s, element_axis=None):
        return s  # leaf elements hold nothing inside them

    def settle_self(self, s, element_axis=None):
        """Replay the leaf's own parked member-removes under the (maybe
        advanced) top, drop caught-up slots."""
        valid = _replay_parked(
            s.eid, s.act, s.ctr, s.valid, s.dcl, s.didx, s.dvalid
        )
        still = ~jnp.all(s.dcl <= s.top[..., None, :], axis=-1)
        eid, act, ctr, valid, _ = _canon(
            s.eid, s.act, jnp.where(valid, s.ctr, 0), valid, s.eid.shape[-1]
        )
        return s._replace(
            eid=eid, act=act, ctr=ctr, valid=valid, dvalid=s.dvalid & still
        )

    def rm_route(self, s, levels_down: int, rm_clock, ids):
        assert levels_down == 0, "leaf cannot route deeper"
        return sp.apply_rm(s, rm_clock, ids)


SPARSE_LEAF = SparseLeaf()


class SparseNestLevel:
    """One application of the sparse nesting induction: wraps a
    protocol-satisfying sparse slab with one outer parked-keylist
    buffer. The result satisfies the same protocol, so levels compose to
    any depth (mirrors ops/nest.py ``NestLevel``, list-flavored).

    ``span`` — leaf ids per key of THIS level (static). For
    ``Map<K, Orswot>`` with member capacity M: span = M. For
    ``Map<K1, Map<K2, Orswot>>``: outer level span = K2·M over an inner
    level with span M."""

    def __init__(self, core, span: int, state_cls=SparseNestState):
        self.core = core
        self.span = span
        self.state_cls = state_cls
        core_span = getattr(core, "span", 1)
        if span % core_span or span <= core_span:
            raise ValueError(
                f"level span {span} must be a proper multiple of the "
                f"core's span {core_span}"
            )

    def _make(self, core_state, kcl, kidx, kdvalid):
        return self.state_cls(core_state, kcl, kidx, kdvalid)

    def _bufs(self, s):
        return s[1], s[2], s[3]

    def empty(self, core_state, n_actors: int, deferred_cap: int = 4,
              rm_width: int = 8, batch: tuple = ()):
        return self._make(
            core_state,
            jnp.zeros((*batch, deferred_cap, n_actors), DTYPE),
            jnp.full((*batch, deferred_cap, rm_width), -1, jnp.int32),
            jnp.zeros((*batch, deferred_cap), bool),
        )

    # ---- protocol -----------------------------------------------------

    def leaf(self, s) -> SparseOrswotState:
        return self.core.leaf(s[0])

    def top(self, s):
        return self.core.top(s[0])

    def witness(self, s, actor, counter):
        return self._make(
            self.core.witness(s[0], actor, counter), *self._bufs(s)
        )

    def replay_keylist(self, s, kcl, kidx, kdvalid, span: int):
        # (shard-oblivious: kills only dots present in THIS table)
        """An OUTER level's parked removes replay straight through to
        the leaf segments (content only; buffers untouched — matching
        NestLevel.replay_keyset)."""
        return self._make(
            self.core.replay_keylist(s[0], kcl, kidx, kdvalid, span),
            *self._bufs(s),
        )

    def replay_outer(self, s):
        """Replay THIS level's parked keyset-removes, then drop slots
        the top has caught up to (oracle ``_apply_deferred``)."""
        replayed = self.core.replay_keylist(s[0], s[1], s[2], s[3], self.span)
        still = ~jnp.all(s[1] <= self.top(s)[..., None, :], axis=-1)
        kdvalid = s[3] & still
        return self._make(
            replayed,
            jnp.where(kdvalid[..., None], s[1], 0),
            jnp.where(kdvalid[..., None], s[2], -1),
            kdvalid,
        )

    def scrub_enclosing(self, s, span: int, element_axis=None):
        """Called by an ENCLOSING level: drop this level's parked
        entries (and recursively the core's) whose enclosing span-key is
        dead. A key id j at this level starts at leaf id j·self.span, so
        its enclosing key is (j·self.span) // span."""
        leaf = self.leaf(s)
        entry_key = jnp.where(
            s[2] >= 0, (s[2] * self.span) // span, -1
        )
        alive = _ids_alive(leaf, entry_key, span, element_axis)
        kidx = _canon_rmlist(jnp.where(alive, s[2], -1))
        kdvalid = s[3] & jnp.any(kidx >= 0, axis=-1)
        return self._make(
            self.core.scrub_enclosing(s[0], span, element_axis),
            jnp.where(kdvalid[..., None], s[1], 0),
            jnp.where(kdvalid[..., None], kidx, -1),
            kdvalid,
        )

    def scrub_self(self, s, element_axis=None):
        """Drop parked state inside THIS level's bottomed children —
        recursing inner-first (a replayed remove here can newly bottom
        an inner child). This level's OWN buffer is never self-scrubbed
        (it belongs to the level, not to any child)."""
        core = self.core.scrub_self(s[0], element_axis)
        core = self.core.scrub_enclosing(core, self.span, element_axis)
        return self._make(core, *self._bufs(s))

    def settle_self(self, s, element_axis=None):
        core = self.core.settle_self(s[0], element_axis)
        out = self.replay_outer(self._make(core, *self._bufs(s)))
        return self.scrub_self(out, element_axis)

    def settle_outer(self, s, cap: int, element_axis=None):
        """Post-union buffer settlement: dedupe equal-clock slots →
        replay → compact → scrub; the order is correctness-critical
        (ops/nest.py ``settle_outer`` documents why)."""
        kcl, kidx, kdvalid = _dedupe_parked(s[1], s[2], s[3])
        s = self.replay_outer(self._make(s[0], kcl, kidx, kdvalid))
        kcl, kidx, kdvalid, overflow = _compact_parked(s[1], s[2], s[3], cap)
        s = self.scrub_self(self._make(s[0], kcl, kidx, kdvalid), element_axis)
        return s, jnp.any(overflow)

    def join(self, a, b, element_axis=None):
        """Pairwise lattice join. Returns ``(state, flags[L+1])`` —
        core lanes first, this level's parked-capacity lane last.
        ``element_axis`` (inside shard_map, leaf sharded by eid % S)
        routes the scrub's key-liveness psum across element shards."""
        core, core_flags = self.core.join(a[0], b[0], element_axis)
        kcl = jnp.concatenate([a[1], b[1]], axis=-2)
        kidx = jnp.concatenate([a[2], b[2]], axis=-2)
        kdvalid = jnp.concatenate([a[3], b[3]], axis=-1)
        state, of = self.settle_outer(
            self._make(core, kcl, kidx, kdvalid), a[1].shape[-2], element_axis
        )
        return state, jnp.concatenate([core_flags, of[None]])

    def fold(self, states, element_axis=None):
        """Log-tree fold of a replica batch (leading axis)."""
        from functools import partial

        from .lattice import tree_fold

        identity = jax.tree.map(
            lambda x: jnp.zeros(x.shape[1:], x.dtype), states
        )
        identity = _sparse_identity_like(identity)
        return tree_fold(
            states, identity, partial(self.join, element_axis=element_axis)
        )

    # ---- op application (CmRDT) --------------------------------------

    def rm_parked(self, s, rm_clock, ids):
        """``Op::Rm { clock, keyset }`` at THIS level: kill covered leaf
        dots of the listed keys now, park if the clock runs ahead, scrub
        newly-bottomed children. Returns ``(s, overflow)``."""
        rm_clock = jnp.asarray(rm_clock, self.top(s).dtype)
        killed = self.core.replay_keylist(
            s[0],
            rm_clock[..., None, :],
            ids[..., None, :],
            jnp.ones(rm_clock.shape[:-1] + (1,), bool),
            self.span,
        )
        ahead = ~jnp.all(rm_clock <= self.top(s), axis=-1)
        kcl, kidx, kdvalid, overflow = _park_list(
            s[1], s[2], s[3], rm_clock, ids, ahead
        )
        out = self.scrub_self(self._make(killed, kcl, kidx, kdvalid))
        return out, overflow

    def rm_route(self, s, levels_down: int, rm_clock, ids):
        """Route a keyset-remove ``levels_down`` levels into the core
        (0 = this level). ``ids`` are key ids AT THE TARGET LEVEL."""
        if levels_down == 0:
            return self.rm_parked(s, rm_clock, ids)
        core, overflow = self.core.rm_route(s[0], levels_down - 1, rm_clock, ids)
        return self._make(core, *self._bufs(s)), overflow

    def apply_up_add(self, s, actor, counter, eids):
        """``Op::Up { dot, key, Add { members } }`` — member adds inside
        one (or several) children, all witnessed by one minted dot.
        ``eids`` are FLATTENED leaf ids (key·span + member). Dup-drop on
        a seen dot (oracle apply returns early). Returns (s, overflow)."""
        counter = jnp.asarray(counter).astype(self.top(s).dtype)
        seen = self.top(s)[..., actor] >= counter
        leaf0 = self.leaf(s)
        new_leaf, overflow = sp.apply_add(leaf0, actor, counter, eids)
        out = _graft_leaf(self, s, new_leaf)
        out = self.settle_self(out)
        keep = lambda old, new: jnp.where(
            seen.reshape(seen.shape + (1,) * (new.ndim - seen.ndim)), old, new
        )
        out = jax.tree.map(keep, s, out)
        return out, overflow & ~seen

    def apply_up_rm(self, s, actor, counter, rm_clock, ids,
                    levels_down: int):
        """``Op::Up^j { dot, …, Rm { clock, keyset } }`` — a
        keyset-remove routed ``levels_down`` levels in (0 = this level's
        buffer; for a member-remove inside a child pass levels_down =
        depth so it lands on the LEAF buffer with flattened ids),
        witnessed by one minted dot. Returns (s, overflow)."""
        counter = jnp.asarray(counter).astype(self.top(s).dtype)
        seen = self.top(s)[..., actor] >= counter
        rmed, overflow = self.rm_route(s, levels_down, rm_clock, ids)
        out = self.settle_self(self.witness(rmed, actor, counter))
        keep = lambda old, new: jnp.where(
            seen.reshape(seen.shape + (1,) * (new.ndim - seen.ndim)), old, new
        )
        out = jax.tree.map(keep, s, out)
        return out, overflow & ~seen


def _graft_leaf(level, s, new_leaf):
    """Rebuild the nest state with a replaced leaf slab."""
    if not isinstance(level.core, SparseNestLevel):  # any leaf adapter
        return level._make(new_leaf, *level._bufs(s))
    inner = _graft_leaf(level.core, s[0], new_leaf)
    return level._make(inner, *level._bufs(s))


def _sparse_identity_like(identity):
    """Fix -1 pad conventions on a zeros-built identity pytree."""
    def fix(node):
        if isinstance(node, SparseOrswotState):
            return node._replace(
                eid=jnp.full_like(node.eid, -1),
                didx=jnp.full_like(node.didx, -1),
            )
        if isinstance(node, tuple) and hasattr(node, "_fields"):
            if "kid" in node._fields:  # sparse register-map cell table
                return node._replace(
                    kid=jnp.full_like(node.kid, -1),
                    kidx=jnp.full_like(node.kidx, -1),
                )
            fixed = fix(node[0])
            return type(node)(
                fixed, node[1], jnp.full_like(node[2], -1), node[3]
            )
        return node

    return fix(identity)


def _park_list(kcl, kidx, kdvalid, rm_clock, ids, ahead):
    """Park (clock, id-list) into the bounded slot table: union onto an
    equal-clock slot when the canonical union fits, else claim a free
    slot (the list flavor of ops/orswot._park_remove — same logic as
    sparse_orswot.apply_rm's parking tail). Returns
    ``(kcl, kidx, kdvalid, overflow)``."""
    q = kidx.shape[-1]
    w = ids.shape[-1]
    assert w <= q, "rm op id-list width must fit the buffer lane"
    same = kdvalid & jnp.all(kcl == rm_clock[None, :], axis=-1)
    merged = _canon_rmlist(
        jnp.concatenate(
            [kidx, jnp.broadcast_to(ids, (kidx.shape[0], w))], axis=-1
        )
    )
    fits = jnp.sum(merged >= 0, axis=-1) <= q
    use_same = same & fits
    has_same = jnp.any(use_same)
    free = ~kdvalid
    has_free = jnp.any(free)
    slot = jnp.where(has_same, jnp.argmax(use_same), jnp.argmax(free))
    park = ahead & (has_same | has_free)
    overflow = ahead & ~has_same & ~has_free
    onehot = jax.nn.one_hot(slot, kdvalid.shape[-1], dtype=bool) & park
    fresh = _canon_rmlist(jnp.pad(ids, (0, q - w), constant_values=-1))
    new_list = jnp.where(has_same, merged[slot][:q], fresh)
    kcl = jnp.where(onehot[:, None], rm_clock[None, :], kcl)
    kidx = jnp.where(onehot[:, None], new_list[None, :], kidx)
    kdvalid = kdvalid | onehot
    return kcl, kidx, kdvalid, overflow


# ---- the concrete depth-2 flavor: sparse Map<K, Orswot> ------------------

def level_map_orswot(span: int) -> SparseNestLevel:
    """``Map<K, Orswot>`` over a member capacity of ``span`` leaf ids
    per key (the universe bound is K·span·A < 2^31)."""
    return SparseNestLevel(SPARSE_LEAF, span)


def empty_map_orswot(
    span: int,
    dot_cap: int,
    n_actors: int,
    deferred_cap: int = 4,
    rm_width: int = 8,
    key_deferred_cap: int = 4,
    key_rm_width: int = 8,
    batch: tuple = (),
) -> SparseNestState:
    """The join identity for sparse ``Map<K, Orswot>``."""
    lvl = level_map_orswot(span)
    return lvl.empty(
        sp.empty(dot_cap, n_actors, deferred_cap, rm_width, batch=batch),
        n_actors, key_deferred_cap, key_rm_width, batch=batch,
    )


# ---- static-analysis registration (crdt_tpu.analysis) --------------------

def _law_states():
    """Sparse ``Map<K, Orswot>`` (span 2): member adds, leaf-routed
    member-removes, and covered/ahead key-removes with headroom."""
    lvl = level_map_orswot(2)
    cl = lambda x, y: jnp.array([x, y], DTYPE)
    ids = lambda *xs: jnp.array(list(xs) + [-1] * (4 - len(xs)), jnp.int32)
    mk = lambda: empty_map_orswot(
        2, 8, 2, deferred_cap=3, rm_width=4,
        key_deferred_cap=3, key_rm_width=4,
    )
    e = mk()
    a1, _ = lvl.apply_up_add(e, 0, jnp.uint32(1), ids(0))        # key 0, member 0
    a2, _ = lvl.apply_up_add(a1, 0, jnp.uint32(2), ids(2, 3))    # key 1, both members
    b1, _ = lvl.apply_up_add(e, 1, jnp.uint32(1), ids(1, 2))
    mr, _ = lvl.apply_up_rm(a2, 0, jnp.uint32(3), cl(1, 0), ids(0), levels_down=1)
    kr1, _ = lvl.rm_parked(b1, cl(0, 1), ids(0))   # covered key rm
    kr2, _ = lvl.rm_parked(a1, cl(0, 2), ids(1))   # ahead: parks
    return [e, a1, a2, b1, mr, kr1, kr2]


def _law_canon(s: SparseNestState) -> SparseNestState:
    from ..analysis.canon import canon_epochs
    from .sparse_orswot import _law_canon as _canon_leaf

    kcl, kidx, kdvalid = canon_epochs(s.kcl, s.kidx, s.kdvalid, payload_fill=-1)
    return SparseNestState(
        core=_canon_leaf(s.core), kcl=kcl, kidx=kidx, kdvalid=kdvalid,
    )


def _law_join(a, b):
    return level_map_orswot(2).join(a, b)


@jax.jit
def compact(state: SparseNestState, frontier: jax.Array):
    """Causal-stability compaction (reclaim/) for any sparse nest:
    compact the core slab (recursing through inner levels down to the
    ORSWOT segment table or the register-map cell table), then retire
    this level's stable parked keylist slots and scrub their stale
    payload. Returns ``(state, freed_slots, freed_bytes)``."""
    from ..reclaim.compaction import retire_epochs
    from ..reclaim.frontier import top_of

    core = state.core
    if isinstance(core, SparseNestState):
        core, n0, b0 = compact(core, frontier)
    elif hasattr(core, "eid"):
        core, n0, b0 = sp.compact(core, frontier)
    else:  # the sparse register-map cell table (ops/sparse_mvmap.py)
        from .sparse_mvmap import compact as _smv_compact

        core, n0, b0 = _smv_compact(core, frontier)
    kcl, kidx, kdvalid, n1, b1 = retire_epochs(
        state.kcl, state.kidx, state.kdvalid, top_of(state), frontier,
        payload_fill=-1,
    )
    return (
        type(state)(core, kcl, kidx, kdvalid),
        n0 + n1,
        b0 + b1,
    )


def _observe(s: SparseNestState):
    """The observable read: the LEAF slab's read (membership ids for an
    ORSWOT leaf, (key, value) cells for a register-map leaf) — the
    causal-composition rule makes every outer level's read a projection
    of it."""
    leaf = s
    while isinstance(leaf, SparseNestState):
        leaf = leaf.core
    if hasattr(leaf, "eid"):
        from .sparse_orswot import _observe as _leaf_observe

        return _leaf_observe(leaf)
    from .sparse_mvmap import _observe as _leaf_observe

    return _leaf_observe(leaf)


def _decomp_split(s: SparseNestState):
    """Decomposition granularity (delta_opt/): one δ lane per LEAF-slab
    table lane (recursing through inner levels down to the ORSWOT
    segment table or the register-map cell table); every level's parked
    keylist buffer plus the leaf residual ride whole. The level stack is
    encoded positionally in the residual, so ``_decomp_unsplit`` can
    rebuild the nest without a type tag (leaf arity disambiguates the
    two leaf slabs)."""
    levels = []
    core = s
    while isinstance(core, SparseNestState):
        levels.append((core.kcl, core.kidx, core.kdvalid))
        core = core.core
    if hasattr(core, "eid"):
        from .sparse_orswot import _decomp_split as _leaf_split
    else:
        from .sparse_mvmap import _decomp_split as _leaf_split
    rows, leaf_res = _leaf_split(core)
    return rows, (tuple(levels), leaf_res)


def _decomp_unsplit(rows, res) -> SparseNestState:
    levels, leaf_res = res
    if len(rows) == 4:  # (eid, act, ctr, valid) — the ORSWOT leaf
        from .sparse_orswot import _decomp_unsplit as _leaf_unsplit
    else:  # 6 planes — the register-map cell leaf (ops/sparse_mvmap.py)
        from .sparse_mvmap import _decomp_unsplit as _leaf_unsplit
    core = _leaf_unsplit(rows, leaf_res)
    for kcl, kidx, kdvalid in reversed(levels):
        core = SparseNestState(core=core, kcl=kcl, kidx=kidx, kdvalid=kdvalid)
    return core


from ..analysis.registry import (  # noqa: E402
    register_compactor,
    register_decomposition,
    register_merge,
)

register_merge(
    "sparse_nested_map", module=__name__, join=_law_join,
    states=_law_states, canon=_law_canon,
)
def _top_of(s):
    from ..reclaim.frontier import top_of

    return top_of(s)


register_compactor(
    "sparse_nested_map", module=__name__, compact=compact,
    observe=_observe, top_of=_top_of,
)
register_decomposition(
    "sparse_nested_map", module=__name__, split=_decomp_split,
    unsplit=_decomp_unsplit,
)
