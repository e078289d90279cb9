"""BatchedSparseMapOrswot — N segment-encoded ``Map<K, Orswot>``
replicas on device.

The sparse counterpart of :class:`.map_nested.BatchedMapOrswot` for key
universes where the dense K×M slab stops scaling (reference: src/map.rs
``Map<K, V: Val<A>, A>``): state tracks LIVE
(key, member, actor) cells plus parked-remove LISTS, never a K×M cube.
Flattening matches the dense model (cell id = key_id · span +
member_id, global member interner) so the two backends are directly
comparable; conversion to/from the oracle is lossless and the
bit-identical A/B gates in tests/test_sparse_nest.py mirror the dense
suite's.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import sparse_nest as nest
from ..ops import sparse_orswot as sp
from ..pure.map import Map, MapRm, Nop, Up
from ..pure.orswot import Add as OrswotAdd, Orswot, Rm as OrswotRm
from ..utils import Interner, clock_lanes, pad_id_list, transactional_apply
from ..utils.metrics import metrics, observe_depth
from ..vclock import VClock
from .orswot import DeferredOverflow
from .sparse_orswot import DotCapacityOverflow
from .validation import strict_validate_dot


class BatchedSparseMapOrswot:
    def __init__(
        self,
        n_replicas: int,
        span: int,
        dot_cap: int,
        n_actors: int,
        deferred_cap: int = 4,
        rm_width: int = 8,
        key_deferred_cap: int = 4,
        key_rm_width: int = 8,
        keys: Optional[Interner] = None,
        members: Optional[Interner] = None,
        actors: Optional[Interner] = None,
    ):
        self.keys = keys if keys is not None else Interner()
        self.members = members if members is not None else Interner()
        self.actors = actors if actors is not None else Interner()
        self.level = nest.level_map_orswot(span)
        self.state = nest.empty_map_orswot(
            span, dot_cap, n_actors, deferred_cap, rm_width,
            key_deferred_cap, key_rm_width, batch=(n_replicas,),
        )

    @property
    def n_replicas(self) -> int:
        return self.state.core.top.shape[0]

    @property
    def span(self) -> int:
        return self.level.span

    @property
    def dot_cap(self) -> int:
        return self.state.core.eid.shape[-1]

    # ---- conversion (the A/B gate boundary) ---------------------------
    @classmethod
    def from_pure(
        cls,
        pures: Sequence[Map],
        span: int = 64,
        dot_cap: int = 256,
        deferred_cap: int = 4,
        rm_width: int = 8,
        key_deferred_cap: int = 4,
        key_rm_width: int = 8,
        keys: Optional[Interner] = None,
        members: Optional[Interner] = None,
        actors: Optional[Interner] = None,
        n_actors: int = 1,
    ) -> "BatchedSparseMapOrswot":
        keys = keys if keys is not None else Interner()
        members = members if members is not None else Interner()
        actors = actors if actors is not None else Interner()
        for p in pures:
            for actor in p.clock.dots:
                actors.intern(actor)
            for k, child in p.entries.items():
                keys.intern(k)
                if not isinstance(child, Orswot):
                    raise TypeError(
                        f"children must be Orswot, got {type(child)}"
                    )
                if child.clock != p.clock:
                    raise ValueError(
                        f"child at {k!r} violates the covered invariant "
                        f"(child clock != map clock); not a composed state"
                    )
                for m, clock in child.entries.items():
                    members.intern(m)
                    for actor in clock.dots:
                        actors.intern(actor)
                for clock, ms in child.deferred.items():
                    for actor in clock.dots:
                        actors.intern(actor)
                    for m in ms:
                        members.intern(m)
            for clock, ks in p.deferred.items():
                for actor in clock.dots:
                    actors.intern(actor)
                for k in ks:
                    keys.intern(k)
        if len(members) > span:
            raise ValueError(
                f"{len(members)} members exceed the per-key span {span}"
            )

        r = len(pures)
        na = max(len(actors), n_actors, 1)
        out = cls(
            r, span, dot_cap, na, deferred_cap, rm_width,
            key_deferred_cap, key_rm_width,
            keys=keys, members=members, actors=actors,
        )
        top = np.zeros((r, na), np.uint32)
        eid = np.full((r, dot_cap), -1, np.int32)
        act = np.zeros((r, dot_cap), np.int32)
        ctr = np.zeros((r, dot_cap), np.uint32)
        valid = np.zeros((r, dot_cap), bool)
        dcl = np.zeros((r, deferred_cap, na), np.uint32)
        didx = np.full((r, deferred_cap, rm_width), -1, np.int32)
        dvalid = np.zeros((r, deferred_cap), bool)
        kcl = np.zeros((r, key_deferred_cap, na), np.uint32)
        kidx = np.full((r, key_deferred_cap, key_rm_width), -1, np.int32)
        kdvalid = np.zeros((r, key_deferred_cap), bool)
        for i, p in enumerate(pures):
            for actor, c in p.clock.dots.items():
                top[i, actors.id_of(actor)] = c
            cells = sorted(
                (
                    keys.id_of(k) * span + members.id_of(m),
                    actors.id_of(a),
                    c,
                )
                for k, child in p.entries.items()
                for m, clock in child.entries.items()
                for a, c in clock.dots.items()
            )
            if len(cells) > dot_cap:
                raise DotCapacityOverflow(
                    f"replica {i}: {len(cells)} live cells > dot_cap {dot_cap}"
                )
            for s, (e, a, c) in enumerate(cells):
                eid[i, s], act[i, s], ctr[i, s], valid[i, s] = e, a, c, True
            # Inner (per-child) parked removes: equal clocks union into
            # shared slots (what a join produces); to_pure splits back.
            inner: dict = {}
            for k, child in p.entries.items():
                ki = keys.id_of(k)
                for clock, ms in child.deferred.items():
                    inner.setdefault(clock, set()).update(
                        ki * span + members.id_of(m) for m in ms
                    )
            if len(inner) > deferred_cap:
                raise DeferredOverflow(
                    f"replica {i}: {len(inner)} inner parked removes; "
                    f"capacity is {deferred_cap}"
                )
            for s, (clock, ids) in enumerate(inner.items()):
                ids = sorted(ids)
                if len(ids) > rm_width:
                    raise DeferredOverflow(
                        f"replica {i} slot {s}: {len(ids)} parked cells "
                        f"> rm_width {rm_width}"
                    )
                for actor, c in clock.dots.items():
                    dcl[i, s, actors.id_of(actor)] = c
                didx[i, s, : len(ids)] = ids
                dvalid[i, s] = True
            if len(p.deferred) > key_deferred_cap:
                raise DeferredOverflow(
                    f"replica {i}: {len(p.deferred)} outer parked removes; "
                    f"capacity is {key_deferred_cap}"
                )
            for s, (clock, ks) in enumerate(p.deferred.items()):
                ids = sorted(keys.id_of(k) for k in ks)
                if len(ids) > key_rm_width:
                    raise DeferredOverflow(
                        f"replica {i} slot {s}: {len(ids)} parked keys "
                        f"> key_rm_width {key_rm_width}"
                    )
                for actor, c in clock.dots.items():
                    kcl[i, s, actors.id_of(actor)] = c
                kidx[i, s, : len(ids)] = ids
                kdvalid[i, s] = True
        core = sp.SparseOrswotState(
            top=jnp.asarray(top), eid=jnp.asarray(eid), act=jnp.asarray(act),
            ctr=jnp.asarray(ctr), valid=jnp.asarray(valid),
            dcl=jnp.asarray(dcl), didx=jnp.asarray(didx),
            dvalid=jnp.asarray(dvalid),
        )
        out.state = nest.SparseNestState(
            core=core, kcl=jnp.asarray(kcl), kidx=jnp.asarray(kidx),
            kdvalid=jnp.asarray(kdvalid),
        )
        return out

    def _row(self, arrs, i: int):
        return jax.tree.map(lambda x: x[i], arrs)

    def to_pure(self, i: int) -> Map:
        st = jax.device_get(self._row(self.state, i))
        span = self.span
        out = Map(Orswot)
        out.clock = VClock(
            {self.actors[a]: int(c) for a, c in enumerate(st.core.top) if c > 0}
        )
        for s in np.nonzero(st.core.valid)[0]:
            e = int(st.core.eid[s])
            k, m = self.keys[e // span], self.members[e % span]
            child = out.entries.get(k)
            if child is None:
                child = Orswot()
                child.clock = out.clock.clone()
                out.entries[k] = child
            entry = child.entries.setdefault(m, VClock())
            entry.dots[self.actors[int(st.core.act[s])]] = int(st.core.ctr[s])
        # Inner parked removes: split each shared slot back per key;
        # dead keys were scrubbed on device (the oracle dropped them too).
        for s in np.nonzero(st.core.dvalid)[0]:
            clock = VClock(
                {self.actors[a]: int(c)
                 for a, c in enumerate(st.core.dcl[s]) if c > 0}
            )
            for e in st.core.didx[s]:
                if e < 0:
                    continue
                child = out.entries.get(self.keys[int(e) // span])
                if child is None:
                    continue
                child.deferred.setdefault(clock.clone(), set()).add(
                    self.members[int(e) % span]
                )
        for s in np.nonzero(st.kdvalid)[0]:
            clock = VClock(
                {self.actors[a]: int(c)
                 for a, c in enumerate(st.kcl[s]) if c > 0}
            )
            # Equal-clock slots union into ONE oracle entry (the sparse
            # form may split a clock's list across slots past rm_width).
            out.deferred.setdefault(clock, set()).update(
                self.keys[int(k)] for k in st.kidx[s] if k >= 0
            )
        return out

    # ---- op path (CmRDT) ----------------------------------------------
    def _ids(self, pairs, width: Optional[int] = None) -> np.ndarray:
        """Flattened (key, member) cell ids, fixed width (power-of-two
        bucket ≥ 8 when unconstrained, to bound jit retraces)."""
        return pad_id_list(pairs, width)

    @transactional_apply("keys", "members", "actors")
    def apply(self, replica: int, op) -> None:
        """Apply an oracle-shaped op to one replica (reference:
        src/map.rs ``CmRDT::apply`` routing orswot child ops)."""
        if isinstance(op, Nop):
            return
        row = self._row(self.state, replica)
        na = self.state.core.top.shape[-1]
        span = self.span
        if isinstance(op, Up):
            strict_validate_dot(
                row.core.top, self.actors, op.dot.actor, op.dot.counter
            )
            aid = self.actors.bounded_intern(op.dot.actor, na, "actor")
            kid = self.keys.intern(op.key)
            if isinstance(op.op, OrswotAdd):
                if op.op.dot != op.dot:
                    raise ValueError(
                        "inner add dot must equal the Up dot (one AddCtx)"
                    )
                eids = self._ids(
                    kid * span + self._member_id(m) for m in op.op.members
                )
                row, overflow = self.level.apply_up_add(
                    row, jnp.asarray(aid),
                    jnp.asarray(np.uint32(op.dot.counter)),
                    jnp.asarray(eids),
                )
                if bool(overflow):
                    raise DotCapacityOverflow(
                        f"replica {replica}: dot_cap {self.dot_cap} exceeded"
                    )
            elif isinstance(op.op, OrswotRm):
                clock = clock_lanes(op.op.clock, self.actors, na)
                ids = self._ids(
                    (kid * span + self._member_id(m) for m in op.op.members),
                    width=self.state.core.didx.shape[-1],
                )
                row, overflow = self.level.apply_up_rm(
                    row, jnp.asarray(aid),
                    jnp.asarray(np.uint32(op.dot.counter)),
                    jnp.asarray(clock), jnp.asarray(ids), levels_down=1,
                )
                if bool(overflow):
                    raise DeferredOverflow(
                        f"replica {replica}: inner deferred buffer full "
                        f"(cap {self.state.core.dvalid.shape[-1]})"
                    )
            else:
                raise TypeError(
                    f"routes Orswot ops only, got {op.op!r}"
                )
        elif isinstance(op, MapRm):
            clock = clock_lanes(op.clock, self.actors, na)
            ids = self._ids(
                (self.keys.intern(k) for k in op.keyset),
                width=self.state.kidx.shape[-1],
            )
            row, overflow = self.level.rm_parked(
                row, jnp.asarray(clock), jnp.asarray(ids)
            )
            if bool(overflow):
                raise DeferredOverflow(
                    f"replica {replica}: outer deferred buffer full "
                    f"(cap {self.state.kdvalid.shape[-1]})"
                )
        else:
            raise TypeError(f"not a Map op: {op!r}")
        self.state = jax.tree.map(
            lambda full, r: full.at[replica].set(r), self.state, row
        )

    def _member_id(self, m) -> int:
        mid = self.members.intern(m)
        if mid >= self.span:
            raise ValueError(
                f"member universe exceeded the per-key span {self.span}"
            )
        return mid

    # ---- state path (CvRDT) -------------------------------------------
    def _check_flags(self, flags, what: str) -> None:
        if bool(flags[0]):
            raise DotCapacityOverflow(
                f"{what}: survivor cells exceed dot_cap {self.dot_cap}"
            )
        if bool(flags[1]) or bool(flags[2]):
            raise DeferredOverflow(
                f"{what}: {'inner' if bool(flags[1]) else 'outer'} deferred "
                f"buffer full — rebuild with a larger capacity"
            )

    def merge_from(self, dst: int, src: int) -> None:
        metrics.count("sparse_map_orswot.merges")
        joined, flags = self.level.join(
            self._row(self.state, dst), self._row(self.state, src)
        )
        self._check_flags(flags, f"merge {src}->{dst}")
        self.state = jax.tree.map(
            lambda full, r: full.at[dst].set(r), self.state, joined
        )

    def fold(self) -> Map:
        """Full-mesh anti-entropy: join all replicas, return the
        converged oracle-form state."""
        metrics.count("sparse_map_orswot.merges", max(self.n_replicas - 1, 0))
        observe_depth("sparse_map_orswot", self.state)
        folded, flags = self.level.fold(self.state)
        self._check_flags(flags, "fold")
        tmp = BatchedSparseMapOrswot(
            1, self.span, self.dot_cap, self.state.core.top.shape[-1],
            self.state.core.dcl.shape[-2], self.state.core.didx.shape[-1],
            self.state.kcl.shape[-2], self.state.kidx.shape[-1],
            keys=self.keys, members=self.members, actors=self.actors,
        )
        tmp.state = jax.tree.map(lambda x: x[None], folded)
        return tmp.to_pure(0)
