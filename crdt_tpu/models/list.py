"""BatchedList — N device-resident List replicas over a shared
identifier universe.

Oracle: ``crdt_tpu.pure.list.List`` (reference: src/list.rs). The split
per SURVEY.md §7.1: identifier allocation is inherently sequential per
edit trace and runs in the native host engine
(``crdt_tpu.native.ListEngine``, C++); the per-replica op application is
batched on device as masked scatters over an order-maintenance array.

Layout: the engine's total identifier order (which is immutable — dense
identifiers never move) assigns every identifier a static *slot*; the
device holds ``vals int32`` + ``alive bool`` for ``R`` replicas × ``N``
slots in slot order, each replica's slots laid out as ``[W / 128, 128]``
(slot ``s`` at ``[r, s // 128, s % 128]``, ``W ≥ N`` padded to whole
8 × 128 tiles). In that layout the one-dimensional form into which the
TPU compiler flattens a per-(replica, slot) scatter is a bitcast of the
state; a plain ``[R, N]`` array would be copied to and from that form
(a relayout of the whole state) on every scatter.
Applying an insert is ``alive[slot] = True, vals[slot] = v``; a delete is
``alive[slot] = False``; a read is a host-side compress of ``vals`` by
``alive`` (already in sequence order). Epochs of ops across all replicas
land as one scatter each — the batched form of BASELINE config 5's
"100k ops × 1k replicas".

The op log is also kept on the device, cut into fixed chunks of
``chunk_width`` trace ops, each reduced to the last op per slot and
padded with dropped lanes: :meth:`BatchedList.apply_chunks` takes one
chunk id per replica, so replicas at different places in the log apply
their own chunks in one call whose only upload is ``[R]`` ids.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .. import telemetry as tele
from ..dot import OrdDot
from ..native import INSERT, ListEngine
from ..pure.identifier import Identifier
from ..pure.list import List
from ..utils.metrics import metrics

CHUNK_WIDTH = 1024  # trace ops a chunk of the device op log covers
LANES = 128  # slots in one row of a replica's state
TILE = 8 * LANES  # slots in one (8, 128) tile: the slot axis holds whole tiles
_REMAP_BLOCK = 64  # replicas a growth re-permute moves at once


def slot_width(n: int, shards: int = 1) -> int:
    """Slots ``W`` the state holds for ``n`` identifiers: whole tiles in
    each of ``shards`` element shards, at least one."""
    step = TILE * shards
    return max(-(-n // step), 1) * step


def growth_permutation(old_slots: np.ndarray, new_rank: np.ndarray) -> np.ndarray:
    """After the engine mints more identifiers, map the new total order
    back onto the old one: ``src[s]`` is the old slot feeding new slot
    ``s`` (-1 = freshly minted). Handles are stable and ordered
    first-n-minted-first, so the old handles are ``new_rank[:n_old]``."""
    src = np.full(len(new_rank), -1, np.int64)
    src[new_rank[: len(old_slots)]] = old_slots
    return src


def last_per_slot(ops: np.ndarray, keys: np.ndarray) -> np.ndarray:
    """The ops of ``ops`` (trace indices, in trace order) that are the
    last to touch their slot, in ascending slot-key order: a slot's
    lifecycle is insert → delete, so the last write wins exactly and one
    conflict-free scatter applies the whole window. ``keys[op]`` names
    the op's slot (its identifier handle or its current slot)."""
    rev = ops[::-1]
    _, first = np.unique(keys[rev], return_index=True)
    return rev[first]


class BatchedList:
    def __init__(self, n_replicas: int, chunk_width: int = CHUNK_WIDTH):
        if chunk_width < 1:
            raise ValueError(f"chunk_width must be positive, got {chunk_width}")
        self.engine = ListEngine()
        # rank per identifier handle (the current total order)
        self.slots = np.empty(0, np.int64)
        rows = slot_width(0) // LANES
        self.vals = jnp.zeros((n_replicas, rows, LANES), jnp.int32)
        self.alive = jnp.zeros((n_replicas, rows, LANES), bool)
        self._mesh = None  # set by place(): (replica, element) sharding
        # The op log: stable identifier handles (slots move when later
        # inserts interleave the order; handles never do).
        self.op_handles = np.empty(0, np.int64)
        self.op_kinds = np.empty(0, np.uint8)
        self.op_vals = np.empty(0, np.int32)
        self._applied = 0  # watermark: ops [0, _applied) are on device
        # The chunked op log: chunk k holds the ops of trace window
        # [k·W, (k+1)·W) that are last on their slot, -1 padded (host,
        # trace indices), its live lane count, and its device form
        # (slots, kinds, values), pad lanes on the dropped slot.
        self.chunk_width = chunk_width
        self.chunk_ops = np.empty((0, chunk_width), np.int64)
        self.chunk_live = np.empty(0, np.int64)
        self._log = None

    def place(self, mesh) -> None:
        """Shard the replica state over a ``(replica, element)`` mesh:
        replicas data-parallel, the slot universe sharded over the
        element axis (the sequence-parallel analog, SURVEY.md §3.1 —
        identifier space across devices). Epoch scatters carry
        replicated indices and XLA partitions them; streamed universe
        growth re-places after every slot re-permutation.

        Placement is per-session: it is not persisted by
        ``crdt_tpu.checkpoint`` (a mesh names live devices) — re-call
        ``place`` on a restored model."""
        from ..parallel.mesh import REPLICA_AXIS

        # Validate BEFORE installing: a rejected place() must leave the
        # model untouched (an installed mesh would make the next
        # extend_trace mutate the engine and then fail mid-operation).
        rmult = mesh.shape[REPLICA_AXIS]
        if self.vals.shape[0] % rmult:
            raise ValueError(
                f"{self.vals.shape[0]} replicas do not divide the "
                f"{rmult}-way replica mesh axis"
            )
        self._mesh = mesh
        self.vals, self.alive = self._placed(self.vals, self.alive)
        self._upload_log()

    def _placed(self, vals, alive):
        if self._mesh is None:
            return vals, alive
        pad = slot_width(vals.shape[1] * LANES, self._shards) // LANES - vals.shape[1]
        if pad:
            # Dead slot padding: never addressed (scatters drop at the
            # out-of-range lane, reads mask on alive).
            vals = jnp.pad(vals, ((0, 0), (0, pad), (0, 0)))
            alive = jnp.pad(alive, ((0, 0), (0, pad), (0, 0)))
        return (jax.device_put(vals, self._state_sharding),
                jax.device_put(alive, self._state_sharding))

    @property
    def _state_sharding(self):
        """The state's placement (None when unplaced)."""
        if self._mesh is None:
            return None
        from jax.sharding import NamedSharding, PartitionSpec as P

        from ..parallel.mesh import ELEMENT_AXIS, REPLICA_AXIS

        return NamedSharding(self._mesh, P(REPLICA_AXIS, ELEMENT_AXIS, None))

    @property
    def _shards(self) -> int:
        """Element shards of the slot axis (1 when unplaced)."""
        if self._mesh is None:
            return 1
        from ..parallel.mesh import ELEMENT_AXIS

        return self._mesh.shape[ELEMENT_AXIS]

    @property
    def _width(self) -> int:
        """Slots the state holds; slot ``_width`` is the dropped lane."""
        return self.vals.shape[1] * LANES

    @classmethod
    def from_trace(
        cls,
        kinds: Sequence[int],
        indices: Sequence[int],
        values: Sequence[int],
        actors: Sequence[int],
        n_replicas: int,
        chunk_width: int = CHUNK_WIDTH,
    ) -> "BatchedList":
        """Build the shared identifier universe by running the edit trace
        through the native engine, then stand up ``n_replicas`` empty
        device replicas over it. For streamed ingestion start from
        ``BatchedList(n_replicas)`` and call :meth:`extend_trace` per
        chunk instead."""
        out = cls(n_replicas, chunk_width)
        out.extend_trace(kinds, indices, values, actors)
        return out

    def extend_trace(
        self,
        kinds: Sequence[int],
        indices: Sequence[int],
        values: Sequence[int],
        actors: Sequence[int],
    ) -> None:
        """Grow the shared identifier universe with further local edit
        ops (streamed ingestion — the trace need not be known up front,
        SURVEY.md §4.5 / BASELINE config 5). New identifiers may
        interleave existing ones, so device slots are re-permuted to the
        new total order; applied state moves with its identifiers."""
        handles = self.engine.apply_trace(kinds, indices, values, actors)
        self._grow(self.engine.total_order())
        start = len(self.op_handles)
        self.op_handles = np.concatenate([self.op_handles, handles])
        self.op_kinds = np.concatenate(
            [self.op_kinds, np.ascontiguousarray(kinds, np.uint8)]
        )
        self.op_vals = np.concatenate(
            [self.op_vals, np.ascontiguousarray(values, np.int32)]
        )
        self.build_chunks(start)

    def _grow(self, new_rank: np.ndarray) -> None:
        """Re-permute device slots to the engine's grown total order;
        applied state moves with its identifiers."""
        if len(new_rank) != len(self.slots):
            src = growth_permutation(self.slots, new_rank)
            width = slot_width(len(new_rank), self._shards)
            self.vals, self.alive = self._placed(
                *_remap_slots(self.vals, self.alive, jnp.asarray(src), width)
            )
            self.slots = new_rank

    def build_chunks(self, start: int = 0) -> None:
        """Cut the op log into the device chunk log from the chunk that
        holds trace op ``start`` on (earlier chunks are complete and keep
        their ops), then upload it. Each chunk keeps its window's last op
        per identifier, so applying chunks 0..k in order leaves a replica
        exactly at trace prefix ``[0, (k+1)·W)``."""
        w = self.chunk_width
        n_ops = len(self.op_handles)
        first = start // w
        ops = np.full((-(-n_ops // w), w), -1, np.int64)
        ops[:first] = self.chunk_ops[:first]
        for k in range(first, len(ops)):
            keep = last_per_slot(np.arange(k * w, min((k + 1) * w, n_ops)),
                                 self.op_handles)
            ops[k, : len(keep)] = keep
        self.chunk_ops = ops
        self.chunk_live = (ops >= 0).sum(axis=1)
        self._upload_log()

    def _upload_log(self) -> None:
        """The chunk log's device form at the current slots and padded
        width (both move as the universe grows or is placed)."""
        ops = self.chunk_ops
        if len(ops) == 0:
            self._log = None
            return
        live = ops >= 0
        safe = np.where(live, ops, 0)
        slots = np.where(live, self.slots[self.op_handles[safe]], self._width)
        log = (slots.astype(np.int32), self.op_kinds[safe], self.op_vals[safe])
        if self._mesh is None:
            self._log = tuple(jnp.asarray(x) for x in log)
        else:
            from jax.sharding import NamedSharding, PartitionSpec as P

            spec = NamedSharding(self._mesh, P())
            self._log = tuple(jax.device_put(x, spec) for x in log)

    @property
    def n_chunks(self) -> int:
        return len(self.chunk_ops)

    @property
    def op_slots(self) -> np.ndarray:
        """Current slot of every logged op (recomputed: slots move as the
        universe grows, handles don't)."""
        return self.slots[self.op_handles]

    @property
    def n_replicas(self) -> int:
        return self.vals.shape[0]

    # ---- batched op application (the device hot path) -----------------
    def apply_ops(self, replica_ops: np.ndarray, op_slots: Optional[np.ndarray] = None) -> None:
        """One epoch: ``replica_ops[r]`` lists trace-op indices for
        replica ``r`` (shape [R, C]; -1 pads). Within one epoch a
        replica must not touch the same slot twice (scatter order on
        duplicates is unspecified) — chunk the trace accordingly.
        The whole epoch is two scatters for ALL replicas.

        ``op_slots`` lets loop callers pass the op→slot table computed
        once (it is an O(oplog) gather otherwise)."""
        replica_ops = np.asarray(replica_ops)
        if replica_ops.ndim != 2 or replica_ops.shape[0] != self.n_replicas:
            raise ValueError(f"expected [R={self.n_replicas}, C] op indices")
        from ..config import config

        if config.strict:
            # The device analog of pure.list.List.validate_op's dup
            # rejection: a trace-op index delivered twice to one replica
            # in one epoch is a duplicate dot (the engine mints each op's
            # dot once), and scatter order on duplicates is unspecified.
            # Sorted rows put duplicates side by side (pads, -1, first).
            from ..traits import DotRange

            srt = np.sort(replica_ops, axis=1)
            dup = (srt[:, 1:] == srt[:, :-1]) & (srt[:, 1:] >= 0)
            if dup.any():
                r = int(np.argmax(dup.any(axis=1)))
                op = int(srt[r, 1:][dup[r]].min())
                raise DotRange(f"replica {r} trace op", op, op)
        if op_slots is None:
            op_slots = self.op_slots
        valid = replica_ops >= 0
        safe = np.where(valid, replica_ops, 0)
        # Pad lanes scatter to the out-of-range slot N and are dropped —
        # routing them to slot 0 would duplicate-write a real slot with
        # an unspecified winner.
        slots = jnp.asarray(np.where(valid, op_slots[safe], self._width).astype(np.int32))
        kinds = jnp.asarray(self.op_kinds[safe])
        vals = jnp.asarray(self.op_vals[safe])
        self.vals, self.alive = _donated(_scatter_ops, self._state_sharding)(
            self.vals, self.alive, slots, kinds, vals
        )

    def apply_chunks(self, chunk_of_row) -> None:
        """One round of per-replica delivery from the device op log:
        replica ``r`` applies chunk ``chunk_of_row[r]`` (-1: nothing).
        The lanes are gathered from the log on the device and scattered
        into the donated state; the only upload is the ``[R]`` ids.
        Causal delivery is the caller's: a replica applies its chunks in
        order, each once. Returns when the state is ready."""
        with tele.span("list.apply"):
            with tele.span("list.apply.prep"):
                ids = np.asarray(chunk_of_row)
                if ids.shape != (self.n_replicas,) or ids.dtype.kind not in "iu":
                    raise ValueError(
                        f"expected [R={self.n_replicas}] integer chunk ids, "
                        f"got {ids.dtype}{list(ids.shape)}"
                    )
                on = ids >= 0
                if (ids < -1).any() or (ids >= self.n_chunks).any():
                    raise ValueError(
                        f"chunk ids must lie in [-1, {self.n_chunks}), got "
                        f"[{ids.min()}, {ids.max()}]"
                    )
                metrics.count("list.lanes", self.n_replicas * self.chunk_width)
                metrics.count("list.lanes_live", int(self.chunk_live[ids[on]].sum()))
                metrics.observe("list.universe", len(self.slots))
                if self._log is None:  # an empty log: every id is -1
                    return
                ids = ids.astype(np.int32)
                if self._mesh is None:
                    ids = jnp.asarray(ids)
                else:
                    from jax.sharding import NamedSharding, PartitionSpec as P

                    from ..parallel.mesh import REPLICA_AXIS

                    ids = jax.device_put(ids, NamedSharding(self._mesh, P(REPLICA_AXIS)))
            with tele.span("list.apply.dispatch"):
                self.vals, self.alive = _donated(_list_apply_chunks, self._state_sharding)(
                    self.vals, self.alive, *self._log, ids
                )
            with tele.span("list.apply.wait"):
                jax.block_until_ready((self.vals, self.alive))

    def apply_trace_to_all(self, chunk: int = 4096) -> None:
        """Apply the not-yet-applied tail of the op log to every replica
        in fixed-size epochs (streamed calls pick up where the last one
        stopped). Within an epoch, ops on the same slot compose to the
        LAST one (a slot's lifecycle is insert → delete, so the final
        write wins exactly) — the host dedupes, and each epoch lands as
        one conflict-free scatter for all replicas."""
        n_ops = len(self.op_handles)
        op_slots = self.op_slots  # one gather; slots are stable herein
        for start in range(self._applied, n_ops, chunk):
            keep = last_per_slot(np.arange(start, min(start + chunk, n_ops)), op_slots)
            # Pad to the fixed chunk width (-1 lanes are dropped) so every
            # epoch shares one traced shape — a data-dependent width would
            # recompile the scatter per epoch.
            padded = np.full(chunk, -1, np.int64)
            padded[: len(keep)] = keep
            ops = np.broadcast_to(padded, (self.n_replicas, chunk))
            self.apply_ops(ops, op_slots=op_slots)
        self._applied = n_ops

    # ---- cross-process op exchange (SURVEY §4.5: the reference ships
    # ``Op::Insert { id, val }`` bytes to ANY replica; the TPU build's
    # multi-host analog ships identifier paths over DCN) ----------------
    def export_ops(self, start: int = 0, end: Optional[int] = None):
        """Flatten ops ``[start, end)`` of the local log to plain numpy
        arrays (kind, value, path length, flattened (index, actor,
        counter) components) — the wire form for
        ``parallel.multihost.sync_list``. Identifier paths are globally
        unique and totally ordered by construction, so a remote engine
        ingesting them reproduces the same total order."""
        end = len(self.op_handles) if end is None else end
        kinds = self.op_kinds[start:end]
        values = self.op_vals[start:end]
        paths = [
            self.engine.identifier_path(int(h))
            for h in self.op_handles[start:end]
        ]
        counts = np.asarray([len(p) for p in paths], np.int64)
        flat = [c for p in paths for c in p]
        return {
            "kinds": np.ascontiguousarray(kinds, np.uint8),
            "values": np.ascontiguousarray(values, np.int32),
            "counts": counts,
            "cidx": np.asarray([c[0] for c in flat], np.int64),
            "cactor": np.asarray([c[1] for c in flat], np.int32),
            "cctr": np.asarray([c[2] for c in flat], np.uint64),
        }

    def ingest_remote_ops(self, wire) -> None:
        """Apply a remote process's exported ops into the local engine
        (idempotent: duplicate identifiers no-op) and append them to the
        op log; device slots re-permute to the grown total order."""
        counts = wire["counts"]
        if len(counts) == 0:
            return
        offsets = np.concatenate([[0], np.cumsum(counts)])
        paths = [
            [
                (
                    int(wire["cidx"][i]),
                    int(wire["cactor"][i]),
                    int(wire["cctr"][i]),
                )
                for i in range(offsets[j], offsets[j + 1])
            ]
            for j in range(len(counts))
        ]
        handles = self.engine.apply_remote(
            wire["kinds"], paths, wire["values"]
        )
        self._grow(self.engine.total_order())
        start = len(self.op_handles)
        # A delete of an identifier the engine never saw is an idempotent
        # no-op and yields handle -1 — it must NOT enter the op log
        # (self.slots[-1] would wrap to the highest-ranked identifier and
        # the scatter would clear an unrelated element).
        ok = handles >= 0
        self.op_handles = np.concatenate([self.op_handles, handles[ok]])
        self.op_kinds = np.concatenate(
            [self.op_kinds, np.ascontiguousarray(wire["kinds"], np.uint8)[ok]]
        )
        self.op_vals = np.concatenate(
            [self.op_vals, np.ascontiguousarray(wire["values"], np.int32)[ok]]
        )
        self.build_chunks(start)

    # ---- reads ---------------------------------------------------------
    def read(self, replica: int) -> list:
        """The replica's sequence of value ids (slot order == identifier
        order)."""
        alive = np.asarray(self.alive[replica]).reshape(-1)
        vals = np.asarray(self.vals[replica]).reshape(-1)
        return vals[alive].tolist()

    def to_pure(self, replica: int, actors_table=None) -> List:
        """Reconstruct the oracle form (identifiers from the engine,
        values from device state). ``actors_table`` maps dense actor ids
        back to caller actors (identity if omitted)."""
        alive = np.asarray(self.alive[replica]).reshape(-1)
        vals = np.asarray(self.vals[replica]).reshape(-1)
        out = List()
        handle_of_slot = np.argsort(self.slots, kind="stable")
        for slot in range(len(self.slots)):
            if not alive[slot]:
                continue
            handle = int(handle_of_slot[slot])
            path = self.engine.identifier_path(handle)
            ident = Identifier(
                tuple(
                    (
                        ix,
                        OrdDot(
                            actors_table[a] if actors_table is not None else a,
                            c,
                        ),
                    )
                    for ix, a, c in path
                )
            )
            out.seq.append(ident)
            out.vals[ident] = int(vals[slot])
        return out


@functools.partial(jax.jit, static_argnums=3)
def _remap_slots(vals, alive, src, width):
    """Permute replica state to a new total order of ``width`` slots:
    ``src[s]`` is the old slot feeding new slot ``s`` (-1 = freshly
    minted identifier, empty on every replica; slots past ``src`` pad).
    It goes a block of replicas at a time, so the gather's temporaries
    stay a block's, not the whole state's."""
    src = jnp.pad(src, (0, width - src.shape[0]), constant_values=-1).reshape(-1, LANES)
    safe = jnp.where(src >= 0, src, 0)
    row, lane = safe // LANES, safe % LANES
    fresh = src < 0

    def block(state):
        v, a = state
        return (jnp.where(fresh, 0, v[:, row, lane]),
                jnp.where(fresh, False, a[:, row, lane]))

    r = vals.shape[0]
    b = math.gcd(r, _REMAP_BLOCK)
    split = lambda x: x.reshape((r // b, b) + x.shape[1:])  # noqa: E731
    v, a = jax.lax.map(block, (split(vals), split(alive)))
    return v.reshape((r,) + v.shape[2:]), a.reshape((r,) + a.shape[2:])


def _scatter_ops(vals, alive, slots, kinds, op_vals):
    """Scatter ``[R, C]`` op lanes into every replica: an insert sets
    value and alive, a delete clears alive; a lane whose slot is out of
    range (the state's width) is dropped. No row may address one slot
    twice."""
    r = jnp.arange(vals.shape[0])[:, None]
    insert = kinds == INSERT
    put = jnp.where(insert, slots, vals.shape[1] * LANES)
    vals = vals.at[r, put // LANES, put % LANES].set(op_vals, mode="drop")
    alive = alive.at[r, slots // LANES, slots % LANES].set(insert, mode="drop")
    return vals, alive


def _list_apply_chunks(vals, alive, log_slots, log_kinds, log_vals, chunk_of_row):
    """Replica ``r`` applies log chunk ``chunk_of_row[r]`` (-1: none).
    The device program keeps this name (``jit__list_apply_chunks``): the
    benchmark finds the apply by it."""
    on = (chunk_of_row >= 0)[:, None]
    c = jnp.maximum(chunk_of_row, 0)
    slots = jnp.where(on, log_slots[c], vals.shape[1] * LANES)
    return _scatter_ops(vals, alive, slots, log_kinds[c], log_vals[c])


@functools.lru_cache(maxsize=None)
def _donated(fn, sharding=None):
    """``fn`` jitted with the state (its first two arguments) donated,
    and returned in ``sharding`` where the state is placed, so the
    state's placement never changes between calls (nor recompiles)."""
    out = None if sharding is None else (sharding, sharding)
    return jax.jit(fn, donate_argnums=(0, 1), out_shardings=out)
