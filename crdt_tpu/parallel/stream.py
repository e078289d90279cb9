"""Replica-streaming fold — anti-entropy for populations that do not fit.

Every fold entry point so far assumes the whole replica batch is
co-resident in device memory, which caps the population at whatever
``[R, ...]`` HBM holds. The flagship shape (BASELINE's metric of
record: 10,240 replicas x 1M elements) and the δ-CRDT literature's
setting (Almeida et al. 1603.01529; Enes et al. 1803.02750 — replicas
far outnumber any single machine) both need the opposite: an
**arbitrary-N** population streamed through the mesh in device-sized
blocks. This module is that driver:

    acc = identity
    for block in blocks:              # [B, ...] replica blocks
        acc = join(acc, mesh_fold(block))

with three performance disciplines carried over from the ring family:

- **donation** (``donate=True``, default): the per-block step jits with
  ``donate_argnums=(0,)``, so the running accumulator's output aliases
  its input buffers in place (``input_output_alias`` — the PR 3
  zero-copy discipline, gated by tools/check_aliasing.py via the entry
  registry). The stream holds ONE accumulator in HBM, ever.
- **double buffering** (``pipeline=True``, default): block k+1 is
  staged (``jax.device_put`` under async dispatch) right after block
  k's step is dispatched, so the upload DMA overlaps the join kernels —
  the host-loop analog of the δ-ring's ``pipeline=`` loop-edge
  ppermute. ``stream.overlap_hit`` counts stagings issued while the
  previous join was still in flight; ``pipeline=False`` syncs between
  blocks (and the counter stays 0).
- **bounded residency**: peak device-resident replica state is two
  blocks plus the accumulator, independent of N —
  ``stream.staged_bytes`` totals what was staged so the bench can
  report the co-resident-vs-streamed ratio honestly.

Composition hooks:

- ``widen_policy=`` (an :class:`crdt_tpu.elastic.ElasticPolicy`) turns
  on the PR 1 overflow→widen→resume loop **mid-stream**: a block whose
  join overflows the accumulator's capacity discards that step (the
  join is idempotent; the pre-step accumulator is snapshotted exactly
  like ``gossip_elastic``), widens the implicated axes on the
  accumulator and the staged block, and retries. Subsequent blocks are
  widened at staging to the grown caps. Engaging the policy makes the
  loop check flags per block (a host sync) — the price of recovery.
- ``frontier=`` + ``compact_every=`` run the PR 5 causal-stability
  compactor on the accumulator every k blocks, so its parked-remove
  footprint stays bounded over long streams. SAFETY: the frontier must
  be stable over the WHOLE population (reclaim.host_frontier /
  stable_frontier over every replica, streamed or not) — a frontier
  derived only from already-seen blocks could retire a parked remove an
  unseen straggler still needs. ``frontier=None`` with
  ``compact_every`` set compacts against the all-zeros frontier:
  nothing retires, but stale payload scrubs and lanes repack.

Fault containment: a block that fails to stage (source iterator raise,
host OOM, a bad shard) raises :class:`StreamInterrupted` carrying the
accumulator — by construction the exact join of blocks ``[0, k)`` and a
valid lattice state — plus the resume index; re-entering with
``init=exc.acc`` and the remaining blocks completes the fold
bit-identically (tests/test_fault_injection.py pins this).

Block contract: blocks are ``[B, ...]`` batches of one kind (sparse /
dense ORSWOT, sparse Map<K, MVReg>, or element-sharded sparse
``[B, S, ...]`` from ``sparse_shard.split_segments``). The first block
fixes the template; later blocks may be SMALLER (identity-padded — the
ragged tail) or CARRY NARROWER CAPS (widened at staging); both repacks
fall back to a staged copy outside the zero-copy path and count
``stream.unaliasable_blocks``. Blocks larger than the template refuse
(re-chunk the source instead).
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, Dict, Iterable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import telemetry as tele
from ..utils.metrics import metrics, observe_depth, state_nbytes
from .anti_entropy import _cached, _exchange_count, _tel_reduced
from .collectives import all_reduce_lattice
from .mesh import ELEMENT_AXIS, REPLICA_AXIS


@dataclass
class StreamFaultReport:
    """One faulted stream run's accounting (``faults=`` on the stream
    entries): which block indices (in THIS call's delivery order) were
    lost to an injected upload drop, and which arrived corrupted and
    were REJECTED by the in-kernel checksum verify (faults/integrity.py
    — corrupted content is never joined). The accumulator is the exact
    join of the non-lost blocks, so healing is a re-stream:
    ``mesh_stream_fold*(lost_blocks, mesh, init=acc)`` with the faults
    off (the δ-literature's eventual-resync contract)."""

    dropped_blocks: list
    rejected_blocks: list

    @property
    def lost_blocks(self) -> list:
        return sorted(set(self.dropped_blocks) | set(self.rejected_blocks))


class StreamInterrupted(RuntimeError):
    """A block failed to stage mid-stream. ``acc`` is the accumulator —
    the exact lattice join of the non-lost blocks already applied, a
    valid joinable state — and the stream resumes from block
    ``blocks_done`` via ``init=exc.acc`` on a fresh call. ``telemetry``
    carries the partial Telemetry pytree when the run requested one;
    ``fault_report`` the partial :class:`StreamFaultReport` when the
    run injected faults — an interrupted faulted stream must still name
    the blocks already lost BEFORE the interrupt, or the resume
    contract would silently drop them from the final join."""

    def __init__(self, cause: BaseException, acc, blocks_done: int,
                 telemetry=None, fault_report=None):
        super().__init__(
            f"replica stream interrupted at block {blocks_done} "
            f"({type(cause).__name__}: {cause}); .acc holds the join of "
            f"blocks [0, {blocks_done}) — resume with init=exc.acc"
        )
        self.cause = cause
        self.acc = acc
        self.blocks_done = blocks_done
        self.telemetry = telemetry
        self.fault_report = fault_report


@dataclass(frozen=True)
class _StreamPlan:
    """The per-kind closure set the generic block loop composes."""

    kind: str                      # jit-cache kind head
    join_fn: Callable              # (a, b) -> (state, flags)
    fold_fn: Callable              # [rows, ...] -> (state, flags)
    caps_of: Callable              # unbatched state -> {axis: cap}
    empty: Callable                # (caps, batch) -> identity batch
    widen_state: Callable          # (state, {axis: cap}) -> state
    flag_axes: Tuple[str, ...]     # overflow lane -> elastic axis ("" =
                                   #   lane not recoverable by widening)
    slots_fn: Optional[Callable] = None
    compact_fn: Optional[Callable] = None  # (state, frontier) -> (s, n, b)
    sum_axes: Optional[tuple] = None       # slots psum axes (None = done)
    sharded: bool = False          # blocks [B, S, ...], acc [S, ...]


# ---- per-kind plans -------------------------------------------------------

def _plan_sparse() -> _StreamPlan:
    from ..ops import sparse_orswot as sp

    return _StreamPlan(
        kind="sparse_stream_fold",
        join_fn=sp.join,
        fold_fn=sp.fold,
        caps_of=lambda s: {
            "dot_cap": s.eid.shape[-1], "n_actors": s.top.shape[-1],
            "deferred_cap": s.didx.shape[-2], "rm_width": s.didx.shape[-1],
        },
        empty=lambda caps, batch: sp.empty(
            caps["dot_cap"], caps["n_actors"], caps["deferred_cap"],
            caps["rm_width"], batch=batch,
        ),
        widen_state=lambda s, caps: sp.widen(s, **caps),
        flag_axes=("dot_cap", "deferred_cap"),
        slots_fn=sp.changed_dots,
        compact_fn=sp.compact,
    )


def _plan_dense() -> _StreamPlan:
    from ..ops import orswot as ops

    return _StreamPlan(
        kind="orswot_stream_fold",
        join_fn=ops.join,
        fold_fn=ops.fold,
        caps_of=lambda s: {
            "n_elems": s.ctr.shape[-2], "n_actors": s.top.shape[-1],
            "deferred_cap": s.dvalid.shape[-1],
        },
        empty=lambda caps, batch: ops.empty(
            caps["n_elems"], caps["n_actors"], caps["deferred_cap"],
            batch=batch,
        ),
        widen_state=lambda s, caps: ops.widen(s, **caps),
        flag_axes=("deferred_cap",),
        slots_fn=ops.changed_members,
        compact_fn=ops.compact,
    )


def _plan_sparse_mvmap(sibling_cap: int) -> _StreamPlan:
    from ..ops import sparse_mvmap as smv

    return _StreamPlan(
        kind=f"sparse_mvmap_stream_fold_s{sibling_cap}",
        join_fn=partial(smv.join, sibling_cap=sibling_cap),
        fold_fn=partial(smv.fold, sibling_cap=sibling_cap),
        caps_of=lambda s: {
            "cell_cap": s.kid.shape[-1], "n_actors": s.top.shape[-1],
            "deferred_cap": s.kidx.shape[-2], "rm_width": s.kidx.shape[-1],
        },
        empty=lambda caps, batch: smv.empty(
            caps["cell_cap"], caps["n_actors"], caps["deferred_cap"],
            caps["rm_width"], batch=batch,
        ),
        widen_state=lambda s, caps: smv.widen(s, **caps),
        # The sibling lane is a STATIC join arg, not a state axis — a
        # sibling overflow cannot be widened mid-stream (re-enter with a
        # larger sibling_cap instead), hence the "" lane.
        flag_axes=("cell_cap", "deferred_cap", ""),
        slots_fn=smv.changed_cells,
        compact_fn=smv.compact,
    )


def _plan_sparse_sharded() -> _StreamPlan:
    from ..ops import sparse_orswot as sp

    base = _plan_sparse()
    return _StreamPlan(
        kind="sparse_sharded_stream_fold",
        join_fn=sp.join,
        fold_fn=sp.fold,
        caps_of=base.caps_of,
        empty=base.empty,
        widen_state=base.widen_state,
        # Widening an element-sharded stream would have to repack every
        # shard consistently; unsupported — size the shard caps up front.
        flag_axes=(),
        slots_fn=sp.changed_dots,
        compact_fn=sp.compact,
        sum_axes=(ELEMENT_AXIS,),
        sharded=True,
    )


# ---- the generic block loop -----------------------------------------------

def _specs(plan: _StreamPlan, template) -> Tuple[Any, Any]:
    """(acc_specs, block_specs) for the step's shard_map. Replicated
    kinds: acc replicated everywhere, blocks row-sharded over the
    replica axis. Dense ORSWOT: element axis shards the content planes
    (mesh.orswot_specs discipline). Sharded sparse: the leading shard
    axis rides the element axis on BOTH."""
    from ..ops.orswot import OrswotState
    from .mesh import orswot_out_specs, orswot_specs

    if plan.sharded:
        return (
            jax.tree.map(lambda _: P(ELEMENT_AXIS), template),
            jax.tree.map(lambda _: P(REPLICA_AXIS, ELEMENT_AXIS), template),
        )
    if isinstance(template, OrswotState):
        return orswot_out_specs(), orswot_specs()
    return (
        jax.tree.map(lambda _: P(), template),
        jax.tree.map(lambda _: P(REPLICA_AXIS), template),
    )


def _rows_of(tree) -> int:
    return jax.tree.leaves(tree)[0].shape[0]


def _ready(tree) -> bool:
    """Best-effort 'has this dispatch landed' probe (jax.Array.is_ready
    where available; conservatively True elsewhere) — feeds the
    overlap_hit counter only, never correctness."""
    leaf = jax.tree.leaves(tree)[0]
    fn = getattr(leaf, "is_ready", None)
    if not callable(fn):
        return True
    try:
        return bool(fn())
    except Exception:
        return True


def _widen_to(plan: _StreamPlan, state, caps: Dict[str, int]):
    """Widen ``state`` up to ``caps`` where narrower (no-op when equal;
    ``caps_of`` reads trailing shapes, so batched states report the
    same caps as unbatched ones)."""
    have = plan.caps_of(state)
    grow = {k: v for k, v in caps.items() if have.get(k, v) < v}
    return plan.widen_state(state, grow) if grow else state


def _stream_fold(
    plan: _StreamPlan,
    blocks: Iterable,
    mesh: Mesh,
    *,
    init=None,
    telemetry: bool = False,
    donate: bool = True,
    pipeline: bool = True,
    widen_policy=None,
    frontier=None,
    compact_every: int = 0,
    faults=None,
    wal=None,
    wal_every: int = 0,
    wal_base: int = 0,
):
    """The shared scaffold: template derivation, identity padding and
    cap-matching at staging, the double-buffered dispatch loop, the
    elastic retry, periodic compaction, telemetry accumulation, and the
    interrupt protocol. See the module docstring for semantics.

    ``faults=`` (a ``crdt_tpu.faults.FaultPlan``) injects seeded
    drop/corrupt faults on the BLOCK UPLOAD — the stream's wire: the
    staged block carries a checksum lane, the step corrupts it in-kernel
    per a draw keyed on ``(seed, block index)``, and a failed verify
    REJECTS the block (the accumulator keeps its pre-block value; a
    rejected block's overflow flags are masked so the elastic retry
    never widens for content that was not joined). ``delay`` has no
    meaning here — block order is host-driven — and is ignored. The
    per-block fate is read back host-side (one sync per block, the
    faults-mode price), and a :class:`StreamFaultReport` is appended as
    the LAST output so the caller can re-stream the lost blocks with
    ``init=acc``. The flag-off trace is byte-identical pre-flag.

    ``wal=`` (a ``crdt_tpu.durability.Wal``) makes the stream's
    interrupt contract DURABLE: every ``StreamInterrupted`` raise first
    persists a fsynced resume record (the accumulator — the exact join
    of blocks ``[0, k)`` — plus the resume index), and ``wal_every=k``
    additionally persists one every k blocks, so a HARD kill (no
    exception path at all — the flagship run's preemption case) still
    resumes from the last persisted block via
    ``durability.recover.load_stream_resume`` instead of restarting
    the fold. Resume records carry the ABSOLUTE block index
    (``wal_base + blocks_done``): a RESUMED run must pass the index it
    resumed from as ``wal_base=`` so a second kill still points at the
    true position in the original source, not a run-relative one. Each
    periodic persist syncs the in-flight accumulator to host — the
    durability price; size ``wal_every`` like a checkpoint cadence,
    not a telemetry one. The traced program is untouched."""
    rsize = mesh.shape[REPLICA_AXIS]
    esize = mesh.shape[ELEMENT_AXIS]
    faulted = faults is not None
    if faulted:
        from .. import faults as flt
    wal_b0 = wal.bytes_appended if wal is not None else 0
    wal_f0 = wal.fsyncs if wal is not None else 0

    def persist_resume(acc_now, done: int) -> None:
        """One fsynced resume record (module docstring) — a resume
        point that could vanish with the page cache is no resume
        point. ``done`` is run-relative; the record stores the
        ABSOLUTE source index (``wal_base + done``)."""
        if wal is None or acc_now is None:
            return
        jax.block_until_ready(jax.tree.leaves(acc_now))
        wal.append_resume(plan.kind, acc_now, wal_base + done)
        wal.sync()

    it = iter(blocks)

    def fetch():
        return next(it, None)

    try:
        first = fetch()
    except ValueError:
        raise  # caller bugs propagate as-is — _advance's contract
    except Exception as exc:
        metrics.count("stream.interrupted")
        persist_resume(init, 0)
        raise StreamInterrupted(
            exc, init, 0,
            fault_report=StreamFaultReport([], []) if faulted else None,
        ) from exc
    if first is None and init is None:
        raise ValueError("empty block stream and no init accumulator")

    # ---- template: caps + padded row geometry from the first block ----
    from ..ops.orswot import OrswotState

    dense = isinstance(first if first is not None else init, OrswotState)
    if first is not None:
        if dense:
            # Dense ORSWOT: the element universe must split over the mesh.
            from .mesh import pad_elements

            first = pad_elements(first, esize)
        caps = plan.caps_of(first)
        rows = _rows_of(first)
        template_rows = rows + ((-rows) % rsize)
    else:
        caps = plan.caps_of(init)
        template_rows = rsize
    if init is not None:
        init_caps = plan.caps_of(init)
        caps = {k: max(v, init_caps.get(k, v)) for k, v in caps.items()}
    if plan.sharded:
        s_axis = (jax.tree.leaves(first)[0].shape[1] if first is not None
                  else _rows_of(init))
        if s_axis != esize:
            raise ValueError(
                f"stream blocks carry {s_axis} element shards, mesh axis "
                f"is {esize}"
            )

    acc_template = (
        plan.empty(caps, batch=(esize,)) if plan.sharded
        else plan.empty(caps, batch=())
    )
    acc_specs, block_specs = _specs(plan, acc_template)
    acc_sharding = jax.tree.map(
        lambda s: NamedSharding(mesh, s), acc_specs
    )
    block_sharding = jax.tree.map(
        lambda s: NamedSharding(mesh, s), block_specs
    )

    def stage(raw):
        """Pad rows to the template, widen narrow caps, commit to the
        mesh. Returns the staged block; counts the repack fallback."""
        repack = False
        if dense:
            from .mesh import pad_elements

            padded = pad_elements(raw, esize)
            repack = padded is not raw
            raw = padded
        raw_caps = plan.caps_of(raw)
        if any(raw_caps.get(k, v) > v for k, v in caps.items()):
            raise ValueError(
                f"block caps {raw_caps} exceed the stream template {caps} "
                f"— widen the template (stream from the widest block "
                f"first) or re-chunk"
            )
        widened = _widen_to(plan, raw, caps)
        repack = repack or (widened is not raw)
        rows = _rows_of(widened)
        if rows > template_rows:
            raise ValueError(
                f"block has {rows} rows > stream template {template_rows} "
                f"— re-chunk the source"
            )
        if rows < template_rows:
            pad_batch = (
                (template_rows - rows, esize) if plan.sharded
                else (template_rows - rows,)
            )
            ident = plan.empty(caps, batch=pad_batch)
            widened = jax.tree.map(
                lambda x, p: jnp.concatenate([x, p.astype(x.dtype)], axis=0),
                widened, ident,
            )
            repack = True
        if repack and donate:
            metrics.count("stream.unaliasable_blocks")
        return jax.device_put(widened, block_sharding)

    n_ex = _exchange_count(rsize)

    def build():
        out_specs = [acc_specs, P()]
        if telemetry:
            out_specs.append(tele.specs())
        if faulted:
            out_specs.append(P())  # the block's fate code

        def body(acc, block, bix=None):
            if plan.sharded:
                acc_l = jax.tree.map(lambda x: x[0], acc)
                block_l = jax.tree.map(lambda x: x[:, 0], block)
            else:
                acc_l, block_l = acc, block
            if faulted:
                # The block upload is the stream's wire
                # (faults.block_wire: drop/corrupt draw keyed on the
                # block index, checksum verify over what arrived) — a
                # failed verify rejects the whole block (its join is
                # deselected below).
                block_l, code = flt.block_wire(faults, bix, block_l)
                code = lax.pmax(
                    lax.pmax(code, REPLICA_AXIS), ELEMENT_AXIS
                )
                reject = code > 0
            folded, of_local = plan.fold_fn(block_l)
            joined, of_cross = all_reduce_lattice(
                folded, REPLICA_AXIS, plan.join_fn, plan.fold_fn
            )
            new_acc, of_join = plan.join_fn(acc_l, joined)
            of = (
                lax.psum(
                    (of_local | of_join).astype(jnp.int32), REPLICA_AXIS
                ) > 0
            ) | of_cross
            of = lax.psum(of.astype(jnp.int32), ELEMENT_AXIS) > 0
            if faulted:
                # A rejected block's join never lands, and its overflow
                # flags must not drive the elastic widen retry.
                new_acc = flt.tree_select(~reject, new_acc, acc_l)
                of = of & ~reject
            out_acc = (
                jax.tree.map(lambda x: x[None], new_acc) if plan.sharded
                else new_acc
            )
            outs = [out_acc, of]
            if telemetry:
                slots_of = plan.slots_fn or tele.generic_slots_changed
                slots = slots_of(acc_l, new_acc)
                local_rows = _rows_of(block_l)
                outs.append(_tel_reduced(
                    new_acc, slots,
                    max(local_rows - 1, 0) + n_ex + 1,
                    tele.shipped_bytes(folded) * n_ex,
                    plan.sum_axes,
                ))
            if faulted:
                outs.append(code)
            return tuple(outs)

        if faulted:
            @partial(
                jax.shard_map,
                mesh=mesh,
                in_specs=(acc_specs, block_specs, P()),
                out_specs=tuple(out_specs),
                check_vma=False,
            )
            def step_fn(acc, block, bix):
                return body(acc, block, bix)
        else:
            @partial(
                jax.shard_map,
                mesh=mesh,
                in_specs=(acc_specs, block_specs),
                out_specs=tuple(out_specs),
                check_vma=False,
            )
            def step_fn(acc, block):
                return body(acc, block)

        return step_fn

    def step(acc, staged, bix):
        fn = _cached(
            plan.kind, (acc, staged), mesh, build, telemetry, faults,
            donate_argnums=(0,) if donate else (),
        )
        if faulted:
            return fn(acc, staged, jnp.uint32(bix))
        return fn(acc, staged)

    # ---- accumulator init --------------------------------------------
    if init is not None:
        acc = jax.device_put(_widen_to(plan, init, caps), acc_sharding)
        if donate:
            # Never consume the CALLER's buffers: a resumed stream may
            # retry with the same init, and device_put of an
            # already-matching array can alias it. One copy, then
            # zero-copy from there on.
            acc = jax.tree.map(jnp.copy, acc)
    else:
        acc = jax.device_put(acc_template, acc_sharding)

    tel = tele.zeros() if telemetry else None
    overflow = None
    blocks_done = 0
    staged_bytes = 0
    overlap_hits = 0
    dropped_blocks: list = []
    rejected_blocks: list = []

    def partial_report():
        """The lost-so-far snapshot an interrupt must carry (lists are
        copied: the exception's view must not mutate afterwards)."""
        if not faulted:
            return None
        return StreamFaultReport(list(dropped_blocks),
                                 list(rejected_blocks))
    frontier_arr = None
    reclaimed = (jnp.zeros((), jnp.uint32), jnp.zeros((), jnp.float32))
    if compact_every:
        if plan.compact_fn is None:
            raise ValueError(f"{plan.kind}: no compaction kernel")
        frontier_arr = (
            jnp.zeros_like(acc_template.top[0] if plan.sharded
                           else acc_template.top)
            if frontier is None else jnp.asarray(frontier)
        )
    if widen_policy is not None and not plan.flag_axes:
        raise ValueError(
            f"{plan.kind}: mid-stream widening is not supported for this "
            f"kind (size capacities up front)"
        )

    metrics.count(f"stream.{plan.kind}_rounds")
    try:
        staged = stage(first) if first is not None else None
    except (StreamInterrupted, ValueError):
        raise
    except Exception as exc:
        metrics.count("stream.interrupted")
        jax.block_until_ready(jax.tree.leaves(acc))
        persist_resume(acc, 0)
        raise StreamInterrupted(
            exc, acc, 0, tel, fault_report=partial_report()
        ) from exc

    observe_depth(f"stream.{plan.kind}", first if first is not None else acc)
    with tele.span(f"stream.{plan.kind}"):
        while staged is not None:
            staged_bytes += tele.shipped_bytes(staged)
            if widen_policy is None:
                out = step(acc, staged, blocks_done)
            else:
                # Elastic retry: snapshot the accumulator (the donated
                # step consumes it; the join is idempotent, so
                # re-entering from the snapshot is sound), check flags
                # per block — a host sync — widen the implicated axes
                # and re-enter: gossip_elastic's overflow→widen→resume
                # loop, one block at a time.
                attempts = 0
                while True:
                    snap = jax.tree.map(jnp.copy, acc) if donate else acc
                    out = step(acc, staged, blocks_done)
                    flags = jnp.atleast_1d(out[1])
                    if not bool(jnp.any(flags)):
                        break
                    hot = tuple(
                        axis
                        for lane, axis in enumerate(plan.flag_axes)
                        if lane < flags.shape[0] and bool(flags[lane])
                        and axis
                    )
                    if not hot:
                        raise RuntimeError(
                            f"{plan.kind}: overflow lane not recoverable "
                            f"by widening (flags={flags})"
                        )
                    if attempts >= widen_policy.max_migrations:
                        raise RuntimeError(
                            f"stream still overflowing after {attempts} "
                            f"migrations (caps: {caps}) — raise "
                            f"policy.factor or max_migrations"
                        )
                    from .. import elastic as el

                    caps.update({
                        ax: el._grown(caps[ax], widen_policy.factor)
                        for ax in hot
                    })
                    metrics.count("stream.widen_retries")
                    acc = jax.device_put(
                        _widen_to(plan, snap, caps), acc_sharding
                    )
                    staged = jax.device_put(
                        _widen_to(plan, staged, caps), block_sharding
                    )
                    attempts += 1
            if faulted:
                # One host sync per block — the faults-mode price; the
                # fate feeds the report the caller re-streams from.
                code = int(out[-1])
                if code == 1:
                    dropped_blocks.append(blocks_done)
                elif code == 2:
                    rejected_blocks.append(blocks_done)
                out = out[:-1]
            acc = out[0]
            overflow = out[1] if overflow is None else overflow | out[1]
            if telemetry:
                tel = tele.combine(tel, out[2])
            blocks_done += 1
            if compact_every and blocks_done % compact_every == 0:
                acc, reclaimed = _compact_acc(
                    plan, acc, frontier_arr, reclaimed, acc_sharding
                )
            if wal_every and blocks_done % wal_every == 0:
                persist_resume(acc, blocks_done)
            if not pipeline:
                jax.block_until_ready(jax.tree.leaves(acc))
            elif not _ready(acc):
                # The next staging is issued while this block's join is
                # still in flight: the upload DMA overlaps the kernels.
                overlap_hits += 1
            staged = _advance(
                fetch, stage, acc, tel, blocks_done, partial_report,
                persist_resume,
            )
        jax.block_until_ready(jax.tree.leaves(acc))
        persist_resume(acc, blocks_done)

    if overflow is None:
        overflow = jnp.zeros((), bool)
    metrics.count("stream.blocks", blocks_done)
    metrics.count("stream.staged_bytes", staged_bytes)
    metrics.count("stream.overlap_hit", overlap_hits)
    metrics.observe("stream.acc_bytes", state_nbytes(acc))
    if compact_every:
        from ..reclaim import record_reclaim

        record_reclaim(
            f"stream.{plan.kind}", int(reclaimed[0]), float(reclaimed[1])
        )
    report = None
    if faulted:
        report = StreamFaultReport(dropped_blocks, rejected_blocks)
        if dropped_blocks:
            metrics.count("faults.packets_dropped", len(dropped_blocks))
        if rejected_blocks:
            metrics.count("faults.packets_rejected", len(rejected_blocks))
        if dropped_blocks or rejected_blocks:
            # A non-empty fault report is a postmortem boundary: the
            # caller must re-stream these blocks — record which, and
            # write the flight artifact (obs/recorder.py no-ops both
            # when no recorder is installed).
            from .. import obs

            obs.emit(
                "stream_fault_report",
                dropped=list(dropped_blocks),
                rejected=list(rejected_blocks),
            )
            obs.auto_dump(
                "stream_fault_report",
                dropped=len(dropped_blocks), rejected=len(rejected_blocks),
            )
    if telemetry:
        tel = tel._replace(
            stream_blocks=jnp.uint32(blocks_done),
            stream_staged_bytes=jnp.float32(staged_bytes),
            stream_overlap_hit=jnp.uint32(overlap_hits),
            reclaimed_slots=tel.reclaimed_slots + reclaimed[0],
            reclaimed_bytes=tel.reclaimed_bytes + reclaimed[1],
        )
        if wal is not None:
            tel = tel._replace(
                wal_bytes=jnp.float32(wal.bytes_appended - wal_b0),
                wal_fsyncs=jnp.uint32(wal.fsyncs - wal_f0),
            )
        if faulted:
            tel = tel._replace(
                faults_dropped=jnp.uint32(len(dropped_blocks)),
                faults_rejected=jnp.uint32(len(rejected_blocks)),
            )
        if tele.is_concrete(tel):
            tele.record(plan.kind, tel)
        if faulted:
            return acc, overflow, tel, report
        return acc, overflow, tel
    if faulted:
        return acc, overflow, report
    return acc, overflow


def _advance(fetch, stage, acc, tel, blocks_done, partial_report,
             persist_resume=lambda acc, done: None):
    """Fetch + stage the next block; a failure interrupts the stream
    with the accumulator intact (the failed block never entered a
    step) and, on a faulted run, the lost-so-far report
    (``partial_report`` is the driver's snapshot closure) — persisting
    the resume point first when the run is ``wal=``-durable. Contract
    violations (ValueError from ``stage``) propagate as-is — they are
    caller bugs, not stream faults."""
    try:
        nxt = fetch()
        return stage(nxt) if nxt is not None else None
    except ValueError:
        raise
    except Exception as exc:
        metrics.count("stream.interrupted")
        jax.block_until_ready(jax.tree.leaves(acc))
        persist_resume(acc, blocks_done)
        raise StreamInterrupted(
            exc, acc, blocks_done, tel, fault_report=partial_report()
        ) from exc


def _compact_acc(plan, acc, frontier_arr, reclaimed, acc_sharding):
    """One causal-stability compaction of the accumulator (reclaim/):
    async dispatch, no host sync; freed counts accumulate on device."""
    acc2, freed, freed_b = plan.compact_fn(acc, frontier_arr)
    acc2 = jax.device_put(acc2, acc_sharding)
    return acc2, (
        reclaimed[0] + jnp.sum(freed, dtype=jnp.uint32),
        reclaimed[1] + jnp.sum(freed_b, dtype=jnp.float32).astype(jnp.float32),
    )


# ---- public entry points --------------------------------------------------

def mesh_stream_fold_sparse(
    blocks: Iterable, mesh: Mesh, *, init=None, telemetry: bool = False,
    donate: bool = True, pipeline: bool = True, widen_policy=None,
    frontier=None, compact_every: int = 0, faults=None, wal=None,
    wal_every: int = 0, wal_base: int = 0,
):
    """Stream-fold SPARSE (segment-encoded) ORSWOT replica blocks
    ``[B, ...]`` into one converged state — the flagship arbitrary-N
    driver (``bench.py --flagship`` runs the 10,240 x 1M shape through
    it). Returns ``(state, overflow[2[, Telemetry]])``; semantics and
    flags (incl. the ``wal=`` durable-resume contract) per the module
    docstring."""
    return _stream_fold(
        _plan_sparse(), blocks, mesh, init=init, telemetry=telemetry,
        donate=donate, pipeline=pipeline, widen_policy=widen_policy,
        frontier=frontier, compact_every=compact_every, faults=faults,
        wal=wal, wal_every=wal_every, wal_base=wal_base,
    )


def mesh_stream_fold(
    blocks: Iterable, mesh: Mesh, *, init=None, telemetry: bool = False,
    donate: bool = True, pipeline: bool = True, widen_policy=None,
    frontier=None, compact_every: int = 0, faults=None, wal=None,
    wal_every: int = 0, wal_base: int = 0,
):
    """Stream-fold DENSE ORSWOT replica blocks ``[B, E, A]`` (content
    planes element-sharded over the mesh, ``mesh.orswot_specs``
    discipline). Returns ``(state, overflow[, Telemetry]])``."""
    return _stream_fold(
        _plan_dense(), blocks, mesh, init=init, telemetry=telemetry,
        donate=donate, pipeline=pipeline, widen_policy=widen_policy,
        frontier=frontier, compact_every=compact_every, faults=faults,
        wal=wal, wal_every=wal_every, wal_base=wal_base,
    )


def mesh_stream_fold_sparse_mvmap(
    blocks: Iterable, mesh: Mesh, *, sibling_cap: int = 4, init=None,
    telemetry: bool = False, donate: bool = True, pipeline: bool = True,
    widen_policy=None, frontier=None, compact_every: int = 0, faults=None,
    wal=None, wal_every: int = 0, wal_base: int = 0,
):
    """Stream-fold SPARSE ``Map<K, MVReg>`` replica blocks
    (ops/sparse_mvmap) — the register-family arbitrary-N driver.
    Returns ``(state, overflow[3][, Telemetry]])``. A sibling-cap
    overflow is NOT recoverable mid-stream (static join arg); re-enter
    with a larger ``sibling_cap``."""
    return _stream_fold(
        _plan_sparse_mvmap(sibling_cap), blocks, mesh, init=init,
        telemetry=telemetry, donate=donate, pipeline=pipeline,
        widen_policy=widen_policy, frontier=frontier,
        compact_every=compact_every, faults=faults, wal=wal,
        wal_every=wal_every, wal_base=wal_base,
    )


def mesh_stream_fold_sparse_sharded(
    blocks: Iterable, mesh: Mesh, *, init=None, telemetry: bool = False,
    donate: bool = True, pipeline: bool = True, frontier=None,
    compact_every: int = 0, faults=None, wal=None, wal_every: int = 0,
    wal_base: int = 0,
):
    """Stream-fold element-SHARDED sparse replica blocks ``[B, S, ...]``
    (from ``sparse_shard.split_segments``; S must equal the mesh's
    element axis): shard-local joins are exact (restriction commutes
    with join), so streaming composes with element sharding at no extra
    collective. The accumulator keeps the ``[S, ...]`` element-sharded
    layout. Mid-stream widening is unsupported here (size shard caps up
    front). Returns ``(state [S, ...], overflow[, Telemetry]])``."""
    return _stream_fold(
        _plan_sparse_sharded(), blocks, mesh, init=init,
        telemetry=telemetry, donate=donate, pipeline=pipeline,
        frontier=frontier, compact_every=compact_every, faults=faults,
        wal=wal, wal_every=wal_every, wal_base=wal_base,
    )


def iter_blocks(states, block_rows: int):
    """Slice a co-resident ``[N, ...]`` batch into ``[block_rows, ...]``
    stream blocks — the convenience bridge for populations that DO fit
    (tests, subsampled bit-identity gates) and the reference shape for
    real sources (host shards, checkpoint readers, DCN receivers)."""
    n = _rows_of(states)
    for lo in range(0, n, block_rows):
        yield jax.tree.map(lambda x: x[lo: lo + block_rows], states)


# ---- static-analysis registration (crdt_tpu.analysis) --------------------
#
# Every stream entry point registers kind + example-args builder +
# donation arity, so the aliasing gate (tools/check_aliasing.py) pins
# the accumulator's input_output_alias and the jit-lint walks the step
# program — the same coverage contract as the gossip/fold family. The
# registered args ARE the cached step's args: (accumulator, block).

def _register():
    from ..analysis import gate_states as gs
    from ..analysis.registry import register_entry_point

    def reg(name, kind, mk_acc, mk_block, invoke):
        register_entry_point(
            name, kind=kind,
            make_args=lambda mesh: (mk_acc(mesh), mk_block(mesh)),
            invoke=invoke, n_donated=1,
        )

    def sparse_acc(mesh):
        from ..ops import sparse_orswot as sp

        return sp.empty(gs.GE, gs.GA, gs.GD, 8)

    def dense_acc(mesh):
        from ..ops import orswot as ops

        return ops.empty(gs.GE, gs.GA, gs.GD)

    def mvmap_acc(mesh):
        from ..ops import sparse_mvmap as smv

        return smv.empty(gs.GE, gs.GA, gs.GD, 8)

    def sharded_acc(mesh):
        from ..ops import sparse_orswot as sp

        return sp.empty(
            gs.GE, gs.GA, gs.GD, 8, batch=(mesh.shape[ELEMENT_AXIS],)
        )

    reg(
        "mesh_stream_fold_sparse", "sparse_stream_fold",
        sparse_acc, lambda mesh: gs.mk_sparse(gs.replicas(mesh)),
        lambda mesh, args: mesh_stream_fold_sparse(
            [args[1]], mesh, init=args[0], donate=True
        ),
    )
    reg(
        "mesh_stream_fold", "orswot_stream_fold",
        dense_acc, lambda mesh: gs.mk_dense(gs.replicas(mesh)),
        lambda mesh, args: mesh_stream_fold(
            [args[1]], mesh, init=args[0], donate=True
        ),
    )
    reg(
        "mesh_stream_fold_sparse_mvmap", "sparse_mvmap_stream_fold_s4",
        mvmap_acc, lambda mesh: gs.mk_sparse_mvmap(gs.replicas(mesh)),
        lambda mesh, args: mesh_stream_fold_sparse_mvmap(
            [args[1]], mesh, init=args[0], donate=True
        ),
    )
    def sharded_block(mesh):
        from .sparse_shard import split_segments

        return split_segments(
            gs.mk_sparse(gs.replicas(mesh)), mesh.shape[ELEMENT_AXIS]
        )

    reg(
        "mesh_stream_fold_sparse_sharded", "sparse_sharded_stream_fold",
        sharded_acc, sharded_block,
        lambda mesh, args: mesh_stream_fold_sparse_sharded(
            [args[1]], mesh, init=args[0], donate=True
        ),
    )

    from ..analysis.registry import register_fault_surface

    for name in (
        "mesh_stream_fold", "mesh_stream_fold_sparse",
        "mesh_stream_fold_sparse_mvmap", "mesh_stream_fold_sparse_sharded",
    ):
        register_fault_surface(name, module=__name__)

    from ..analysis.registry import register_obs_event

    register_obs_event(
        "stream_fault_report", subsystem="parallel.stream",
        fields=("dropped", "rejected"), module=__name__,
    )


_register()
