"""Pallas TPU kernels for the anti-entropy hot loop.

The jnp ``tree_fold`` (ops/lattice.py) makes log2(R) passes over HBM —
each reduction level materialises a half-size replica batch. The fused
fold here streams every replica's dot matrix through VMEM once and
accumulates the lattice join on-chip: HBM traffic drops from
O(R·E·A·log R) to O(R·E·A), which is the whole game for the
bandwidth-bound ORSWOT merge (SURVEY.md §4.2; BASELINE config 3).

Layout: the dense state keeps ``ctr[R, E, A]`` with a small actor axis
(A ≈ 8–32). Lanes are 128-wide on TPU, so computing in ``[E, A]`` layout
wastes 15/16 of the VPU — the kernel therefore runs transposed
``[A, E]`` blocks (E on the lane axis), with the wrapper paying two XLA
transposes (one pass each) around the single fused pass.

Mosaic constraints shape two choices here:
- tops ride as ``[R, A, 1]`` so every access is a static slice on the
  untiled leading axis. (A ``[A, R]`` layout would need
  ``tops_ref[:, pl.ds(r, 1)]`` per replica, which does not compile:
  dynamic lane-axis slices must be 128-aligned.)
- the replica axis is walked by an inner sequential grid dimension in
  chunks of ``r_chunk``, with the running join living in the output
  block (same revisited block across the chunk steps — the standard
  TPU reduction pattern). VMEM holds one ``[r_chunk, A, tile_e]``
  input block, so R is unbounded.
- within a resident chunk the fold is a statically-unrolled
  pairwise-halving tree (``r_chunk`` is forced to a power of two):
  log2(rc) *batched* joins over ``[h, A, tile_e]`` values instead of
  rc sequential ``[A, tile_e]`` joins. Same bits by associativity/
  commutativity of the join; the long scalar-loop dependency chain —
  which left the VPU idle and capped the first version at ~136 GB/s —
  disappears, so the stream runs near DMA speed.

Only the entry matrices fold in-kernel. The deferred-removal buffers
([R, D, A] clocks + [R, D, E] masks with D ≈ 4–8) replay as a pointwise
mask over the folded result, so the wrapper handles them with stock jnp
after the kernel: union all parked removes, replay once against the
folded entries, drop caught-up slots, compact to D. Replaying once at
the end is equivalent to the pairwise join's replay-at-every-node
because replay is idempotent and monotone (it zeroes exactly the dots
the rm clocks cover, which no join can resurrect past the final
replay), and a slot is always replayed before the catch-up drop — the
property suite pins fused == tree fold.

The union's dedupe is quadratic in its R·D slot rows (a [S, S] clock
equality and a [S, S] × [S, E] group-OR), and a synced population
parks few removes: a remove parks only when it arrives ahead of the
adds it covers. With no valid slot the epilogue is the identity (ctr
unchanged, an empty compacted buffer, no overflow), so the wrapper
tests on the device whether any slot is valid and skips it when none is.
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .orswot import (
    OrswotState,
    _apply_parked,
    _compact_deferred,
    _dedupe_deferred,
)


def _umax(a, b):
    # Mosaic cannot legalize vector arith.maxui/minui on this toolchain;
    # compare+select (cmpi ult + arith.select) lowers fine and keeps
    # unsigned semantics for u32 counters.
    return jnp.where(a >= b, a, b)


def _umin(a, b):
    return jnp.where(a <= b, a, b)


def _join_step(acc_top, acc_ctr, b_top, b_ctr):
    """Pairwise entry-matrix join in transposed [..., A, E] layout —
    2D ``[A, E]`` operands or a batch ``[H, A, 1]``/``[H, A, E]`` of
    independent pairs (the tree levels below). Reference merge rule
    (ops/orswot.py ``join``): unseen dots survive, common members keep
    common dots ∪ each side's unseen dots."""
    wa = jnp.where(acc_ctr > b_top, acc_ctr, 0)
    wb = jnp.where(b_ctr > acc_top, b_ctr, 0)
    pa = jnp.any(acc_ctr > 0, axis=-2, keepdims=True)  # [..., 1, TILE_E]
    pb = jnp.any(b_ctr > 0, axis=-2, keepdims=True)
    common = _umax(_umin(acc_ctr, b_ctr), _umax(wa, wb))
    new_ctr = jnp.where(pa & pb, common, jnp.where(pa, wa, wb))
    return _umax(acc_top, b_top), new_ctr


def _join_step_cells(acc_top, acc_ctr, b_top, b_ctr):
    """Cell-granular dot join for the dense Map<K, MVReg> encoding: cell
    (k, y) holds actor y's sole live witness counter at key k (the
    per-(key, actor) uniqueness invariant — ``_decode_wide``), so the
    survival rule collapses per cell: same counter ⇒ same dot (keep);
    else each side's counter survives only if the other side's top never
    saw it — at most one side can win (y's counters are totally ordered
    and each side's top covers its own dots). No cross-lane presence
    term: absent is 0 and 0==0 keeps 0."""
    wa = jnp.where(acc_ctr > b_top, acc_ctr, 0)
    wb = jnp.where(b_ctr > acc_top, b_ctr, 0)
    new_ctr = jnp.where(acc_ctr == b_ctr, acc_ctr, _umax(wa, wb))
    return _umax(acc_top, b_top), new_ctr


def _fold_kernel(tops_ref, ctrs_ref, top_out_ref, ctr_out_ref, *, join_step):
    """Lattice fold over one replica chunk, one E-tile per program.
    tops_ref: [RC, A, 1]; ctrs_ref: [RC, A, TILE_E], RC a power of two.

    The in-chunk reduction is a statically-unrolled pairwise-halving
    tree: each level joins the chunk's top half against its bottom half
    as ONE batched [h, A, TILE_E] op, so the VPU always works on large
    vectors and the dependency chain is log2(RC) deep, not RC. The
    output block is the running accumulator across the (inner,
    sequential) replica-chunk grid axis; tree order equals sequential
    order because the join is associative/commutative/idempotent.

    ``join_step`` picks the merge rule: the orswot element rule
    (``_join_step``) or the cell-granular MVReg rule
    (``_join_step_cells``)."""
    rc = ctrs_ref.shape[0]
    tops = tops_ref[:]
    ctrs = ctrs_ref[:]
    n = rc
    while n > 1:
        h = n // 2
        tops, ctrs = join_step(tops[h:n], ctrs[h:n], tops[:h], ctrs[:h])
        n = h
    chunk_top, chunk_ctr = tops[0], ctrs[0]

    first = pl.program_id(1) == 0

    @pl.when(first)
    def _init():
        top_out_ref[:] = chunk_top
        ctr_out_ref[:] = chunk_ctr

    @pl.when(jnp.logical_not(first))
    def _acc():
        acc_top, acc_ctr = join_step(
            top_out_ref[:], ctr_out_ref[:], chunk_top, chunk_ctr
        )
        top_out_ref[:] = acc_top
        ctr_out_ref[:] = acc_ctr


def _fold_entries_fused(
    top: jax.Array,
    ctr: jax.Array,
    tile_e: int,
    r_chunk: int,
    interpret: bool,
    cellwise: bool = False,
    pre_t: bool = False,
    out_t: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Fused fold of the entry matrices only: ``top[R, A]``,
    ``ctr[R, E, A]`` → ``(top[A], ctr[E, A])``.

    ``cellwise`` selects the cell-granular MVReg dot rule
    (``_join_step_cells``) instead of the orswot element rule.
    ``pre_t`` accepts ``ctr`` already in the kernel's transposed
    ``[R, A, E]`` layout; ``out_t`` returns ``ctr[A, E]`` untransposed
    (E-minor) — large-E callers keep everything E-minor so no
    lane-padded [.., E, small] temp ever materialises (TPU tiling pads
    a narrow minor dim to 128 lanes; at E ≈ 1M that 32× blow-up is an
    OOM, the r5 config-4 failure)."""
    if pre_t:
        r, a, e = ctr.shape
        ctrs_t = ctr
    else:
        r, e, a = ctr.shape
        ctrs_t = jnp.swapaxes(ctr, -1, -2)  # [R, A, E]
    tile_e = min(tile_e, max(e, 1))
    rc = _pick_r_chunk(r, a, tile_e, r_chunk)  # clamped power of two
    pad_e = (-e) % tile_e
    pad_r = (-r) % rc

    tops3 = top[:, :, None]             # [R, A, 1]
    if pad_e:
        ctrs_t = jnp.pad(ctrs_t, ((0, 0), (0, 0), (0, pad_e)))
    if pad_r:
        # Empty replicas are the join identity (ops/orswot.py ``empty``).
        ctrs_t = jnp.pad(ctrs_t, ((0, pad_r), (0, 0), (0, 0)))
        tops3 = jnp.pad(tops3, ((0, pad_r), (0, 0), (0, 0)))
    e_padded = e + pad_e
    r_steps = (r + pad_r) // rc

    top_t, ctr_t = pl.pallas_call(
        partial(
            _fold_kernel,
            join_step=_join_step_cells if cellwise else _join_step,
        ),
        # Replica chunks on the inner (fastest) axis so the output block
        # accumulates across them before the E-tile advances.
        # ``j % r_steps`` is ``j`` on this grid; the modulo keeps the
        # index map, and so the compiled kernel, as the cells measure it.
        grid=(e_padded // tile_e, r_steps),
        in_specs=[
            pl.BlockSpec(
                (rc, a, 1), lambda i, j: (j % r_steps, 0, 0), memory_space=pltpu.VMEM
            ),
            pl.BlockSpec(
                (rc, a, tile_e),
                lambda i, j: (j % r_steps, 0, i),
                memory_space=pltpu.VMEM,
            ),
        ],
        out_specs=[
            pl.BlockSpec((a, 1), lambda i, j: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((a, tile_e), lambda i, j: (0, i), memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((a, 1), top.dtype),
            jax.ShapeDtypeStruct((a, e_padded), ctr.dtype),
        ],
        interpret=interpret,
    )(tops3, ctrs_t)

    return top_t[:, 0], (ctr_t[:, :e] if out_t else ctr_t.T[:e])


# VMEM budget for the streamed input block (double-buffered by the
# pipeline). 1 MiB measured fastest on v5e: the in-kernel halving tree
# holds a block copy plus ~block-sized intermediates, so a 2 MiB block
# leaves too little VMEM to overlap DMA with compute (484 GB/s at 1 MiB
# vs 77-436 GB/s at 2 MiB in the r3 sweep), and 4 MiB fails to compile.
_VMEM_BLOCK_BUDGET = 1024 * 1024

# Measured (tile_e, r_chunk) overrides from tools/tile_sweep.py
# --write-table, committed at tools/tile_table.json. None = not loaded
# yet; {} = no table / no entries (pure heuristic). The sweep writes
# entries keyed by actor count so the defaults are evidence, not
# folklore — see _tile_table().
_TILE_TABLE: Optional[dict] = None


def _tile_table() -> dict:
    """The committed autotune table (tools/tile_table.json), loaded
    once per process: ``{"entries": [{"a": .., "tile_e": ..,
    "r_chunk": ..}, ...]}`` as written by ``tools/tile_sweep.py
    --write-table``. Missing or malformed files degrade to the
    heuristic (an empty table) — the committed table is an override,
    never a requirement."""
    global _TILE_TABLE
    if _TILE_TABLE is None:
        import json
        import os

        path = os.path.join(
            os.path.dirname(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__)))),
            "tools", "tile_table.json",
        )
        try:
            with open(path) as f:
                table = json.load(f)
            table.get("entries", [])  # shape check
            _TILE_TABLE = table if isinstance(
                table.get("entries", None), list) else {}
        except OSError:
            _TILE_TABLE = {}  # no committed table: the heuristic is fine
        except (ValueError, AttributeError):
            # A table that EXISTS but does not parse is operator error,
            # not absence — degrade to the heuristic, but visibly.
            from ..utils.metrics import metrics

            metrics.count("pallas.tile_table.load_failed")
            _TILE_TABLE = {}
    return _TILE_TABLE


def _pick_r_chunk(
    r: int, a: int, tile_e: int, r_chunk: Optional[int],
    family: str = "fold", row_bytes: Optional[int] = None,
) -> int:
    if r_chunk is None:
        # A committed sweep result for this (kernel family, actor
        # count, tile_e) wins over the VMEM-budget heuristic; both
        # still get clamped to the batch and rounded to the halving
        # tree's power of two below. Entries are keyed by ``family``
        # ("fold" when absent — the pre-wire table form) so a sweep of
        # the fused WIRE kernel (ops/wire_kernels.py) can never be
        # silently reused by the fold kernels, or vice versa: the two
        # families stream different shapes through VMEM and a tile
        # optimal for one is folklore for the other. A malformed entry
        # (missing/non-numeric r_chunk) degrades to the heuristic —
        # the table is an override, never a requirement — but counts
        # in the registry so a fat-fingered sweep table is an operator
        # signal, not silence (tests/test_analysis.py pins it).
        for entry in _tile_table().get("entries", ()):
            try:
                if (entry.get("family", "fold") == family
                        and entry.get("a") == a
                        and entry.get("tile_e") == tile_e):
                    r_chunk = int(entry["r_chunk"])
                    break
            except (AttributeError, KeyError, TypeError, ValueError):
                from ..utils.metrics import metrics

                metrics.count("pallas.tile_table.malformed_entry")
                continue
    if r_chunk is None:
        # ``row_bytes``: the caller's VMEM bytes per row, lane-padded,
        # summed over every block it streams (the wire kernel's narrow
        # planes pad 2 -> 128 lanes, so unpadded bytes undercount 64x).
        if row_bytes is None:
            row_bytes = max(a, 1) * tile_e * 4
        r_chunk = max(8, _VMEM_BLOCK_BUDGET // row_bytes)
    r_chunk = min(r_chunk, max(r, 1))
    # The in-kernel halving tree needs a power of two; round down (the
    # replica axis is padded with join-identity empties to a multiple).
    return 1 << (r_chunk.bit_length() - 1)


def _fused_backend() -> bool:
    """THE backend-dispatch decision, in one place: the fused Pallas
    kernels run where they compile to Mosaic (the TPU); everywhere else
    "fused" would mean the Pallas *interpreter*, orders of magnitude
    slower than XLA:CPU."""
    return jax.default_backend() == "tpu"


def fold_fused(
    states: OrswotState,
    tile_e: int = 512,
    r_chunk: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> Tuple[OrswotState, jax.Array]:
    """Drop-in replacement for ``ops.orswot.fold`` (same result, same
    overflow flag) with the replica reduction fused into one HBM pass.

    ``r_chunk`` defaults to a VMEM-safe size for the given actor count;
    ``interpret`` defaults to auto: compiled on TPU, interpreter
    elsewhere (CPU tests exercise the same kernel semantics).
    """
    if interpret is None:
        interpret = not _fused_backend()
    r, e, a = states.ctr.shape
    tile_e = min(tile_e, max(e, 1))
    r_chunk = _pick_r_chunk(r, a, tile_e, r_chunk)
    return _fold_fused_jit(states, tile_e, r_chunk, interpret)


def fold_auto(states: OrswotState, prefer: str = "auto"):
    """Local replica-batch fold with backend-appropriate dispatch: the
    fused Pallas kernel where it compiles to Mosaic (TPU backends), the
    jnp log-tree fold elsewhere (where "fused" would mean the Pallas
    *interpreter* — orders of magnitude slower than XLA:CPU).

    ``prefer``: "auto" (backend pick), "fused", or "tree" — the forced
    modes exist so CPU tests can pin fused-in-situ semantics and so
    callers can opt out. Same ``(state, overflow)`` contract as
    ``ops.orswot.fold``; bit-identical results either way (the property
    suite pins it)."""
    from .orswot import fold as tree_fold

    if prefer not in ("auto", "fused", "tree"):
        raise ValueError(f"prefer must be auto|fused|tree, got {prefer!r}")
    if prefer == "fused" or (prefer == "auto" and _fused_backend()):
        return fold_fused(states)
    return tree_fold(states)


@partial(jax.jit, static_argnames=("tile_e", "r_chunk", "interpret"))
def _fold_fused_jit(
    states: OrswotState,
    tile_e: int,
    r_chunk: int,
    interpret: bool,
) -> Tuple[OrswotState, jax.Array]:
    r, e, a = states.ctr.shape
    top, ctr = _fold_entries_fused(
        states.top,
        states.ctr,
        tile_e=tile_e,
        r_chunk=r_chunk,
        interpret=interpret,
    )

    # Deferred epilogue (stock jnp; see module docstring): union every
    # replica's parked removes, replay once, drop caught-up, compact.
    # Skipped when no slot is valid, where it is the identity.
    d = states.dcl.shape[-2]

    def settle():
        dcl = states.dcl.reshape(r * d, a)
        dmask = states.dmask.reshape(r * d, e)
        dvalid = states.dvalid.reshape(r * d)
        dcl, dmask, dvalid = _dedupe_deferred(dcl, dmask, dvalid)
        out = _apply_parked(ctr, dcl, dmask, dvalid)
        still_ahead = ~jnp.all(dcl <= top[None, :], axis=-1)
        dvalid = dvalid & still_ahead
        return (out, *_compact_deferred(dcl, dmask, dvalid, d))

    def skip():
        return (ctr, jnp.zeros((d, a), states.dcl.dtype),
                jnp.zeros((d, e), states.dmask.dtype),
                jnp.zeros((d,), states.dvalid.dtype), jnp.zeros((), bool))

    ctr, dcl, dmask, dvalid, overflow = lax.cond(
        jnp.any(states.dvalid), settle, skip)
    return (
        OrswotState(top=top, ctr=ctr, dcl=dcl, dmask=dmask, dvalid=dvalid),
        jnp.any(overflow),
    )


# ---- fused folds for the composition layer -------------------------------

def _level_chain(level, states):
    """(outermost-first wrapper list, leaf OrswotState) of a nested
    orswot-leaf state."""
    from .nest import NestLevel

    chain, st = [], states
    lv = level
    while isinstance(lv, NestLevel):
        chain.append((lv, st))
        lv, st = lv.core, st[0]
    return chain, st


def fold_fused_level(
    level,
    states,
    tile_e: int = 512,
    r_chunk: Optional[int] = None,
    interpret: Optional[bool] = None,
    element_axis=None,
) -> Tuple[object, jax.Array]:
    """Drop-in fused replacement for ``NestLevel.fold`` on any
    orswot-leaf nested level (map_orswot, map3, deeper compositions):
    the leaf entry slab folds in ONE Pallas HBM pass exactly as
    ``fold_fused`` does, and every deferred-buffer level settles once in
    a jnp epilogue — leaf member-removes first (flatten R·D slots →
    dedupe → replay → drop caught-up → compact, skipped when none is
    valid), then each keyset level innermost-out through the level's
    own ``settle_outer`` (dedupe → replay → compact → scrub). Same
    once-at-the-end soundness argument as the plain fold (module
    docstring): replay is monotone, idempotent zeroing and always
    precedes the catch-up drop; the per-level property gates in
    tests/test_pallas_fold.py pin fused == tree.

    Returns ``(state, flags[L])`` with ``NestLevel.fold``'s lane order
    (innermost level first)."""
    if interpret is None:
        interpret = not _fused_backend()
    _, leaf = _level_chain(level, states)
    if isinstance(leaf, OrswotState):
        r, e, a = leaf.ctr.shape
    else:  # the Map<K, MVReg> leaf: dense cells are [R, K, A]
        r, e, _ = leaf.child.wact.shape
        a = leaf.top.shape[-1]
    tile_e = min(tile_e, max(e, 1))
    r_chunk = _pick_r_chunk(r, a, tile_e, r_chunk)
    return _fold_fused_level_jit(
        level, states, tile_e, r_chunk, interpret, element_axis
    )


@partial(
    jax.jit,
    static_argnames=("level", "tile_e", "r_chunk", "interpret", "element_axis"),
)
def _fold_fused_level_jit(
    level, states, tile_e, r_chunk, interpret, element_axis=None
):
    chain, leaf = _level_chain(level, states)
    if isinstance(leaf, OrswotState):
        folded_leaf, leaf_of = _fold_fused_jit(leaf, tile_e, r_chunk, interpret)
    else:  # the Map<K, MVReg> leaf (map_map family)
        folded_leaf, leaf_of = _fold_fused_map_jit(
            leaf, tile_e, r_chunk, interpret
        )

    folded = folded_leaf
    flags = [jnp.atleast_1d(leaf_of)]
    for lv, bst in reversed(chain):  # innermost wrapper first
        d = bst[1].shape[-2]
        flat = lambda x: x.reshape((-1,) + x.shape[2:])
        wrapped = lv._make(folded, flat(bst[1]), flat(bst[2]), flat(bst[3]))
        wrapped, of = lv.settle_outer(wrapped, d, element_axis)
        folded = wrapped
        flags.append(of[None])
    return folded, jnp.concatenate(flags)


def _decode_wide(child, a: int):
    """Slot table ``MVRegState [R, K, S…]`` → K-minor dense per-(actor,
    key) witness counters ``wctr [R, A, K]`` (0 = no live sibling).

    Sound because a key holds at most one live sibling per actor: a
    later write by the same actor carries a clock ≥ its earlier write's
    (actor knowledge is monotone), so apply-time domination evicts the
    older one, and the merge survival rule kills the smaller counter
    against the witnessing side's top (``_join_step_cells``). The A/B
    suite pins the round-trip on every reachable state.

    K-minor layout throughout: TPU tiling pads the two minor dims to
    (8, 128), so any [.., K, small] temp pays a 16-64× lane-padding
    blow-up — at K = 1M that is an instant OOM (the r5 config-4
    failure). With K on the lane axis padding is ≤2× (the tiny
    slot/actor axis rides the sublane dim), and the decode itself is a
    static unroll over the S ≤ 8 slots instead of a device scatter."""
    r, k, s = child.wact.shape
    act_t = jnp.swapaxes(child.wact, -1, -2)    # [R, S, K]
    wctr_t = jnp.swapaxes(child.wctr, -1, -2)
    live_t = jnp.swapaxes(child.valid, -1, -2)
    ids = jnp.arange(a, dtype=child.wact.dtype)
    wctr = jnp.zeros((r, a, k), child.wctr.dtype)
    for si in range(s):
        own = (act_t[:, si, None, :] == ids[None, :, None]) & live_t[:, si, None, :]
        wctr = _umax(wctr, jnp.where(own, wctr_t[:, si, None, :], 0))
    return wctr


def _slot_order(folded_w, s: int):
    """Folded counters ``[A, K]`` → the canonical slot table's dots, fitted
    to S slots: per key the live cells, valid first and then by actor
    (unique per key, so no further tiebreak; ops/map._canon_child), as
    ``(wact [S, K], wctr [S, K], valid [S, K])``. Truncating when A > S
    keeps the lowest actors, as the tree join's canonical compaction
    does; dead slots are zero.

    Slot j takes the live actor with exactly j live actors below it (the
    exclusive running count over the actor axis): elementwise work over
    ``[A, K]`` per slot. A sort and its ``take_along_axis`` would lower to
    per-element gathers over all A·K cells on the TPU."""
    a, k = folded_w.shape
    present = folded_w > 0
    rank = jnp.cumsum(present, axis=0, dtype=jnp.int32) - present
    ids = jnp.arange(a, dtype=jnp.int32)[:, None]
    wact, wctr, valid = [], [], []
    for j in range(min(s, a)):
        sel = present & (rank == j)     # at most one actor per key
        valid.append(jnp.any(sel, axis=0))
        wact.append(jnp.max(jnp.where(sel, ids, 0), axis=0))
        wctr.append(jnp.max(jnp.where(sel, folded_w, 0), axis=0))
    pad = lambda x: jnp.pad(jnp.stack(x), ((0, max(s - a, 0)), (0, 0)))  # noqa: E731
    return pad(wact), pad(wctr), pad(valid)


def _payload_of(child, wact, wctr):
    """The payload of the selected dots (``wact``/``wctr [S, K]``, counter
    0 = none) among a block of slot tables ``MVRegState [N, K, S…]``:
    ``(val + 1 [S, K], clk [S, A, K])``, 0 where no row holds the dot.
    Every row that holds a dot holds the same payload for it (one dot is
    one write), so the max over rows is that payload. One reduction over
    (rows, slots) per selected slot: XLA streams each from the slot
    planes without materialising a wide temporary."""
    act_t = jnp.swapaxes(child.wact, -1, -2)    # [N, S, K]
    wctr_t = jnp.swapaxes(child.wctr, -1, -2)
    # val ids are ≥ 0; shift by one so "absent" is distinguishable.
    val_t = jnp.swapaxes(child.val, -1, -2).astype(jnp.uint32) + 1
    live_t = jnp.swapaxes(child.valid, -1, -2)
    clk_t = jnp.transpose(child.clk, (0, 2, 3, 1))  # [N, S, A, K]
    vals, clks = [], []
    for j in range(wact.shape[0]):
        hit = live_t & (wctr[j] > 0) & (act_t == wact[j]) & (wctr_t == wctr[j])
        vals.append(jnp.max(jnp.where(hit, val_t, 0), axis=(0, 1)))
        clks.append(jnp.max(jnp.where(hit[:, :, None, :], clk_t, 0), axis=(0, 1)))
    return jnp.stack(vals), jnp.stack(clks)


# HBM budget for the replica chunk a map fold decodes at once: at config
# 4's width (A = 8, K = 2^20) the wide counters are 32 MiB a replica, so
# a chunk is 32 replicas and a 64-replica fold holds half its batch in
# the wide form at a time, whatever R is.
_MAP_CHUNK_BUDGET = 1 << 30


def _map_chunk_rows(r: int, a: int, k: int) -> int:
    """Replicas a map fold decodes per chunk: the most whose wide
    counters ``[rows, A, K]``, padded to the (8, 128) tiles, fit
    ``_MAP_CHUNK_BUDGET``, rounded down to a power of two (the kernel's
    halving tree then takes a whole chunk unpadded), and at most R."""
    row = -(-a // 8) * 8 * (-(-k // 128) * 128) * 4
    rows = max(1, min(r, _MAP_CHUNK_BUDGET // row))
    return 1 << (rows.bit_length() - 1)


def fold_fused_map(
    states,
    tile_e: int = 512,
    r_chunk: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> Tuple[object, jax.Array]:
    """Fused fold for ``Map<K, MVReg>`` (``ops.map.MapState``), the
    config-4 hot loop, in replica chunks of ``_map_chunk_rows``.

    Two passes over the batch, each chunk by chunk with a running
    accumulator, so the fold's temporaries are bounded by one chunk and
    not by R. Counters: each chunk's slot tables decode to the dense
    per-(key, actor) witness counters (``_decode_wide``), whose replica
    fold is the cell-granular dot rule (the Pallas kernel with
    ``_join_step_cells``); the chunk's result joins the accumulator by
    the same rule. Then the parked keyset-removes replay once on the
    folded counters BEFORE the sibling-capacity check (the tree join's
    transient-overflow semantics), and the surviving cells, put in
    canonical slot order, are the dots the result keeps. Payload: each
    chunk supplies the val and clk of the kept dots it holds
    (``_payload_of``); payload follows the dot, so the max over chunks
    is exact. Returns ``(state, overflow[2])`` like ``ops.map.fold``."""
    if interpret is None:
        interpret = not _fused_backend()
    r, k, s = states.child.wact.shape
    a = states.top.shape[-1]
    tile_e = min(tile_e, max(k, 1))
    r_chunk = _pick_r_chunk(r, a, tile_e, r_chunk)
    return _fold_fused_map_jit(
        states, tile_e, r_chunk, interpret, _map_chunk_rows(r, a, k))


def _chunked(states, rows: int, step, acc):
    """``step(acc, block)`` over the replica axis of ``states`` in blocks
    of ``rows`` (a loop, so one block is live at a time), then once over
    the remainder."""
    r = jax.tree.leaves(states)[0].shape[0]
    full, tail = divmod(r, rows)
    cut = lambda lo, n: jax.tree.map(  # noqa: E731
        lambda x: lax.dynamic_slice_in_dim(x, lo, n, axis=0), states)
    if full == 1:
        acc = step(acc, cut(0, rows))
    elif full:
        acc = lax.fori_loop(0, full, lambda i, c: step(c, cut(i * rows, rows)), acc)
    if tail:
        acc = step(acc, cut(full * rows, tail))
    return acc


@partial(jax.jit, static_argnames=("tile_e", "r_chunk", "interpret"))
def _map_chunk_fold(top, wctr, tile_e, r_chunk, interpret):
    """One chunk's counters ``[N, A, K]`` folded by the Pallas kernel's
    cell rule: ``(top [A], wctr [A, K])``. A jit of its own so that the
    kernel keeps this name in the compiled program and the device trace."""
    return _fold_entries_fused(top, wctr, tile_e, r_chunk, interpret,
                               cellwise=True, pre_t=True, out_t=True)


@partial(jax.jit, static_argnames=("tile_e", "r_chunk", "interpret", "chunk_rows"))
def _fold_fused_map_jit(states, tile_e, r_chunk, interpret, chunk_rows=None):
    from ..utils.metrics import metrics
    from . import map as map_ops
    from .mvreg import MVRegState

    r, k, s = states.child.wact.shape
    a = states.top.shape[-1]
    rows = chunk_rows or _map_chunk_rows(r, a, k)
    # Static per compiled program: recorded once, when it is traced.
    metrics.observe("pallas.map_fold.chunk_rows", rows)
    metrics.observe("pallas.map_fold.chunks", -(-r // rows))

    def fold_counters(acc, block):
        top_b, child_b = block
        top_c, w_c = _map_chunk_fold(
            top_b, _decode_wide(child_b, a), tile_e, r_chunk, interpret)
        top, w = _join_step_cells(acc[0][:, None], acc[1], top_c[:, None], w_c)
        return top[:, 0], w

    zero = (jnp.zeros((a,), states.top.dtype), jnp.zeros((a, k), states.child.wctr.dtype))
    top, folded_w = _chunked((states.top, states.child), rows, fold_counters, zero)

    # Parked keyset-removes, chunk by chunk: replay every one on the
    # K-minor cells (cell (y, k) dies iff some parked slot masks key k
    # with a clock covering its dot), and keep those still ahead of the
    # folded top, united by clock in order of first appearance — what
    # dedupe → drop caught-up → compact over all R·D slots keeps. Then
    # the sibling-capacity check: the tree join's transient-overflow
    # semantics (replay precedes the capacity check).
    d = states.dcl.shape[-2]

    def park(acc, block):
        maxcov, pcl, pkeys, pvalid, of = acc
        n = block[0].shape[0]
        dcl = block[0].reshape(n * d, a)
        dkeys = block[1].reshape(n * d, k)
        dvalid = block[2].reshape(n * d)
        live = dvalid[:, None] & dkeys                               # [N·D, K]
        maxcov = _umax(maxcov, jnp.max(
            jnp.where(live[:, None, :], dcl[:, :, None], 0), axis=0))  # [A, K]
        ahead = dvalid & ~jnp.all(dcl <= top[None, :], axis=-1)
        cl, keys, valid = _dedupe_deferred(
            jnp.concatenate([pcl, dcl]), jnp.concatenate([pkeys, dkeys]),
            jnp.concatenate([pvalid, ahead]))
        cl, keys, valid, o = _compact_deferred(cl, keys, valid, d)
        return maxcov, cl, keys, valid, of | o

    maxcov, dcl, dkeys, dvalid, d_of = _chunked(
        (states.dcl, states.dkeys, states.dvalid), rows, park,
        (jnp.zeros_like(folded_w), jnp.zeros((d, a), states.dcl.dtype),
         jnp.zeros((d, k), bool), jnp.zeros((d,), bool), jnp.zeros((), bool)),
    )
    folded_w = jnp.where(folded_w <= maxcov, 0, folded_w)

    c_of = jnp.any(jnp.sum(folded_w > 0, axis=0) > s)
    wact, wctr, valid = _slot_order(folded_w, s)        # [S, K]
    val1, clk = _chunked(
        states.child, rows,
        lambda acc, b: jax.tree.map(_umax, acc, _payload_of(b, wact, wctr)),
        (jnp.zeros((s, k), jnp.uint32), jnp.zeros((s, a, k), states.child.clk.dtype)),
    )
    child = MVRegState(
        wact=wact.T,
        wctr=wctr.T,
        clk=jnp.transpose(clk, (2, 0, 1)),
        val=jnp.where(valid, val1.astype(jnp.int32) - 1, 0).T,
        valid=valid.T,
    )
    return (
        map_ops.MapState(
            top=top, child=child, dcl=dcl, dkeys=dkeys, dvalid=dvalid
        ),
        jnp.stack([c_of, d_of]),
    )


def fold_auto_level(level, states, prefer: str = "auto", element_axis=None):
    """Backend-appropriate fold dispatch for the nested family — the
    ``fold_auto`` of composed slabs: the fused Pallas path where it
    compiles to Mosaic (TPU backends), the jnp log-tree fold elsewhere.
    Same ``(state, flags)`` contract as ``NestLevel.fold``."""
    if prefer not in ("auto", "fused", "tree"):
        raise ValueError(f"prefer must be auto|fused|tree, got {prefer!r}")
    if prefer == "fused" or (prefer == "auto" and _fused_backend()):
        return fold_fused_level(level, states, element_axis=element_axis)
    return level.fold(states, element_axis)


def fold_auto_map(states, prefer: str = "auto"):
    """Backend-appropriate fold dispatch for ``Map<K, MVReg>`` replica
    batches; same contract as ``ops.map.fold``."""
    from .map import _tree_fold

    if prefer not in ("auto", "fused", "tree"):
        raise ValueError(f"prefer must be auto|fused|tree, got {prefer!r}")
    if prefer == "fused" or (prefer == "auto" and _fused_backend()):
        return fold_fused_map(states)
    return _tree_fold(states)
