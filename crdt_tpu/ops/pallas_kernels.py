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

Only the entry matrices fold in-kernel. The deferred-removal buffers are
tiny ([R, D, A] clocks + [R, D, E] masks with D ≈ 4–8) and their replay
is a pointwise mask over the folded result, so the wrapper handles them
with stock jnp (XLA fuses it into the epilogue): union all parked
removes, replay once against the folded entries, drop caught-up slots.
Replaying once at the end is equivalent to the pairwise join's
replay-at-every-node because replay is idempotent and monotone (it
zeroes exactly the dots the rm clocks cover, which no join can
resurrect past the final replay), and a slot is always replayed before
the catch-up drop — the property suite pins fused == tree fold.
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
    n_passes: int = 1,
    cellwise: bool = False,
    pre_t: bool = False,
    out_t: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Fused fold of the entry matrices only: ``top[R, A]``,
    ``ctr[R, E, A]`` → ``(top[A], ctr[E, A])``.

    ``n_passes > 1`` makes the grid re-walk the resident replica chunk
    that many times, accumulating into the same output block. Because
    the join is idempotent the result is unchanged, but the DMA and
    compute stream is exactly that of folding ``n_passes * R`` distinct
    replicas — the honest way to time a config-3-scale stream whose full
    dot-state exceeds HBM (bench.py), with one dispatch.

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
        grid=(e_padded // tile_e, n_passes * r_steps),
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
    slower than XLA:CPU. bench.py labels its reported path with this
    same predicate so cross-round numbers stay comparable."""
    return jax.default_backend() == "tpu"


def fold_fused(
    states: OrswotState,
    tile_e: int = 512,
    r_chunk: Optional[int] = None,
    interpret: Optional[bool] = None,
    n_passes: int = 1,
) -> Tuple[OrswotState, jax.Array]:
    """Drop-in replacement for ``ops.orswot.fold`` (same result, same
    overflow flag) with the replica reduction fused into one HBM pass.

    ``r_chunk`` defaults to a VMEM-safe size for the given actor count;
    ``interpret`` defaults to auto: compiled on TPU, interpreter
    elsewhere (CPU tests exercise the same kernel semantics).
    ``n_passes`` re-walks the replica batch that many times (identical
    result by idempotence; used by bench.py to time a stream of
    ``n_passes * R`` replicas in one dispatch).
    """
    if interpret is None:
        interpret = not _fused_backend()
    r, e, a = states.ctr.shape
    tile_e = min(tile_e, max(e, 1))
    r_chunk = _pick_r_chunk(r, a, tile_e, r_chunk)
    return _fold_fused_jit(states, tile_e, r_chunk, interpret, n_passes)


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


@partial(jax.jit, static_argnames=("tile_e", "r_chunk", "interpret", "n_passes"))
def _fold_fused_jit(
    states: OrswotState,
    tile_e: int,
    r_chunk: int,
    interpret: bool,
    n_passes: int = 1,
) -> Tuple[OrswotState, jax.Array]:
    r, e, a = states.ctr.shape
    top, ctr = _fold_entries_fused(
        states.top,
        states.ctr,
        tile_e=tile_e,
        r_chunk=r_chunk,
        interpret=interpret,
        n_passes=n_passes,
    )

    # Deferred epilogue (stock jnp; see module docstring): union every
    # replica's parked removes, replay once, drop caught-up, compact.
    d = states.dcl.shape[-2]
    dcl = states.dcl.reshape(r * d, a)
    dmask = states.dmask.reshape(r * d, e)
    dvalid = states.dvalid.reshape(r * d)
    dcl, dmask, dvalid = _dedupe_deferred(dcl, dmask, dvalid)
    ctr = _apply_parked(ctr, dcl, dmask, dvalid)
    still_ahead = ~jnp.all(dcl <= top[None, :], axis=-1)
    dvalid = dvalid & still_ahead
    dcl, dmask, dvalid, overflow = _compact_deferred(dcl, dmask, dvalid, d)
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
    dedupe → replay → drop caught-up → compact), then each keyset level
    innermost-out through the level's own ``settle_outer`` (dedupe →
    replay → compact → scrub). Same once-at-the-end soundness argument
    as the plain fold (module docstring): replay is monotone, idempotent
    zeroing and always precedes the catch-up drop; the per-level
    property gates in tests/test_pallas_fold.py pin fused == tree.

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
    key) arrays (wctr [R, A, K], val1 [R, A, K], clk [R, A, A, K]).

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
    val_t = jnp.swapaxes(child.val, -1, -2)
    live_t = jnp.swapaxes(child.valid, -1, -2)
    clk_t = jnp.transpose(child.clk, (0, 2, 3, 1))  # [R, S, A, K]
    ids = jnp.arange(a, dtype=child.wact.dtype)
    wctr = jnp.zeros((r, a, k), child.wctr.dtype)
    val1 = jnp.zeros((r, a, k), jnp.uint32)
    clk = jnp.zeros((r, a, a, k), child.clk.dtype)
    for si in range(s):
        own = (act_t[:, si, None, :] == ids[None, :, None]) & live_t[:, si, None, :]
        wctr = _umax(wctr, jnp.where(own, wctr_t[:, si, None, :], 0))
        # val ids are ≥ 0; shift by one so "absent" is distinguishable.
        val1 = _umax(
            val1,
            jnp.where(own, val_t[:, si, None, :].astype(jnp.uint32) + 1, 0),
        )
        clk = _umax(clk, jnp.where(own[:, :, None, :], clk_t[:, si, None, :, :], 0))
    return wctr, val1, clk


def _wide_to_slots(wctr, val1, clk, s: int):
    """K-minor dense cells (unbatched: wctr [A, K], val1 [A, K],
    clk [A, A, K]) → canonical slot table fitted to S slots, API shapes
    ``[K, S(, A)]``. Every large intermediate stays K-minor; only the
    final (output) transposes leave the lane-friendly layout."""
    from .mvreg import MVRegState

    a, k = wctr.shape
    present = wctr > 0
    # Canonical slot order (ops/map._canon_child): valid first, then by
    # actor (unique per key, so no further tiebreak needed).
    order = jnp.argsort(~present, axis=0, stable=True)  # [A, K] actor ids
    take = lambda x: jnp.take_along_axis(x, order, axis=0)
    valid = take(present)
    acts = jnp.broadcast_to(jnp.arange(a, dtype=jnp.int32)[:, None], (a, k))
    wact_s = jnp.where(valid, take(acts), 0)
    wctr_s = jnp.where(valid, take(wctr), 0)
    val_s = jnp.where(valid, take(val1).astype(jnp.int32) - 1, 0)
    clk_s = jnp.where(
        valid[:, None, :],
        jnp.take_along_axis(clk, order[:, None, :], axis=0),
        0,
    )

    # Back to the slot capacity: truncate (A > S) or zero-pad (A < S) —
    # canonical form keeps dead slots zeroed either way.
    def fit(x):
        if a >= s:
            return x[:s]
        return jnp.pad(x, [(0, s - a)] + [(0, 0)] * (x.ndim - 1))

    return MVRegState(
        wact=fit(wact_s).T,
        wctr=fit(wctr_s).T,
        clk=jnp.transpose(fit(clk_s), (2, 0, 1)),
        val=fit(val_s).T,
        valid=fit(valid).T,
    )


def fold_fused_map(
    states,
    tile_e: int = 512,
    r_chunk: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> Tuple[object, jax.Array]:
    """Fused fold for ``Map<K, MVReg>`` (``ops.map.MapState``) — the
    config-4 hot loop in one streamed HBM pass.

    The slot tables convert to a dense per-(key, actor) witness-counter
    slab (``_decode_wide``), whose replica fold is the cell-granular
    dot rule — the Pallas kernel with ``_join_step_cells``. Payload
    (val, clk) follows the surviving counter by a winner-select
    reduction in the jnp epilogue, then the parked keyset-removes replay
    once on the A-wide decoded table BEFORE the sibling-capacity check
    (the tree join's transient-overflow semantics). Returns
    ``(state, overflow[2])`` like ``ops.map.fold``."""
    if interpret is None:
        interpret = not _fused_backend()
    r, k, s = states.child.wact.shape
    a = states.top.shape[-1]
    tile_e = min(tile_e, max(k, 1))
    r_chunk = _pick_r_chunk(r, a, tile_e, r_chunk)
    return _fold_fused_map_jit(states, tile_e, r_chunk, interpret)


@partial(jax.jit, static_argnames=("tile_e", "r_chunk", "interpret"))
def _fold_fused_map_jit(states, tile_e, r_chunk, interpret):
    from . import map as map_ops

    r, k, s = states.child.wact.shape
    a = states.top.shape[-1]
    wctr, val1, clk = _decode_wide(states.child, a)  # [R, A, K] K-minor

    top, folded_w = _fold_entries_fused(
        states.top, wctr, tile_e, r_chunk, interpret, cellwise=True,
        pre_t=True, out_t=True,
    )  # top [A], folded_w [A, K]

    # Winner-select payload: the surviving counter's replica supplies
    # val and clk (ties ⇒ same dot ⇒ same payload, max is safe).
    match = (wctr == folded_w[None]) & (folded_w[None] > 0)
    val1 = jnp.max(jnp.where(match, val1, 0), axis=0)              # [A, K]
    clk = jnp.max(jnp.where(match[:, :, None, :], clk, 0), axis=0)  # [A, A, K]

    # Parked keyset-removes: union → dedupe → replay directly on the
    # K-minor cells (cell (y, k) dies iff some parked slot masks key k
    # with a clock covering its dot) → drop caught-up → compact, then
    # the sibling-capacity check — the tree join's transient-overflow
    # semantics (replay precedes the capacity check).
    d = states.dcl.shape[-2]
    dcl = states.dcl.reshape(r * d, a)
    dkeys = states.dkeys.reshape(r * d, k)
    dvalid = states.dvalid.reshape(r * d)
    dcl, dkeys, dvalid = _dedupe_deferred(dcl, dkeys, dvalid)

    def cover(maxcov, slot):
        cl, keys, dv = slot
        return _umax(maxcov, jnp.where(dv & keys[None, :], cl[:, None], 0)), None

    maxcov, _ = lax.scan(cover, jnp.zeros_like(folded_w), (dcl, dkeys, dvalid))
    kill = (folded_w > 0) & (folded_w <= maxcov)
    folded_w = jnp.where(kill, 0, folded_w)
    val1 = jnp.where(kill, 0, val1)
    clk = jnp.where(kill[:, None, :], 0, clk)

    still_ahead = ~jnp.all(dcl <= top[None, :], axis=-1)
    dvalid = dvalid & still_ahead
    dcl, dkeys, dvalid, d_of = _compact_deferred(dcl, dkeys, dvalid, d)

    c_of = jnp.any(jnp.sum(folded_w > 0, axis=0) > s)
    child = _wide_to_slots(folded_w, val1, clk, s)
    return (
        map_ops.MapState(
            top=top, child=child, dcl=dcl, dkeys=dkeys, dvalid=dvalid
        ),
        jnp.stack([c_of, jnp.any(d_of)]),
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
