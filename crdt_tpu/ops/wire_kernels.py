"""The fused δ wire kernel: gate ∧ mask ∧ encode ∧ checksum ∧ count in
ONE pass over the packet's clock lanes.

By PR 12 every δ ring round made five separate elementwise passes over
the outbound packet planes — the digest gate (PR 3), the ack-window
mask (PR 9), the integrity checksum lane (PR 8), the fault draws'
payload walk (PR 8), and the telemetry byte counters (PR 2/12) — each
a full read of the same lanes, exactly the layered-HBM-traffic shape
the fused fold in :mod:`.pallas_kernels` was built to kill for merges.
This module is the wire-side twin: one Pallas kernel reads each slot's
clock lanes ONCE and emits

- the **gate verdicts** — digest-covered (``ctxs == know`` ∧
  ``know <= digest``, the ``gate_delta`` rule) and ack-covered
  (content equal to the peer's positively confirmed rows under a
  covered context, the ``ackwin.gate_window`` rule) — so the two
  redundancy layers cost no extra reads;
- the **bit-packed encoding** — every clock lane delta-encoded against
  the link watermark as a biased u16 (`(value - base) + 32768`, exact
  for values within ±32 Ki of the base) with TWO lanes packed per u32
  wire word (the half-split pairing: output word ``j`` carries input
  columns ``j`` and ``W + j``), masked slots zeroed so the wire stays
  canonical;
- the **fit mask** — slots whose encoding would not round-trip are
  DEFERRED (shipped invalid; the ring re-marks them dirty and the
  residue certificate counts the starvation — parallel/wire.py
  documents the soundness contract);
- the **checksum partial** — the position-weighted modular sum of the
  output words, bit-equal to what ``faults.integrity.checksum`` would
  compute for this leaf, so the receiver verifies the wire with the
  stock integrity lane;
- the **packed-word count** — nonzero output words, the
  ``wire_packed_bytes`` telemetry unit.

The kernel is ONE program; each δ flavor (dense, map, map3/map_orswot
nested) instantiates it with its own static lane map (column ranges of
the ctx plane, gate/ack flags — :class:`WireLaneSpec`), so autotuning
and the static-analysis surface registry see one kernel FAMILY with
per-flavor instances (``tools/tile_table.json`` entries carry
``family: "wire"`` — :func:`.pallas_kernels._pick_r_chunk` refuses to
reuse fold-family tiles here).

Backend dispatch follows :func:`.pallas_kernels._fused_backend`:
compiled on TPU, the Pallas **interpreter** elsewhere — the interpret
path traces to plain lax ops, so CPU tier-1 exercises bit-identical
kernel semantics (tests/test_wire.py pins fused == layered per
flavor).
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_kernels import _fused_backend, _pick_r_chunk

# The biased-u16 window: a clock lane encodes exactly when its value is
# within [-BIAS, BIAS - 1] of the link watermark (wrapping arithmetic —
# exact round-trip for any unsigned clock dtype).
BIAS = 32768
_U16 = 65536


class WireLaneSpec(NamedTuple):
    """The static lane map of one flavor's slot clock matrix.

    ``lc`` clock columns per slot (concatenated plane columns, in the
    flavor codec's declared order), with the per-slot causal-context
    plane occupying columns ``[ctx_lo, ctx_hi)``. ``gated`` /
    ``acked`` select which verdict lanes the kernel computes;
    ``parked`` marks the parked-buffer instantiation (no gates — a
    fit failure there is a LOST slot, not a deferral). Hashable: one
    jit/pallas specialization per flavor instance."""

    lc: int
    ctx_lo: int = 0
    ctx_hi: int = 0
    gated: bool = False
    acked: bool = False
    parked: bool = False

    @property
    def w(self) -> int:
        """Output wire words per slot (two u16 lanes per u32 word)."""
        return (self.lc + 1) // 2


class WirePackOut(NamedTuple):
    """One fused pack pass's outputs (all per the kernel's single read
    of the slot lanes)."""

    words: jax.Array   # [N, W] u32 — the bit-packed wire lanes
    keep: jax.Array    # [N] bool — slots that ship (post gate+fit)
    defer: jax.Array   # [N] bool — valid, ungated, but unencodable
    covered: jax.Array # [N] bool — ack-window verdicts (skip-byte unit)
    nnz: jax.Array     # u32 — nonzero wire words (packed-bytes unit)
    chk: jax.Array     # u32 — integrity-checksum partial for `words`


def _wire_kernel(spec: WireLaneSpec, n: int, rc: int, *refs):
    """The fused pass, one row chunk per program. Positional refs
    (presence by spec flags): the clock columns split into halves lo
    [RC, W] (columns ``[0, W)``) and hi [RC, W] (columns ``[W, 2W)``),
    the watermark halves blo/bhi [RC, W], valid [RC, 1], [ctxs [RC, A],
    know [RC, A], dig [RC, A]] when gated, [winc halves wlo/whi [RC, W],
    ack_ok [RC, 1]] when acked, then outputs: out [RC, W],
    keep/defer/cov [RC, 1], stats [1, 8] (the same revisited block
    across the sequential row-chunk grid — the standard TPU reduction
    pattern the fold kernel uses). The wrapper splits the columns: a
    lane slice at an offset inside the 128-lane tile (``x[:, W:2W]``)
    reads the wrong lanes under Mosaic, so the kernel slices nothing.
    ``n`` is the UNPADDED row count — checksum weights must match the
    shipped (unpadded) leaf's flat lane order, so rows are indexed
    globally via the program id."""
    i = 0
    lo = refs[i][:]; i += 1
    hi = refs[i][:]; i += 1
    blo = refs[i][:]; i += 1
    bhi = refs[i][:]; i += 1
    valid = refs[i][:] != 0; i += 1
    if spec.gated:
        ctxs = refs[i][:]; i += 1
        know = refs[i][:]; i += 1
        dig = refs[i][:]; i += 1
    if spec.acked:
        wlo = refs[i][:]; i += 1
        whi = refs[i][:]; i += 1
        ack_ok = refs[i][:] != 0; i += 1
    out_ref, keep_ref, defer_ref, cov_ref, stats_ref = refs[i:]

    ct = lo.dtype
    w = lo.shape[-1]

    def all_lanes(x):
        return jnp.min(x.astype(jnp.int32), axis=-1, keepdims=True) != 0

    # ---- encode: biased-u16 delta vs the watermark, one read --------
    enc_lo = lo - blo + jnp.asarray(BIAS, ct)   # wraps in ct
    enc_hi = hi - bhi + jnp.asarray(BIAS, ct)
    # Padded columns hold clocks == base == 0 -> enc == BIAS: fits.
    fit_slot = (all_lanes(enc_lo < jnp.asarray(_U16, ct))
                & all_lanes(enc_hi < jnp.asarray(_U16, ct)))

    # ---- gate verdicts (the delta.gate_delta / ackwin.gate_window
    # rules, evaluated on the same resident lanes) --------------------
    if spec.gated:
        cov_d = valid & all_lanes(ctxs == know) & all_lanes(know <= dig)
    else:
        cov_d = jnp.zeros_like(valid)
    if spec.acked:
        # Content columns are every clock column OUTSIDE the ctx range
        # (padding columns compare equal by construction); the ctx
        # columns check coverage instead of equality.
        col = jax.lax.broadcasted_iota(jnp.int32, (1, w), 1)

        def half_ok(x, wx, first):
            is_ctx = (col + first >= spec.ctx_lo) & (col + first < spec.ctx_hi)
            # int32 selects: Mosaic cannot narrow a selected i8 to i1.
            same = all_lanes(jnp.where(is_ctx, 1, (x == wx).astype(jnp.int32)))
            covc = all_lanes(jnp.where(is_ctx, (x <= wx).astype(jnp.int32), 1))
            return same & covc

        cov_a = (valid & ~cov_d & ack_ok & half_ok(lo, wlo, 0)
                 & half_ok(hi, whi, w))
    else:
        cov_a = jnp.zeros_like(valid)

    keep = valid & ~cov_d & ~cov_a & fit_slot
    defer = valid & ~cov_d & ~cov_a & ~fit_slot

    # ---- masked pack: two u16 lanes per u32 word (half-split) -------
    def masked(enc):
        return jnp.where(keep, enc, jnp.zeros_like(enc)).astype(
            jnp.uint32
        ) & jnp.uint32(0xFFFF)

    words = masked(enc_lo) | (masked(enc_hi) << 16)

    # ---- checksum partial + packed-word count, same read ------------
    # Weights replicate integrity._lanes_u32's flat order over the
    # UNPADDED [n, w] leaf; padded rows contribute zero values, so
    # their (out-of-range) weights multiply zeros. Mosaic reduces only
    # signed integers, so the sums run in int32: two's-complement
    # wrap-around gives the same bits as the u32 modular sum.
    row0 = pl.program_id(0) * rc
    r_ix = row0 + jax.lax.broadcasted_iota(jnp.int32, words.shape, 0)
    c_ix = jax.lax.broadcasted_iota(jnp.int32, words.shape, 1)
    wts = 2 * (r_ix * w + c_ix) + 1
    chk = jnp.sum(words.astype(jnp.int32) * wts, dtype=jnp.int32)
    nnz = jnp.sum((words != 0).astype(jnp.int32), dtype=jnp.int32)

    out_ref[:] = words
    keep_ref[:] = keep.astype(jnp.int32)
    defer_ref[:] = defer.astype(jnp.int32)
    cov_ref[:] = cov_a.astype(jnp.int32)
    # No in-kernel scatter on Mosaic: place the two stats by lane select.
    col8 = jax.lax.broadcasted_iota(jnp.int32, (1, 8), 1)
    stats = jnp.where(col8 == 0, nnz, jnp.where(col8 == 1, chk, 0))

    first = pl.program_id(0) == 0

    @pl.when(first)
    def _init():
        stats_ref[:] = stats

    @pl.when(jnp.logical_not(first))
    def _acc():
        stats_ref[:] = stats_ref[:] + stats


def _lanes(cols: int) -> int:
    """Columns of one VMEM block row once padded to the 128-lane tile."""
    return -(-cols // 128) * 128


def _row_vmem_bytes(spec: WireLaneSpec, a: int) -> int:
    """Lane-padded 4-byte words per row over every block the kernel
    streams: the clock and watermark halves, valid, [ctxs, know, dig],
    [winc halves, ack_ok], and the four outputs."""
    lanes = 5 * _lanes(spec.w) + 4 * _lanes(1)
    if spec.gated:
        lanes += 3 * _lanes(a)
    if spec.acked:
        lanes += 2 * _lanes(spec.w) + _lanes(1)
    return 4 * lanes


def wire_pack(
    spec: WireLaneSpec,
    clocks: jax.Array,
    base: jax.Array,
    valid: jax.Array,
    know: Optional[jax.Array] = None,
    dig: Optional[jax.Array] = None,
    winc: Optional[jax.Array] = None,
    ack_ok: Optional[jax.Array] = None,
    interpret: Optional[bool] = None,
) -> WirePackOut:
    """One fused pack pass over a flavor's slot clock matrix
    ``clocks [N, LC]`` with per-lane watermark ``base`` and per-slot
    ``valid``. ``know``/``dig`` feed the digest verdict (``gated``),
    ``winc``/``ack_ok`` the ack verdict (``acked``) — shapes per
    :func:`_wire_kernel`. Returns :class:`WirePackOut`; the ``words``
    leaf is what ships.

    Dispatch follows the fold kernels: compiled on TPU backends, the
    Pallas interpreter elsewhere (bit-identical semantics — the CPU
    tier-1 path)."""
    if interpret is None:
        interpret = not _fused_backend()
    n, lc = clocks.shape
    assert lc == spec.lc, (lc, spec.lc)
    w = spec.w
    a = max(spec.ctx_hi - spec.ctx_lo, 1)
    # Row-chunk the grid via the shared autotune table, keyed on the
    # WIRE family so fold-family sweeps are never silently reused
    # (tools/tile_table.json; tests/test_wire.py pins the key split).
    rc = _pick_r_chunk(n, a, 2 * w, None, family="wire",
                       row_bytes=_row_vmem_bytes(spec, a))
    steps = (n + rc - 1) // rc
    pad_r = steps * rc - n

    def padded(x, cols=None):
        p = ((0, pad_r), (0, 0 if cols is None else cols - x.shape[-1]))
        return jnp.pad(x, p) if (p[0][1] or p[1][1]) else x

    def halves(x):
        x = padded(x, 2 * w)
        return [x[:, :w], x[:, w:]]

    ins = halves(clocks) + halves(base) + [
        padded(valid.astype(jnp.int32)[:, None])
    ]
    if spec.gated:
        ins += [padded(clocks[:, spec.ctx_lo:spec.ctx_hi]), padded(know),
                padded(dig)]
    if spec.acked:
        ins += halves(winc) + [padded(ack_ok.astype(jnp.int32)[:, None])]
    row2 = lambda i: (i, 0)  # noqa: E731
    in_specs = [
        pl.BlockSpec((rc, x.shape[-1]), row2, memory_space=pltpu.VMEM)
        for x in ins
    ]
    outs = pl.pallas_call(
        partial(_wire_kernel, spec, n, rc),
        grid=(steps,),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((rc, w), row2, memory_space=pltpu.VMEM),
            pl.BlockSpec((rc, 1), row2, memory_space=pltpu.VMEM),
            pl.BlockSpec((rc, 1), row2, memory_space=pltpu.VMEM),
            pl.BlockSpec((rc, 1), row2, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 8), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((steps * rc, w), jnp.uint32),
            jax.ShapeDtypeStruct((steps * rc, 1), jnp.int32),
            jax.ShapeDtypeStruct((steps * rc, 1), jnp.int32),
            jax.ShapeDtypeStruct((steps * rc, 1), jnp.int32),
            jax.ShapeDtypeStruct((1, 8), jnp.int32),
        ],
        interpret=interpret,
        name="wire_pack",
    )(*ins)
    words, keep, defer, cov, stats = outs
    return WirePackOut(
        words=words[:n],
        keep=keep[:n, 0] != 0,
        defer=defer[:n, 0] != 0,
        covered=cov[:n, 0] != 0,
        nnz=stats[0, 0].astype(jnp.uint32),
        chk=stats[0, 1].astype(jnp.uint32),
    )


def wire_unpack(
    spec: WireLaneSpec, words: jax.Array, base: jax.Array,
    keep: jax.Array, ct,
) -> jax.Array:
    """Invert :func:`wire_pack`'s encoding for the kept slots:
    ``value = base + (enc16 - BIAS)`` (wrapping in the clock dtype
    ``ct``), zeros elsewhere — bit-exact against the sender's masked
    packet by construction (the round-trip property
    tests/test_wire.py pins). Receive is deliberately plain lax — one
    pass XLA fuses with the apply's gathers; the Pallas kernel earns
    its keep on the SEND side where five layers used to stack."""
    w = spec.w
    lo = (words & jnp.uint32(0xFFFF)).astype(ct)
    hi = (words >> 16).astype(ct)
    enc = jnp.concatenate([lo, hi], axis=-1)[:, :spec.lc]
    dec = base[:, :spec.lc] + enc - jnp.asarray(BIAS, ct)
    sel = keep.reshape((-1, 1))
    return jnp.where(sel, dec, jnp.zeros_like(dec))


# ---- bitmaps: bool planes as u32 words ------------------------------------

def pack_bits(bits: jax.Array) -> jax.Array:
    """A flat bool vector as little-endian u32 bitmap words
    (``ceil(n / 32)`` of them) — the presence/ack masks' wire form.
    Pure lax on static shapes."""
    n = bits.shape[0]
    wn = max((n + 31) // 32, 1)
    padded = jnp.pad(bits.astype(jnp.uint32), (0, wn * 32 - n))
    lanes = padded.reshape(wn, 32)
    shifts = jnp.arange(32, dtype=jnp.uint32)
    return jnp.sum(lanes << shifts[None, :], axis=-1, dtype=jnp.uint32)


def unpack_bits(words: jax.Array, n: int) -> jax.Array:
    """Invert :func:`pack_bits` to the first ``n`` bools."""
    shifts = jnp.arange(32, dtype=jnp.uint32)
    bits = (words[:, None] >> shifts[None, :]) & jnp.uint32(1)
    return bits.reshape(-1)[:n] != 0


def pack_u16_pairs(vals: jax.Array) -> jax.Array:
    """A flat vector of values < 2^16 as half-split u16 pairs in u32
    words (word ``j`` carries lanes ``j`` and ``H + j`` — the same
    pairing convention as the clock kernel). Used for the id planes
    (slot indices, actor ids) whose static bound proves the narrowing
    lossless."""
    n = vals.shape[0]
    h = (n + 1) // 2
    v = jnp.pad(vals.astype(jnp.uint32), (0, 2 * h - n)) & jnp.uint32(0xFFFF)
    return v[:h] | (v[h:] << 16)


def unpack_u16_pairs(words: jax.Array, n: int, dtype) -> jax.Array:
    """Invert :func:`pack_u16_pairs` to the first ``n`` lanes."""
    lo = words & jnp.uint32(0xFFFF)
    hi = words >> 16
    return jnp.concatenate([lo, hi])[:n].astype(dtype)


def leaf_checksum(leaf: jax.Array) -> jax.Array:
    """``integrity.checksum``'s per-leaf partial (position-weighted
    modular sum) for a small host-assembled wire leaf — the chaining
    twin of the kernel's in-pass ``chk`` output
    (parallel/wire.py ``wire_checksum`` composes the two)."""
    from ..faults.integrity import _lanes_u32

    lanes = _lanes_u32(leaf)
    w = jnp.arange(lanes.shape[0], dtype=jnp.uint32) * 2 + 1
    return jnp.sum(lanes * w, dtype=jnp.uint32)


__all__ = [
    "BIAS", "WireLaneSpec", "WirePackOut", "leaf_checksum", "pack_bits",
    "pack_u16_pairs", "unpack_bits", "unpack_u16_pairs", "wire_pack",
    "wire_unpack",
]
