"""The shared ring scaffold for δ-state anti-entropy.

Both delta flavors (orswot rows — delta.py; map keys — delta_map.py)
run the identical mesh program: pad and shard (state, dirty, fctx),
locally fold the replica block (OR-folding dirty, max-folding
contexts), then ``rounds`` ppermute ring rounds of extract → shift →
apply, and finally the top-closure collective (tops stay FROZEN at
their local-fold values through the ring — see delta.py for why
contexts must never fold into them — so they lag the full-join top and
diverge across element shards; the union of the LOCAL-FOLD tops over
the whole mesh IS the full-join top, and once content has converged,
adopting it and re-replaying parked removes reproduces the full fold
exactly).

Only the type-specific pieces come in as closures: the local fold, the
extract/apply pair, the state specs, the post-closure replay — and,
for the zero-copy pipelined mode, the per-flavor digest gate.

Three orthogonal performance modes (all default-on where safe):

- ``donate=True`` — the jit donates (state, dirty); when the padded
  replica axis equals the mesh's the outputs alias those buffers in
  place (``input_output_alias``, gated by tools/check_aliasing.py), so
  the ring holds ONE copy of the state in HBM instead of two. ``fctx``
  is never donated: it has no matching output (the per-device fctx is
  loop-internal), so donating it would only trip XLA's unusable-
  donation warning.
- ``pipeline=True`` — double-buffered schedule: round r+1's packet is
  extracted from the pre-apply state and its ``ppermute`` put in
  flight BEFORE round r's packet merges, so the in-flight DMA crosses
  the loop edge and XLA's latency-hiding scheduler overlaps it with
  the merge kernels. The price is sends one apply stale: knowledge
  advances one hop per TWO rounds, so the default budget and the
  residue-certificate window widen to ``2*(P-1)-1`` (a pair of
  consecutive starvation-free rounds advances every mark one hop, and
  P-1 hops complete the ring). Same packets-per-round as the
  sequential schedule — latency is hidden, not bandwidth spent.
- ``digest=True`` — one tiny inverse-ring exchange of the FROZEN
  receiver tops before the loop (tops never change mid-ring, so one
  [A]-clock ppermute serves every round), then the flavor's ``gate``
  masks out packet slots whose whole knowledge the receiver's top
  already covers. Converged states are bit-identical — a covered
  slot's apply is a content no-op, and the tracking contract
  guarantees the covering device minted its own marks for those dots,
  so transitive delivery survives the dropped re-mark (delta.py
  ``gate_delta``). ``bytes_useful`` telemetry drops to O(changed
  lanes) while the wire shape (``bytes_exchanged``) stays static.

- ``ack_window=True`` — **ack-window back-propagation**
  (crdt_tpu/delta_opt/ackwin.py, Enes et al. 1803.02750 §4.2): each
  receiver ships one bool per applied packet slot back up-ring on the
  same inverse-ring channel the digest exchange uses; the sender
  promotes the confirmed slots into a per-link acked-interval window
  and masks every later δ whose content the peer has POSITIVELY
  confirmed joining under an equal-or-stronger context — including
  removals, which the stateless top digest can never vouch for (acks
  are positive knowledge of delivered content, not top inference, so
  the PR 3 wider-gate unsoundness does not arise). Layering: the
  digest gate needs no round-trip state and fires from round 0; the
  ack window needs per-link memory and starts paying once re-
  circulated knowledge comes back around — together they generalize
  ``gate_delta`` from "add-only slots under the frozen top" to
  arbitrary covered intervals. Converged states stay bit-identical;
  ``bytes_useful`` drops further and ``bytes_acked_skipped`` /
  ``ack_window_depth`` report the window's win (telemetry.py).

- ``fused=True`` — **one fused wire pass + bit-packed format**
  (crdt_tpu/parallel/wire.py over the Pallas kernel in
  crdt_tpu/ops/wire_kernels.py): the whole send side of a round —
  digest gate ∧ ack mask ∧ watermark encode ∧ checksum ∧ byte counts —
  executes as a single read of the packet lanes, and the packet ships
  as the all-u32 packed wire tree (bool planes as bitmaps, ids as u16
  pairs, clock lanes as biased-u16 deltas against the link watermark)
  instead of its in-memory pytree. Converged states are bit-identical
  to the layered path; slots outside the encoding window defer into
  the residue certificate and unencodable parked removes count as
  wire loss (wire.py documents the narrow-window soundness contract).
  ``fused=False`` traces the byte-identical layered (PR 12-era)
  program.

A sixth, non-performance mode is ``faults=`` (a
``crdt_tpu.faults.FaultPlan``, default None): seeded in-kernel fault
injection on every inbound link — drop / corrupt / delay draws minted
from ``jax.random`` inside the loop, an integrity checksum lane riding
each packet (corrupted arrivals are REJECTED, never joined), dead-rank
outbound drops, and eviction (the ring permutation rebuilt over live
ranks — still a true bijection — with evicted tops excluded from the
final closure). Two semantic consequences, both deliberate:

- lost packets VOID the residue certificate — the ring forces
  ``residue >= 1`` whenever anything dropped or was rejected, so a
  degraded run can never read as certified-converged; heal by
  state-driven resync (full-state gossip/fold over the returned rows —
  Almeida et al. 1603.01529, Enes et al. 1803.02750) or a fault-free δ
  re-run, and
- the final top-closure ADOPTS the mesh top only when the run lost
  nothing (adoption after loss would make receivers claim
  observed-and-removed for dots they never received — the delta.py
  inflated-context failure); lossy runs keep each device's own frozen
  top, leaving every row a valid, joinable partial state.

With every flag at its off value the traced program is byte-identical
to the pre-flag sequential ring (pinned by HLO comparison in
tests/test_zero_copy_ring.py, the PR-2 telemetry pattern; the
``faults=None`` pin lives in tests/test_faults.py)."""

from __future__ import annotations

import time
from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from .. import telemetry as tele
from ..delta_opt import ackwin as _ackwin
from ..obs import hist as _hist
from ..ops import wire_kernels as _wk
from ..utils.metrics import metrics, state_nbytes
from . import wire as _wire
from .mesh import ELEMENT_AXIS, REPLICA_AXIS


def run_delta_ring(
    kind: str,
    state,
    dirty: jax.Array,
    fctx: jax.Array,
    mesh: Mesh,
    rounds: Optional[int],
    cap: int,
    specs,                    # PartitionSpec pytree for the state
    local_fold: Callable,     # local -> (folded, overflow)
    extract: Callable,        # (state, dirty, fctx, cap, start) -> (pkt, dirty, fctx)
    apply_fn: Callable,       # (state, pkt, dirty, fctx) -> (state, dirty, fctx, of)
    close_top: Callable,      # (state, full_top) -> state  (re-replay parked)
    top_of: Callable = lambda s: s.top,  # composed states nest their top
    cache_extra: tuple = (),
    telemetry: bool = False,
    slots_fn: Optional[Callable] = None,
    pipeline: bool = True,
    digest: bool = True,
    gate: Optional[Callable] = None,  # (pkt, digest_clock) -> pkt
    donate: bool = False,
    faults=None,                      # crdt_tpu.faults.FaultPlan
    ack_window=False,                 # delta_opt/ackwin.py (False/None off)
    wal=None,                         # crdt_tpu.durability.Wal
    wal_kind: Optional[str] = None,   # registry merge kind for δ records
    fused: bool = True,               # parallel/wire.py fused wire path
):
    """Run the δ ring program; ``state``/``dirty``/``fctx`` must already
    be padded to the mesh. Returns ``(states [P, ...], dirty, overflow,
    residue)`` — the first three with the same conventions as
    mesh_gossip; ``residue`` is the RUNTIME convergence indicator the
    ROUNDS BUDGET docstrings promise (int32 scalar): the mesh-wide count
    of slot-starved row-rounds WITHIN THE FINAL CERTIFICATE WINDOW —
    rows that wanted a packet slot but lost it to ``cap``. Extract
    clears every row it ships, so rows still dirty right after an
    extract ARE the round's unshipped backlog — domain-forwarding
    re-marks (added back at apply time) never inflate the count.

    Soundness: every ever-changed row keeps at least one circulating
    mark (digest gating retires a mark only at a device whose frozen
    top covers it — a device the tracking contract guarantees minted
    its own equivalent mark), and a starvation-free round advances
    every mark one hop — one hop per TWO rounds under ``pipeline=True``
    (sends are one apply stale). The certificate window is therefore
    ``P-1`` sequential rounds, ``2*(P-1)-1`` pipelined; that many
    consecutive starvation-free FINAL rounds walk every mark through
    all P devices — ``residue == 0`` means the gossip provably equals
    the full join. The indicator is ONE-SIDED: ``residue > 0`` does not
    prove divergence, it means the run cannot be certified — either
    genuine residue, or a ``cap`` too small to clear the circulating
    forwarding marks (ungated marks never die, they only coalesce, so a
    tight cap can starve forever even after content converges). Re-run
    with more rounds (the budget formula in delta.py — doubled under
    ``pipeline=True``) and a cap comfortably above the steady-state
    per-device mark count. Starvation in EARLIER rounds of an extended
    budget is expected drain behavior and deliberately not counted. A
    budget below the window cannot complete the ring's propagation at
    all, so residue is forced >= 1 there regardless of starvation.

    ``telemetry=True`` appends an in-kernel Telemetry pytree as a fifth
    output (telemetry.py): per-round packet wire AND post-mask payload
    bytes (``bytes_exchanged`` / ``bytes_useful``) and ``slots_fn``
    changed-lane counts accumulate in the loop carry, the final-state
    gauges read the post-closure fold, and ``residue`` mirrors the
    fourth output. ``pipeline`` / ``digest`` / ``donate`` are the
    zero-copy modes the module docstring describes; with every flag off
    the trace is the flag-free program.

    ``faults=`` (a ``crdt_tpu.faults.FaultPlan``) turns on in-kernel
    fault injection (module docstring): the ring runs over the plan's
    LIVE ranks, every packet carries a checksum lane, and a
    ``faults.FaultCounters`` pytree is appended as the LAST output
    (after the Telemetry pytree when both flags are on). Lost packets
    force ``residue >= 1`` and suppress top adoption — the returned
    rows are then valid partial states awaiting state-driven resync.

    ``ack_window=True`` (module docstring; crdt_tpu/delta_opt/ackwin.py)
    adds the per-link acked-interval window: one bool-per-slot ack
    ppermute per round on the inverse channel, sender-side masking of
    positively confirmed δs. Output arity is unchanged — the window
    lives and dies in the loop carry; its win shows up in
    ``bytes_useful`` / ``bytes_acked_skipped`` / ``ack_window_depth``
    under ``telemetry=True`` and the ``delta_opt.acked_skipped[.kind]``
    registry twins. Off (the default) traces the byte-identical
    pre-flag program, like every other mode flag.

    ``fused=True`` (the default) routes every packet through the ONE
    fused wire pass (crdt_tpu/parallel/wire.py over the Pallas kernel
    in crdt_tpu/ops/wire_kernels.py): digest gate ∧ ack mask ∧
    watermark encode ∧ checksum ∧ byte counts in a single read of the
    packet lanes, shipped as the bit-packed all-u32 wire tree (bool
    planes as bitmaps, ids as u16 pairs, clock lanes as biased-u16
    deltas against the link watermark). Converged states are
    bit-identical to the layered path; slots outside the encoding
    window DEFER (re-marked dirty before the round's backlog count, so
    the residue certificate prices them) and an unencodable parked
    remove counts as wire loss (residue forced ≥ 1, top adoption
    suppressed — wire.py documents the soundness contract).
    ``fused=False`` traces the byte-identical PR 12-era layered
    program (HLO-pinned in tests/test_wire.py) and marks its jit-cache
    entry with ``wire.WireKey`` so the analysis gates keep reading the
    default program.

    ``wal=`` (a ``crdt_tpu.durability.Wal``) makes the run DURABLE,
    host-side: the pre-run state seeds the log's diff base (a device
    copy, so ``donate=True`` stays sound), and after the run the
    converged rows append as ONE irreducible δ record
    (``delta_opt.decompose`` over the previous logged state) followed
    by a round barrier (``Wal.mark_round`` — the ``on_round`` fsync
    policy's one-barrier-per-round point). ``wal_kind`` names the
    registered merge kind the record decomposes under (the δ flavors
    pass their own). A crash then recovers to the last durable round
    via ``durability.recover`` — the traced program is UNTOUCHED (the
    append reads the returned arrays; flag off = no trace change by
    construction)."""
    from .anti_entropy import _cached, _ring_donate_argnums, _tel_reduced

    if wal is not None and wal_kind is None:
        raise ValueError(
            "wal= needs wal_kind= (the registered merge kind δ records "
            "decompose under)"
        )
    p = mesh.shape[REPLICA_AXIS]
    gated = digest and gate is not None
    faulted = faults is not None
    acked = bool(ack_window)
    # The fused wire path needs the flavor's registered codec (its
    # know function — parallel/wire.py); kinds without one (a future
    # flavor mid-bringup) fall back to the layered wire.
    fused_on = bool(fused) and kind in _wire.WIRE_SURFACES
    delay_mode = faulted and faults.delay > 0
    # Certificate window / propagation diameter: one hop per round
    # sequentially, one hop per two rounds pipelined (module docstring).
    win = (p - 1) if not pipeline else max(2 * (p - 1) - 1, 0)
    if rounds is None:
        rounds = win
    if faulted:
        from .. import faults as flt

        # The ring over LIVE ranks (evicted self-loop — still a true
        # bijection of the axis, so the collective lint holds).
        perm = flt.ring_perm(p, faults.evicted)
        inv_perm = flt.inv_ring_perm(p, faults.evicted)
        snd_tbl = flt.sender_of(p, faults.evicted)
    else:
        perm = [(i, (i + 1) % p) for i in range(p)]
        # Digest exchange runs AGAINST the ring: device i's packets land
        # on i+1, so i needs i+1's frozen top — ship tops one hop
        # down-ring.
        inv_perm = [(i, (i - 1) % p) for i in range(p)]
    argnums = _ring_donate_argnums(state, mesh, donate, n=2)

    def build():
        out_specs = (specs, P(REPLICA_AXIS, ELEMENT_AXIS), P(), P())
        if telemetry:
            out_specs = out_specs + (tele.specs(),)
        if faulted:
            out_specs = out_specs + (flt.counters_specs(),)
        slots_of = slots_fn or tele.generic_slots_changed
        # Telemetry loop-carry width: slots, shipped, useful, plus the
        # two in-kernel histograms (per-round backlog and per-round
        # useful bytes — obs/hist.py Hist subtrees riding the carry);
        # the fused wire adds the packed-bytes scalar and its
        # histogram (wire_packed_bytes / hist_packed_bytes).
        n_tel = (7 if fused_on else 5) if telemetry else 0

        @partial(
            jax.shard_map,
            mesh=mesh,
            in_specs=(
                specs,
                P(REPLICA_AXIS, ELEMENT_AXIS),
                P(REPLICA_AXIS, ELEMENT_AXIS, None),
            ),
            out_specs=out_specs,
            check_vma=False,
        )
        def gossip_fn(local, local_dirty, local_fctx):
            folded, of = local_fold(local)
            d = jnp.any(local_dirty, axis=0)
            f = jnp.max(local_fctx, axis=0)
            if gated:
                rtop = lax.ppermute(top_of(folded), REPLICA_AXIS, inv_perm)

            # ---- fault helpers (traced ONLY when faults is not None;
            # the flag-off program below is byte-identical pre-flag) --

            def ship(pkt):
                """Put one packet on the wire — with the integrity
                checksum lane riding the same ppermute when faulted."""
                if not faulted:
                    return jax.tree.map(
                        lambda x: lax.ppermute(x, REPLICA_AXIS, perm), pkt
                    )
                return jax.tree.map(
                    lambda x: lax.ppermute(x, REPLICA_AXIS, perm),
                    (pkt, flt.checksum(pkt)),
                )

            def receive(wire, r, final=False):
                """Receiver side of the wire for the packet applied at
                round ``r`` (faults.receive_wire: draws, evicted
                self-loop masking, corruption, checksum verify).
                ``final=True`` (ring epilogue) delivers a would-be-
                delayed packet now — no later round to hold it for."""
                if not faulted:
                    return wire, None, None
                pkt, chk_in = wire
                return flt.receive_wire(
                    faults, r, REPLICA_AXIS, snd_tbl, pkt, chk_in,
                    delay_ok=delay_mode and not final,
                )

            def select_apply(applied, prior, keep):
                """Discard a dropped/rejected/held delivery: the apply
                ran, its outputs are deselected (no traced branch)."""
                st2, d2, f2, of_r = applied
                st0, d0, f0 = prior
                return (
                    flt.tree_select(keep, st2, st0),
                    jnp.where(keep, d2, d0),
                    jnp.where(keep, f2, f0),
                    of_r & keep,
                )

            def tick(fc, fates):
                # The shared 4-lane update plus the ring's `lost` lane
                # (the residue-voiding quantity).
                out = flt.tick_counters(fc, fates)
                lostq = fates[0] | fates[1]
                return out[:4] + (fc[4] + lostq.astype(jnp.int32),)

            if faulted:
                fc0 = (
                    jnp.zeros((), jnp.uint32), jnp.zeros((), jnp.uint32),
                    jnp.zeros((), jnp.uint32), jnp.zeros((), jnp.int32),
                    jnp.zeros((), jnp.int32),
                )
            if delay_mode or acked or fused_on:
                pkt_shape = jax.eval_shape(
                    lambda s, dd, ff: extract(s, dd, ff, cap, start=0)[0],
                    folded, d, f,
                )
            if delay_mode:
                held0 = jax.tree.map(
                    lambda a: jnp.zeros(a.shape, a.dtype), pkt_shape
                )
            if acked:
                awin0 = _ackwin.init_window(pkt_shape, d.shape[-1])
                slot_price = jnp.float32(_ackwin.slot_bytes(pkt_shape))

                def ack_exchange(awin, sent, rcvd, keep):
                    """Back-propagate one applied packet's per-slot
                    confirmation one inverse hop and promote the
                    sender's own shipped copy into its window (ackwin
                    module docstring: bits follow the DATA packet's
                    fate, the ack lane itself rides the un-faulted
                    inverse channel). Under the fused wire the bits
                    ship as a u32 bitmap (8× the bool lane density);
                    the receiver's bits also promote its watermark
                    MIRROR (wire.py) before they leave."""
                    bits = _ackwin.ack_bits(rcvd, keep)
                    if fused_on:
                        bw = _wk.pack_bits(bits)
                        bw = lax.ppermute(bw, REPLICA_AXIS, inv_perm)
                        return (
                            _ackwin.update_window(
                                awin, sent,
                                _wk.unpack_bits(bw, bits.shape[0]),
                            ),
                            bw,
                        )
                    bits = lax.ppermute(bits, REPLICA_AXIS, inv_perm)
                    return _ackwin.update_window(awin, sent, bits), bits
            # Ack carry width: window (+ sender's in-flight copy under
            # pipelining, + the skipped-bytes scalar and the per-round
            # window-depth histogram under telemetry).
            pipe_on = pipeline and rounds > 0
            n_ack = (
                ((2 if pipe_on else 1) + (2 if telemetry else 0))
                if acked else 0
            )
            # Fused-wire carry width: the parked narrow-loss counter,
            # plus the receiver's ack-watermark mirror (and its lagged
            # copy under pipelining — wire.py's lockstep discipline).
            if fused_on:
                wcodec = _wire.WireCodec(
                    pkt_shape, d.shape[-1], _wire.WIRE_SURFACES[kind],
                    gated=gated, acked=acked,
                )
                mctx0 = jnp.zeros(
                    (d.shape[-1], wcodec.a), wcodec.ct
                )
                n_wire = 1 + (
                    (2 if pipe_on else 1) if acked else 0
                )

                def pack_ship(pkt, awin):
                    """The fused send: ONE kernel pass (gate ∧ mask ∧
                    encode ∧ checksum ∧ count), then the ppermute of
                    the packed wire — with the kernel's checksum as
                    the integrity lane when faulted."""
                    w, aux = wcodec.pack(
                        pkt,
                        rtop=rtop if gated else None,
                        win=awin if acked else None,
                    )
                    if faulted:
                        wired = jax.tree.map(
                            lambda x: lax.ppermute(x, REPLICA_AXIS, perm),
                            (w, aux.checksum),
                        )
                    else:
                        wired = jax.tree.map(
                            lambda x: lax.ppermute(x, REPLICA_AXIS, perm),
                            w,
                        )
                    return wired, aux

                def unpack_in(w, st, mctx):
                    return wcodec.unpack(
                        w,
                        own_top=top_of(st) if gated else None,
                        mirror_ctx=mctx if acked else None,
                    )
            else:
                n_wire = 0

            def deliver_held(st, d, f, of, held, heldv):
                """The one-round-late link buffer lands (delay faults)."""
                applied = apply_fn(st, held, d, f)
                st, d, f, of_h = select_apply(applied, (st, d, f), heldv)
                return st, d, f, of | of_h

            def round_body(r, carry):
                if delay_mode:
                    fc, held, heldv = carry[5 + n_tel + n_ack + n_wire:]
                elif faulted:
                    (fc,) = carry[5 + n_tel + n_ack + n_wire:]
                if acked:
                    awin = carry[5 + n_tel]
                    if telemetry:
                        skip = carry[5 + n_tel + n_ack - 2]
                        hack = carry[5 + n_tel + n_ack - 1]
                if fused_on:
                    woff = 5 + n_tel + n_ack
                    if acked:
                        mctx = carry[woff]
                    nlost = carry[woff + n_wire - 1]
                if telemetry:
                    (st, d, f, of, starved, slots, shipped, useful,
                     hresid, huseful) = carry[:10]
                    if fused_on:
                        wpacked, hpacked = carry[10], carry[11]
                    u0 = useful
                else:
                    st, d, f, of, starved = carry[:5]
                pkt, d, f = extract(st, d, f, cap, start=r * cap)
                if fused_on:
                    # The fused send: one kernel pass replaces the
                    # gate/ack/checksum/count layers. Deferred slots
                    # re-mark dirty BEFORE the backlog count so the
                    # residue certificate prices them (wire.py).
                    wired, waux = pack_ship(
                        pkt, awin if acked else None
                    )
                    d = _wire.remark_deferred(
                        d, _wire.core_idx(pkt), waux.defer
                    )
                    nlost = nlost + waux.parked_lost
                in_window = r >= rounds - win
                # Explicit accumulator dtype: without it jnp.sum widens
                # int32 -> int64 under x64 mode (counter_dtype="uint64")
                # and the fori_loop carry type changes mid-loop.
                backlog = jnp.sum(d, dtype=jnp.int32)
                starved = starved + jnp.where(in_window, backlog, 0)
                if telemetry:
                    # Per-round residue-quantity distribution: the rows
                    # still dirty right after the extract ARE the
                    # round's unshipped backlog (observed EVERY round —
                    # the drain curve, not just the certificate window).
                    hresid = _hist.observe(hresid, backlog)
                if fused_on:
                    if acked:
                        sent = wcodec.mask(pkt, waux.keep)
                        if telemetry:
                            skip = skip + jnp.sum(
                                waux.covered, dtype=jnp.float32
                            ) * slot_price
                    if telemetry:
                        before = st
                        shipped = shipped + jnp.float32(
                            tele.shipped_bytes(wired)
                        )
                        useful = useful + wcodec.useful_bytes(
                            pkt, waux.keep
                        ) + jnp.float32(4.0 if faulted else 0.0)
                        wpacked = wpacked + 4.0 * (
                            waux.packed_words
                            + jnp.uint32(1 if faulted else 0)
                        ).astype(jnp.float32)
                    pkt = wired
                else:
                    if gated:
                        pkt = gate(pkt, rtop)
                    if acked:
                        # Layering: the digest gate fired first
                        # (stateless top inference); the window masks
                        # what the peer has POSITIVELY confirmed —
                        # including removals.
                        pkt, covered = _ackwin.gate_window(pkt, awin)
                        sent = pkt
                        if telemetry:
                            skip = skip + jnp.sum(
                                covered, dtype=jnp.float32
                            ) * slot_price
                    pkt = ship(pkt)
                    if telemetry:
                        before = st
                        shipped = shipped + jnp.float32(
                            tele.shipped_bytes(pkt)
                        )
                        if faulted:
                            useful = useful + tele.packet_useful_bytes(
                                pkt[0]
                            ) + jnp.float32(tele.shipped_bytes(pkt[1]))
                        else:
                            useful = useful + tele.packet_useful_bytes(pkt)
                pkt, keep, fates = receive(pkt, r)
                if fused_on:
                    # Decode with the receiver's copy of the watermark
                    # (own frozen top + the ack mirror — sequential
                    # schedule: the mirror BEFORE this round's
                    # promotion matches the sender's encode state).
                    pkt = unpack_in(pkt, st, mctx if acked else None)
                if delay_mode:
                    st, d, f, of = deliver_held(st, d, f, of, held, heldv)
                applied = apply_fn(st, pkt, d, f)
                if faulted:
                    st, d, f, of_r = select_apply(applied, (st, d, f), keep)
                    fc = tick(fc, fates)
                    if delay_mode:
                        held = flt.tree_select(fates[2], pkt, held0)
                        heldv = fates[2]
                        tail = (fc, held, heldv)
                    else:
                        tail = (fc,)
                else:
                    st, d, f, of_r = applied
                    tail = ()
                if acked:
                    if fused_on:
                        mctx = _wire.mirror_promote(
                            mctx, pkt, _ackwin.ack_bits(pkt, keep),
                            jnp.ones((), bool),
                        )
                    awin, bits = ack_exchange(awin, sent, pkt, keep)
                    if telemetry:
                        ab = jnp.float32(tele.shipped_bytes(bits))
                        shipped, useful = shipped + ab, useful + ab
                        if fused_on:
                            wpacked = wpacked + 4.0 * jnp.sum(
                                (bits != 0).astype(jnp.uint32),
                                dtype=jnp.uint32,
                            ).astype(jnp.float32)
                        hack = _hist.observe(
                            hack, _ackwin.window_depth(awin)
                        )
                    ack_tail = (awin, skip, hack) if telemetry else (awin,)
                else:
                    ack_tail = ()
                if fused_on:
                    wire_tail = ((mctx,) if acked else ()) + (nlost,)
                else:
                    wire_tail = ()
                if telemetry:
                    slots = slots + slots_of(before, st)
                    huseful = _hist.observe(huseful, useful - u0)
                    tel_mid = (slots, shipped, useful, hresid, huseful)
                    if fused_on:
                        hpacked = _hist.observe(
                            hpacked, wpacked - carry[10]
                        )
                        tel_mid = tel_mid + (wpacked, hpacked)
                    return ((st, d, f, of | of_r, starved) + tel_mid
                            + ack_tail + wire_tail + tail)
                return ((st, d, f, of | of_r, starved) + ack_tail
                        + wire_tail + tail)

            def pipe_body(r, carry):
                # Double-buffered round: extract round r+1's packet
                # from the PRE-apply state and put its ppermute in
                # flight, THEN merge round r's in-flight packet — the
                # send crosses the loop edge, so its DMA overlaps the
                # merge kernels (module docstring; stale by one apply).
                if delay_mode:
                    fc, held, heldv = carry[6 + n_tel + n_ack + n_wire:]
                elif faulted:
                    (fc,) = carry[6 + n_tel + n_ack + n_wire:]
                if acked:
                    awin, sent = carry[6 + n_tel], carry[6 + n_tel + 1]
                    if telemetry:
                        skip = carry[6 + n_tel + n_ack - 2]
                        hack = carry[6 + n_tel + n_ack - 1]
                if fused_on:
                    woff = 6 + n_tel + n_ack
                    if acked:
                        mctx, mctx_prev = carry[woff], carry[woff + 1]
                    nlost = carry[woff + n_wire - 1]
                if telemetry:
                    (st, d, f, of, starved, flight, slots, shipped,
                     useful, hresid, huseful) = carry[:11]
                    if fused_on:
                        wpacked, hpacked = carry[11], carry[12]
                    u0 = useful
                else:
                    st, d, f, of, starved, flight = carry[:6]
                pkt, d, f = extract(st, d, f, cap, start=(r + 1) * cap)
                if fused_on:
                    # Encode against the CURRENT window state — the
                    # receiver decodes with its one-promotion-lagged
                    # mirror, matching this exact state (wire.py's
                    # pipelined lockstep discipline).
                    wired, waux = pack_ship(
                        pkt, awin if acked else None
                    )
                    d = _wire.remark_deferred(
                        d, _wire.core_idx(pkt), waux.defer
                    )
                    nlost = nlost + waux.parked_lost
                backlog = jnp.sum(d, dtype=jnp.int32)
                starved = starved + jnp.where(
                    (r + 1) >= rounds - win, backlog, 0
                )
                if telemetry:
                    hresid = _hist.observe(hresid, backlog)
                if fused_on:
                    if acked:
                        if telemetry:
                            skip = skip + jnp.sum(
                                waux.covered, dtype=jnp.float32
                            ) * slot_price
                    if telemetry:
                        before = st
                        shipped = shipped + jnp.float32(
                            tele.shipped_bytes(wired)
                        )
                        useful = useful + wcodec.useful_bytes(
                            pkt, waux.keep
                        ) + jnp.float32(4.0 if faulted else 0.0)
                        wpacked = wpacked + 4.0 * (
                            waux.packed_words
                            + jnp.uint32(1 if faulted else 0)
                        ).astype(jnp.float32)
                    nxt = wired
                else:
                    if gated:
                        pkt = gate(pkt, rtop)
                    if acked:
                        pkt, covered = _ackwin.gate_window(pkt, awin)
                        if telemetry:
                            skip = skip + jnp.sum(
                                covered, dtype=jnp.float32
                            ) * slot_price
                    nxt = ship(pkt)
                    if telemetry:
                        before = st
                        shipped = shipped + jnp.float32(
                            tele.shipped_bytes(nxt)
                        )
                        if faulted:
                            useful = useful + tele.packet_useful_bytes(
                                nxt[0]
                            ) + jnp.float32(tele.shipped_bytes(nxt[1]))
                        else:
                            useful = useful + tele.packet_useful_bytes(nxt)
                flight, keep, fates = receive(flight, r)
                if fused_on:
                    flight = unpack_in(
                        flight, st, mctx_prev if acked else None
                    )
                if delay_mode:
                    st, d, f, of = deliver_held(st, d, f, of, held, heldv)
                applied = apply_fn(st, flight, d, f)
                if faulted:
                    st, d, f, of_r = select_apply(applied, (st, d, f), keep)
                    fc = tick(fc, fates)
                    if delay_mode:
                        held = flt.tree_select(fates[2], flight, held0)
                        heldv = fates[2]
                        tail = (fc, held, heldv)
                    else:
                        tail = (fc,)
                else:
                    st, d, f, of_r = applied
                    tail = ()
                if acked:
                    # The ack is for the packet applied THIS round —
                    # shipped LAST round, whose pre-ship copy rides the
                    # carry (the window lags one extra round under
                    # pipelining, like knowledge itself).
                    if fused_on:
                        mctx_prev, mctx = mctx, _wire.mirror_promote(
                            mctx, flight,
                            _ackwin.ack_bits(flight, keep),
                            jnp.ones((), bool),
                        )
                    awin, bits = ack_exchange(awin, sent, flight, keep)
                    sent = (
                        wcodec.mask(pkt, waux.keep) if fused_on else pkt
                    )
                    if telemetry:
                        ab = jnp.float32(tele.shipped_bytes(bits))
                        shipped, useful = shipped + ab, useful + ab
                        if fused_on:
                            wpacked = wpacked + 4.0 * jnp.sum(
                                (bits != 0).astype(jnp.uint32),
                                dtype=jnp.uint32,
                            ).astype(jnp.float32)
                        hack = _hist.observe(
                            hack, _ackwin.window_depth(awin)
                        )
                    ack_tail = (
                        (awin, sent, skip, hack) if telemetry
                        else (awin, sent)
                    )
                else:
                    ack_tail = ()
                if fused_on:
                    wire_tail = (
                        ((mctx, mctx_prev) if acked else ()) + (nlost,)
                    )
                else:
                    wire_tail = ()
                if telemetry:
                    slots = slots + slots_of(before, st)
                    huseful = _hist.observe(huseful, useful - u0)
                    tel_mid = (slots, shipped, useful, hresid, huseful)
                    if fused_on:
                        hpacked = _hist.observe(
                            hpacked, wpacked - carry[11]
                        )
                        tel_mid = tel_mid + (wpacked, hpacked)
                    return ((st, d, f, of | of_r, starved, nxt) + tel_mid
                            + ack_tail + wire_tail + tail)
                return ((st, d, f, of | of_r, starved, nxt) + ack_tail
                        + wire_tail + tail)

            zeros_tel = (
                jnp.zeros((), jnp.uint32),   # slots
                jnp.zeros((), jnp.float32),  # shipped (wire)
                jnp.zeros((), jnp.float32),  # useful (post-mask)
            )
            fault_tail = ()
            if delay_mode:
                fault_tail = (fc0, held0, jnp.zeros((), bool))
            elif faulted:
                fault_tail = (fc0,)
            if pipeline and rounds > 0:
                # Prologue: round 0's packet goes in flight pre-loop.
                pkt, d, f = extract(folded, d, f, cap, start=0)
                if fused_on:
                    # The round-0 window is empty, so the watermark is
                    # the digest alone — the receiver's round-0 mirror
                    # matches by construction.
                    wired0, waux0 = pack_ship(
                        pkt, awin0 if acked else None
                    )
                    d = _wire.remark_deferred(
                        d, _wire.core_idx(pkt), waux0.defer
                    )
                backlog0 = jnp.sum(d, dtype=jnp.int32)
                starved = jnp.where(
                    jnp.asarray(0 >= rounds - win), backlog0, 0,
                )
                if fused_on:
                    flight = wired0
                else:
                    if gated:
                        pkt = gate(pkt, rtop)
                    # The round-0 window is empty — nothing to mask; the
                    # pre-ship copy seeds the carry as the first ackable
                    # send.
                    flight = ship(pkt)
                init = (folded, d, f, of, starved, flight)
                if telemetry:
                    if fused_on:
                        useful0 = wcodec.useful_bytes(
                            pkt, waux0.keep
                        ) + jnp.float32(4.0 if faulted else 0.0)
                        wpacked0 = 4.0 * (
                            waux0.packed_words
                            + jnp.uint32(1 if faulted else 0)
                        ).astype(jnp.float32)
                    elif faulted:
                        useful0 = (
                            tele.packet_useful_bytes(flight[0])
                            + jnp.float32(tele.shipped_bytes(flight[1]))
                        )
                    else:
                        useful0 = tele.packet_useful_bytes(flight)
                    init = init + (
                        zeros_tel[0],
                        zeros_tel[1]
                        + jnp.float32(tele.shipped_bytes(flight)),
                        zeros_tel[2] + useful0,
                        _hist.observe(_hist.zeros(), backlog0),
                        _hist.observe(_hist.zeros(), useful0),
                    )
                    if fused_on:
                        init = init + (
                            wpacked0,
                            _hist.observe(_hist.zeros(), wpacked0),
                        )
                if acked:
                    sent0 = (
                        wcodec.mask(pkt, waux0.keep) if fused_on else pkt
                    )
                    init = init + (
                        (awin0, sent0, jnp.zeros((), jnp.float32),
                         _hist.zeros())
                        if telemetry else (awin0, sent0)
                    )
                if fused_on:
                    init = init + (
                        ((mctx0, mctx0) if acked else ())
                        + (waux0.parked_lost,)
                    )
                init = init + fault_tail
                carry = lax.fori_loop(0, rounds - 1, pipe_body, init)
                folded, d, f, of, starved, flight = carry[:6]
                if acked:
                    awin = carry[6 + n_tel]
                if fused_on:
                    woff = 6 + n_tel + n_ack
                    if acked:
                        mctx_prev = carry[woff + 1]
                    nlost = carry[woff + n_wire - 1]
                if delay_mode:
                    fc, held, heldv = carry[6 + n_tel + n_ack + n_wire:]
                elif faulted:
                    (fc,) = carry[6 + n_tel + n_ack + n_wire:]
                # Epilogue: merge the final in-flight packet.
                if telemetry:
                    before = folded
                flight, keep, fates = receive(flight, rounds - 1, final=True)
                if fused_on:
                    flight = unpack_in(
                        flight, folded, mctx_prev if acked else None
                    )
                if delay_mode:
                    folded, d, f, of = deliver_held(
                        folded, d, f, of, held, heldv
                    )
                applied = apply_fn(folded, flight, d, f)
                if faulted:
                    folded, d, f, of_r = select_apply(
                        applied, (folded, d, f), keep
                    )
                    fc = tick(fc, fates)
                else:
                    folded, d, f, of_r = applied
                of = of | of_r
                if telemetry:
                    slots, shipped, useful, hresid, huseful = carry[6:11]
                    if fused_on:
                        wpacked, hpacked = carry[11], carry[12]
                    slots = slots + slots_of(before, folded)
                    if acked:
                        skip = carry[6 + n_tel + n_ack - 2]
                        hack = carry[6 + n_tel + n_ack - 1]
            else:
                init = (folded, d, f, of, jnp.zeros((), jnp.int32))
                if telemetry:
                    init = init + zeros_tel + (_hist.zeros(), _hist.zeros())
                    if fused_on:
                        init = init + (
                            jnp.zeros((), jnp.float32), _hist.zeros()
                        )
                if acked:
                    init = init + (
                        (awin0, jnp.zeros((), jnp.float32), _hist.zeros())
                        if telemetry else (awin0,)
                    )
                if fused_on:
                    init = init + (
                        ((mctx0,) if acked else ())
                        + (jnp.zeros((), jnp.int32),)
                    )
                init = init + fault_tail
                carry = lax.fori_loop(0, rounds, round_body, init)
                folded, d, f, of, starved = carry[:5]
                if telemetry:
                    slots, shipped, useful, hresid, huseful = carry[5:10]
                    if fused_on:
                        wpacked, hpacked = carry[10], carry[11]
                if acked:
                    awin = carry[5 + n_tel]
                    if telemetry:
                        skip = carry[5 + n_tel + n_ack - 2]
                        hack = carry[5 + n_tel + n_ack - 1]
                if fused_on:
                    nlost = carry[5 + n_tel + n_ack + n_wire - 1]
                if delay_mode:
                    fc, held, heldv = carry[5 + n_tel + n_ack + n_wire:]
                    # A packet still held when the loop ends arrives now
                    # (one round late past the ring edge, not lost).
                    folded, d, f, of = deliver_held(
                        folded, d, f, of, held, heldv
                    )
                elif faulted:
                    (fc,) = carry[5 + n_tel + n_ack + n_wire:]
            if telemetry and gated:
                # The digest exchange itself rides the wire once.
                dig = jnp.float32(tele.shipped_bytes(rtop))
                shipped, useful = shipped + dig, useful + dig
            if fused_on:
                # Unencodable parked removes never reached the wire:
                # count them as loss mesh-wide (wire.py's narrow-window
                # contract — residue forced below, adoption gated
                # here).
                nlost_tot = lax.psum(
                    nlost, (REPLICA_AXIS, ELEMENT_AXIS)
                )
            if faulted:
                # Adopt the mesh top ONLY when the run lost nothing:
                # adoption after loss makes receivers claim
                # observed-and-removed for dots they never received (the
                # delta.py inflated-context failure). Evicted ranks are
                # excluded from the live pmax and never adopt.
                own_top = top_of(folded)
                ev = flt.evicted_mask(faults, REPLICA_AXIS)
                top_live = lax.pmax(
                    lax.pmax(jnp.where(ev, 0, own_top), REPLICA_AXIS),
                    ELEMENT_AXIS,
                )
                lost_tot = lax.psum(fc[4], REPLICA_AXIS)
                adopt = (lost_tot == 0) & ~ev
                if fused_on:
                    adopt = adopt & (nlost_tot == 0)
                top = jnp.where(adopt, top_live, own_top)
            elif fused_on:
                # Same adoption guard for narrow-lost parked removes on
                # a fault-free ring; with nothing lost this selects the
                # mesh top bit-identically to the unconditional path.
                own_top = top_of(folded)
                top_live = lax.pmax(
                    lax.pmax(own_top, REPLICA_AXIS), ELEMENT_AXIS
                )
                top = jnp.where(nlost_tot == 0, top_live, own_top)
            else:
                top = lax.pmax(
                    lax.pmax(top_of(folded), REPLICA_AXIS), ELEMENT_AXIS
                )
            folded = close_top(folded, top)
            of = (
                lax.psum(of.astype(jnp.int32), (REPLICA_AXIS, ELEMENT_AXIS))
                > 0
            )
            residue = lax.psum(starved, (REPLICA_AXIS, ELEMENT_AXIS))
            if faulted:
                # Lost packets void the certificate: a degraded run must
                # never read as certified-converged (module docstring).
                residue = jnp.maximum(
                    residue, (lost_tot > 0).astype(jnp.int32)
                )
            if fused_on:
                # Narrow-lost parked removes are wire loss too
                # (wire.py): the certificate must not be issuable when
                # removal knowledge never shipped.
                residue = jnp.maximum(
                    residue, (nlost_tot > 0).astype(jnp.int32)
                )
            if rounds < win:
                # A budget below the certificate window can never
                # complete the ring's propagation; the certificate must
                # not be issuable no matter the cap.
                residue = jnp.maximum(residue, 1)
            outs = (
                jax.tree.map(lambda x: x[None], folded), d[None], of, residue
            )
            if telemetry:
                local_rows = jax.tree.leaves(local)[0].shape[0]
                tel = _tel_reduced(
                    folded, slots,
                    max(local_rows - 1, 0) + rounds, shipped,
                    (REPLICA_AXIS, ELEMENT_AXIS), residue=residue,
                    useful_per_dev=useful,
                )
                # The in-kernel distributions: per-(round, device)
                # samples psum into one mesh-wide histogram, like the
                # scalar throughput counters (obs/hist.py).
                tel = tel._replace(
                    hist_residue=_hist.psum(
                        hresid, (REPLICA_AXIS, ELEMENT_AXIS)
                    ),
                    hist_useful_bytes=_hist.psum(
                        huseful, (REPLICA_AXIS, ELEMENT_AXIS)
                    ),
                )
                if fused_on:
                    tel = tel._replace(
                        wire_packed_bytes=lax.psum(
                            wpacked, (REPLICA_AXIS, ELEMENT_AXIS)
                        ),
                        hist_packed_bytes=_hist.psum(
                            hpacked, (REPLICA_AXIS, ELEMENT_AXIS)
                        ),
                    )
                if acked:
                    tel = tel._replace(
                        bytes_acked_skipped=lax.psum(
                            skip, (REPLICA_AXIS, ELEMENT_AXIS)
                        ),
                        ack_window_depth=lax.pmax(
                            _ackwin.window_depth(awin),
                            (REPLICA_AXIS, ELEMENT_AXIS),
                        ),
                        hist_ack_depth=_hist.psum(
                            hack, (REPLICA_AXIS, ELEMENT_AXIS)
                        ),
                    )
                if faulted:
                    tel = tel._replace(
                        faults_dropped=lax.psum(fc[0], REPLICA_AXIS),
                        faults_rejected=lax.psum(fc[1], REPLICA_AXIS),
                        faults_delayed=lax.psum(fc[2], REPLICA_AXIS),
                    )
                outs = outs + (tel,)
            if faulted:
                # Packet counters psum over the REPLICA axis only: the
                # fault draw is per logical link (element shards share
                # the fate), so a replica-axis sum counts packets, not
                # device shards.
                outs = outs + (flt.FaultCounters(
                    packets_dropped=lax.psum(fc[0], REPLICA_AXIS),
                    packets_rejected=lax.psum(fc[1], REPLICA_AXIS),
                    packets_delayed=lax.psum(fc[2], REPLICA_AXIS),
                    miss_streak=fc[3].reshape(1),
                ),)
            return outs

        return gossip_fn

    with tele.span(f"ae.{kind}"):
        with tele.span(f"ae.{kind}.prep"):
            metrics.count(f"anti_entropy.{kind}_rounds", rounds)
            metrics.observe("anti_entropy.state_bytes", state_nbytes(state))
            if (wal is not None and wal.tail is None
                    and not isinstance(
                        jax.tree.leaves(state)[0], jax.core.Tracer
                    )):
                # Seed the diff base BEFORE the jitted call — donation consumes
                # the input buffers; attach takes a device copy. Skipped under
                # an outer jit (tracers must never leak into the log's diff
                # base) — the append below is skipped symmetrically.
                wal.attach(state)
            fn = _cached(
                kind, state, mesh, build, rounds, cap, telemetry, pipeline,
                gated, faults, _ackwin.AckWindowKey() if acked else None,
                # A fused=False run is the LEGACY program: mark its cache
                # entry so the analysis gates keep reading the default
                # (fused) trace — the FaultPlan/AckWindowKey discipline.
                None if fused_on else _wire.WireKey(),
                *cache_extra, donate_argnums=argnums,
            )
        t0 = time.perf_counter()
        with tele.span(f"ae.{kind}.dispatch"):
            out = fn(state, dirty, fctx)
        with tele.span(f"ae.{kind}.wait"):
            jax.block_until_ready(out)
        if telemetry and tele.is_concrete(out[4]):
            out = out[:4] + (tele.time_dispatch(
                out[4], time.perf_counter() - t0
            ),) + out[5:]
        if donate:
            # Free whatever the donation did not consume in place: the
            # unaliasable fallback, and originals implicitly resharded onto
            # the mesh (the executable then donated the committed copies).
            from .anti_entropy import _consume

            _consume(True, state, dirty)
        # A faulted run's residue is forced >= 1 BY DESIGN (lost packets
        # void the certificate) — the budget warning would misdiagnose it
        # and burn the once-per-kind dedupe a genuine under-budget run
        # needs; the gauge still records, the fault counters are the signal.
        _warn_residue(kind, out, warn=not faulted)
        if wal is not None and not isinstance(
            jax.tree.leaves(out[0])[0], jax.core.Tracer
        ):
            # Host-side durability append (skipped under an outer jit —
            # like tele.record, the caller then owns persistence).
            b0, f0 = wal.bytes_appended, wal.fsyncs
            with tele.span("durability.wal_append"):
                wal.append_state(wal_kind, out[0])
                wal.mark_round()
            if telemetry and tele.is_concrete(out[4]):
                out = out[:4] + (out[4]._replace(
                    wal_bytes=jnp.float32(wal.bytes_appended - b0),
                    wal_fsyncs=jnp.uint32(wal.fsyncs - f0),
                ),) + out[5:]
        if acked:
            metrics.count("delta_opt.ack_window_runs")
            if telemetry and tele.is_concrete(out[4]):
                skipped = int(out[4].bytes_acked_skipped)
                metrics.count("delta_opt.acked_skipped", skipped)
                metrics.count(f"delta_opt.acked_skipped.{kind}", skipped)
        if fused_on:
            metrics.count("wire.fused_runs")
            if telemetry and tele.is_concrete(out[4]):
                # The registry twins of the in-kernel packed-bytes counter
                # (tools/telemetry_schema.json `wire_packed_bytes`).
                pb = int(out[4].wire_packed_bytes)
                metrics.count("wire.packed_bytes", pb)
                metrics.count(f"wire.packed_bytes.{kind}", pb)
        if telemetry and tele.is_concrete(out[4]):
            tele.record(kind, out[4])
        if faulted:
            from .. import faults as flt

            flt.record(out[-1])  # no-op under tracing, like tele.record
    return out


# Kinds whose residue warning already fired this process — repeats only
# count in the registry (see _warn_residue).
_RESIDUE_WARNED: set = set()


def reset_residue_warnings() -> None:
    """Re-arm the once-per-kind residue warning (tests; or after an
    operator fixed the budget and wants fresh signal)."""
    _RESIDUE_WARNED.clear()


def _warn_residue(kind: str, out, warn: bool = True) -> None:
    if not isinstance(out[3], jax.core.Tracer):
        # Host-side residue accounting — skipped when the ring runs
        # under an outer jit (callers then read the returned residue).
        # ``warn=False`` (faulted runs) records the gauge only: their
        # residue is injected loss, not an under-budgeted ring.
        residue = int(out[3])
        metrics.observe(f"anti_entropy.{kind}.residue", float(residue))
        if residue and warn:
            # Every occurrence counts in the registry; the warning
            # itself fires once per kind per process — an under-budgeted
            # ring in a loop would otherwise emit one warning per round
            # (the repeat count lives in the counter, where operators
            # can actually read a rate).
            metrics.count(f"anti_entropy.{kind}.residue_runs")
            if kind in _RESIDUE_WARNED:
                return
            _RESIDUE_WARNED.add(kind)
            import warnings

            warnings.warn(
                f"{kind}: round budget left residue ({residue} slot-starved "
                f"row-rounds) — the ring is NOT guaranteed converged; raise "
                f"`rounds` (see the ROUNDS BUDGET note in parallel/delta.py; "
                f"pipeline=True budgets are ~2x) or `cap`. Warned once per "
                f"kind; repeats count in anti_entropy.{kind}.residue_runs",
                # _warn_residue -> run_delta_ring -> mesh entry -> user.
                stacklevel=4,
            )


def delta_gossip_elastic(
    model,
    dirty: jax.Array,
    fctx: jax.Array,
    mesh: Mesh,
    rounds: Optional[int] = None,
    cap: int = 64,
    local_fold: str = "auto",
    policy=None,
    telemetry: bool = False,
    pipeline: bool = True,
    digest: bool = True,
    donate: bool = False,
    reclaim=None,
    faults=None,
    ack_window=False,
    wal=None,
    fused: bool = True,
):
    """δ-ring anti-entropy with elastic capacity recovery for dense
    ORSWOT replica batches (``BatchedOrswot``): the mid-round
    overflow→widen→resume loop of ``anti_entropy.gossip_elastic``, δ
    flavored.

    When a ring run flags parked-buffer overflow, the run's result is
    discarded (the δ entry never commits to the model), the replica
    pauses while ``deferred_cap`` widens 2× (policy-configurable) with
    the live state re-encoded on device, and the ring re-enters with the
    SAME (dirty, fctx) tracking — sound because the rejected run
    mutated nothing, the widened state is bit-identical to a
    wider-born one, and the tracking contract (delta.py) binds dirty
    marks to dots, not to layout. Element/actor-axis growth composes
    the same way: ``mesh_delta_gossip`` re-pads dirty/fctx to the
    state's (post-migration) shape. The residue certificate is
    unchanged — the re-entered ring's ``residue == 0`` still proves the
    gossip equals the full join of the widened family.

    ``pipeline`` / ``digest`` thread through to every attempt
    (run_delta_ring). ``donate=True`` donates each attempt's
    (state, dirty) into the ring and restores ``model.state`` and the
    tracking pair from a pre-round device copy afterwards — the widen
    fallback needs the pre-round state alive across a failed attempt,
    so the wrapper trades the ring-internal second state copy for one
    explicit snapshot (net HBM even; the in-ring temporaries still
    shrink) while keeping the model coherent either way.

    Returns ``(states, dirty, overflow, residue, widened)`` — the
    ``mesh_delta_gossip`` tuple plus the dict of axes grown (empty when
    capacity sufficed). ``telemetry=True`` appends a Telemetry pytree
    folded across every attempt (``telemetry.combine``) as the last
    element.

    ``reclaim=`` takes an ``elastic.Hysteresis`` tracker — the shrink
    half of the elastic loop, composing here exactly as in
    ``anti_entropy.gossip_elastic``: after the successful attempt the
    tracker observes occupancy and narrows cleared axes in place (the
    δ path computes its frontier host-side —
    ``reclaim.host_frontier`` / ``reclaim.compact_model`` — since the
    residue-certificated ring has no spare output lane for it).

    ``faults=`` threads a ``crdt_tpu.faults.FaultPlan`` into every
    attempt (run_delta_ring); the LAST tuple element is then the
    ``FaultCounters`` pytree with packet counters summed across
    attempts (``faults.combine_counters``). ``ack_window=True`` threads
    the acked-interval masking into every attempt too — each attempt
    starts a fresh window (sound: the window is per-run positive
    knowledge, and a rejected overflowing attempt confirmed nothing it
    could carry over).

    ``wal=`` logs ONLY the committed attempt (a rejected overflowing
    run mutated nothing, so it must not reach the log either); a
    mid-loop widen changes the shapes, which the log absorbs as a
    full-``state`` record (``Wal.append_state``'s fallback) — replay
    re-anchors there, so recovery stays bit-identical across
    migrations."""
    from .. import elastic
    from .delta import mesh_delta_gossip

    if wal is not None and wal.tail is None:
        wal.attach(model.state)
    policy = policy or elastic.DEFAULT_POLICY
    widened: dict = {}
    migrations = 0
    tel = None
    fcs = None
    while True:
        if donate:
            snap = jax.tree.map(jnp.copy, model.state)
            snap_dirty = jnp.copy(dirty)
        out = mesh_delta_gossip(
            model.state, dirty, fctx, mesh, rounds, cap, local_fold,
            telemetry=telemetry, pipeline=pipeline, digest=digest,
            donate=donate, faults=faults, ack_window=ack_window,
            fused=fused,
        )
        if donate:
            model.state, dirty = snap, snap_dirty
        if faults is not None:
            from .. import faults as flt

            fcs = flt.accumulate_counters(fcs, out[-1])
            out = out[:-1]
        if telemetry:
            tel = out[4] if tel is None else tele.combine(tel, out[4])
        if not bool(jnp.any(out[2])):
            if reclaim is not None:
                from ..reclaim import compact_model
                from .anti_entropy import _commit_rows

                _commit_rows(model, out[0])
                # The δ ring has no spare output lane for an in-kernel
                # frontier; compact host-side against the committed
                # rows' own frontier (the batch IS the replica set)
                # so retired slots do not pin lanes the shrink needs.
                compact_model(model)
                reclaim.observe(model)
            if wal is not None:
                # The committed attempt is the durable transition.
                wal.append_state("orswot", out[0])
                wal.mark_round()
            ret = (*out[:4], widened)
            if telemetry:
                ret = ret + (tel,)
            if fcs is not None:
                ret = ret + (fcs,)
            return ret
        if migrations >= policy.max_migrations:
            raise RuntimeError(
                f"δ ring still overflowing after {migrations} migrations "
                f"(axes grown: {widened}) — raise policy.factor or "
                f"max_migrations"
            )
        metrics.count("elastic.delta_migrations")
        widened.update(elastic.widen(model, ("deferred_cap",), policy))
        migrations += 1


# ---- static-analysis registration (crdt_tpu.analysis) --------------------
# The generic ring engine and the elastic wrapper both expose faults=
# directly (the registered δ flavors thread through them); fault-surface
# registration is the coverage contract crdt_tpu.faults.static_checks
# enforces.

from ..analysis.registry import register_fault_surface as _reg_fs  # noqa: E402

_reg_fs("run_delta_ring", module=__name__)
_reg_fs("delta_gossip_elastic", module=__name__)
