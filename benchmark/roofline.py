"""Bytes a kernel's algorithm must move, from the shapes of its call:
unpadded, each input read once and each output written once. Divided by
the kernel's device time and the chip's HBM bandwidth, they give the
kernel's share of its roofline. Both kernels do a few integer compares per
word, so memory bounds them."""

from __future__ import annotations

U32 = 4
BOOL = 1


def fold_fused_bytes(rows: int, elems: int, actors: int) -> int:
    """``fold_fused`` over ``rows`` replicas: it reads every replica's
    top ``[A]`` and dot counters ``[E, A]`` and writes one of each."""
    per_state = (actors + elems * actors) * U32
    return rows * per_state + per_state


def wire_pack_bytes(slots: int, clock_lanes: int) -> int:
    """``wire_pack`` over ``slots`` δ lanes of ``clock_lanes`` clocks: it
    reads each slot's clocks, its base clocks and its valid flag, and
    writes the packed words (two 16-bit lanes to a word) and three
    verdict flags."""
    words = (clock_lanes + 1) // 2
    read = 2 * clock_lanes * U32 + BOOL
    write = words * U32 + 3 * BOOL
    return slots * (read + write)
