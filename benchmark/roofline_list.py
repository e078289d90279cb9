"""Bytes a round of per-replica list op delivery must move, whatever
implements it: each live lane reads and writes its replica's state words
for the slot once (an int32 value and a bool alive flag), and each
distinct log chunk a round applies is read once (per lane an int32 slot,
a uint8 kind and an int32 value). Divided by the apply program's device
time and the chip's HBM bandwidth they give the apply's share of its
roofline."""

from __future__ import annotations

U32 = 4
BOOL = 1
U8 = 1

STATE_WORD = U32 + BOOL
LOG_LANE = U32 + U8 + U32


def apply_bytes(live_lanes: int, chunk_reads: int, chunk_width: int) -> int:
    """``live_lanes`` lanes scattered and ``chunk_reads`` chunks of
    ``chunk_width`` lanes gathered from the log, over any number of
    rounds."""
    return 2 * STATE_WORD * live_lanes + chunk_reads * chunk_width * LOG_LANE
