"""Bytes a ``Map<K, MVReg>`` replica fold must move, from the shapes of the
population it folds: each replica's state read once and the converged
state written once, unpadded, whatever implements the fold. Divided by
the device's busy time and the chip's HBM bandwidth, they give the fold's
share of its roofline: the join does a few integer compares per word, so
memory bounds it."""

from __future__ import annotations

U32 = 4
BOOL = 1


def map_state_bytes(keys: int, actors: int, sibling_cap: int, deferred_cap: int) -> int:
    """One ``MapState``: ``top [A]``; per key and slot ``wact``, ``wctr``,
    ``val`` (32-bit), ``valid`` and the slot's clock ``[A]``; the parked
    removes ``dcl [D, A]``, ``dkeys [D, K]``, ``dvalid [D]``."""
    slot = 3 * U32 + BOOL + actors * U32
    return (actors * U32 + keys * sibling_cap * slot
            + deferred_cap * (actors * U32 + keys * BOOL + BOOL))


def map_fold_bytes(rows: int, keys: int, actors: int, sibling_cap: int,
                   deferred_cap: int) -> int:
    """One fold of ``rows`` replicas: each read once, one state written."""
    return (rows + 1) * map_state_bytes(keys, actors, sibling_cap, deferred_cap)
