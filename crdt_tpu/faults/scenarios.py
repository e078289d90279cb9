"""Shared fault-scenario generators (SURVEY §6.3 delivery contract).

These were minted inside tests/test_fault_injection.py; the chaos-soak
suite needs the SAME schedule semantics, so the generators live here
once instead of drifting as copies. The delivery
contract they encode:

- per-origin causal order is preserved (each site's own op stream is
  delivered as a prefix — dropping is always a SUFFIX drop),
- cross-site order is free (arbitrary interleaving),
- duplication is unbounded (CmRDT apply must be idempotent on dups).

Deterministic given the caller's ``random.Random`` — chaos runs replay.
"""

from __future__ import annotations

import random
from typing import List, Tuple

MEMBERS = list(range(5))


def mint_streams(rng: random.Random, n_sites: int, n_ops: int,
                 members=None) -> Tuple[list, List[list]]:
    """Per-site op streams minted under each site's own actor (per-origin
    causal order is the delivery contract; cross-site order is free).
    Returns ``(sites, streams)`` — the pure replicas after self-applying
    their own ops, and each site's op list."""
    from ..pure.orswot import Orswot

    members = MEMBERS if members is None else members
    sites = [Orswot() for _ in range(n_sites)]
    streams: List[list] = [[] for _ in range(n_sites)]
    for _ in range(n_ops):
        i = rng.randrange(n_sites)
        s = sites[i]
        if rng.random() < 0.7 or not s.read().val:
            op = s.add(rng.choice(members), s.read().derive_add_ctx(f"s{i}"))
        else:
            victim = rng.choice(sorted(s.read().val))
            op = s.rm(victim, s.contains(victim).derive_rm_ctx())
        s.apply(op)
        streams[i].append(op)
    return sites, streams


def genesis_tracking(state):
    """δ-tracking (dirty, fctx) for a dense ORSWOT batch whose replicas
    were last mutually synced at GENESIS — every live row marked dirty
    with its own dots as context (``interval_accumulate`` from the
    all-zero state). The bootstrap every chaos/scale-out scenario run
    starts from; lived as per-file closure copies until ISSUE 11."""
    import jax
    import jax.numpy as jnp

    from ..parallel.delta import interval_accumulate

    zero = jax.tree.map(jnp.zeros_like, state)
    dirty = jnp.zeros(state.ctr.shape[:-1], bool)
    fctx = jnp.zeros(state.ctr.shape, state.ctr.dtype)
    return interval_accumulate(dirty, fctx, zero, state)


def faulty_delivery(rng: random.Random, streams: List[list],
                    r_ix: int) -> list:
    """One receiver's faulty delivery schedule:

    - DROP a suffix of each foreign stream (prefix delivery is the
      causal contract);
    - DUPLICATE random ops (CmRDT apply must be idempotent on dups);
    - REORDER across sites (interleave streams arbitrarily, each
      stream's own order preserved)."""
    plan = []
    for s_ix, stream in enumerate(streams):
        if s_ix == r_ix:
            continue
        keep = rng.randint(0, len(stream))  # drop a suffix
        prefix = stream[:keep]
        dups = [op for op in prefix if rng.random() < 0.3]
        plan.append(prefix + dups)
    merged, cursors = [], [0] * len(plan)
    while any(c < len(p) for c, p in zip(cursors, plan)):
        choices = [
            i for i, (c, p) in enumerate(zip(cursors, plan)) if c < len(p)
        ]
        i = rng.choice(choices)
        merged.append(plan[i][cursors[i]])
        cursors[i] += 1
    return merged


__all__ = [
    "MEMBERS", "faulty_delivery", "genesis_tracking", "mint_streams",
]
