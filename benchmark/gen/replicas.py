"""A synced dense ORSWOT replica population and its per-round writes,
made on the device from the seed.

Copied from ``bench.py``'s ``dense_chunk`` and ``chip_smoke.py``'s
``_synced_population`` (PR 21), so a later change to either cannot move
the yardstick. Every replica holds one synced base state; each round, one
writer replica per actor adds one new dot over ``writer_elems`` elements
and removes ``writer_elems`` others (an observed remove: every dot it has
seen there), elements the round picks, starting again from the base. So
every round is the same amount of work: an anti-entropy round from a
synced moment.

The leaves follow the dense layout the program takes (``top[R, A]``,
``ctr[R, E, A]``, ``dcl[R, D, A]``, ``dmask[R, D, E]``, ``dvalid[R, D]``),
built here with ``jax.numpy`` alone.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


class Rows(NamedTuple):
    top: jax.Array
    ctr: jax.Array
    dcl: jax.Array
    dmask: jax.Array
    dvalid: jax.Array


def base_row(key, e: int, a: int, d: int, counter_max: int) -> Rows:
    """``dense_chunk`` for one replica: ~70% of (element, actor) cells
    hold a dot, the top covers every dot. ``counter_max`` is the counter
    range of a replica that has served for a while."""
    k1, k2, k3 = jax.random.split(key, 3)
    ctr = jax.random.randint(k1, (e, a), 0, counter_max, dtype=jnp.int32)
    keep = jax.random.randint(k2, (e, a), 0, 10, dtype=jnp.int32) >= 3
    ctr = jnp.where(keep, ctr, 0).astype(jnp.uint32)
    extra = jax.random.randint(k3, (a,), 0, counter_max, dtype=jnp.int32)
    top = jnp.maximum(ctr.max(axis=0), extra.astype(jnp.uint32))
    return Rows(top=top, ctr=ctr, dcl=jnp.zeros((d, a), jnp.uint32),
                dmask=jnp.zeros((d, e), bool), dvalid=jnp.zeros((d,), bool))


class Population:
    """Shapes and writer layout of one population (host-side constants)."""

    def __init__(self, seed: int, replicas: int, elems: int, actors: int,
                 deferred_cap: int, counter_max: int, writer_elems: int):
        self.seed = int(seed)
        self.r, self.e, self.a, self.d = replicas, elems, actors, deferred_cap
        self.counter_max = counter_max
        self.wr = writer_elems
        # One writer replica per actor, spread over the replica axis.
        self.writers = np.arange(actors) * (replicas // actors)

    def key(self):
        # Seeds run past 32 bits: fold both halves into the key.
        s = self.seed
        return jax.random.fold_in(
            jax.random.key(s & 0xFFFFFFFF), (s >> 32) & 0xFFFFFFFF)

    def base(self) -> Rows:
        return base_row(self.key(), self.e, self.a, self.d, self.counter_max)

    def targets(self, k):
        """``([A, E], [A, E])``: the elements each writer adds to and
        removes in round ``k`` — ``2 * A * wr`` distinct elements per
        round, moving on every round."""
        n = self.a * self.wr
        elems = ((jnp.arange(2 * n) + k * 2 * n) * 7) % self.e
        owner = jnp.repeat(jnp.arange(self.a), self.wr)
        mark = lambda es: jnp.zeros((self.a, self.e), bool).at[owner, es].set(True)  # noqa: E731
        return mark(elems[:n]), mark(elems[n:])

    def touched(self, k) -> jax.Array:
        """``[A, E]`` rows each writer changes in round ``k``."""
        adds, rms = self.targets(k)
        return adds | rms

    def writer_rows(self, base: Rows, k) -> Rows:
        """The ``A`` writer rows after round ``k``'s writes: actor ``a``'s
        writer adds dot ``(a, top[a] + 1)`` on its add elements and drops
        every dot on its remove elements."""
        adds, rms = self.targets(k)                               # [A, E]
        eye = jnp.eye(self.a, dtype=bool)                         # [A, A]
        new = (base.top + 1)[None, :]                             # [1, A]
        top = jnp.where(eye, new, base.top[None, :])              # [A, A]
        ctr = jnp.where(adds[:, :, None] & eye[:, None, :],
                        jnp.maximum(base.ctr[None], new[:, None, :]),
                        base.ctr[None])                           # [A, E, A]
        ctr = jnp.where(rms[:, :, None], 0, ctr).astype(base.ctr.dtype)
        rep = lambda x: jnp.broadcast_to(x, (self.a,) + x.shape)  # noqa: E731
        return Rows(top=top, ctr=ctr, dcl=rep(base.dcl),
                    dmask=rep(base.dmask), dvalid=rep(base.dvalid))

    def block(self, base: Rows, lo, n: int, k) -> Rows:
        """Replicas ``[lo, lo + n)`` after round ``k``'s writes."""
        rows = jax.tree.map(lambda x: jnp.broadcast_to(x, (n,) + x.shape), base)
        w = self.writer_rows(base, k)
        ids = lo + jnp.arange(n)
        hit = ids[:, None] == jnp.asarray(self.writers)[None, :]  # [n, A]
        which = jnp.argmax(hit, axis=1)
        is_w = jnp.any(hit, axis=1)
        pick = lambda b, x: jnp.where(  # noqa: E731
            is_w.reshape((n,) + (1,) * (b.ndim - 1)), x[which], b)
        return Rows(*[pick(b, x) for b, x in zip(rows, w)])
