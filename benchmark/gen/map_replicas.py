"""A synced ``Map<K, MVReg>`` replica population and its per-round writes,
made on the device from the seed with ``jax.numpy`` alone.

Every replica holds one synced base state: every key is live, a quarter
of the keys hold two concurrent siblings written by different actors,
counters are drawn below ``counter_max``, and the map's clock covers every
dot. Each round, one writer replica per actor (actors ``2i`` and ``2i + 1``
are a pair) writes, starting again from the base, on keys the round picks
and moves on every round:

- ``writer_puts`` puts, each a new dot whose put clock is the writer's
  clock, so it evicts the key's base siblings. Half of the keys are
  shared with the pair writer, so they converge to exactly two concurrent
  siblings; the rest are the writer's own.
- ``writer_removes`` keyset removes of one clock, the writer's clock after
  its puts. A quarter of those keys are put in the same round by a writer
  of another pair, so that put's dot survives the remove (add wins); the
  rest are removed by this writer alone.

So every round is the same amount of work: an anti-entropy round from a
synced moment, that never holds more than two siblings a key and parks no
remove. The leaves follow the layout the program takes (``top[R, A]``,
``wact/wctr/val/valid[R, K, S]``, ``clk[R, K, S, A]``, ``dcl[R, D, A]``,
``dkeys[R, D, K]``, ``dvalid[R, D]``).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

VAL_MAX = 1 << 30


class Rows(NamedTuple):
    top: jax.Array
    wact: jax.Array
    wctr: jax.Array
    clk: jax.Array
    val: jax.Array
    valid: jax.Array
    dcl: jax.Array
    dkeys: jax.Array
    dvalid: jax.Array


class Population:
    """Shapes and writer layout of one population (host-side constants)."""

    def __init__(self, seed: int, replicas: int, keys: int, actors: int, sibling_cap: int,
                 deferred_cap: int, counter_max: int, writer_puts: int, writer_removes: int):
        if actors % 2 or actors < 4:
            raise ValueError("writers come in pairs, and need a second pair")
        if sibling_cap < 2:
            raise ValueError("the traffic writes two concurrent siblings a key")
        self.seed = int(seed)
        self.r, self.k, self.a = replicas, keys, actors
        self.s, self.d = sibling_cap, deferred_cap
        self.counter_max = counter_max
        self.puts, self.removes = writer_puts, writer_removes
        self.shared = writer_puts // 2               # keys a pair both put
        self.solo = writer_puts - self.shared        # keys a writer puts alone
        self.rm_put = writer_removes // 4            # removed keys another pair puts
        self.rm_only = writer_removes - self.rm_put  # keys only this writer touches
        if self.rm_put > self.solo:
            raise ValueError("more removed-while-put keys than solo puts")
        self.touched = (actors // 2) * self.shared + actors * (self.solo + self.rm_only)
        if self.touched > keys:
            raise ValueError("a round touches more keys than the map holds")
        # One writer replica per actor, spread over the replica axis.
        self.writers = np.arange(actors) * (replicas // actors)

    def key(self):
        # Seeds run past 32 bits: fold both halves into the key.
        s = self.seed
        return jax.random.fold_in(
            jax.random.key(s & 0xFFFFFFFF), (s >> 32) & 0xFFFFFFFF)

    def base(self) -> Rows:
        """The synced base state, unbatched."""
        k, a, s, d, cmax = self.k, self.a, self.s, self.d, self.counter_max
        ks = jax.random.split(self.key(), 8)
        a1 = jax.random.randint(ks[0], (k,), 0, a, dtype=jnp.int32)
        a2 = (a1 + 1 + jax.random.randint(ks[1], (k,), 0, a - 1, dtype=jnp.int32)) % a
        c1 = jax.random.randint(ks[2], (k,), 1, cmax, dtype=jnp.int32).astype(jnp.uint32)
        c2 = jax.random.randint(ks[3], (k,), 1, cmax, dtype=jnp.int32).astype(jnp.uint32)
        two = jax.random.randint(ks[4], (k,), 0, 4, dtype=jnp.int32) == 0
        ids = jnp.arange(a, dtype=jnp.int32)

        def clock(key, own, ctr, other, other_ctr):
            """A put clock: what its writer had seen of each actor, its
            own dot, and (for a sibling) not the concurrent sibling's."""
            seen = jax.random.randint(key, (k, a), 0, cmax, dtype=jnp.int32).astype(jnp.uint32)
            seen = jnp.where(ids == own[:, None], ctr[:, None], seen)
            cap = jnp.where(two, other_ctr - 1, jnp.uint32(cmax))
            return jnp.where(ids == other[:, None], jnp.minimum(seen, cap[:, None]), seen)

        clk1 = clock(ks[5], a1, c1, a2, c2)
        clk2 = clock(ks[6], a2, c2, a1, c1)
        vals = jax.random.randint(ks[7], (k, 2), 0, VAL_MAX, dtype=jnp.int32)
        # Canonical slot order: the lower actor first.
        first = ~two | (a1 < a2)
        pick = lambda x, y: jnp.where(first.reshape((k,) + (1,) * (x.ndim - 1)), x, y)  # noqa: E731
        dead = lambda x: jnp.where(two.reshape((k,) + (1,) * (x.ndim - 1)), x, 0)  # noqa: E731
        slot0 = (pick(a1, a2), pick(c1, c2), pick(clk1, clk2), pick(vals[:, 0], vals[:, 1]))
        slot1 = (dead(pick(a2, a1)), dead(pick(c2, c1)), dead(pick(clk2, clk1)),
                 dead(pick(vals[:, 1], vals[:, 0])))
        cols = [jnp.stack([x, y], axis=1) for x, y in zip(slot0, slot1)]
        cols = [jnp.pad(x, ((0, 0), (0, s - 2)) + ((0, 0),) * (x.ndim - 2)) for x in cols]
        valid = jnp.pad(jnp.stack([jnp.ones((k,), bool), two], axis=1), ((0, 0), (0, s - 2)))
        top = jnp.maximum(clk1.max(axis=0), jnp.where(two[:, None], clk2, 0).max(axis=0))
        return Rows(top=top, wact=cols[0], wctr=cols[1], clk=cols[2], val=cols[3],
                    valid=valid, dcl=jnp.zeros((d, a), jnp.uint32),
                    dkeys=jnp.zeros((d, k), bool), dvalid=jnp.zeros((d,), bool))

    def targets(self, k):
        """Round ``k``'s keys: ``(puts [A, writer_puts], removes [A,
        writer_removes])``, writer by writer. Put ``j`` of writer ``a`` is
        its dot ``(a, top[a] + j + 1)``."""
        a, sh, so, rp, ro = self.a, self.shared, self.solo, self.rm_put, self.rm_only
        n = self.touched
        keys = ((jnp.arange(n) + k * n) * 7) % self.k    # distinct, moving
        pair_keys = keys[:(a // 2) * sh].reshape(a // 2, sh)
        solo = keys[(a // 2) * sh:(a // 2) * sh + a * so].reshape(a, so)
        only = keys[(a // 2) * sh + a * so:].reshape(a, ro)
        writers = jnp.arange(a)
        puts = jnp.concatenate([pair_keys[writers // 2], solo], axis=1)
        # A writer of another pair (a + 2) puts these in the same round.
        removes = jnp.concatenate([solo[(writers + 2) % a, :rp], only], axis=1)
        return puts, removes

    def writer_rows(self, base: Rows, k) -> Rows:
        """The ``A`` writer rows after round ``k``'s writes."""
        a, kk = self.a, self.k
        puts, removes = self.targets(k)
        writers = jnp.arange(a)
        ctr = base.top[:, None] + 1 + jnp.arange(self.puts, dtype=jnp.uint32)[None]  # [A, P]
        val = jax.random.randint(jax.random.fold_in(self.key(), k), (a, self.puts), 0,
                                 VAL_MAX, dtype=jnp.int32)
        rows_p = jnp.broadcast_to(writers[:, None], puts.shape)
        put_ctr = jnp.zeros((a, kk), jnp.uint32).at[rows_p, puts].set(ctr)      # [A, K]
        put_val = jnp.zeros((a, kk), jnp.int32).at[rows_p, puts].set(val)
        put = put_ctr > 0
        rows_r = jnp.broadcast_to(writers[:, None], removes.shape)
        gone = jnp.zeros((a, kk), bool).at[rows_r, removes].set(True) | put
        eye = jnp.eye(a, dtype=bool)
        # The put clock: the writer's clock when it wrote, its dot included.
        put_clk = jnp.where(eye[:, None, :], put_ctr[:, :, None], base.top[None, None, :])
        slot0 = jnp.arange(self.s) == 0                                           # [S]
        new = put[:, :, None] & slot0                                             # [A, K, S]
        keep = ~gone[:, :, None]
        bcast = lambda x: jnp.broadcast_to(x, (a,) + x.shape)  # noqa: E731
        return Rows(
            top=jnp.where(eye, base.top[None] + self.puts, base.top[None]),
            wact=jnp.where(new, writers[:, None, None], jnp.where(keep, base.wact[None], 0)),
            wctr=jnp.where(new, put_ctr[:, :, None], jnp.where(keep, base.wctr[None], 0)),
            clk=jnp.where(new[..., None], put_clk[:, :, None, :],
                          jnp.where(keep[..., None], base.clk[None], 0)),
            val=jnp.where(new, put_val[:, :, None], jnp.where(keep, base.val[None], 0)),
            valid=new | (keep & base.valid[None]),
            dcl=bcast(base.dcl), dkeys=bcast(base.dkeys), dvalid=bcast(base.dvalid),
        )

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
