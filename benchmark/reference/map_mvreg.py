"""The plain ``Map<K, MVReg>`` reference: rust-crdt's ``Map<K, MVReg<V>>``
semantics (src/map.rs, src/mvreg.rs), written from the data type's
definition and independent of the program under test (it imports nothing
of ``crdt_tpu``).

A state is ``top[A]`` (the map's clock), per key its live contents, each a
witness dot ``(actor, counter)`` with the put's clock and value, and the
parked keyset removes, a map from a remove's clock to its keys.

- ``put(actor, counter, key, clock, val)``: a dot the map's clock has seen
  does nothing. Otherwise the register applies the put: contents whose
  clock the put's clock covers are evicted (apply-time domination), and
  the put joins the rest unless one of them has a clock ahead of it. The
  map's clock takes the dot; parked removes are replayed.
- ``rm(clock, keys)``: each key drops the contents whose witness dot the
  clock covers; a remove whose clock the map's clock does not cover is
  parked (united with a parked remove of the same clock) until the map's
  clock catches up.
- ``merge``: per key, a content survives if both sides hold its dot or
  the other side's clock never saw it; clocks take the maximum; parked
  removes are united and replayed.

The canonical form is the program's: per key the live contents sorted by
(actor, counter) in ``S`` slots, live slots first, dead slots zero.

The host rows run in numpy, one replica at a time; the replica fold runs
in ``jax.numpy`` on the device, in row blocks, for states with no parked
removes (the replica cell's population holds none).
"""

from __future__ import annotations

import numpy as np


def _covers(clock, other) -> bool:
    return all(int(c) >= int(o) for c, o in zip(clock, other))


class Row:
    """One Map<K, MVReg> state (host)."""

    def __init__(self, keys: int, actors: int):
        self.top = np.zeros(actors, np.uint32)
        self.content = [dict() for _ in range(keys)]  # {(actor, ctr): (clock, val)}
        self.parked: dict = {}                        # clock -> set of keys

    def clone(self) -> "Row":
        out = Row(len(self.content), len(self.top))
        out.top = self.top.copy()
        out.content = [dict(c) for c in self.content]
        out.parked = {c: set(ks) for c, ks in self.parked.items()}
        return out

    def _drop_covered(self, key: int, clock) -> None:
        self.content[key] = {d: v for d, v in self.content[key].items()
                             if d[1] > int(clock[d[0]])}

    def _replay_parked(self) -> None:
        for clock, keys in list(self.parked.items()):
            for key in keys:
                self._drop_covered(key, clock)
            if _covers(self.top, clock):
                del self.parked[clock]

    def put(self, actor: int, counter: int, key: int, clock, val: int) -> None:
        if int(self.top[actor]) >= counter:
            return
        clock = tuple(int(c) for c in clock)
        kept = {d: v for d, v in self.content[key].items() if not _covers(clock, v[0])}
        if not any(_covers(v[0], clock) for v in kept.values()):
            kept[(actor, counter)] = (clock, int(val))
        self.content[key] = kept
        self.top[actor] = counter
        self._replay_parked()

    def rm(self, clock, keys) -> None:
        clock = tuple(int(c) for c in clock)
        for key in keys:
            self._drop_covered(key, clock)
        if not _covers(self.top, clock):
            self.parked.setdefault(clock, set()).update(int(k) for k in keys)

    def merge(self, other: "Row") -> None:
        for key, (mine, theirs) in enumerate(zip(self.content, other.content)):
            out = {d: v for d, v in mine.items()
                   if d in theirs or d[1] > int(other.top[d[0]])}
            out.update({d: v for d, v in theirs.items()
                        if d not in mine and d[1] > int(self.top[d[0]])})
            self.content[key] = out
        self.top = np.maximum(self.top, other.top)
        for clock, keys in other.parked.items():
            self.parked.setdefault(clock, set()).update(keys)
        self._replay_parked()

    def canonical(self, slots: int):
        """``(top, (wact, wctr, clk, val, valid), parked, siblings_over)``:
        the content planes in canonical form, truncated to ``slots``
        slots a key, the parked removes as a set of ``(clock, keys)``, and
        whether some key held more contents than the slots."""
        k, a = len(self.content), len(self.top)
        wact = np.zeros((k, slots), np.int32)
        wctr = np.zeros((k, slots), np.uint32)
        clk = np.zeros((k, slots, a), np.uint32)
        val = np.zeros((k, slots), np.int32)
        valid = np.zeros((k, slots), bool)
        over = False
        for key, c in enumerate(self.content):
            dots = sorted(c)
            over |= len(dots) > slots
            for s, d in enumerate(dots[:slots]):
                wact[key, s], wctr[key, s] = d
                clk[key, s] = c[d][0]
                val[key, s] = c[d][1]
                valid[key, s] = True
        parked = {(c, frozenset(ks)) for c, ks in self.parked.items()}
        return self.top.copy(), (wact, wctr, clk, val, valid), parked, over


# ---- the replica fold, on the device -------------------------------------

def _top_at(top, act):
    """``top[..., act]`` per slot: ``top [..., A]``, ``act [..., K, S]``."""
    import jax.numpy as jnp

    ids = jnp.arange(top.shape[-1], dtype=act.dtype)
    hit = act[..., None] == ids
    return jnp.max(jnp.where(hit, top[..., None, None, :], 0), axis=-1)


def _held(x, y):
    """Per slot of ``x``: does ``y`` hold the same dot at the same key?"""
    import jax.numpy as jnp

    xa, xc, xv = x
    ya, yc, yv = y
    same = ((xa[..., :, None] == ya[..., None, :]) & (xc[..., :, None] == yc[..., None, :])
            & xv[..., :, None] & yv[..., None, :])
    return jnp.any(same, axis=-1)


def _canon(cols, slots: int):
    """Sort each key's slots into canonical order (live first, then by
    actor and counter; dead slots zeroed) with an odd-even transposition
    network, and keep ``slots`` of them. Returns the columns and, per key,
    whether live contents were cut."""
    import jax.numpy as jnp

    wact, wctr, clk, val, valid = cols
    n = valid.shape[-1]
    # One array per slot: [act, ctr, val, live] and the clocks.
    per = [[jnp.where(valid[..., i], x[..., i], 0) for i in range(n)]
           for x in (wact, wctr, val)] + [[valid[..., i] for i in range(n)]]
    clks = [jnp.where(valid[..., i, None], clk[..., i, :], 0) for i in range(n)]
    for rnd in range(n):
        for i in range(rnd % 2, n - 1, 2):
            j = i + 1
            act, ctr, _, live = per
            swap = (live[j] & ~live[i]) | ((live[i] == live[j]) & (
                (act[i] > act[j]) | ((act[i] == act[j]) & (ctr[i] > ctr[j]))))
            for col in per:
                col[i], col[j] = jnp.where(swap, col[j], col[i]), jnp.where(swap, col[i], col[j])
            sw = swap[..., None]
            clks[i], clks[j] = jnp.where(sw, clks[j], clks[i]), jnp.where(sw, clks[i], clks[j])
    cut = sum(x.astype(jnp.int32) for x in per[3]) > slots
    act, ctr, vals, live = (jnp.stack(col[:slots], axis=-1) for col in per)
    return (act, ctr, jnp.stack(clks[:slots], axis=-2), vals, live), cut


def join(a, b):
    """Pairwise merge of two states ``(top [..., A], (wact, wctr, clk,
    val, valid) [..., K, S…])`` with no parked removes (jax.numpy;
    leading axes broadcast). Returns the merged state in canonical form
    and the number of keys whose contents overflowed the slots."""
    import jax.numpy as jnp

    a_top, ac = a
    b_top, bc = b
    a_dot, b_dot = (ac[0], ac[1], ac[4]), (bc[0], bc[1], bc[4])
    keep_a = ac[4] & (_held(a_dot, b_dot) | (ac[1] > _top_at(b_top, ac[0])))
    # A dot both hold is taken from ``a``.
    keep_b = bc[4] & ~_held(b_dot, a_dot) & (bc[1] > _top_at(a_top, bc[0]))
    union = tuple(jnp.concatenate([x, y], axis=ac[0].ndim - 1) for x, y in zip(ac[:4], bc[:4]))
    union += (jnp.concatenate([keep_a, keep_b], axis=-1),)
    cols, cut = _canon(union, ac[0].shape[-1])
    return (jnp.maximum(a_top, b_top), cols), jnp.sum(cut)


def fold_rows(top, cols):
    """Merge ``top [R, A]`` and content planes ``[R, K, S…]`` into one
    state by halving (merge is associative and commutative). Returns the
    state and the count of keys that overflowed along the way."""
    import jax.numpy as jnp

    over = jnp.zeros((), jnp.int32)
    while top.shape[0] > 1:
        n = top.shape[0]
        h = n // 2
        (jt, jc), o = join((top[:h], tuple(x[:h] for x in cols)),
                           (top[h:2 * h], tuple(x[h:2 * h] for x in cols)))
        over = over + o
        if n % 2:
            (t0, c0), o = join((jt[0], tuple(x[0] for x in jc)),
                               (top[-1], tuple(x[-1] for x in cols)))
            over = over + o
            jt = jt.at[0].set(t0)
            jc = tuple(x.at[0].set(y) for x, y in zip(jc, c0))
        top, cols = jt, jc
    return (top[0], tuple(x[0] for x in cols)), over
