"""The plain ORSWOT reference: rust-crdt's ``Orswot`` semantics on dense
planes, written from the data type's definition and independent of the
program under test (it imports nothing of ``crdt_tpu``).

A state is ``top[A]`` (the set's clock), ``ctr[E, A]`` (per element, the
birth clock of its live dots: one counter per actor, 0 = none), and the
parked removes ``dcl[D, A]``, ``dmask[D, E]``, ``dvalid[D]``.

- ``add(actor, c, members)``: a dot the clock has seen does nothing;
  otherwise every member's birth clock takes ``(actor, c)`` and the clock
  advances; parked removes are then replayed.
- ``rm(clock, members)``: each member drops the dots the remove's clock
  covers; a remove whose clock the set's clock does not cover is parked
  (merged into a slot of the same clock, else the first free slot) until
  the set's clock catches up.
- ``merge``: per element, the dots both sides hold (the lower counter per
  actor) plus each side's dots the other side's clock has never seen;
  clocks take the maximum; parked removes are united and replayed.

The tenant reference runs in numpy on the host, one tenant row at a time;
the replica fold runs in ``jax.numpy`` on the device, in row blocks.
"""

from __future__ import annotations

import numpy as np


class Row:
    """One dense ORSWOT state (numpy)."""

    def __init__(self, elems: int, actors: int, deferred_cap: int):
        self.top = np.zeros(actors, np.uint32)
        self.ctr = np.zeros((elems, actors), np.uint32)
        self.dcl = np.zeros((deferred_cap, actors), np.uint32)
        self.dmask = np.zeros((deferred_cap, elems), bool)
        self.dvalid = np.zeros(deferred_cap, bool)

    def leaves(self):
        return [self.top, self.ctr, self.dcl, self.dmask, self.dvalid]

    def _replay_parked(self) -> None:
        for s in np.nonzero(self.dvalid)[0]:
            hit = self.dmask[s][:, None] & (self.ctr <= self.dcl[s][None, :])
            self.ctr[hit] = 0
            if np.all(self.dcl[s] <= self.top):
                self.dvalid[s] = False
                self.dcl[s] = 0
                self.dmask[s] = False

    def add(self, actor: int, counter: int, members: np.ndarray) -> None:
        if self.top[actor] >= counter:
            return
        self.ctr[members, actor] = np.maximum(self.ctr[members, actor], counter)
        self.top[actor] = counter
        self._replay_parked()

    def rm(self, clock: np.ndarray, members: np.ndarray) -> bool:
        """Returns False when the remove had to be parked and no slot was
        free (the caller's capacity fault)."""
        clock = np.asarray(clock, np.uint32)
        hit = members[:, None] & (self.ctr <= clock[None, :])
        self.ctr[hit] = 0
        if np.all(clock <= self.top):
            return True
        same = self.dvalid & np.all(self.dcl == clock[None, :], axis=1)
        if same.any():
            self.dmask[np.argmax(same)] |= members
            return True
        free = ~self.dvalid
        if not free.any():
            return False
        s = int(np.argmax(free))
        self.dcl[s], self.dmask[s], self.dvalid[s] = clock, members, True
        return True


def tenant_row(caps: dict, history) -> Row:
    """A tenant's state after its ops in order. ``history`` entries are
    ``("add", actor, counter, mask)`` or ``("rm", clock, mask)``."""
    row = Row(caps["n_elems"], caps["n_actors"], caps["deferred_cap"])
    for op in history:
        if op[0] == "add":
            row.add(op[1], op[2], op[3])
        elif not row.rm(op[1], op[2]):
            raise OverflowError("parked-remove buffer full")
    return row


def words_differing(got, want) -> int:
    """32-bit words (or flags) in which two states differ, leaf by leaf;
    a leaf of another shape counts whole."""
    n = 0
    for g, w in zip(got, want):
        g, w = np.asarray(g), np.asarray(w)
        n += int(w.size) if g.shape != w.shape else int(np.sum(g != w))
    return n


# ---- the replica fold, on the device -------------------------------------

def join(a, b):
    """Pairwise merge of two dense states (jax.numpy; leading axes
    broadcast). Parked removes must be empty on both sides: the replica
    cells' populations carry none, and the caller checks the program's
    output holds none either."""
    import jax.numpy as jnp

    a_top, a_ctr = a
    b_top, b_ctr = b
    a_unseen = jnp.where(a_ctr > b_top[..., None, :], a_ctr, 0)
    b_unseen = jnp.where(b_ctr > a_top[..., None, :], b_ctr, 0)
    kept = jnp.maximum(jnp.minimum(a_ctr, b_ctr),
                       jnp.maximum(a_unseen, b_unseen))
    return jnp.maximum(a_top, b_top), kept


def fold_rows(top, ctr):
    """Merge ``top[R, A]`` / ``ctr[R, E, A]`` into one state by halving
    (merge is associative and commutative)."""
    while top.shape[0] > 1:
        n = top.shape[0]
        h = n // 2
        jt, jc = join((top[:h], ctr[:h]), (top[h:2 * h], ctr[h:2 * h]))
        if n % 2:
            t0, c0 = join((jt[0], jc[0]), (top[-1], ctr[-1]))
            jt, jc = jt.at[0].set(t0), jc.at[0].set(c0)
        top, ctr = jt, jc
    return top[0], ctr[0]
