"""A seeded single-character edit trace in the shape of automerge-perf's
``edit-by-index/trace.json`` (github.com/automerge/automerge-perf): one
author writing a document, as (kind, position, value, actor) arrays in
the form ``BatchedList.extend_trace`` and ``native.ListEngine`` take.

- Totals are exact: ``n_ops`` ops of which ``n_inserts`` are inserts, so
  the final text is ``n_inserts - (n_ops - n_inserts)`` long.
- Deletes come as backspace runs of geometric length (mean
  ``delete_run_mean``) between typing runs whose lengths split the
  inserts at random; a delete that would meet an empty document swaps
  with the next insert, so the totals hold and every position is valid.
- A moving cursor places the ops: an insert types at the cursor and
  moves it right, a backspace deletes left of it (at an empty left, the
  character right of it). Before each op the cursor jumps to a uniform
  position of the document with probability ``jump_p``.
- Values are printable ASCII codes, 32-126.
- Actors: the single author's ops go to ``sites`` writer sites in
  round-robin runs of ``run`` ops.
"""

from __future__ import annotations

import numpy as np

INSERT, DELETE = 0, 1  # as crdt_tpu.native numbers them


def generate(seed: int, n_ops: int, n_inserts: int, jump_p: float, delete_run_mean: float,
             sites: int, run: int):
    """(kinds uint8, positions int64, values int32, actors int32)."""
    n_del = n_ops - n_inserts
    if not 0 <= n_del <= n_inserts:
        raise ValueError("a trace cannot delete more than it inserts")
    rng = np.random.default_rng([seed, 29])
    # Backspace runs, then the typing runs between them.
    runs = rng.geometric(1.0 / delete_run_mean, size=n_del + 1)
    runs = runs[: int(np.searchsorted(np.cumsum(runs), n_del)) + 1]
    runs[-1] -= int(runs.sum()) - n_del
    runs = runs[runs > 0]
    cuts = np.sort(rng.integers(0, n_inserts + 1, size=len(runs)))
    typed = np.diff(np.concatenate([[0], cuts, [n_inserts]]))
    kinds = np.empty(n_ops, np.uint8)
    at = 0
    for t, d in zip(typed[:-1], runs):
        kinds[at:at + t] = INSERT
        kinds[at + t:at + t + d] = DELETE
        at += t + d
    kinds[at:] = INSERT
    jump = rng.random(n_ops) < jump_p
    where = rng.random(n_ops)
    values = rng.integers(32, 127, size=n_ops, dtype=np.int32)
    positions = np.empty(n_ops, np.int64)
    length = cursor = 0
    nxt = 0  # the search for the next insert only moves forward
    for i in range(n_ops):
        if kinds[i] == DELETE and length == 0:
            nxt = max(nxt, i + 1)
            while kinds[nxt] != INSERT:
                nxt += 1
            kinds[i], kinds[nxt] = INSERT, DELETE
        if jump[i]:
            cursor = int(where[i] * (length + 1))
        if kinds[i] == INSERT:
            positions[i] = cursor
            cursor += 1
            length += 1
        else:
            if cursor > 0:
                cursor -= 1
            positions[i] = cursor
            length -= 1
    actors = ((np.arange(n_ops) // run) % sites).astype(np.int32)
    return kinds, positions, values, actors
