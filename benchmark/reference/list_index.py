"""The plain reference for a sequence CRDT replica under causal delivery:
replay an edit trace by index on a Python list. Insert puts the value at
the position, delete removes the element at the position; no
identifiers, no repository code. A single author's trace is one causal
sequence, so every prefix is causally closed, and a replica that has
applied ops ``[0, w)`` must read the text this replay holds after them.

The control replays with the deletes dropped, so deleted characters come
back: the guarantee that a delivered delete is applied is broken."""

from __future__ import annotations

INSERT = 0  # as crdt_tpu.native numbers it


def texts_at(kinds, positions, values, stops, drop_deletes: bool = False) -> dict:
    """``{w: text after ops [0, w)}`` for each ``w`` in ``stops``, in one
    pass. ``drop_deletes`` gives the control."""
    kinds, positions, values = kinds.tolist(), positions.tolist(), values.tolist()
    want = sorted(set(int(w) for w in stops))
    text, out, i = [], {}, 0
    for w in want:
        for i in range(i, w):
            if kinds[i] == INSERT:
                text.insert(positions[i], values[i])
            elif not drop_deletes:
                del text[positions[i]]
        i = w
        out[w] = list(text)
    return out


def words_off(got, want) -> int:
    """Positions at which two texts differ, a missing character counting
    as one."""
    n = min(len(got), len(want))
    return sum(a != b for a, b in zip(got[:n], want[:n])) + abs(len(got) - len(want))
