"""List apply: the share of the apply's lanes that carry an op, over the
window: 100 × Σ ``list.lanes_live`` ÷ Σ ``list.lanes`` (the program's
counters; a lane is dead where its row applies nothing this round or its
chunk's op was superseded in the chunk, or it pads the chunk)."""


def read(c):
    k = c["res"]["counters"]
    if not k.get("lanes"):
        return None
    return 100.0 * k["lanes_live"] / k["lanes"]
