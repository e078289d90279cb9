"""Anti-entropy entry: median milliseconds, call by call, of the δ ring's
entry (the ``ae.delta_gossip`` program span of ``run_delta_ring``) outside
its ``ae.delta_gossip.wait`` span: the entry's host work around one ring."""

from program_spans import median_less_ms


def read(c):
    return median_less_ms("ae.delta_gossip", "ae.delta_gossip.wait")
