"""Actor lifecycle mechanics: the two
remedies CounterSaturation prescribes — u32→u64 widening and
retired-actor compaction — as migrations that preserve converged state
bit-identically at the oracle level."""

import jax.numpy as jnp
import numpy as np
import pytest

from crdt_tpu.config import configured
from crdt_tpu.lifecycle import (
    RETIRED,
    compact_actors,
    retire_actor,
    widen_counters,
)
from crdt_tpu.models import BatchedGCounter, BatchedPNCounter, BatchedVClock
from crdt_tpu.pure.gcounter import GCounter
from crdt_tpu.traits import CounterSaturation
from crdt_tpu.utils import Interner


def _near_saturated_gcounter():
    """A GCounter whose 'old' actor lane sits at the u32 ceiling."""
    p = GCounter()
    p.inner.dots["old"] = 2**32 - 1
    p.inner.dots["young"] = 7
    return BatchedGCounter.from_pure([p, p.clone()])


def test_strict_mode_traps_saturation():
    m = _near_saturated_gcounter()
    with configured(strict=True):
        with pytest.raises(CounterSaturation):
            m.inc(0, "old")


def test_widen_counters_lifts_ceiling_bit_identically():
    m = _near_saturated_gcounter()
    before = [m.to_pure(i) for i in range(m.n_replicas)]
    with configured(counter_dtype="uint64", strict=True):
        widen_counters(m)
        assert m.inner.clocks.dtype == jnp.uint64
        # Bit-identical migration: oracle forms unchanged.
        assert [m.to_pure(i) for i in range(m.n_replicas)] == before
        # And the trap no longer fires — the lane has u64 headroom.
        m.inc(0, "old")
        assert m.to_pure(0).read() == (2**32 - 1) + 1 + 7
        # Exactness past 2^53 (the float ceiling): host-int reads.
        m.inner.clocks = m.inner.clocks.at[0, 0].set(2**60)
        assert m.to_pure(0).read() == 2**60 + 7


def test_widen_requires_x64():
    m = _near_saturated_gcounter()
    with pytest.raises(RuntimeError, match="x64"):
        widen_counters(m)


def test_retire_actor_preserves_reads_exactly():
    m = _near_saturated_gcounter()  # converged: both rows identical
    reads = [m.read(i) for i in range(m.n_replicas)]
    fold_before = m.fold_read()
    retire_actor(m, "old")
    assert [m.read(i) for i in range(m.n_replicas)] == reads
    assert m.fold_read() == fold_before
    # The actor's own lane is zeroed; its count lives in RETIRED.
    aid = m.actors.id_of("old")
    rid = m.actors.id_of(RETIRED)
    col = np.asarray(m.inner.clocks)
    assert (col[:, aid] == 0).all()
    assert (col[:, rid] == 2**32 - 1).all()
    # Oracle form: same total, actor renamed into the aggregate.
    assert m.to_pure(0).read() == fold_before


def test_retire_diverged_lane_refused():
    p1, p2 = GCounter(), GCounter()
    p1.inner.dots["a"] = 5
    p2.inner.dots["a"] = 9  # not yet converged
    m = BatchedGCounter.from_pure([p1, p2])
    with pytest.raises(ValueError, match="converge"):
        retire_actor(m, "a")
    # vclock models are refused outright (lane merge breaks the order)
    vc = BatchedVClock(2, actors=Interner(["a"]))
    with pytest.raises(TypeError):
        retire_actor(vc, "a")


def test_retire_then_compact_pncounter():
    m = BatchedPNCounter(2, actors=Interner(["a", "b", "c"]), n_actors=8)
    for r in range(2):
        m.inc(r, "a", 10)
        m.dec(r, "a", 3)
        m.inc(r, "b", 5)
    # Converge so every lane agrees (replica rows were built identically
    # here; a real deployment folds first).
    reads = [m.read(i) for i in range(2)]
    retire_actor(m, "a")
    assert [m.read(i) for i in range(2)] == reads

    compact_actors(m)
    # 'a' (zeroed) and 'c' (never used) are gone; 'b' and RETIRED stay.
    assert "a" not in m.actors and "c" not in m.actors
    assert "b" in m.actors and RETIRED in m.actors
    # Lane WIDTH is preserved — the freed tail is headroom.
    assert m.p.clocks.shape[-1] == 8
    assert m.p.actors is m.n.actors  # shared-interner invariant
    assert [m.read(i) for i in range(2)] == reads
    # Life goes on: old AND brand-new actors under the compacted universe.
    m.inc(0, "b", 2)
    m.inc(0, "fresh", 4)
    assert m.read(0) == reads[0] + 6


def test_compact_never_used_lanes_only():
    m = BatchedGCounter(2, actors=Interner(["a", "b"]), n_actors=16)
    m.inc(0, "a")
    m.inc(1, "a")
    compact_actors(m)
    assert len(m.actors) == 1 and "a" in m.actors
    assert m.inner.clocks.shape == (2, 16)  # width preserved as headroom
    assert m.read(0) == 1 and m.read(1) == 1
