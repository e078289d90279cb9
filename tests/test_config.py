"""Config / flag system (SURVEY.md §6.6): backend selection is the
feature-flag analog and drives the A/B gate."""

import os

import pytest

from crdt_tpu.config import config, configure, configured, replicaset


def test_backend_selects_execution_path():
    with configured(backend="pure"):
        reps = replicaset("orswot", 3)
        assert isinstance(reps, list) and len(reps) == 3
        from crdt_tpu.pure.orswot import Orswot

        assert all(isinstance(r, Orswot) for r in reps)

    with configured(backend="xla", deferred_cap=4):
        model = replicaset("orswot", 3, n_members=8, n_actors=4)
        from crdt_tpu.models import BatchedOrswot

        assert isinstance(model, BatchedOrswot)
        assert model.n_replicas == 3
        assert model.state.dcl.shape[-2] == 4  # deferred_cap flows through


def test_all_kinds_construct_under_both_backends():
    kinds = [
        "orswot", "sparse_orswot", "map", "map_orswot", "map_map", "map3",
        "sparse_map_orswot", "sparse_map", "sparse_map_map",
        "gcounter", "pncounter", "gset", "lwwreg", "mvreg",
    ]
    with configured(backend="pure"):
        for kind in kinds:
            assert len(replicaset(kind, 2)) == 2
    with configured(backend="xla"):
        for kind in kinds:
            model = replicaset(kind, 2, n_members=4, n_actors=2, n_keys=4)
            assert model.n_replicas == 2


def test_unknown_fields_and_kinds_rejected():
    with pytest.raises(TypeError):
        configure(no_such_flag=True)
    with pytest.raises(ValueError):
        configure(backend="cuda")
    configure(backend="xla")  # restore
    with pytest.raises(ValueError):
        replicaset("btree", 2)


def test_scoped_override_restores():
    before = config.backend
    with configured(backend="pure"):
        assert config.backend == "pure"
    assert config.backend == before


def test_strict_mode_validation():
    # v7 validate_op: strict appliers reject gapped/duplicate dots.
    from crdt_tpu.pure.orswot import Orswot
    from crdt_tpu.traits import DotRange

    site = Orswot()
    op1 = site.add("m", site.read().derive_add_ctx("a"))
    site.apply(op1)
    replica = Orswot()
    gapped = site.add("m2", site.read().derive_add_ctx("a"))  # dot (a,2)
    with pytest.raises(DotRange):
        replica.validate_op(gapped)  # (a,2) without (a,1): gap
    replica.apply(op1)
    replica.validate_op(gapped)  # now contiguous
    with pytest.raises(DotRange):
        replica.validate_op(op1)  # duplicate


def test_strict_validation_on_xla_backend():
    # A gapped/duplicate dot must raise DotRange on the
    # batched path too, not only through the pure types' validate_op.
    from crdt_tpu.models import BatchedMap, BatchedOrswot
    from crdt_tpu.pure.map import Map
    from crdt_tpu.pure.mvreg import MVReg
    from crdt_tpu.pure.orswot import Orswot
    from crdt_tpu.traits import DotRange
    from crdt_tpu.utils import Interner

    site = Orswot()
    op1 = site.add("m", site.read().derive_add_ctx("a"))
    site.apply(op1)
    gapped = site.add("m2", site.read().derive_add_ctx("a"))  # dot (a,2)

    def fresh():
        return BatchedOrswot(
            1, 4, 2, 2, members=Interner(["m", "m2"]), actors=Interner(["a"])
        )

    with configured(backend="xla", strict=True):
        device = fresh()
        with pytest.raises(DotRange):
            device.apply(0, gapped)  # (a,2) without (a,1): gap
        device.apply(0, op1)
        device.apply(0, gapped)  # now contiguous
        with pytest.raises(DotRange):
            device.apply(0, op1)  # duplicate
    # non-strict: dup/gap ops are silently handled (oracle drop rule)
    device = fresh()
    device.apply(0, gapped)
    device.apply(0, op1)

    # the composition layer too
    msite = Map(MVReg)
    mop = msite.update(
        "k", msite.len().derive_add_ctx("a"), lambda r, c: r.write(1, c)
    )
    msite.apply(mop)
    mgap = msite.update(
        "k", msite.len().derive_add_ctx("a"), lambda r, c: r.write(2, c)
    )
    with configured(backend="xla", strict=True):
        dmap = BatchedMap(1, 2, 2, 4, 4, keys=Interner(["k"]), actors=Interner(["a"]))
        with pytest.raises(DotRange):
            dmap.apply(0, mgap)
        dmap.apply(0, mop)
        dmap.apply(0, mgap)


def test_validate_op_counters_and_map():
    from crdt_tpu import GCounter, Map, MVReg, PNCounter, VClock
    from crdt_tpu.traits import DotRange

    g = GCounter()
    op = g.inc("a")
    g.validate_op(op)
    g.apply(op)
    with pytest.raises(DotRange):
        g.validate_op(op)

    pn = PNCounter()
    pop = pn.dec("a")
    pn.validate_op(pop)
    pn.apply(pop)
    with pytest.raises(DotRange):
        pn.validate_op(pop)

    m = Map(val_default=MVReg)
    up = m.update("k", m.len().derive_add_ctx("a"), lambda r, c: r.write(1, c))
    m.validate_op(up)
    m.apply(up)
    with pytest.raises(DotRange):
        m.validate_op(up)

    vc = VClock()
    d = vc.inc("a")
    vc.validate_op(d)
    vc.apply(d)
    with pytest.raises(DotRange):
        vc.validate_op(d)


def test_validate_op_mvreg_both_backends():
    """v7 validation parity for MVReg (SURVEY §3.2: "the same set"):
    dup/gap Puts raise DotRange on the oracle AND, under strict mode, on
    the batched path; malformed Puts (clock missing its own witness dot)
    are rejected outright."""
    from crdt_tpu.dot import Dot
    from crdt_tpu.models import BatchedMVReg
    from crdt_tpu.pure.mvreg import MVReg, Put
    from crdt_tpu.traits import DotRange, ValidationError
    from crdt_tpu.utils import Interner
    from crdt_tpu.vclock import VClock

    site = MVReg()
    op1 = site.write(10, site.read().derive_add_ctx("a"))
    site.apply(op1)
    op2 = site.write(20, site.read().derive_add_ctx("a"))  # dot (a,2)

    replica = MVReg()
    with pytest.raises(DotRange):
        replica.validate_op(op2)  # gap: (a,2) before (a,1)
    replica.validate_op(op1)
    replica.apply(op1)
    with pytest.raises(DotRange):
        replica.validate_op(op1)  # duplicate
    replica.validate_op(op2)  # contiguous now
    with pytest.raises(ValidationError):
        replica.validate_op(Put(dot=Dot("a", 2), clock=VClock({"b": 1}), val=0))
    with pytest.raises(ValidationError):
        replica.validate_op("garbage")

    def fresh():
        return BatchedMVReg(
            1, 2, n_slots=4, actors=Interner(["a"]), values=Interner([10, 20])
        )

    with configured(backend="xla", strict=True):
        device = fresh()
        with pytest.raises(DotRange):
            device.apply(0, op2)  # gap
        device.apply(0, op1)
        with pytest.raises(DotRange):
            device.apply(0, op1)  # duplicate
        device.apply(0, op2)
    # non-strict: the oracle drop rule handles dups silently
    device = fresh()
    device.apply(0, op1)
    device.apply(0, op1)


def test_validate_op_list_both_backends():
    """v7 validation parity for List (SURVEY §3.2: "+ List"): gapped and
    duplicate insert dots, deletes of unseen identifiers, and duplicate
    trace delivery on the device path all raise DotRange."""
    import numpy as np

    from crdt_tpu.models import BatchedList
    from crdt_tpu.pure.list import List
    from crdt_tpu.traits import DotRange, ValidationError

    site = List()
    ins1 = site.insert_index(0, "x", "a")
    site.apply(ins1)
    ins2 = site.insert_index(1, "y", "a")  # dot (a,2)
    site.apply(ins2)
    dele = site.delete_index(0, "a")       # dot (a,3), targets ins1

    replica = List()
    with pytest.raises(DotRange):
        replica.validate_op(ins2)  # gap
    replica.validate_op(ins1)
    replica.apply(ins1)
    with pytest.raises(DotRange):
        replica.validate_op(ins1)  # duplicate
    with pytest.raises(DotRange):
        # delete whose own dot gaps ((a,3) after (a,1))
        replica.validate_op(dele)
    replica.apply(ins2)
    replica.validate_op(dele)  # contiguous + target observed

    # unseen-target branch: the delete's OWN dot is contiguous (fresh
    # actor "b"), but the targeted insert (a,2) was never observed
    deleter = site.clone()
    del_unseen = deleter.delete_index(1, "b")  # dot (b,1), targets (a,2)
    behind = List()
    behind.apply(ins1)  # saw only (a,1)
    with pytest.raises(DotRange):
        behind.validate_op(del_unseen)
    replica.validate_op(del_unseen)  # replica saw (a,2): fine
    with pytest.raises(ValidationError):
        replica.validate_op(object())

    # device path: duplicate delivery of one trace op to one replica
    from crdt_tpu.native import INSERT

    kinds, idxs, vals, actors = [INSERT, INSERT], [0, 1], [1, 2], [0, 0]
    model = BatchedList.from_trace(kinds, idxs, vals, actors, n_replicas=2)
    with configured(strict=True):
        with pytest.raises(DotRange):
            model.apply_ops(np.asarray([[0, 0], [1, -1]]))
    model.apply_ops(np.asarray([[0, 1], [1, -1]]))  # unique: fine


def test_counter_dtype_u64_and_saturation_trap():
    """Counter-width parity (reference src/vclock.rs u64; SURVEY §7.3
    overflow discipline): the clock/counter family widens to uint64 via
    config, and the u32 path traps saturation under strict mode instead
    of silently wrapping."""
    import numpy as np

    from crdt_tpu.models import BatchedPNCounter, BatchedVClock
    from crdt_tpu.traits import CounterSaturation
    from crdt_tpu.utils import Interner

    # u64: increments past 2^32 accumulate exactly
    with configured(counter_dtype="uint64"):
        pn = BatchedPNCounter(1, actors=Interner(["a"]))
        assert str(pn.p.clocks.dtype) == "uint64"
        big = (1 << 32) + 5
        pn.inc(0, "a", steps=big)
        pn.dec(0, "a", steps=3)
        assert pn.fold_read() == big - 3
        vc = BatchedVClock(1, actors=Interner(["a"]))
        assert str(vc.clocks.dtype) == "uint64"

    # u32 + strict: an increment that would exceed the lane max traps
    with configured(counter_dtype="uint32", strict=True):
        pn32 = BatchedPNCounter(1, actors=Interner(["a"]))
        pn32.inc(0, "a", steps=(1 << 32) - 2)
        with pytest.raises(CounterSaturation):
            pn32.inc(0, "a", steps=5)
        # a saturated top lane rejects further dot mints too
        vc32 = BatchedVClock(1, actors=Interner(["a"]))
        vc32.clocks = vc32.clocks.at[0, 0].set(np.uint32((1 << 32) - 1))
        from crdt_tpu.dot import Dot

        with pytest.raises(CounterSaturation):
            vc32.apply(0, Dot("a", 1))

    # steps outside the dtype envelope rejected on both widths
    pn = BatchedPNCounter(1, actors=Interner(["a"]))
    with pytest.raises(ValueError):
        pn.inc(0, "a", steps=1 << 33)


def test_compile_cache_placement(monkeypatch, tmp_path):
    import jax

    from crdt_tpu.utils import compile_cache

    # Set from outside: used as is, and no path is set in code.
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.setattr(jax.config, "update", lambda k, v: updates.append(k))
    updates = []
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert "jax_compilation_cache_dir" not in updates
    # Unset: the fixed path under the checkout, exported to children.
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    want = os.path.join(root, ".jax_cache")
    assert compile_cache.enable_compile_cache() == want
    assert os.environ["JAX_COMPILATION_CACHE_DIR"] == want
    assert "jax_compilation_cache_dir" in updates
