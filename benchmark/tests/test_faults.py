"""The harness, with the timed path broken underneath, must come out not
correct: once for each fault a cell can have."""

import numpy as np
import pytest

from tiny import rehearse


def _serve_apply_fault(monkeypatch, how):
    import jax
    import jax.numpy as jnp

    from crdt_tpu.ops import superblock as sb_ops
    from crdt_tpu.serve import superblock as sbmod

    real = sbmod.mesh_serve_apply

    def broken(state, slab, idx, mesh, **kw):
        if how == "unchanged":
            out = real(state, slab, idx, mesh, **dict(kw, donate=False))
            return (state,) + tuple(out[1:])
        if how == "half":
            half = slab.kind.shape[0] // 2
            keep = jnp.arange(slab.kind.shape[0]) < half
            slab = slab._replace(kind=jnp.where(keep[:, None], slab.kind, sb_ops.NOOP))
            return real(state, slab, idx, mesh, **kw)
        out = real(state, slab, idx, mesh, **kw)   # "altered": one counter off
        new = out[0]._replace(ctr=out[0].ctr.at[0, 0, 0].add(1))
        return (new,) + tuple(out[1:])

    monkeypatch.setattr(sbmod, "mesh_serve_apply", broken)
    del jax


@pytest.mark.parametrize("how", ["unchanged", "half", "altered"])
def test_serving_apply_faults_are_caught(monkeypatch, how):
    _serve_apply_fault(monkeypatch, how)
    line = rehearse("tenants_resident_sat", seed=21, seconds=0.5)["line"]
    assert not line["correct"], line["checks"]


def test_lost_wal_record_is_caught(monkeypatch):
    from crdt_tpu.serve import wal as walmod

    real = walmod.ServeWal.log_slab
    n = {"calls": 0}

    def drop_some(self, *a, **kw):
        n["calls"] += 1
        if n["calls"] % 2:     # every other group commit never reaches disk
            return self.wal.last_seq
        return real(self, *a, **kw)

    monkeypatch.setattr(walmod.ServeWal, "log_slab", drop_some)
    checks = rehearse("tenants_resident_sat", seed=22, seconds=0.5)["line"]["checks"]
    assert checks["recovered_row_words_off"]["value"] > 0


def test_altered_push_is_caught(monkeypatch):
    from crdt_tpu.fanout import plane as planemod

    real = planemod.mesh_fanout_push

    def altered(*a, **kw):
        out = real(*a, **kw)
        bad = out[0]._replace(words=out[0].words ^ 1)   # every packed word off
        return (bad,) + tuple(out[1:])

    monkeypatch.setattr(planemod, "mesh_fanout_push", altered)
    checks = rehearse("tenants_resident_rate", seed=23, seconds=0.5)["line"]["checks"]
    assert checks["client_row_words_off"]["value"] > 0


def _fold_fault(monkeypatch, how):
    import jax
    import jax.numpy as jnp

    import crdt_tpu.parallel as par

    real = par.mesh_fold
    memo = {}

    def broken(pop, mesh, **kw):
        if how == "half":
            r = pop.top.shape[0] // 2
            return real(jax.tree.map(lambda x: x[:r], pop), mesh, **kw)
        out, of = real(pop, mesh, **kw)
        if how == "unchanged":
            return memo.setdefault("first", (out, of))
        return out._replace(ctr=out.ctr.at[3, 1].add(1)), of

    monkeypatch.setattr(par, "mesh_fold", broken)
    del jnp


@pytest.mark.parametrize("how", ["unchanged", "half", "altered"])
def test_fold_faults_are_caught(monkeypatch, how):
    _fold_fault(monkeypatch, how)
    line = rehearse("replicas_fold_1chip", seed=31, seconds=0.5)["line"]
    assert line["checks"]["state_words_off"]["value"] > 0


@pytest.mark.parametrize("how", ["no_exchange", "altered"])
def test_ring_faults_are_caught(monkeypatch, how):
    from crdt_tpu.parallel import delta

    real = delta.mesh_delta_gossip

    def broken(pop, dirty, fctx, mesh, **kw):
        if how == "no_exchange":
            kw["rounds"] = 0
        states, d, of, res = real(pop, dirty, fctx, mesh, **kw)
        if how == "altered":
            states = states._replace(ctr=states.ctr.at[1, 2, 3].add(1))
        return states, d, of, np.int32(0) * res

    monkeypatch.setattr(delta, "mesh_delta_gossip", broken)
    line = rehearse("replicas_ring_4chip", seed=41, seconds=0.5)["line"]
    assert line["checks"]["state_words_off"]["value"] > 0
