"""Driver for ORSWOT anti-entropy rounds over a resident replica
population: each round, the writers' step, then the traffic's entry
(``mesh_fold`` or ``mesh_delta_gossip``) until the converged state is
ready on every device."""

from __future__ import annotations

import time

import numpy as np

from gen.replicas import Population
from reference import orswot as ref


def _entry(ctx, mesh):
    tr = ctx.traffic
    if tr["entry"] == "mesh_fold":
        from crdt_tpu.parallel import mesh_fold

        # The state as the entry returns it, with no device axis: the
        # check broadcasts it, and adding one here cost a round five
        # host round trips that are no part of the fold.
        def fold(pop, dirty, fctx):
            out, of = mesh_fold(pop, mesh)
            return out, of, 0
        return fold
    if tr["entry"] == "mesh_delta_gossip":
        from crdt_tpu.parallel.delta import mesh_delta_gossip

        def gossip(pop, dirty, fctx):
            states, _, of, residue = mesh_delta_gossip(
                pop, dirty, fctx, mesh, rounds=tr["ring_rounds"],
                cap=tr["cap"], pipeline=tr["pipeline"], fused=True)
            return states, of, residue
        return gossip
    raise ValueError(f"unknown entry {tr['entry']!r}")


def _programs(gen, mesh, track: bool):
    """The population, made on the device in one call, and the writers'
    step, which rewrites the writer rows in place (one scatter per
    device block)."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from crdt_tpu.ops.orswot import OrswotState
    from crdt_tpu.parallel.mesh import ELEMENT_AXIS, REPLICA_AXIS, orswot_specs

    r, a = gen.r, gen.a
    n_dev = mesh.shape[REPLICA_AXIS]
    local = r // n_dev
    specs = orswot_specs()
    dspec = P(REPLICA_AXIS, ELEMENT_AXIS)
    fspec = P(REPLICA_AXIS, ELEMENT_AXIS, None)
    shard = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                         is_leaf=lambda x: isinstance(x, P))

    # The δ ring reads the writes since the sync; a fold does not, so its
    # cell keeps no tracking planes (an empty element axis).
    te = gen.e if track else 0

    def make():
        base = gen.base()
        rows = gen.block(base, 0, r, 0)
        touched = jnp.zeros((r, gen.e), bool).at[jnp.asarray(gen.writers)].set(
            gen.touched(0))[:, :te]
        # The δ context of a changed row: every dot it held or holds.
        fctx = jnp.where(touched[..., None],
                         jnp.maximum(rows.ctr, base.ctr[None])[:, :te], 0)
        return OrswotState(*rows), touched, fctx, base

    make = jax.jit(make, out_shardings=(
        shard, NamedSharding(mesh, dspec), NamedSharding(mesh, fspec), None))

    def write_local(top, ctr, dirty, fctx, base, k):
        w = gen.writer_rows(base, k)
        touched = gen.touched(k)[:, :te]
        d = lax.axis_index(REPLICA_AXIS)
        g = jnp.asarray(gen.writers)
        idx = jnp.where(g // local == d, g % local, local)  # `local` drops
        top = top.at[idx].set(w.top, mode="drop")
        ctr = ctr.at[idx].set(w.ctr, mode="drop")
        dirty = dirty.at[idx].set(touched, mode="drop")
        fctx = fctx.at[idx].set(jnp.where(
            touched[..., None], jnp.maximum(w.ctr, base.ctr[None])[:, :te], 0),
            mode="drop")
        return top, ctr, dirty, fctx

    rep = P()
    local_write = jax.shard_map(
        write_local, mesh=mesh,
        in_specs=(specs.top, specs.ctr, dspec, fspec, rep, rep),
        out_specs=(specs.top, specs.ctr, dspec, fspec), check_vma=False)

    def _write(pop, dirty, fctx, base, k):
        top, ctr, dirty, fctx = local_write(pop.top, pop.ctr, dirty, fctx, base, k)
        return pop._replace(top=top, ctr=ctr), dirty, fctx

    return make, jax.jit(_write, donate_argnums=(0, 1, 2))


def run(ctx) -> dict:
    import jax
    import jax.numpy as jnp

    from crdt_tpu.parallel import make_mesh

    cfg, tr, seed = ctx.config, ctx.traffic, ctx.seed
    n_dev = ctx.chips
    mesh = make_mesh(n_dev, 1)
    gen = Population(seed, cfg["replicas"] * n_dev, cfg["elems"], cfg["actors"],
                     cfg["deferred_cap"], cfg["counter_max"], tr["writer_elems"])
    entry = _entry(ctx, mesh)
    with ctx.phase("build_s"):
        make, write = _programs(gen, mesh, tr["entry"] == "mesh_delta_gossip")
    with ctx.phase("population_s"):
        pop, dirty, fctx, base = make()
        jax.block_until_ready(pop)
    with ctx.phase("warmup_s"):
        for k in range(2):  # the first compiles, the second must not
            pop, dirty, fctx = write(pop, dirty, fctx, base, jnp.int32(k))
            out = entry(pop, dirty, fctx)
            jax.block_until_ready(out)

    rng = np.random.default_rng([seed, 6])
    check_at = set(int(x) for x in rng.choice(
        np.arange(2, tr["check_span"]), tr["check_rounds"], replace=False))
    kept, flags, round_s = {}, [], []
    spans = ctx.spans
    c0 = ctx.compiles.snap()
    k = 2
    with ctx.window() as w:
        end = w.t0 + ctx.seconds
        while time.perf_counter() < end:
            t0 = time.perf_counter()
            with spans.span("span.write"):
                pop, dirty, fctx = write(pop, dirty, fctx, base, jnp.int32(k))
            with spans.span("span.converge"):
                out = entry(pop, dirty, fctx)
                jax.block_until_ready(out)
            round_s.append(time.perf_counter() - t0)
            flags.append(out[1:])
            if k in check_at:
                kept[k] = out[0]
            last = (k, out[0])
            k += 1
    window_s = w.seconds
    c1 = ctx.compiles.snap()
    peak = ctx.memory_peak()
    kept[last[0]] = last[1]
    rounds = len(round_s)
    bad_rounds = sum(bool(of) or int(res) > 0 for of, res in jax.device_get(flags))
    del pop, dirty, fctx, out, last
    out = {
        "e2e": {
            "merges_per_s": gen.r * rounds / window_s,
            "converge_p95_ms": float(np.percentile(round_s, 95)) * 1e3,
        },
        "attempted": rounds,
        "failed": bad_rounds,
        "window_s": window_s,
        "memory_peak_bytes": peak,
        "counters": {
            "rounds": rounds, "rows": gen.r, "rows_per_device": gen.r // n_dev,
            "elems": gen.e, "actors": gen.a, "deferred_cap": gen.d,
            "devices": n_dev, "ring_rounds": tr.get("ring_rounds", 0),
            "window_compiles": c1["compiles"] - c0["compiles"],
            "window_cache_misses": c1["cache_misses"] - c0["cache_misses"],
        },
        "samples": {"round_s": round_s},
    }
    out["checks"] = _check(ctx, gen, base, kept, bad_rounds)
    return out


def _check(ctx, gen, base, kept, bad_rounds) -> list:
    """Every kept round's converged state on every device against the
    plain reference fold of the same rows, regenerated from the seed."""
    import jax
    import jax.numpy as jnp

    dev0 = jax.devices()[0]
    blk = ctx.traffic["ref_block_rows"]
    block = jax.jit(lambda lo, k, b: gen.block(b, lo, blk, k))
    fold = jax.jit(lambda t, c: ref.fold_rows(t, c))
    join = jax.jit(ref.join)
    base = jax.device_put(base, dev0)

    @jax.jit
    def off(states, top, ctr):
        """Words in which each device's state differs from the reference
        (parked removes: the reference holds none)."""
        return (jnp.sum(states.top != top[None]) + jnp.sum(states.ctr != ctr[None])
                + jnp.sum(states.dcl != 0) + jnp.sum(states.dmask)
                + jnp.sum(states.dvalid))

    words = 0
    for k, states in sorted(kept.items()):
        acc = None
        for lo in range(0, gen.r, blk):
            rows = block(jnp.int32(lo), jnp.int32(k), base)
            part = fold(rows.top, rows.ctr)
            acc = part if acc is None else join(acc, part)
        if ctx.control:   # the control takes the program's place
            words += _control(gen, base, k, block, blk, acc)
        else:
            states = jax.device_put(states, dev0)
            words += int(off(states, acc[0], acc[1]))
    checks = [
        ("state_words_off", words, 0),
        ("rounds_overflow_or_residue", bad_rounds, 0),
        ("rounds_checked", len(kept), None),
    ]
    return checks


def _control(gen, base, k, block, blk, want) -> int:
    """The reference with the observed-remove rule dropped: dots merged by
    their maximum, as a grow-only set would (about half the compares of
    the ORSWOT merge), so a removed element comes back."""
    import jax
    import jax.numpy as jnp

    grow = jax.jit(lambda t, c: (t.max(axis=0), c.max(axis=0)))
    acc = None
    for lo in range(0, gen.r, blk):
        rows = block(jnp.int32(lo), jnp.int32(k), base)
        part = grow(rows.top, rows.ctr)
        acc = part if acc is None else (jnp.maximum(acc[0], part[0]),
                                        jnp.maximum(acc[1], part[1]))
    return int(jnp.sum(acc[0] != want[0]) + jnp.sum(acc[1] != want[1]))
