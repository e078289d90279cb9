"""Driver for ``Map<K, MVReg>`` anti-entropy rounds over a resident replica
population: each round, the writers' step, then ``mesh_fold_map`` until
the converged state is ready."""

from __future__ import annotations

import time

import numpy as np

from gen.map_replicas import Population, Rows
from reference import map_mvreg as ref


def _state(rows):
    """The generator's flat leaves as the program's ``MapState``."""
    from crdt_tpu.ops.map import MapState
    from crdt_tpu.ops.mvreg import MVRegState

    return MapState(top=rows.top, child=MVRegState(
        wact=rows.wact, wctr=rows.wctr, clk=rows.clk, val=rows.val, valid=rows.valid),
        dcl=rows.dcl, dkeys=rows.dkeys, dvalid=rows.dvalid)


def _programs(gen, mesh):
    """The population, made on the device in one call, and the writers'
    step, which rewrites the writer rows in place."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding

    from crdt_tpu.parallel.mesh import map_specs

    shard = jax.tree.map(lambda s: NamedSharding(mesh, s), map_specs())
    idx = jnp.asarray(gen.writers)

    def write(pop, base, k):
        w = _state(gen.writer_rows(base, k))
        return jax.tree.map(lambda x, y: x.at[idx].set(y), pop, w)

    def make():
        # Every row the base, then round 0's writer rows in place: the
        # population is the one buffer the program returns.
        base = gen.base()
        pop = jax.tree.map(lambda x: jnp.broadcast_to(x, (gen.r,) + x.shape), _state(base))
        return write(pop, base, 0), base

    return (jax.jit(make, out_shardings=(shard, None)),
            jax.jit(write, donate_argnums=(0,), out_shardings=shard))


def run(ctx) -> dict:
    import jax
    import jax.numpy as jnp

    from crdt_tpu.parallel import make_mesh
    from crdt_tpu.parallel.anti_entropy import mesh_fold_map

    cfg, tr, seed = ctx.config, ctx.traffic, ctx.seed
    n_dev = ctx.chips
    mesh = make_mesh(n_dev, 1)
    gen = Population(seed, cfg["replicas"] * n_dev, cfg["keys"], cfg["actors"],
                     cfg["sibling_cap"], cfg["deferred_cap"], cfg["counter_max"],
                     tr["writer_puts"], tr["writer_removes"])
    with ctx.phase("build_s"):
        make, write = _programs(gen, mesh)
    with ctx.phase("population_s"):
        pop, base = make()
        jax.block_until_ready(pop)
    with ctx.phase("warmup_s"):
        for k in range(2):  # the first compiles, the second must not
            pop = write(pop, base, jnp.int32(k))
            out = mesh_fold_map(pop, mesh)
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
                pop = write(pop, base, jnp.int32(k))
            with spans.span("span.converge"):
                out = mesh_fold_map(pop, mesh)
                jax.block_until_ready(out)
            round_s.append(time.perf_counter() - t0)
            flags.append(out[1])
            if k in check_at:
                kept[k] = out[0]
            last = (k, out[0])
            k += 1
    window_s = w.seconds
    c1 = ctx.compiles.snap()
    peak = ctx.memory_peak()
    kept[last[0]] = last[1]
    rounds = len(round_s)
    bad_rounds = sum(bool(np.any(of)) for of in jax.device_get(flags))
    del pop, out, last
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
            "keys": gen.k, "actors": gen.a, "sibling_cap": gen.s, "deferred_cap": gen.d,
            "devices": n_dev,
            "window_compiles": c1["compiles"] - c0["compiles"],
            "window_cache_misses": c1["cache_misses"] - c0["cache_misses"],
        },
        "samples": {"round_s": round_s},
    }
    out["checks"] = _check(ctx, gen, base, kept, bad_rounds)
    return out


def _check(ctx, gen, base, kept, bad_rounds) -> list:
    """Every kept round's converged state against the plain reference
    fold of the same rows, regenerated from the seed in blocks."""
    import jax
    import jax.numpy as jnp

    dev0 = jax.devices()[0]
    blk = ctx.traffic["ref_block_rows"]
    block = jax.jit(lambda lo, k, b: gen.block(b, lo, blk, k))
    content = lambda rows: (rows.wact, rows.wctr, rows.clk, rows.val, rows.valid)  # noqa: E731
    fold = jax.jit(lambda rows: ref.fold_rows(rows.top, content(rows)))
    join = jax.jit(ref.join)
    base = jax.device_put(base, dev0)

    @jax.jit
    def off(state, want):
        """Words in which the program's state differs from the reference
        (parked removes: the reference holds none)."""
        top, cols = want
        got = (state.child.wact, state.child.wctr, state.child.clk, state.child.val,
               state.child.valid)
        return (jnp.sum(state.top != top) + sum(jnp.sum(g != w) for g, w in zip(got, cols))
                + jnp.sum(state.dcl != 0) + jnp.sum(state.dkeys) + jnp.sum(state.dvalid))

    words, over = 0, 0
    for k, state in sorted(kept.items()):
        acc = None
        for lo in range(0, gen.r, blk):
            rows = block(jnp.int32(lo), jnp.int32(k), base)
            part, o = fold(rows)
            over += int(o)
            if acc is not None:
                part, o = join(acc, part)
                over += int(o)
            acc = part
        if ctx.control:   # the control takes the program's place
            words += _control(gen, base, k, block, blk, acc)
        else:
            words += int(off(jax.device_put(state, dev0), acc))
    return [
        ("state_words_off", words, 0),
        ("rounds_overflow", bad_rounds, 0),
        ("reference_keys_overflow", over, 0),
        ("rounds_checked", len(kept), None),
    ]


def _control(gen, base, k, block, blk, want) -> int:
    """The reference with the observed-remove rule dropped: per (key,
    actor), the largest counter any replica holds, as a grow-only merge
    would keep it, so a removed key comes back. Compared on the dots."""
    import jax
    import jax.numpy as jnp

    ids = jnp.arange(gen.a, dtype=jnp.int32)

    @jax.jit
    def most(rows: Rows):
        own = (rows.wact[..., None] == ids) & rows.valid[..., None]         # [n, K, S, A]
        return jnp.max(jnp.where(own, rows.wctr[..., None], 0), axis=(0, 2))  # [K, A]

    ctr = None
    for lo in range(0, gen.r, blk):
        part = most(block(jnp.int32(lo), jnp.int32(k), base))
        ctr = part if ctr is None else jnp.maximum(ctr, part)

    @jax.jit
    def dots_off(ctr, want):
        live = ctr > 0
        order = jnp.argsort(~live, axis=-1, stable=True)[:, :gen.s]         # [K, S]
        valid = jnp.take_along_axis(live, order, axis=-1)
        act = jnp.where(valid, order, 0)
        c = jnp.where(valid, jnp.take_along_axis(ctr, order, axis=-1), 0)
        _, (w_act, w_ctr, _, _, w_valid) = want
        return jnp.sum(act != w_act) + jnp.sum(c != w_ctr) + jnp.sum(valid != w_valid)

    return int(dots_off(ctr, want))
