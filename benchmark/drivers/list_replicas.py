"""Driver for per-replica op delivery to a fleet of ``List`` replicas of
one document: the edit trace is minted once in set-up, and each round
every replica applies its next chunk of the device op log through
``BatchedList.apply_chunks``, some of them lagging a few rounds behind.

A replay types the document from empty to the end of the trace: round
``t`` gives replica ``r`` chunk ``t - lag[r]``, or nothing where that is
outside the log, so the replay's last ``max_lag`` rounds drain the
lagging replicas. Then every replica is reset to empty, inside the next
round, and the next replay starts."""

from __future__ import annotations

import time

import numpy as np

from gen import edit_trace
from reference import list_index as ref

COUNTED = ("list.lanes", "list.lanes_live")


def lags(seed: int, replicas: int, sites: int, lagged: int, max_lag: int):
    """Each replica's lag in rounds, fixed for the run: the writer sites'
    rows (evenly spaced) and most others 0; ``lagged`` of the others
    drawn uniformly from 1..``max_lag``."""
    rng = np.random.default_rng([seed, 7])
    writers = np.arange(sites) * (replicas // sites)
    others = np.setdiff1d(np.arange(replicas), writers)
    lag = np.zeros(replicas, np.int64)
    lag[rng.choice(others, lagged, replace=False)] = rng.integers(1, max_lag + 1, lagged)
    return lag


def schedule(lag, n_chunks: int, max_lag: int):
    """``[n_chunks + max_lag, R]`` int32: the chunk each replica applies
    in each round of a replay, -1 for none."""
    ids = np.arange(n_chunks + max_lag)[:, None] - lag[None, :]
    return np.where((ids >= 0) & (ids < n_chunks), ids, -1).astype(np.int32)


def run(ctx) -> dict:
    import jax
    import jax.numpy as jnp

    from crdt_tpu.models import BatchedList
    from crdt_tpu.parallel import make_mesh
    from crdt_tpu.utils.metrics import metrics

    cfg, tr, seed = ctx.config, ctx.traffic, ctx.seed
    r, width, sites = cfg["replicas"], tr["chunk"], cfg["writer_sites"]
    with ctx.phase("trace_s"):
        trace = edit_trace.generate(seed, cfg["trace_ops"], cfg["trace_inserts"], cfg["jump_p"],
                                    cfg["delete_run_mean"], sites, width // sites)
    with ctx.phase("mint_s"):
        model = BatchedList(r, chunk_width=width)
        model.place(make_mesh(ctx.chips, 1))
        model.extend_trace(*trace)
        jax.block_until_ready((model.vals, model.alive))
    lag = lags(seed, r, sites, tr["lagged_replicas"], tr["max_lag"])
    sched = schedule(lag, model.n_chunks, tr["max_lag"])
    period = len(sched)
    rows_on = (sched >= 0).sum(axis=1)
    chunk_reads = [len(np.unique(s[s >= 0])) for s in sched]

    # The state is the one buffer: the reset empties it in place, where
    # the model placed it (times a zero the compiler cannot fold, so the
    # donated state is the output and no second copy is made).
    zero = jnp.int32(0)
    reset = jax.jit(lambda v, a, k: (v * k, a & (k != 0)), donate_argnums=(0, 1),
                    out_shardings=(model.vals.sharding, model.alive.sharding))
    with ctx.phase("warmup_s"):
        for t in range(2):  # the first compiles, the second must not
            model.vals, model.alive = reset(model.vals, model.alive, zero)
            model.apply_chunks(sched[t])
        model.vals, model.alive = reset(model.vals, model.alive, zero)
        jax.block_until_ready((model.vals, model.alive))

    spans = ctx.spans
    c0, k0 = ctx.compiles.snap(), metrics.snapshot()["counters"]
    round_s, applied, reads, replays, t = [], 0, 0, 0, 0
    with ctx.window() as w:
        end = w.t0 + ctx.seconds
        while time.perf_counter() < end:
            t0 = time.perf_counter()
            if t == period:
                with spans.span("span.reset"):
                    model.vals, model.alive = reset(model.vals, model.alive, zero)
                t, replays = 0, replays + 1
            with spans.span("span.apply"):
                model.apply_chunks(sched[t])
            round_s.append(time.perf_counter() - t0)
            applied += int(rows_on[t])
            reads += chunk_reads[t]
            t += 1
    window_s = w.seconds
    c1, k1 = ctx.compiles.snap(), metrics.snapshot()["counters"]
    peak = ctx.memory_peak()
    lanes = {name: k1.get(name, 0) - k0.get(name, 0) for name in COUNTED}
    # Trace ops each replica has applied at the end of the window.
    done = np.clip(t - lag, 0, model.n_chunks)
    watermark = np.minimum(done * width, cfg["trace_ops"])
    out = {
        "e2e": {
            "merges_per_s": applied / window_s,
            "converge_p95_ms": float(np.percentile(round_s, 95)) * 1e3,
        },
        "attempted": applied,
        "failed": 0,
        "window_s": window_s,
        "memory_peak_bytes": peak,
        "xplane": w.xplane() if ctx.traced else None,
        "counters": {
            "rounds": len(round_s), "replays": replays, "applications": applied,
            "rows": r, "chunk_width": width, "chunks": model.n_chunks,
            "universe": len(model.slots), "chunk_reads": reads,
            "lanes": lanes["list.lanes"], "lanes_live": lanes["list.lanes_live"],
            "window_compiles": c1["compiles"] - c0["compiles"],
            "window_cache_misses": c1["cache_misses"] - c0["cache_misses"],
        },
        "samples": {"round_s": round_s},
    }
    out["checks"] = _check(ctx, model, trace, watermark)
    return out


def _check(ctx, model, trace, watermark) -> list:
    """Every replica's read (its values where alive, in slot order)
    against the index replay of the trace prefix it has applied. One
    replica of each watermark is read on the host and compared with the
    replay's text; every other replica is compared with that one on the
    device, slot by slot, which a correct program matches exactly (the
    same prefix leaves the same slots alive with the same values)."""
    import jax
    import jax.numpy as jnp

    kinds, positions, values, _ = trace
    want = ref.texts_at(kinds, positions, values, watermark)

    @jax.jit
    def rows_off(vals, alive, rep, members):
        off = (alive != alive[rep]) | (alive & (vals != vals[rep]))
        return jnp.sum(members.reshape((-1,) + (1,) * (off.ndim - 1)) & off)

    if ctx.control:   # the control takes the program's place
        got = ref.texts_at(kinds, positions, values, watermark, drop_deletes=True)
        words = sum(int(n) * ref.words_off(got[int(w)], want[int(w)])
                    for w, n in zip(*np.unique(watermark, return_counts=True)))
    else:
        words = 0
        for w in np.unique(watermark):
            members = watermark == w
            rep = int(np.argmax(members))
            words += ref.words_off(model.read(rep), want[int(w)])
            words += int(rows_off(model.vals, model.alive, jnp.int32(rep),
                                  jnp.asarray(members)))
    return [
        ("state_words_off", words, 0),
        ("rows_checked", len(watermark), None),
        ("watermarks", len(np.unique(watermark)), None),
    ]
