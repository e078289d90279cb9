"""Measure the dense↔sparse ORSWOT crossover (SURVEY §7.3).

For a fixed live-dot budget C, the dense join costs O(E·A) HBM traffic
regardless of sparsity while the segment join costs O(C log² C) sort
work — so there is an element-universe size E* past which sparse wins.
This tool times both joins over a sweep of E at constant C — as
chip-side MARGINAL per-join cost (a fori_loop chain of n joins in one
dispatch, t(2n) − t(n), so the fixed dispatch overhead cancels) — and
prints the measured crossover:

    python tools/sparse_crossover.py              # on the TPU
    JAX_PLATFORMS=cpu python tools/sparse_crossover.py --cpu   # scaled

Synthetic states: R=2 replicas, C live dots each scattered uniformly
over E elements in disjoint actor lanes — the worst case for survival
masking: ALL 2C dots survive the join, so the sparse dot capacity is
sized 2C (lossless; the overflow flag is asserted clear)."""

from __future__ import annotations

import argparse
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def _marginal(join1, xa, xb, n: int | None = None, iters: int = 5) -> float:
    """Chip-side marginal per-join time via the K-vs-2K method: a
    ``fori_loop`` chain of ``n`` joins runs in ONE
    dispatch, so the fixed per-dispatch overhead cancels in
    ``t(2n) − t(n)``. The trip count is a traced operand, so both
    lengths share one compile. The n- and 2n-timings interleave within
    one loop so slow host drift cancels too,
    and a non-positive marginal falls back to the conservative
    ``t(2n)/2n`` bound instead of letting jitter fabricate a 0-ms
    winner."""
    import jax
    import numpy as np
    from jax import lax

    if n is None:
        # The chain amortises the dispatch overhead; keep the CPU
        # sweep quick.
        n = 4 if jax.default_backend() == "cpu" else 32

    @jax.jit
    def chain(x, y, k):
        return lax.fori_loop(0, k, lambda i, s: join1(s, y), x)

    def once(k):
        out = chain(xa, xb, k)
        # Scalar device->host fetch: completes only once the work has.
        return np.asarray(jax.tree_util.tree_leaves(out)[0].ravel()[0])

    once(n)
    once(2 * n)  # shared compile + warm both trip counts

    t1s, t2s = [], []
    for _ in range(iters):
        t0 = time.perf_counter()
        once(n)
        t1s.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        once(2 * n)
        t2s.append(time.perf_counter() - t0)
    t1 = sorted(t1s)[len(t1s) // 2]
    t2 = sorted(t2s)[len(t2s) // 2]
    dt = t2 - t1
    if dt <= 0:
        print(
            f"  WARNING: non-positive marginal (T(n)={t1*1e3:.1f} ms, "
            f"T(2n)={t2*1e3:.1f} ms); using conservative T(2n)/2n"
        )
        dt = t2 / 2
    return dt / n


def run(sweep=None, dots: int = 4096, actors: int = 8) -> str:
    """Run the sweep in the CURRENT process/backend. Returns the
    summary line (also printed)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from crdt_tpu.ops import orswot as dense_ops
    from crdt_tpu.ops import sparse_orswot as sp

    if sweep is None:
        sweep = [1 << p for p in range(14, 24)]  # 16k .. 8M

    c, a = dots, actors
    cap = 2 * c  # every dot of both replicas survives (disjoint lanes)
    rng = np.random.default_rng(0)
    print(
        f"backend={jax.default_backend()}  C={c} live dots/replica, "
        f"A={a} actors; dense bytes = 4*E*A per replica, sparse = "
        f"{sp.nbytes(sp.empty(cap, a)):,} fixed (cap {cap})"
    )
    crossover = None
    for e in sweep:
        ctr = np.zeros((2, e, a), np.uint32)
        for r in range(2):
            cells = rng.choice(e, size=c, replace=False)
            lanes = rng.integers(0, a // 2, c) + r * (a // 2)
            ctr[r, cells, lanes] = rng.integers(1, 50, c)
        top = ctr.max(axis=1)
        dense = dense_ops.empty(e, a, deferred_cap=4, batch=(2,))
        dense = dense._replace(top=jnp.asarray(top), ctr=jnp.asarray(ctr))
        da = jax.tree.map(lambda x: x[0], dense)
        db = jax.tree.map(lambda x: x[1], dense)
        t_dense = _marginal(lambda x, y: dense_ops.join(x, y)[0], da, db)

        spstate = sp.from_dense(dense, cap, rm_width=8)
        sa = jax.tree.map(lambda x: x[0], spstate)
        sb = jax.tree.map(lambda x: x[1], spstate)
        joined, of = sp.join(sa, sb)
        assert not bool(jnp.any(of)), "sparse join overflowed — sweep is lossy"
        assert int(joined.valid.sum()) == 2 * c, "survivor count wrong"
        t_sparse = _marginal(lambda x, y: sp.join(x, y)[0], sa, sb)

        flag = "sparse" if t_sparse < t_dense else "dense"
        if crossover is None and t_sparse < t_dense:
            crossover = e
        print(
            f"E={e:>9,}: dense {t_dense*1e3:8.3f} ms/join "
            f"({4*e*a/1e6:8.1f} MB/replica) | sparse {t_sparse*1e3:8.3f} ms/join "
            f"-> {flag}"
        )
    if crossover:
        line = (
            f"crossover: sparse join wins from E ≈ {crossover:,} "
            f"(at {c} live dots, lossless cap {cap})"
        )
    else:
        line = "no crossover within the sweep (dense won throughout)"
    print(line)
    return line


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cpu", action="store_true", help="pin CPU + scaled sweep")
    ap.add_argument("--dots", type=int, default=4096, help="live dots per replica")
    ap.add_argument("--actors", type=int, default=8)
    args = ap.parse_args()

    sweep = None
    if args.cpu:
        from crdt_tpu.utils.cpu_pin import pin_cpu

        pin_cpu()
        sweep = [1 << p for p in range(12, 21)]  # 4k .. 1M
    run(sweep=sweep, dots=args.dots, actors=args.actors)


if __name__ == "__main__":
    main()
