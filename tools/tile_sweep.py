"""On-chip autotune sweep for the fused fold's (tile_e, r_chunk) grid.

The r3 sweep fixed the VMEM block budget at 1 MiB and the default
tile_e at 512 (ops/pallas_kernels.py `_VMEM_BLOCK_BUDGET`). This tool
re-measures the neighborhood on the real toolchain at a config-3-like
stream shape so the defaults are evidence, not folklore:

    python tools/tile_sweep.py                # sweep, print a ranked table
    python tools/tile_sweep.py --write-table  # sweep AND commit the winner
                                              # into tools/tile_table.json

For each candidate it times K and then 2K back-to-back dispatches of
the same resident chunk and takes the difference, the time of K folds
independent of fixed start-up costs, and reports achieved GB/s. Combos
that fail Mosaic compilation are reported as such and skipped — that
is data too (the 4 MiB block failure is recorded in the kernel's
module docstring). Run it on the chip in one process (libtpu is
process-exclusive).

``--write-table`` closes the loop that made sweep results write-only:
the best measured (tile_e, r_chunk) for this shape's actor count is
merged into the committed ``tools/tile_table.json``, which
``ops/pallas_kernels._pick_r_chunk`` consults before its VMEM-budget
heuristic — so a committed sweep changes the production default, with
provenance (GB/s, shape, UTC timestamp) riding each entry.
"""

from __future__ import annotations

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

# Modest default shape: big enough to be bandwidth-bound, small enough
# that a full sweep fits one chip call. Override via env.
R = int(os.environ.get("SWEEP_REPLICAS", 2048))
E = int(os.environ.get("SWEEP_ELEMS", 32768))
PASSES = int(os.environ.get("SWEEP_PASSES", 4))  # K: folds per timed run


def make_chunk_on_device(r, e, a=8, deferred_cap=4):
    """``r`` replicas of ``e`` elements, made in device memory under jit
    (a multi-GB push from the host would time the host link instead):
    ~70% of (element, actor) dots present, each replica's top covering
    its dots."""
    import jax
    import jax.numpy as jnp

    from crdt_tpu.ops import orswot as ops

    def make(key):
        k1, k2, k3 = jax.random.split(key, 3)
        ctr = jax.random.randint(k1, (r, e, a), 0, 100, dtype=jnp.uint32)
        keep = jax.random.randint(k2, (r, e, a), 0, 10, dtype=jnp.uint32) >= 3
        ctr = jnp.where(keep, ctr, 0)
        extra = jax.random.randint(k3, (r, a), 0, 100, dtype=jnp.uint32)
        top = jnp.maximum(ctr.max(axis=1), extra)
        return ops.empty(e, a, deferred_cap=deferred_cap, batch=(r,))._replace(
            top=top, ctr=ctr
        )

    return jax.jit(make)(jax.random.key(42))


def main() -> int:
    import jax

    if jax.devices()[0].platform != "tpu":
        print("FAIL: no TPU backend")
        return 1
    if "--wire" in sys.argv[1:]:
        return sweep_wire()

    import numpy as np

    from crdt_tpu.ops.pallas_kernels import fold_fused

    chunk = make_chunk_on_device(R, E)
    a = chunk.ctr.shape[-1]
    nbytes = chunk.ctr.nbytes + chunk.top.nbytes

    def measure(tile_e: int, r_chunk: int):
        # Warm/compile, correctness vs the default config, then the
        # marginal timing: (2K dispatches) - (K dispatches) of the
        # resident chunk isolates the time of K folds.
        out, _ = fold_fused(chunk, tile_e=tile_e, r_chunk=r_chunk)
        jax.block_until_ready(out.ctr)

        def run(n):
            for _ in range(n):
                o, _ = fold_fused(chunk, tile_e=tile_e, r_chunk=r_chunk)
            jax.block_until_ready(o.ctr)

        ts = []
        for _ in range(3):
            t0 = time.perf_counter()
            run(PASSES)
            t1 = time.perf_counter()
            run(2 * PASSES)
            t2 = time.perf_counter()
            ts.append((t2 - t1) - (t1 - t0))
        dt = sorted(ts)[1]
        gbps = nbytes * PASSES / dt / 1e9
        mps = (PASSES * R) / dt
        return out, gbps, mps

    from crdt_tpu.ops.pallas_kernels import _pick_r_chunk

    rows = []
    # The shipped default first — it is the bit-identity reference for
    # every other combo AND the "vs default" anchor of the ranking.
    cands = [(512, _pick_r_chunk(R, a, 512, None))]
    for tile_e in (256, 512, 1024, 2048):
        for budget_blocks in (0.5, 1, 2):
            rc = max(8, int(budget_blocks * 1024 * 1024) // (a * tile_e * 4))
            rc = 1 << (rc.bit_length() - 1)
            cands.append((tile_e, rc))
    baseline = None
    seen = set()
    for tile_e, rc in cands:
        if (tile_e, rc) in seen:
            continue
        seen.add((tile_e, rc))
        try:
            out, gbps, mps = measure(tile_e, rc)
        except Exception as e:  # Mosaic rejection or OOM — data, not noise
            msg = str(e).splitlines()[0][:100]
            rows.append((tile_e, rc, None, None, msg))
            print(f"tile_e={tile_e:<5} r_chunk={rc:<4} FAILED: {msg}")
            continue
        if baseline is None:
            baseline = out
        else:
            for x, y in zip(jax.tree.leaves(baseline), jax.tree.leaves(out)):
                np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
        rows.append((tile_e, rc, gbps, mps, ""))
        print(
            f"tile_e={tile_e:<5} r_chunk={rc:<4} {gbps:7.1f} GB/s "
            f"{mps:12,.0f} merges/s"
        )

    ok = [r for r in rows if r[2] is not None]
    if not ok:
        print("FAIL: no candidate compiled")
        return 1
    best = max(ok, key=lambda r: r[2])
    print(
        f"BEST: tile_e={best[0]} r_chunk={best[1]} {best[2]:.1f} GB/s "
        f"(all results bit-identical)"
    )
    if "--write-table" in sys.argv[1:]:
        path = write_table(a, best, shape=f"{R}x{E}x{a}")
        print(f"committed tile_e={best[0]} r_chunk={best[1]} -> {path}")
    return 0


def sweep_wire() -> int:
    """Sweep the fused WIRE kernel's row chunk (ops/wire_kernels.py)
    at a δ-ring packet shape: one fused pack pass per
    candidate, correctness pinned bit-identical against the default
    chunk, marginal timing over repeated packs. ``--write-table``
    commits the winner under ``family: "wire"`` — the fold family's
    entries are untouched (``_pick_r_chunk`` keys on family)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from crdt_tpu.ops import wire_kernels as wk
    from crdt_tpu.ops.pallas_kernels import _pick_r_chunk

    c = int(os.environ.get("SWEEP_WIRE_SLOTS", 1024))
    a = int(os.environ.get("SWEEP_WIRE_ACTORS", 8))
    lc = 2 * a
    spec = wk.WireLaneSpec(lc=lc, ctx_lo=a, ctx_hi=lc, gated=True)
    rng = np.random.RandomState(0)
    rows = jnp.asarray(rng.randint(0, 50, (c, a)), jnp.uint32)
    ctxs = rows + jnp.asarray(rng.randint(0, 2, (c, a)), jnp.uint32)
    clocks = jnp.concatenate([rows, ctxs], axis=-1)
    base = jnp.zeros_like(clocks)
    valid = jnp.asarray(rng.rand(c) > 0.2)
    dig = jnp.full((c, a), 100, jnp.uint32)

    def run(rc):
        import crdt_tpu.ops.pallas_kernels as pk

        # Pin the candidate by pre-seeding the family lookup: pass the
        # chunk through a one-entry in-memory table override.
        old = pk._TILE_TABLE
        pk._TILE_TABLE = {"entries": [
            {"family": "wire", "a": a, "tile_e": lc, "r_chunk": rc}
        ]}
        try:
            out = wk.wire_pack(
                spec, clocks, base, valid, know=rows, dig=dig,
                interpret=False,
            )
            jax.block_until_ready(out.words)
            return out
        finally:
            pk._TILE_TABLE = old

    default_rc = _pick_r_chunk(c, a, lc, None, family="wire")
    baseline = None
    results = []
    for rc in sorted({default_rc, 64, 128, 256, 512, 1024}):
        rc = min(rc, c)
        try:
            out = run(rc)  # compile + correctness
            ts = []
            for _ in range(5):
                t0 = time.perf_counter()
                for _ in range(8):
                    jax.block_until_ready(run(rc).words)
                ts.append(time.perf_counter() - t0)
            dt = sorted(ts)[1] / 8
        except Exception as e:
            print(f"r_chunk={rc:<5} FAILED: {str(e).splitlines()[0][:90]}")
            continue
        if baseline is None:
            baseline = out
        else:
            for x, y in zip(baseline, out):
                np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
        gbps = clocks.nbytes / dt / 1e9
        results.append((lc, rc, gbps))
        print(f"r_chunk={rc:<5} {gbps:7.1f} GB/s ({dt * 1e6:.1f} us/pack)")
    if not results:
        print("FAIL: no wire candidate compiled")
        return 1
    best = max(results, key=lambda r: r[2])
    print(f"BEST: r_chunk={best[1]} {best[2]:.1f} GB/s "
          f"(all results bit-identical)")
    if "--write-table" in sys.argv[1:]:
        path = write_table(a, best, shape=f"{c}x{lc}", family="wire")
        print(f"committed wire r_chunk={best[1]} -> {path}")
    return 0


TABLE_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "tile_table.json")


def write_table(a: int, best, shape: str, path: str = TABLE_PATH,
                family: str = "fold") -> str:
    """Merge the winning (tile_e, r_chunk) for actor count ``a`` into
    the committed autotune table — keyed by (kernel FAMILY, a, tile_e),
    so a fused-wire sweep (``--wire``) can never clobber or be reused
    by a fold-family entry (``_pick_r_chunk`` matches families; a
    pre-wire entry with no ``family`` field reads as "fold"). A re-run
    replaces its own previous measurement. Provenance (GB/s, shape,
    UTC timestamp) rides each entry so a stale override is auditable."""
    try:
        with open(path) as f:
            table = json.load(f)
    except (OSError, ValueError):
        table = {"version": 1, "entries": []}
    entries = [
        e for e in table.get("entries", [])
        if not (e.get("family", "fold") == family
                and e.get("a") == a and e.get("tile_e") == best[0])
    ]
    entries.append({
        "family": family,
        "a": a,
        "tile_e": best[0],
        "r_chunk": best[1],
        "gbps": round(best[2], 1),
        "shape": shape,
        "swept_utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
    })
    table["entries"] = sorted(
        entries,
        key=lambda e: (e.get("family", "fold"), e.get("a", 0),
                       e.get("tile_e", 0)),
    )
    table.setdefault("version", 1)
    with open(path, "w") as f:
        json.dump(table, f, indent=2)
        f.write("\n")
    return path


if __name__ == "__main__":
    sys.exit(main())
