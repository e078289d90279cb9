"""Chip smoke: drive crdt_tpu's main paths once on the TPU, at the sizes
its deployments run, and check every result bit for bit.

    python chip_smoke.py              # one chip: fold, stream, serve
    python chip_smoke.py --chips 4    # four chips: mesh_fold, mesh_gossip

Phases (one JSON line each on stdout, then the verdict line):

- ``fold``: BASELINE config 3 — anti-entropy fold of 10,240 replicas x
  102,400 elements x 8 actors (33.5 GB of dot state), streamed as 20
  device-generated chunks of 512 replicas through the compiled
  ``fold_fused``. Checked against ``ops.orswot.fold`` on a slice and
  against the ``crdt_tpu.pure`` merge chain over all replicas on the
  first elements.
- ``stream``: the ``flagship`` entry of BENCH_CONFIGS.json (10,240
  replicas over a 1M-element universe) through
  ``mesh_stream_fold_sparse`` on a 1x1 mesh, gated by bench.py's
  ``_flagship_bit_identity`` and by block-size invariance at full size.
- ``serve``: the ``serve`` entry (1,048,576 tenants on 262,144 lanes):
  3 cycles of ingest -> ServeWal -> device apply with one evict ->
  restore, checked against the per-tenant sequential oracle; then
  fan-out pushes at the ``fanout`` entry's shape (4,096-lane dispatches
  of 256-element rows through ``cohort_wire_encode``), checked on
  sampled client replicas; and the compiled wire kernel against the
  Pallas interpreter at the fan-out's row count.
- ``--chips 4`` runs only ``mesh_fold`` and 4 rounds of
  ``mesh_delta_gossip`` (wire path) on a (4, 1) mesh over 2,048 x
  102,400 x 8, each compared with a single-device ``fold_fused`` of the
  same population, and prints every device's bytes.

Each phase line carries ``compile_s`` (XLA backend compile time,
persistent-cache loads included), ``run_s`` (wall time less compile),
the cache hits/misses, ``peak_bytes_in_use`` and ``bit_identical``.
The script runs in one process, never pins the CPU, and exits non-zero
when the device is not a TPU or any phase fails.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time
import traceback
from functools import partial

import numpy as np

# Pallas kernels compile for the chip. A CPU rehearsal flips this to run
# the same phases through the Pallas interpreter (tests/test_chip_smoke).
INTERPRET = False

CONFIG3 = dict(replicas=10240, elems=102400, actors=8, chunk=512,
               deferred_cap=4, slice_elems=8192, pure_elems=64)
MESH4 = dict(replicas=2048, elems=102400, actors=8, chunk=512,
             deferred_cap=4, writer_rows=32, rounds=4, cap=128)


def log(*a):
    print(*a, file=sys.stderr, flush=True)


# ---- phase accounting ------------------------------------------------------

class _Compiles:
    """Backend compile seconds and persistent-cache hits/misses, read from
    JAX's own monitoring events."""

    def __init__(self):
        import jax

        self.compile_s, self.hits, self.misses = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snap(self):
        return self.compile_s, self.hits, self.misses


def _device_bytes(dev) -> dict:
    stats = dev.memory_stats() or {}
    return {k: stats.get(k) for k in ("bytes_in_use", "peak_bytes_in_use")}


def run_phase(name: str, fn, compiles: _Compiles) -> bool:
    import jax

    c0, h0, m0 = compiles.snap()
    t0 = time.perf_counter()
    rec = {"phase": name}
    try:
        out = fn()
        rec.update(out)
        rec["ok"] = bool(out.get("bit_identical"))
    except Exception as exc:  # reported on the phase line; the run fails
        traceback.print_exc()
        rec.update(ok=False, error=repr(exc)[:500])
    wall = time.perf_counter() - t0
    c1, h1, m1 = compiles.snap()
    rec.update(
        compile_s=c1 - c0, run_s=wall - (c1 - c0), wall_s=wall,
        cache_hits=h1 - h0, cache_misses=m1 - m0,
        peak_bytes_in_use=_device_bytes(jax.devices()[0])["peak_bytes_in_use"],
    )
    print(json.dumps(rec), flush=True)
    return rec["ok"]


# ---- checks ----------------------------------------------------------------

def _same(x, y) -> bool:
    import jax

    lx, ly = jax.tree.leaves(x), jax.tree.leaves(y)
    return len(lx) == len(ly) and all(
        a.shape == b.shape and bool(np.array_equal(np.asarray(a), np.asarray(b)))
        for a, b in zip(lx, ly)
    )


def _pure_chain(tops: np.ndarray, ctrs: np.ndarray):
    """The pure (reference-semantics) Orswot merge chain over every row
    of ``tops [R, A]`` / ``ctrs [R, E, A]``, read back as dense planes."""
    from crdt_tpu.pure.orswot import Orswot
    from crdt_tpu.vclock import VClock

    oracle = Orswot()
    for top, ctr in zip(tops, ctrs):
        o = Orswot()
        o.clock = VClock({a: int(c) for a, c in enumerate(top) if c})
        for e in np.nonzero(ctr.any(axis=-1))[0]:
            o.entries[int(e)] = VClock(
                {a: int(c) for a, c in enumerate(ctr[e]) if c}
            )
        oracle.merge(o)
    assert not oracle.deferred
    top = np.zeros(tops.shape[-1], np.uint32)
    for a, c in oracle.clock.dots.items():
        top[a] = c
    ctr = np.zeros(ctrs.shape[1:], np.uint32)
    for e, clock in oracle.entries.items():
        for a, c in clock.dots.items():
            ctr[e, a] = c
    return top, ctr


# ---- one-chip phases -------------------------------------------------------

def phase_fold(cfg=CONFIG3) -> dict:
    """BASELINE config 3 streamed through the compiled fused fold."""
    import jax
    import jax.numpy as jnp

    from bench import dense_chunk
    from crdt_tpu.ops import orswot as ops
    from crdt_tpu.ops.pallas_kernels import fold_fused

    r, e, a, chunk = cfg["replicas"], cfg["elems"], cfg["actors"], cfg["chunk"]
    dcap, se, pe = cfg["deferred_cap"], cfg["slice_elems"], cfg["pure_elems"]
    n_chunks = -(-r // chunk)
    key = jax.random.key(3)

    def gen(i):
        return dense_chunk(jax.random.fold_in(key, i), chunk, e, a, dcap)

    @jax.jit
    def step(acc, i):
        part, of1 = fold_fused(gen(i), interpret=INTERPRET)
        acc, of2 = ops.join(acc, part)
        return acc, of1 | of2

    @jax.jit
    def head(i):
        """The rows the pure chain reads: tops and the first elements."""
        c = gen(i)
        return c.top, c.ctr[:, :pe]

    @jax.jit
    def slice_check(i):
        """Chunk i's fused fold against the log-tree fold on a slice of
        elements (the fold is element-parallel given the tops)."""
        c = gen(i)
        fused, _ = fold_fused(c, interpret=INTERPRET)
        sl = c._replace(ctr=c.ctr[:, :se], dmask=c.dmask[..., :se])
        tree, _ = ops.fold(sl)
        got = fused._replace(ctr=fused.ctr[:se], dmask=fused.dmask[..., :se])
        return jnp.stack([jnp.array_equal(x, y) for x, y in zip(got, tree)])

    acc = ops.empty(e, a, deferred_cap=dcap)
    step = step.lower(acc, jnp.int32(0)).compile()
    ofs = []
    t0 = time.perf_counter()
    for i in range(n_chunks):
        acc, of = step(acc, jnp.int32(i))
        ofs.append(of)
    jax.block_until_ready(acc)
    stream_s = time.perf_counter() - t0
    overflow = any(bool(of) for of in ofs)

    slice_ok = bool(jnp.all(slice_check(0)))
    heads = [jax.device_get(head(i)) for i in range(n_chunks)]
    tops = np.concatenate([h[0] for h in heads])
    ctrs = np.concatenate([h[1] for h in heads])
    want_top, want_ctr = _pure_chain(tops, ctrs)
    pure_ok = (
        np.array_equal(np.asarray(acc.top), want_top)
        and np.array_equal(np.asarray(acc.ctr[:pe]), want_ctr)
        and not bool(jnp.any(acc.dvalid))
    )
    return {
        "shape": f"{n_chunks * chunk}x{e}x{a}", "chunks": n_chunks,
        "dot_state_bytes": n_chunks * chunk * e * a * 4,
        "stream_s": stream_s, "overflow": overflow,
        "step_temp_bytes": step.memory_analysis().temp_size_in_bytes,
        "equals_tree_fold_slice": slice_ok,
        "equals_pure_chain": pure_ok, "pure_rows": int(len(tops)),
        "pure_elems": pe,
        "bit_identical": slice_ok and pure_ok and not overflow,
    }


def phase_stream(cfg=None) -> dict:
    """The flagship replica stream on a 1x1 mesh."""
    import jax
    import jax.numpy as jnp

    from bench import _flagship_bit_identity, _flagship_population, bench_configs
    from crdt_tpu.parallel import make_mesh, mesh_stream_fold_sparse

    cfg = cfg or bench_configs()["flagship"]
    r, br = cfg["replicas"], cfg["block_rows"]
    mesh = make_mesh(1, 1)
    gate = _flagship_bit_identity(mesh)
    gen, _ = _flagship_population(cfg["segment_cap"], cfg["universe"],
                                  cfg["actors"])
    n_blocks = -(-r // br)

    def run(rows):
        assert (n_blocks * br) % rows == 0, (n_blocks, br, rows)
        blocks = (gen(jnp.arange(b * rows, (b + 1) * rows))
                  for b in range(-(-n_blocks * br // rows)))
        acc, of = mesh_stream_fold_sparse(blocks, mesh)
        jax.block_until_ready(acc)
        return acc, bool(jnp.any(of))

    t0 = time.perf_counter()
    run(br)
    cold_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    acc, of = run(br)
    stream_s = time.perf_counter() - t0
    acc2, of2 = run(2 * br)
    invariant = _same(acc, acc2)
    return {
        "shape": f"{n_blocks * br}x{cfg['universe']}", "blocks": n_blocks,
        "block_rows": br, "segment_cap": cfg["segment_cap"],
        "live_dots": int(jnp.sum(acc.valid)), "cold_stream_s": cold_s,
        "stream_s": stream_s,
        "overflow": of or of2, "gate": gate,
        "block_size_invariant": invariant,
        "bit_identical": bool(gate["bit_identical"]) and invariant
        and not (of or of2),
    }


def phase_serve(serve=None, fanout=None) -> dict:
    """Ingest -> ServeWal -> device apply at the serve entry's shape, one
    evict -> restore, the per-tenant oracle; then fan-out pushes at the
    fanout entry's shape through the wire kernel."""
    from bench import bench_configs

    cfgs = bench_configs()
    rng = np.random.default_rng(21)
    fanout = fanout or cfgs["fanout"]
    with tempfile.TemporaryDirectory(prefix="chip-smoke-") as root:
        out = _serve_cycles(serve or cfgs["serve"], root, rng)
        out.update(_fanout_pushes(fanout, root, rng))
    out.update(_wire_vs_interpreter(
        fanout["dispatch_lanes"] * fanout["elems"], fanout["actors"]))
    out["bit_identical"] = (
        out["oracle_mismatches"] == 0 and out["client_mismatches"] == 0
        and out["wire_equals_interpreter"]
        and out["evicted"] >= 1 and out["restored_in_window"] >= 1
        and out["fanout_deliveries"] >= 1
    )
    return out


def _serve_cycles(cfg, root, rng, cycles: int = 3) -> dict:
    from crdt_tpu.ops import superblock as sb_ops
    from crdt_tpu.parallel import make_mesh
    from crdt_tpu.serve import Evictor, IngestQueue, Superblock
    from crdt_tpu.serve.wal import ServeWal

    caps = dict(n_elems=cfg["elems"], n_actors=cfg["actors"],
                deferred_cap=cfg["deferred_cap"])
    tenants, e, a = cfg["tenants"], cfg["elems"], cfg["actors"]
    sb = Superblock(tenants, make_mesh(1, 1), kind="orswot", caps=caps,
                    n_lanes=cfg["lanes"])
    ev = Evictor(sb, os.path.join(root, "snap"), pressure_batch=256)
    wal = ServeWal(os.path.join(root, "wal"))
    q = IngestQueue(sb, lanes=cfg["slab_lanes"], depth=cfg["slab_depth"],
                    max_pending=1 << 20, evictor=ev, wal=wal)
    next_ctr = np.zeros(tenants, np.uint32)
    history: dict = {}

    def add(t, m):
        act = t % a
        c = int(next_ctr[t]) + 1
        next_ctr[t] = c
        q.add(t, act, c, m)
        history.setdefault(t, []).append((sb_ops.ADD, act, c, None, m))

    def submit(cycle, n_ops):
        hot_set = cfg["hot_set"]
        off = (cycle * cfg["hot_shift"]) % max(tenants - hot_set, 1)
        hot = rng.integers(off, off + hot_set, n_ops)
        ts = np.where(rng.random(n_ops) < 0.85, hot,
                      rng.integers(0, tenants, n_ops))
        is_add = rng.random(n_ops) < 0.85
        masks = rng.random((n_ops, e)) < 0.4
        for i in range(n_ops):
            t = int(ts[i])
            if is_add[i] or next_ctr[t] == 0:
                add(t, masks[i])
            else:  # covered remove at the tenant's applied top
                clock = np.zeros(a, np.uint32)
                clock[t % a] = next_ctr[t]
                q.rm(t, clock, masks[i])
                history[t].append((sb_ops.RM, 0, 0, clock, masks[i]))

    ops_applied, evicted, restored = 0, 0, 0
    retouch = []
    t0 = time.perf_counter()
    for cycle in range(cycles):
        submit(cycle, cfg["ops_per_cycle"])
        ops_applied += q.drain()[0].ops_applied
        if cycle == cycles // 2:
            cold = ev.select_cold(cfg["evict_cohort"])
            evicted = ev.evict(cold)
            retouch = cold[:cfg["retouch"]]
            for t in retouch:
                add(int(t), rng.random(e) < 0.4)
            rep = q.drain()[0]
            ops_applied += rep.ops_applied
            restored = rep.restored
    serve_s = time.perf_counter() - t0
    touched = np.asarray(sorted(history))
    sample = [int(t) for t in retouch[:cfg["oracle_sample"] // 3]]
    sample += [int(t) for t in rng.choice(
        touched, cfg["oracle_sample"] - len(sample), replace=False)
        if int(t) not in sample]
    mismatches = 0
    for t in sample:
        ev.restore(t)
        want = sb_ops.sequential_oracle(sb.tk, sb.tk.empty(**sb.caps),
                                        history[t])
        mismatches += not _same(sb.row(t), want)
    wal.close()
    return dict(
        serve_tenants=tenants, serve_lanes=cfg["lanes"],
        serve_ops=ops_applied, serve_s=serve_s,
        wal_bytes=wal.bytes_appended, wal_fsyncs=wal.fsyncs,
        evicted=evicted, restored_in_window=restored,
        oracle_sample=len(sample), oracle_mismatches=mismatches,
    )


def _fanout_pushes(cfg, root, rng, cycles: int = 2) -> dict:
    """δ pushes through the wire kernel, delivered to sampled clients."""
    from crdt_tpu.fanout import ClientReplica, FanoutPlane
    from crdt_tpu.parallel import make_mesh
    from crdt_tpu.serve import Evictor, IngestQueue, Superblock

    caps = dict(n_elems=cfg["elems"], n_actors=cfg["actors"],
                deferred_cap=cfg["deferred_cap"])
    tenants, e, a = cfg["tenants"], cfg["elems"], cfg["actors"]
    sb = Superblock(tenants, make_mesh(1, 1), kind="orswot", caps=caps,
                    n_lanes=cfg["lanes"])
    ev = Evictor(sb, os.path.join(root, "fsnap"), pressure_batch=256)
    q = IngestQueue(sb, lanes=cfg["slab_lanes"], depth=cfg["slab_depth"],
                    max_pending=1 << 20, evictor=ev)
    plane = FanoutPlane(sb, evictor=ev, window_cap=cfg["window_cap"],
                        dispatch_lanes=cfg["dispatch_lanes"],
                        capacity=cfg["subscribers"])
    plane.subscribe(np.arange(cfg["subscribers"], dtype=np.int64) % tenants)
    n_clients = cfg["client_sample"]
    clients = {s: ClientReplica("orswot", sb.empty_row())
               for s in range(n_clients)}
    next_ctr = np.zeros(tenants, np.uint32)
    deliveries, push_s = 0, 0.0
    for _ in range(cycles):
        n_ops = cfg["ops_per_cycle"]
        ts = rng.integers(0, cfg["hot_set"], n_ops)
        ts[:n_clients] = np.arange(n_clients)
        masks = rng.random((n_ops, e)) < (6.0 / e)
        for i in range(n_ops):
            t = int(ts[i])
            next_ctr[t] += 1
            q.add(t, t % a, int(next_ctr[t]), masks[i])
        q.drain()
        plane.note_dirty(np.unique(ts))
        t0 = time.perf_counter()
        rep = plane.push()
        push_s += time.perf_counter() - t0
        for cp in rep.pushes:
            for s in cp.members:
                if int(s) in clients:
                    clients[int(s)].apply_wire(cp.wire, cp.to_ver)
        for rs in rep.resyncs:
            for s in rs.members:
                if int(s) in clients:
                    clients[int(s)].adopt(rs.state, rs.to_ver)
        for c in clients.values():
            c.ack()
        members = [cp.members for cp in rep.pushes + rep.resyncs]
        if members:
            plane.ack(np.concatenate(members))
        deliveries += rep.subscribers
    bad = [s for s, c in clients.items() if not c.equals(sb.row(s))]
    return dict(
        fanout_tenants=tenants, fanout_subscribers=plane.n_live,
        fanout_rows_per_dispatch=cfg["dispatch_lanes"] * e,
        fanout_deliveries=deliveries, fanout_push_s=push_s,
        clients_checked=len(clients), client_mismatches=len(bad),
        client_mismatch_ids=bad[:8],
    )


def _wire_vs_interpreter(n: int, lc: int) -> dict:
    """The compiled wire kernel against the Pallas interpreter on the same
    device, over ``n`` slots of ``lc`` clock lanes (the fan-out's
    ``cohort_wire_encode`` flavor). The CPU tests only ever run the
    interpreter, so this is where a Mosaic lowering that disagrees with
    the kernel's semantics shows."""
    import jax
    import jax.numpy as jnp

    from crdt_tpu.ops.wire_kernels import WireLaneSpec, wire_pack

    k1, k2, k3, k4 = jax.random.split(jax.random.key(5), 4)
    base = jax.random.randint(k1, (n, lc), 0, 1 << 24).astype(jnp.uint32)
    step = jax.random.randint(k2, (n, lc), 0, 1 << 12).astype(jnp.uint32)
    # ~4% of slots hold a lane outside the biased-u16 window: deferred.
    far = jax.random.bernoulli(k3, 0.01, (n, lc))
    clocks = base + jnp.where(far, step + (1 << 16), step)
    valid = jax.random.bernoulli(k4, 0.9, (n,))
    spec = WireLaneSpec(lc=lc)

    def pack(interpret):
        return jax.jit(partial(wire_pack, spec, interpret=interpret))(
            clocks, base, valid)

    got, want = pack(INTERPRET), pack(True)
    return {
        "wire_slots": n, "wire_deferred": int(jnp.sum(want.defer)),
        "wire_words_differing": int(jnp.sum(got.words != want.words)),
        "wire_equals_interpreter": _same(got, want),
    }


# ---- four-chip phases ------------------------------------------------------

def _synced_population(cfg, mesh):
    """Four-chip population: every replica holds one synced base state;
    8 writer replicas (one per actor, spread over the replica axis) then
    add one new dot over ``writer_rows`` fresh elements each. Returns a
    row-block generator ``gen(lo, rows)`` and the (dirty, fctx) tracking
    of the writes since the sync, all sharded over ``mesh``."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from bench import dense_chunk
    from crdt_tpu.parallel.mesh import ELEMENT_AXIS, REPLICA_AXIS, orswot_specs

    r, e, a, dcap = cfg["replicas"], cfg["elems"], cfg["actors"], cfg["deferred_cap"]
    wr = cfg["writer_rows"]
    base = dense_chunk(jax.random.key(4), 1, e, a, dcap)
    writers = jnp.asarray(np.arange(a) * (r // a))      # replica per actor
    rows = jnp.asarray((np.arange(a * wr) * 7) % e)     # [A * wr] distinct

    def gen(lo, n):
        """Replicas [lo, lo + n) after the writes, and their touched rows."""
        is_w = (lo + jnp.arange(n))[:, None] == writers[None, :]   # [n, A]
        touched = jnp.zeros((n, e), bool).at[:, rows].set(
            jnp.repeat(is_w, wr, axis=1))
        new_ctr = base.top[0] + 1                                  # [A]
        dot = jnp.where(is_w, new_ctr[None, :], 0)                 # [n, A]
        pop = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (n,) + x.shape[1:]), base)
        ctr = jnp.where(touched[..., None],
                        jnp.maximum(pop.ctr, dot[:, None, :]), pop.ctr)
        return pop._replace(top=jnp.maximum(pop.top, dot), ctr=ctr), touched

    def full():
        pop, touched = gen(0, r)
        return pop, touched, jnp.where(touched[..., None], pop.ctr, 0)

    shard = jax.tree.map(lambda s: NamedSharding(mesh, s), orswot_specs(),
                         is_leaf=lambda x: isinstance(x, P))
    full = jax.jit(full, out_shardings=(
        shard, NamedSharding(mesh, P(REPLICA_AXIS, ELEMENT_AXIS)),
        NamedSharding(mesh, P(REPLICA_AXIS, ELEMENT_AXIS, None))))
    return gen, full


def phase_mesh(cfg=MESH4, n_dev: int = 4) -> dict:
    """mesh_fold and mesh_delta_gossip on an (n_dev, 1) mesh against a
    single-device fused fold of the same population."""
    import jax

    from crdt_tpu.ops import orswot as ops
    from crdt_tpu.ops.pallas_kernels import fold_fused
    from crdt_tpu.parallel import make_mesh, mesh_fold
    from crdt_tpu.parallel.delta import mesh_delta_gossip

    mesh = make_mesh(n_dev, 1)
    gen, full = _synced_population(cfg, mesh)
    r, e, a, chunk = cfg["replicas"], cfg["elems"], cfg["actors"], cfg["chunk"]

    # Reference: one device, fused fold over row chunks, joined.
    dev0 = jax.devices()[0]

    @jax.jit
    def ref_step(acc, lo):
        part, of1 = fold_fused(gen(lo, chunk)[0], interpret=INTERPRET)
        acc, of2 = ops.join(acc, part)
        return acc, of1 | of2

    acc = jax.device_put(ops.empty(e, a, deferred_cap=cfg["deferred_cap"]), dev0)
    ref_of = False
    for lo in range(0, r, chunk):
        acc, of = ref_step(acc, jax.device_put(np.int32(lo), dev0))
        ref_of |= bool(of)
    ref = jax.device_get(acc)
    del acc

    pop, dirty, fctx = full()
    per_device = {str(d.id): _device_bytes(d) for d in jax.devices()[:n_dev]}
    folded, fold_of = mesh_fold(pop, mesh)
    fold_ok = _same(jax.device_get(folded), ref) and not bool(fold_of)
    del folded

    states, _, gossip_of, residue = mesh_delta_gossip(
        pop, dirty, fctx, mesh, rounds=cfg["rounds"], cap=cfg["cap"],
        pipeline=False, fused=True,
    )
    states = jax.device_get(states)
    rows_ok = [
        _same(jax.tree.map(lambda x: x[i], states), ref)
        for i in range(n_dev)
    ]
    return {
        "shape": f"{r}x{e}x{a}", "mesh": [n_dev, 1],
        "reference_overflow": ref_of,
        "mesh_fold_equals_reference": fold_ok,
        "gossip_rounds": cfg["rounds"], "gossip_residue": int(residue),
        "gossip_rows_equal_reference": rows_ok,
        "gossip_overflow": bool(gossip_of),
        "per_device_bytes_after_population": per_device,
        "per_device_bytes_end": {
            str(d.id): _device_bytes(d) for d in jax.devices()[:n_dev]
        },
        "bit_identical": fold_ok and all(rows_ok) and int(residue) == 0
        and not bool(gossip_of) and not ref_of,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        log(f"chip_smoke: no TPU (JAX found {devs[0].platform!r})")
        return 1
    if len(devs) < args.chips:
        log(f"chip_smoke: --chips {args.chips} but {len(devs)} devices")
        return 1

    from crdt_tpu.utils.compile_cache import enable_compile_cache

    log(f"compile cache: {enable_compile_cache()}")
    compiles = _Compiles()
    if args.chips == 4:
        phases = [("mesh", phase_mesh)]
    else:
        phases = [("fold", phase_fold), ("stream", phase_stream),
                  ("serve", phase_serve)]
    ok = True
    for name, fn in phases:
        ok &= run_phase(name, fn, compiles)
    if not ok:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
