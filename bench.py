"""Benchmark of record: ORSWOT merges/sec, batched TPU fold vs the
sequential CPU oracle (BASELINE.md config 3: 10k replicas x 100k elems,
full-mesh anti-entropy as one lattice-join reduction).

Prints exactly ONE JSON line on stdout:
``{"metric", "value", "unit", "vs_baseline", "path", "gbps",
"bytes_moved", "shape"}`` — ``path`` records which kernel actually ran
("fused" = the pallas one-pass fold, "tree" = the jnp log-tree
fallback), so numbers across rounds are comparable; ``gbps`` is achieved
HBM bandwidth over the replica dot-state actually read (the MFU analog
for this memory-bound workload). All progress goes to stderr.

Method: the full 10k x 100k x 8 dot-state is ~33 GB — bigger than one
chip's HBM — so the fold streams replica chunks through a resident
accumulator: acc = join(acc, fold(chunk)). One synthetic chunk is
generated once and re-read from HBM every stream step (the fold is
dense, data-independent work, so re-using one chunk's bytes times
exactly what distinct chunks would); the stream is timed end to end.
The CPU baseline is the same serial ``Orswot::merge`` fold through the
pure oracle at the same element universe (per-merge cost is
replica-count independent), reported as merges/sec.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def device_info() -> dict:
    """The device every record names: platform, ``device_kind`` and
    count, as JAX reports them. The bench runs on the TPU, or on the CPU
    only when the caller set ``JAX_PLATFORMS=cpu`` (8 virtual devices,
    each leg's ``cpu_fallback`` shape). Anything else exits non-zero:
    there is no fallback and no cached number."""
    cpu = os.environ.get("JAX_PLATFORMS") == "cpu"
    if cpu:
        from crdt_tpu.utils.cpu_pin import pin_cpu

        pin_cpu(virtual_devices=8)
        os.environ["BENCH_CPU_FALLBACK"] = "1"
    import jax

    from crdt_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    devs = jax.devices()
    if devs[0].platform != ("cpu" if cpu else "tpu"):
        sys.exit(
            f"bench.py: no TPU (found {devs[0].platform!r}); "
            f"set JAX_PLATFORMS=cpu for a CPU run"
        )
    return {"platform": devs[0].platform, "device_kind": devs[0].device_kind,
            "device_count": len(devs)}


# Config-3 shape; override via env for scaled runs.
R = int(os.environ.get("BENCH_REPLICAS", 10240))
E = int(os.environ.get("BENCH_ELEMS", 102400))
A = int(os.environ.get("BENCH_ACTORS", 8))
CHUNK = int(os.environ.get("BENCH_CHUNK", 512))
R_CPU = int(os.environ.get("BENCH_CPU_REPLICAS", 4))
ITERS = int(os.environ.get("BENCH_ITERS", 3))

_CONFIGS_PATH = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "BENCH_CONFIGS.json"
)
_CONFIGS_CACHE = None


def bench_configs() -> dict:
    """The committed shape configs (BENCH_CONFIGS.json) — one source of
    truth for the sparse legs and the flagship streaming leg, shared
    with chip_smoke.py so the chip runs the exact committed shapes. Results no longer live in this file (they go to
    BENCH_RECORDS.json)."""
    global _CONFIGS_CACHE
    if _CONFIGS_CACHE is None:
        with open(_CONFIGS_PATH) as f:
            _CONFIGS_CACHE = json.load(f)
    return _CONFIGS_CACHE


def _cfg(leg: str, key: str, env: str, cpu_fallback: bool = False) -> int:
    """One leg shape knob: env var > cpu_fallback sub-block (when the
    leg runs on the CPU stand-in) > the committed config value."""
    cfg = bench_configs()[leg]
    val = cfg[key]
    if cpu_fallback:
        val = cfg.get("cpu_fallback", {}).get(key, val)
    return int(os.environ.get(env, val))


def _flight_start(capacity: int = 8192):
    """Install a fresh flight recorder for one bench leg (obs/
    recorder.py) and remember both the previous recorder and the
    registry counter baseline, so the postmortem cross-check can
    attribute exactly this leg's counters."""
    from crdt_tpu import obs
    from crdt_tpu.utils.metrics import metrics

    base = metrics.snapshot()
    rec = obs.FlightRecorder(capacity=capacity)
    prev = obs.install(rec)
    return rec, prev, base


def _flight_finish(name: str, rec, prev, base, slo: bool = False) -> dict:
    """Dump the leg's flight artifact (gitignored
    ``BENCH_FLIGHT_<name>.jsonl``), replay it through
    tools/obs_report.py against the LIVE registry counters accrued
    since ``base``, ASSERT the bit-exact cross-check and a clean
    invariant audit (the ISSUE 12 acceptance gate), and return the
    record fields: dump path + folded p50/p95/p99 histogram summaries
    (the p99 riding the headline BENCH record). ``slo`` additionally
    replays the op-journey trace events bit-exactly (``obs_report
    --slo``) and ASSERTS the replay actually ran — a dump that dropped
    trace events from the ring would only skip, not prove."""
    import sys

    from crdt_tpu import obs
    from crdt_tpu.utils.metrics import metrics

    dump_path = os.path.abspath(
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     f"BENCH_FLIGHT_{name}.jsonl")
    )
    rec.dump(dump_path, reason=f"bench-{name}")
    obs.install(prev)
    tools_dir = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tools"
    )
    if tools_dir not in sys.path:
        sys.path.insert(0, tools_dir)
    import obs_report

    live = metrics.snapshot().get("counters", {})
    base_c = base.get("counters", {})
    since = {"counters": {
        k: v - base_c.get(k, 0) for k, v in live.items()
    }}
    report = obs_report.build_report(dump_path, snapshot=since, slo=slo)
    assert report["ok"], (
        f"flight dump failed the postmortem gate: "
        f"parse={report['parse_errors'][:2]} "
        f"mismatches={report['counter_mismatches'][:3]} "
        f"audit={[f for f in report['audit'] if f['severity'] == 'error'][:2]}"
        + (f" replay={report['slo']['mismatches'][:2]}" if slo else "")
    )
    extra = {}
    if slo:
        rp = report["slo"]
        assert rp["skipped"] is None, (
            f"trace replay skipped — not a bit-exact proof: "
            f"{rp['skipped']}"
        )
        extra = {
            "trace_replay_ok": rp["ok"],
            "trace_replayed": rp["traces_completed"],
        }
    hist = {
        key: {
            "count": s["count"],
            "p50": round(s["p50"], 3),
            "p95": round(s["p95"], 3),
            "p99": round(s["p99"], 3),
        }
        for key, s in sorted(report["histograms"].items())
    }
    return {
        "flight_dump": dump_path,
        "flight_ok": True,
        "flight_events": report["events"],
        "hist": hist,
        **extra,
    }


def make_arrays(r, e=None):
    """Host-side (numpy) replica states for the CPU oracle baseline."""
    e = E if e is None else e
    rng = np.random.default_rng(42)
    # ~70% of (element, actor) dots present — a well-mixed replica set.
    ctr = rng.integers(0, 100, (r, e, A)).astype(np.uint32)
    ctr[rng.random((r, e, A)) < 0.3] = 0
    top = np.maximum(ctr.max(axis=1), rng.integers(0, 100, (r, A)).astype(np.uint32))
    return top, ctr


def dense_chunk(key, r, e, a=A, deferred_cap=4):
    """Same distribution as ``make_arrays``, traced: ~70% of (element,
    actor) dots present, each replica's top covering its dots."""
    import jax
    import jax.numpy as jnp

    from crdt_tpu.ops import orswot as ops

    k1, k2, k3 = jax.random.split(key, 3)
    ctr = jax.random.randint(k1, (r, e, a), 0, 100, dtype=jnp.uint32)
    keep = jax.random.randint(k2, (r, e, a), 0, 10, dtype=jnp.uint32) >= 3
    ctr = jnp.where(keep, ctr, 0)
    extra = jax.random.randint(k3, (r, a), 0, 100, dtype=jnp.uint32)
    top = jnp.maximum(ctr.max(axis=1), extra)
    return ops.empty(e, a, deferred_cap=deferred_cap, batch=(r,))._replace(
        top=top, ctr=ctr
    )


def make_chunk_on_device(r, e):
    """``dense_chunk`` generated directly in device memory (jax.random
    under jit): multi-GB host→device pushes would time the host link,
    and a real deployment would receive replica state over ICI/DCN, not
    from the host."""
    import jax

    return jax.jit(lambda key: dense_chunk(key, r, e))(jax.random.key(42))


def bench_tpu():
    """Returns (merges_per_sec, path, gbps, bytes_moved, shape,
    degraded).

    The fused kernel streams the whole R-replica fold in ONE dispatch
    (``n_passes`` grid re-walks of the resident chunk — the DMA/compute
    stream of folding R distinct replicas, by idempotence), and the
    reported time is the K-vs-2K marginal, which cancels every fixed
    dispatch overhead: dt = T(2K passes) - T(K passes) = time of exactly
    one R-replica stream on the chip. On the CPU (``JAX_PLATFORMS=cpu``)
    the log-tree fold runs instead, labeled ``path: "tree"``."""
    import jax
    import jax.numpy as jnp

    from crdt_tpu.ops import orswot as ops
    from crdt_tpu.ops.pallas_kernels import _fused_backend, fold_fused

    log(f"jax backend: {jax.default_backend()}, devices: {jax.devices()}")
    chunk_r = min(CHUNK, R)
    n_passes = max(-(-R // chunk_r), 1)  # ceil: never time fewer than R
    r_total = chunk_r * n_passes
    chunk = make_chunk_on_device(chunk_r, E)
    jax.block_until_ready(chunk.ctr)
    bytes_moved = r_total * E * A * 4  # replica dot-state read per stream

    if _fused_backend():
        path = "fused"
        if os.environ.get("BENCH_CHECK", "1") != "0":
            # Bit-identity gate on a SLICE of the chunk, with a forced-
            # small r_chunk so the slice still walks MULTIPLE replica-
            # chunk grid steps (the cross-block accumulator path the
            # full-size bench exercises — Mosaic specializes its grid
            # per shape).
            sl = jax.tree.map(lambda x: x[: min(64, chunk_r)], chunk)
            sl = sl._replace(ctr=sl.ctr[:, : min(8192, E)])
            sl = sl._replace(dmask=sl.dmask[:, :, : min(8192, E)])
            probe, _ = fold_fused(sl, r_chunk=16, interpret=False)
            tree, _ = ops.fold(sl)
            same = all(
                bool(jnp.array_equal(x, y)) for x, y in zip(probe, tree)
            )
            assert same, "fused fold != tree fold on the bench slice"
            log("fused/tree bit-identity check passed on a chunk slice")

        def run(k: int):
            out, _ = fold_fused(chunk, n_passes=k, interpret=False)
            jax.block_until_ready(out)
    else:
        path = "tree"
        tree_k = _fold_k_runner(ops.fold, ops.join, chunk)

        def run(k: int):
            tree_k(k)
    log(f"fold path: {path}")
    dt, timing_degraded = marginal_time(run, n_passes, f"config3 {path} fold")

    mps = (r_total - 1) / dt
    gbps = bytes_moved / dt / 1e9
    from crdt_tpu.utils.metrics import metrics, observe_depth

    metrics.count("bench.merges", r_total - 1)
    metrics.observe("bench.orswot_merges_per_sec", mps)
    observe_depth("bench.orswot_chunk", chunk)
    log(
        f"{path} fold: {r_total} replicas x {E} elems x {A} actors "
        f"({n_passes} passes of {chunk_r}): {dt*1e3:.1f} ms/stream -> "
        f"{mps:,.0f} merges/s, {gbps:.0f} GB/s achieved"
    )
    return mps, path, gbps, bytes_moved, f"{r_total}x{E}x{A}", timing_degraded


def _fold_k_runner(fold_fn, join_fn, state):
    """A one-dispatch k-pass fold of ``state`` — the jnp-leg analog of
    the fused kernel's ``n_passes`` grid re-walks (``bench_tpu``'s
    methodology). Each pass re-folds the whole replica batch with the
    PREVIOUS pass's result joined into row 0: a lattice no-op by
    idempotence (the result stays ``fold(state)`` bit-exactly), but a
    real loop-carried data dependence, so XLA cannot hoist or CSE the
    loop-invariant fold — all k passes stream the batch through the
    joins for real."""
    import jax
    import jax.numpy as jnp
    from functools import partial

    row0 = jax.tree.map(lambda x: x[0], state)

    @partial(jax.jit, static_argnums=(1,))
    def fold_k(st, k):
        def body(acc, _):
            seed_row, _ = join_fn(jax.tree.map(lambda x: x[0], st), acc)
            seeded = jax.tree.map(
                lambda full, row: full.at[0].set(row), st, seed_row
            )
            out, _ = fold_fn(seeded)
            return out, None

        acc, _ = jax.lax.scan(body, row0, None, length=k)
        return acc

    def run(k: int):
        out = fold_k(state, k)
        jax.block_until_ready(out)
        return out

    return run


def marginal_time(run, k: int, label: str, iters=None):
    """The K-vs-2K marginal (``bench_tpu``'s methodology): dt = median
    T(2K) - median T(K) cancels every fixed per-dispatch overhead.
    Returns ``(seconds for k passes, degraded)``; ``degraded=True``
    means jitter swamped the marginal and the conservative bound
    T(2K)/2 stands in — callers MUST label the record so."""
    iters = ITERS if iters is None else iters
    run(k)
    run(2 * k)  # compile + warm both pass counts
    t1s, t2s = [], []
    for _ in range(iters):
        t0 = time.perf_counter()
        run(k)
        t1s.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        run(2 * k)
        t2s.append(time.perf_counter() - t0)
    t1 = sorted(t1s)[len(t1s) // 2]
    t2 = sorted(t2s)[len(t2s) // 2]
    dt = t2 - t1
    if dt <= 0:
        log(
            f"  WARNING {label}: non-positive marginal (T(K)={t1*1e3:.1f} "
            f"ms, T(2K)={t2*1e3:.1f} ms); using conservative T(2K)/2 — "
            f"record labeled degraded"
        )
        return t2 / 2, True
    log(
        f"  {label}: T(K={k})={t1*1e3:.1f} ms, T(2K)={t2*1e3:.1f} ms -> "
        f"marginal {dt*1e3:.1f} ms"
    )
    return dt, False


def bench_comms():
    """Anti-entropy COMMS leg (``--quick-comms`` runs it alone): wire
    and payload bytes per ring round for full-state gossip vs the
    digest-gated δ exchange, on a sparse low-churn workload (<5% dirty
    rows — the regime the δ papers target, PAPERS.md 1603.01529 /
    1803.02750). The in-kernel telemetry counters (telemetry.py
    ``bytes_exchanged`` wire / ``bytes_useful`` post-mask) ARE the
    measurement, so the number reported is exactly what the links
    carried. Converged states are asserted bit-identical across digest
    on/off before any ratio is reported — a byte win that changed the
    lattice would be a bug, not a win."""
    import jax
    import jax.numpy as jnp

    from crdt_tpu.ops import orswot as ops
    from crdt_tpu.parallel import (
        make_mesh, mesh_delta_gossip, mesh_gossip,
    )

    n_dev = len(jax.devices())
    if n_dev < 2:
        log("comms leg needs >= 2 devices for a ring; skipping")
        return []
    p = n_dev
    e = int(os.environ.get("BENCH_COMMS_ELEMS", 2048))
    a = int(os.environ.get("BENCH_COMMS_ACTORS", 8))
    cap = int(os.environ.get("BENCH_COMMS_CAP", 64))
    mesh = make_mesh(p, 1)

    # Synced base (every replica holds the same first-half dots), then
    # <5% churn: each replica mints one fresh dot on its own row — the
    # steady-state shape of a large, mostly-quiet element universe.
    base = jnp.zeros((p, e, a), jnp.uint32).at[:, : e // 2, 0].set(1)
    state = ops.empty(e, a, deferred_cap=4, batch=(p,))
    churn_rows = jnp.arange(p) + e // 2
    actors = jnp.arange(p) % a
    ctr = base.at[jnp.arange(p), churn_rows, actors].set(2)
    top = jnp.max(ctr, axis=1)
    state = state._replace(top=top, ctr=ctr)
    dirty = jnp.zeros((p, e), bool).at[jnp.arange(p), churn_rows].set(True)
    fctx = jnp.where(dirty[..., None], ctr, 0)
    churn = float(dirty.sum() / dirty.size)
    assert churn < 0.05

    _, _, tel_full = mesh_gossip(state, mesh, telemetry=True)
    rounds_full = p - 1
    # Pin the δ budget explicitly (the pipelined default window) so the
    # per-link-round denominators below always match the rounds run.
    rounds_delta = 2 * (p - 1) - 1
    outs = {}
    for digest in (False, True):
        outs[digest] = mesh_delta_gossip(
            state, dirty, fctx, mesh, rounds=rounds_delta, cap=cap,
            digest=digest, telemetry=True,
        )
    rows_off, rows_on = outs[False][0], outs[True][0]
    identical = all(
        bool(jnp.array_equal(x, y))
        for x, y in zip(jax.tree.leaves(rows_off), jax.tree.leaves(rows_on))
    )
    assert identical, "digest gating changed the converged lattice"
    assert int(outs[True][3]) == 0, "comms leg did not certify convergence"
    tel_off, tel_on = outs[False][4], outs[True][4]

    # The fused-wire story (PR 14): the default runs above ARE fused —
    # pin them bit-identical to the UNFUSED (layered, PR 12-era wire)
    # oracle, then run the acked config both ways so the packed wire
    # bytes can be compared against PR 9's acked-useful bytes.
    out_unfused = mesh_delta_gossip(
        state, dirty, fctx, mesh, rounds=rounds_delta, cap=cap,
        telemetry=True, fused=False,
    )
    fused_identical = all(
        bool(jnp.array_equal(x, y))
        for x, y in zip(
            jax.tree.leaves(rows_on), jax.tree.leaves(out_unfused[0])
        )
    )
    assert fused_identical, "the fused wire changed the converged lattice"
    # fused=False: the acked-useful baseline must be the number the
    # ACTUAL PR 9 program produced (the fused ack lane is priced as a
    # bitmap, so its bytes_useful is not the same quantity).
    tel_acked = mesh_delta_gossip(
        state, dirty, fctx, mesh, rounds=rounds_delta, cap=cap,
        telemetry=True, ack_window=True, fused=False,
    )[4]
    tel_layered = out_unfused[4]

    # Per-link-round byte rates make the three modes comparable across
    # their different round budgets.
    links_full = p * rounds_full
    links_delta = p * rounds_delta
    full_rate = float(tel_full.bytes_exchanged) / links_full
    wire_rate = float(tel_on.bytes_exchanged) / links_delta
    wire_rate_layered = float(tel_layered.bytes_exchanged) / links_delta
    packed_rate = float(tel_on.wire_packed_bytes) / links_delta
    acked_useful_rate = float(tel_acked.bytes_useful) / links_delta
    useful_rate = float(tel_on.bytes_useful) / links_delta
    useful_rate_off = float(tel_off.bytes_useful) / links_delta
    ratio = wire_rate / full_rate
    fused_ratio = wire_rate / wire_rate_layered
    # The ISSUE 14 acceptance relation: the packed wire (what a
    # zero-suppressing transport carries) sits strictly below PR 9's
    # acked-useful bytes — the previous best payload number.
    assert packed_rate < acked_useful_rate, (
        packed_rate, acked_useful_rate
    )
    log(
        f"config-comms: {p} ranks x {e} elems ({churn:.2%} churn, cap "
        f"{cap}): full-state {full_rate:,.0f} B/link-round; δ wire "
        f"fused {wire_rate:,.0f} ({ratio:.1%} of full, {fused_ratio:.1%}"
        f" of the layered wire's {wire_rate_layered:,.0f}); packed "
        f"{packed_rate:,.0f} < acked-useful {acked_useful_rate:,.0f}; "
        f"δ useful gated {useful_rate:,.0f} vs ungated "
        f"{useful_rate_off:,.0f}; converged states bit-identical "
        f"(digest on/off AND fused vs layered)"
    )
    return [{
        "config": "comms", "metric": "delta_wire_vs_full_ratio",
        "value": round(ratio, 4), "unit": "ratio",
        "bytes_full_per_link_round": round(full_rate, 1),
        "bytes_delta_wire_per_link_round": round(wire_rate, 1),
        "bytes_delta_wire_layered_per_link_round":
            round(wire_rate_layered, 1),
        "bytes_delta_packed_per_link_round": round(packed_rate, 1),
        "bytes_delta_acked_useful_per_link_round":
            round(acked_useful_rate, 1),
        "bytes_delta_useful_per_link_round": round(useful_rate, 1),
        "bytes_delta_useful_ungated_per_link_round":
            round(useful_rate_off, 1),
        "bytes_exchanged_full_total": float(tel_full.bytes_exchanged),
        "bytes_exchanged_delta_total": float(tel_on.bytes_exchanged),
        "bytes_useful_delta_total": float(tel_on.bytes_useful),
        "wire_packed_bytes_total": float(tel_on.wire_packed_bytes),
        # Derived from the run, not asserted by fiat: a silent fallback
        # to the layered wire reports wire_packed_bytes == 0.
        "fused": bool(float(tel_on.wire_packed_bytes) > 0),
        "fused_wire_vs_layered": round(fused_ratio, 4),
        "rounds_full": rounds_full, "rounds_delta": rounds_delta,
        "churn": round(churn, 4), "cap": cap,
        "bit_identical": identical and fused_identical,
        "shape": f"{p}x{e}x{a}",
    }]


def bench_elastic():
    """Elastic capacity migration (diagnostic, stderr): wall-clock of the
    sanctioned overflow recovery — ``elastic.widen`` 2×-ing the
    element/dot axis with the live device state re-encoded in place
    (crdt_tpu/elastic.py) — for the dense and sparse ORSWOT flavors.
    Also the operator pressure view: per-kind headroom gauges plus the
    ``elastic.widen_events`` / ``elastic.migrated_bytes`` counters land
    in the metrics snapshot main() logs."""
    import jax

    from crdt_tpu import elastic
    from crdt_tpu.models.orswot import BatchedOrswot
    from crdt_tpu.models.sparse_orswot import BatchedSparseOrswot
    from crdt_tpu.utils.metrics import state_nbytes

    r = int(os.environ.get("BENCH_ELASTIC_REPLICAS", 256))
    e = int(os.environ.get("BENCH_ELASTIC_ELEMS", 4096))
    recs = []
    for kind, axis, model in (
        ("orswot", "n_members", BatchedOrswot(r, e, A, 8)),
        ("sparse_orswot", "dot_cap", BatchedSparseOrswot(r, e, A, 8, 8)),
    ):
        elastic.record_headroom(model)
        before = state_nbytes(model.state)
        t0 = time.perf_counter()
        grown = elastic.widen(model, (axis,))
        jax.block_until_ready(jax.tree.leaves(model.state))
        dt = time.perf_counter() - t0
        after = state_nbytes(model.state)
        log(
            f"config-elastic {kind}: {axis} {e} -> {grown[axis]} over "
            f"{r} replicas in {dt*1e3:.1f} ms "
            f"({before/1e6:.1f} -> {after/1e6:.1f} MB, first-shape "
            f"compile included — migrations are one-shot)"
        )
        recs.append({
            "config": "elastic", "metric": f"widen_ms_{kind}",
            "value": round(dt * 1e3, 2), "unit": "ms",
            "axis": axis, "grown_to": grown[axis],
            "state_bytes_before": before, "state_bytes_after": after,
            "shape": f"{r}x{e}x{A}",
        })
    return recs


def bench_reclaim():
    """Causal-stability reclamation leg (``--reclaim`` runs it alone):
    a long-churn workload — waves of adds then observed-removes over
    many elastic gossip rounds — on the sparse ORSWOT with
    ``stability=`` on and the shrink hysteresis engaged, against the
    never-reclaimed flags-off twin. The in-kernel counters
    (``reclaimed_slots``/``reclaimed_bytes``/``frontier_lag``) plus the
    ``reclaim.*`` registry counters ARE the measurement; converged
    reads are asserted bit-identical across the two runs before any
    number is reported — a byte win that changed the lattice would be
    a bug, not a win."""
    import jax

    from crdt_tpu import elastic
    from crdt_tpu import telemetry as tele
    from crdt_tpu.models.sparse_orswot import BatchedSparseOrswot
    from crdt_tpu.parallel import gossip_elastic, make_mesh
    from crdt_tpu.pure.orswot import Orswot
    from crdt_tpu.utils.metrics import metrics, state_nbytes

    n_dev = len(jax.devices())
    if n_dev < 2:
        log("reclaim leg needs >= 2 devices for a ring; skipping")
        return []
    p = n_dev
    waves = int(os.environ.get("BENCH_RECLAIM_WAVES", 3))
    adds_per_wave = int(os.environ.get("BENCH_RECLAIM_ADDS", 8))
    mesh = make_mesh(p, 1)
    policy = elastic.ElasticPolicy(
        low_water=0.25, shrink_rounds=2, shrink_floor=4
    )
    hyst = elastic.Hysteresis(policy)

    reps = [Orswot() for _ in range(p)]
    model = BatchedSparseOrswot.from_pure(reps, dot_cap=4, n_actors=p)
    base = BatchedSparseOrswot.from_pure(
        reps, dot_cap=4, n_actors=p,
        members=model.members.clone(), actors=model.actors.clone(),
    )

    from crdt_tpu.parallel.anti_entropy import _commit_rows as commit

    peak_occ = 0
    peak_bytes = 0
    shrink_rounds_run = 0
    tel_total = None
    snap0 = metrics.snapshot()["counters"]
    t0 = time.perf_counter()
    for wave in range(waves):
        for i in range(p):
            pu = model.to_pure(i)
            for k in range(adds_per_wave):
                a = pu.add(f"w{wave}_r{i}_{k}", pu.read().derive_add_ctx(f"s{i}"))
                pu.apply(a)
                # The op path rides the overflow→widen→resume loop too:
                # a burst that outgrows dot_cap widens mid-wave.
                elastic.elastic_call(lambda: model.apply(i, a), model, policy)
                elastic.elastic_call(lambda: base.apply(i, a), base, policy)
        # Remove churn: one replica observes-removes most of its view.
        pu = model.to_pure(wave % p)
        for v in sorted(pu.read().val)[: (adds_per_wave * p * 3) // 4]:
            rm = pu.rm(v, pu.contains(v).derive_rm_ctx())
            pu.apply(rm)
            elastic.elastic_call(
                lambda: model.apply(wave % p, rm), model, policy
            )
            elastic.elastic_call(
                lambda: base.apply(wave % p, rm), base, policy
            )
        for _ in range(3):
            out = gossip_elastic(
                model, mesh, policy=policy, telemetry=True,
                stability=True, reclaim=hyst,
            )
            tel = out[2]
            tel_total = tel if tel_total is None else tele.combine(tel_total, tel)
            b_rows, _ = gossip_elastic(base, mesh, policy=policy)
            commit(base, b_rows)
            occ = elastic.utilization(model)["dot_cap"][1]
            peak_occ = max(peak_occ, occ)
            peak_bytes = max(peak_bytes, state_nbytes(model.state))
            shrink_rounds_run += 1
    dt = time.perf_counter() - t0

    identical = all(
        model.to_pure(i) == base.to_pure(i) for i in range(p)
    )
    assert identical, "reclamation changed a converged read"
    snap1 = metrics.snapshot()["counters"]
    shrinks = snap1.get("reclaim.shrink_events", 0) - snap0.get(
        "reclaim.shrink_events", 0
    )
    reclaimed = snap1.get("reclaim.reclaimed_bytes", 0) - snap0.get(
        "reclaim.reclaimed_bytes", 0
    )
    end_bytes = state_nbytes(model.state)
    end_bytes_base = state_nbytes(base.state)
    log(
        f"config-reclaim: {p} ranks x {waves} churn waves "
        f"({shrink_rounds_run} gossip rounds, {dt:.1f}s): peak occupancy "
        f"{peak_occ}, peak bytes {peak_bytes:,}, shrink events {shrinks}, "
        f"reclaimed {reclaimed:,} B; end state {end_bytes:,} B vs "
        f"never-reclaimed {end_bytes_base:,} B; reads bit-identical"
    )
    return [{
        "config": "reclaim", "metric": "reclaimed_bytes",
        "value": reclaimed, "unit": "bytes",
        "shrink_events": shrinks,
        "peak_occupancy": peak_occ,
        "peak_state_bytes": peak_bytes,
        "end_state_bytes": end_bytes,
        "end_state_bytes_never_reclaimed": end_bytes_base,
        "reclaimed_slots_in_kernel": int(tel_total.reclaimed_slots),
        "frontier_lag_final": int(tel_total.frontier_lag),
        "rounds": shrink_rounds_run, "waves": waves,
        "bit_identical": identical,
        "shape": f"{p}x{adds_per_wave}",
    }]


def bench_chaos():
    """Degraded-mesh fault-tolerance leg (``--chaos`` runs it alone;
    ISSUE 8's acceptance gate): sustained injected corruption + drops
    on the 8-rank δ ring with ONE evicted-then-rejoined rank, healed by
    state-driven resync and asserted BIT-IDENTICAL to the fault-free
    fixpoint before any number is reported; plus the frontier-unpinning
    measurement — the straggler-parked reclamation scenario where the
    pinned (pre-PR) frontier retires nothing and the membership-driven
    eviction frontier fires. The damage absorbed (packets lost and
    rejected while convergence survives) is the metric."""
    import random

    import jax
    import jax.numpy as jnp

    from crdt_tpu import reclaim
    from crdt_tpu.faults import FaultPlan, Membership
    from crdt_tpu.faults.scenarios import mint_streams
    from crdt_tpu.models import BatchedOrswot
    from crdt_tpu.parallel import make_mesh, mesh_delta_gossip, mesh_gossip
    from crdt_tpu.parallel.delta import interval_accumulate
    from crdt_tpu.parallel.mesh import shard_orswot
    from crdt_tpu.pure.orswot import Orswot
    from crdt_tpu.utils import Interner

    n_dev = len(jax.devices())
    if n_dev < 2:
        log("chaos leg needs >= 2 devices for a ring; skipping")
        return []
    p = min(n_dev, 8)
    runs = int(os.environ.get("BENCH_CHAOS_RUNS", 3))
    rng = random.Random(int(os.environ.get("BENCH_CHAOS_SEED", 17)))
    sites, _ = mint_streams(rng, p, 4 * p)
    batched = BatchedOrswot.from_pure(
        sites,
        members=Interner(list(range(5))),
        actors=Interner([f"s{i}" for i in range(p)]),
    )
    mesh = make_mesh(p, 1)
    cur = shard_orswot(batched.state, mesh)

    rows_ref, _ = mesh_gossip(cur, mesh, local_fold="tree")
    ref0 = jax.tree.map(lambda x: x[0], rows_ref)
    # A mid-ring rank on big meshes; the LAST rank on tiny ones (p - 3
    # would go negative at p == 2 and silently evict nobody).
    evicted_rank = p - 3 if p >= 4 else p - 1
    plan = FaultPlan(
        seed=int(os.environ.get("BENCH_CHAOS_SEED", 17)),
        corrupt=0.6, drop=0.2, evicted=(evicted_rank,),
    )

    def tracking(state):
        z = jax.tree.map(jnp.zeros_like, state)
        d0 = jnp.zeros(state.ctr.shape[:-1], bool)
        f0 = jnp.zeros(state.ctr.shape, state.ctr.dtype)
        return interval_accumulate(d0, f0, z, state)

    # The whole soak runs under a flight recorder: telemetry events per
    # dispatch (with the in-kernel histograms), fault counters,
    # membership transitions — dumped and replayed through
    # tools/obs_report.py before any number is reported. The finally
    # below keeps the process-global recorder from leaking past a
    # failed assert (re-installing prev after _flight_finish already
    # did is a harmless same-value store).
    from crdt_tpu import obs as _obs

    rec, prev_rec, snap_base = _flight_start()
    dropped = rejected = 0
    wire_packed = 0.0
    cur0 = cur
    t0 = time.perf_counter()
    try:
        for _ in range(runs):
            d, f = tracking(cur)
            out = mesh_delta_gossip(cur, d, f, mesh, local_fold="tree",
                                    faults=plan, telemetry=True)
            fc = out[-1]
            dropped += int(fc.packets_dropped)
            rejected += int(fc.packets_rejected)
            wire_packed += float(out[4].wire_packed_bytes)
            assert int(out[3]) >= 1, "loss must void the residue certificate"
            cur = out[0]
            rec.snapshot_delta()
    except BaseException:
        _obs.install(prev_rec)
        raise
    chaos_s = time.perf_counter() - t0
    try:
        # The fused wire must absorb the SAME damage to the SAME
        # degraded state: replay the soak over the layered (PR 12-era)
        # wire and pin the mid-degraded rows bit-identical — a stronger
        # statement than post-heal equality, since the checksum/drop
        # fates themselves must line up packet for packet. (Inside the
        # recorder guard: a divergence here must not leak the
        # process-global recorder past the failed assert.)
        cur_unfused = cur0
        for _ in range(runs):
            d, f = tracking(cur_unfused)
            cur_unfused = mesh_delta_gossip(
                cur_unfused, d, f, mesh, local_fold="tree", faults=plan,
                fused=False,
            )[0]
        fused_identical = all(
            bool(jnp.array_equal(x, y))
            for x, y in zip(
                jax.tree.leaves(cur), jax.tree.leaves(cur_unfused)
            )
        )
        assert fused_identical, \
            "fused chaos soak diverged from the layered oracle"
    except BaseException:
        _obs.install(prev_rec)
        raise
    try:
        # Heal = state-driven resync; it is ALSO the evicted rank's rejoin.
        t0 = time.perf_counter()
        healed, _ = mesh_gossip(cur, mesh, local_fold="tree")
        heal_s = time.perf_counter() - t0
        identical = all(
            all(
                bool(jnp.array_equal(x, y))
                for x, y in zip(
                    jax.tree.leaves(jax.tree.map(lambda v: v[i], healed)),
                    jax.tree.leaves(ref0),
                )
            )
            for i in range(p)
        )
        assert identical, "chaos heal diverged from the fault-free fixpoint"

        # Frontier unpinning: live ranks hold a parked remove their tops
        # cover; the straggler's stale top pins the all-ranks frontier
        # (pre-PR: nothing retires) while the membership eviction frontier
        # lets compaction fire.
        n = 5
        stragglers = [Orswot() for _ in range(n)]
        for i in range(n):
            stragglers[i].apply(stragglers[i].add(
                i, stragglers[i].read().derive_add_ctx(f"s{i}")
            ))
        ghost = Orswot()
        ghost.apply(ghost.add("never", ghost.read().derive_add_ctx("zz")))
        rm_op = ghost.rm("never", ghost.contains("never").derive_rm_ctx())
        for i in range(n - 1):
            stragglers[i].apply(rm_op)
        model = BatchedOrswot.from_pure(
            stragglers,
            members=Interner(list(range(n)) + ["never"]),
            actors=Interner([f"s{i}" for i in range(n)] + ["zz"]),
        )
        zz = model.actors.id_of("zz")
        model.state = model.state._replace(
            top=model.state.top.at[: n - 1, zz].set(1)
        )
        parked = int(jnp.sum(model.state.dvalid))
        pinned = reclaim.compact_model(model, reclaim.model_frontier(model))
        members = Membership(n, k_suspect=2)
        members.evict(n - 1)
        live_frontier = reclaim.host_frontier(
            [np.asarray(model.state.top[i]) for i in members.live()]
        )
        unpinned = reclaim.compact_model(model, live_frontier)
        members.rejoin(n - 1)
        assert pinned["reclaimed_slots"] == 0
        assert unpinned["reclaimed_slots"] >= parked

        flight = _flight_finish("chaos", rec, prev_rec, snap_base)
    except BaseException:
        _obs.install(prev_rec)
        raise
    p99_us = flight["hist"].get(
        "delta_gossip.dispatch_us", {}
    ).get("p99", 0.0)

    log(
        f"config-chaos: {p}-rank δ ring x {runs} degraded runs "
        f"(corrupt=0.6 drop=0.2, rank {evicted_rank} evicted): "
        f"{rejected} rejected + {dropped} dropped packets absorbed in "
        f"{chaos_s:.1f}s, healed bit-identical in {heal_s:.1f}s; "
        f"frontier eviction retired {unpinned['reclaimed_slots']} parked "
        f"slots the pinned frontier kept ({pinned['reclaimed_slots']}); "
        f"flight dump replayed bit-exact ({flight['flight_events']} "
        f"events), dispatch p99 {p99_us:,.0f} µs"
    )
    return [{
        "config": "chaos", "metric": "packets_lost_and_healed",
        "value": dropped + rejected, "unit": "packets",
        "packets_rejected": rejected,
        "packets_dropped": dropped,
        "runs": runs,
        "evicted_rank": evicted_rank,
        "chaos_seconds": round(chaos_s, 3),
        "heal_seconds": round(heal_s, 3),
        "reclaimed_slots_pinned": pinned["reclaimed_slots"],
        "reclaimed_slots_evicted": unpinned["reclaimed_slots"],
        "bit_identical": identical,
        # Derived from the run (a silent layered fallback reports zero
        # packed bytes), so a fused-path gate stays falsifiable.
        "fused": bool(wire_packed > 0),
        "fused_vs_layered_identical": fused_identical,
        "wire_packed_bytes_total": round(wire_packed, 1),
        "dispatch_p99_us": p99_us,
        "shape": f"{p}x{4 * p}",
        **flight,
    }]


def bench_heal():
    """Optimal-δ-synchronization leg (``--heal`` runs it alone; ISSUE
    9's acceptance gate), two measurements on the 8-rank ring:

    1. **steady state** — a low-churn hot-row workload with shared
       REMOVALS (the knowledge class the PR 3 frozen-top digest can
       never mask) under a capped-drain budget (backlog > cap, the
       ROUNDS BUDGET formula's extra circuits — where re-circulated
       forwarding traffic actually crosses a link twice): δ ring
       digest-only vs digest+ack-window, converged states asserted
       bit-identical, post-mask payload (``bytes_useful``) per
       link-round reported for both — the acked rate must land
       STRICTLY below the digest-only baseline. (A second effect rides
       the record: masked marks retire instead of re-circulating, so
       the acked ring certifies ``residue == 0`` at budgets where the
       digest-only ring still starves.)
    2. **partition heal** — replicas diverge from a certified synced
       base, a ``FaultPlan`` drop window voids the certificate, and the
       degraded rows heal two ways: full-state gossip (the PR 8 path;
       its in-kernel ``bytes_exchanged`` is the cost) vs decomposition
       resync over the pre-partition snapshot
       (``crdt_tpu.faults.resync`` — Enes et al. 1803.02750). Both are
       asserted bit-identical to each other before the byte ratio is
       reported; the decomposition must ship < 25% of full-state
       bytes."""
    import jax
    import jax.numpy as jnp

    from crdt_tpu import faults as flt
    from crdt_tpu.ops import orswot as ops
    from crdt_tpu.parallel import make_mesh, mesh_delta_gossip, mesh_gossip

    n_dev = len(jax.devices())
    if n_dev < 2:
        log("heal leg needs >= 2 devices for a ring; skipping")
        return []
    p = n_dev
    e = int(os.environ.get("BENCH_HEAL_ELEMS", 2048))
    a = int(os.environ.get("BENCH_HEAL_ACTORS", 8))
    cap = int(os.environ.get("BENCH_HEAL_CAP", 32))
    hot = int(os.environ.get("BENCH_HEAL_HOT_ROWS", 32))
    n_rm = int(os.environ.get("BENCH_HEAL_RM_ROWS", 8))
    mesh = make_mesh(p, 1)

    # ---- 1. steady state: hot rows + shared removals, capped drain -------
    # Base: the first half of the universe holds dot (actor0, 1)
    # everywhere (synced). Churn: EVERY replica mints a dot on the same
    # ``hot`` rows (popular keys churn at many replicas — overlapping
    # marks are what makes forwarding traffic re-cross links) and all
    # replicas saw ``n_rm`` base members removed (row ctr zeroed under
    # a covering fctx — removal re-circulation is un-gateable by the
    # frozen-top digest by design). Backlog (hot + n_rm) > cap forces
    # the drain circuits the ROUNDS BUDGET formula prices.
    base = jnp.zeros((p, e, a), jnp.uint32).at[:, : e // 2, 0].set(1)
    state = ops.empty(e, a, deferred_cap=4, batch=(p,))
    hot_rows = jnp.arange(hot) + e // 2
    rm_rows = jnp.arange(n_rm)
    actors = jnp.arange(p) % a
    ctr = base.at[
        jnp.arange(p)[:, None], hot_rows[None, :], actors[:, None]
    ].set(2)
    top = jnp.max(ctr, axis=1)
    ctr = ctr.at[:, rm_rows, :].set(0)
    state = state._replace(top=top, ctr=ctr)
    dirty = (
        jnp.zeros((p, e), bool)
        .at[:, hot_rows].set(True)
        .at[:, rm_rows].set(True)
    )
    fctx = jnp.where(dirty[..., None], ctr, 0)
    fctx = fctx.at[:, rm_rows, 0].set(1)  # the removed dot
    churn = float(dirty.sum() / dirty.size)

    # Pipelined capped-drain budget: 2 * (P-1) * (1 + backlog/cap) - 1.
    backlog = hot + n_rm
    rounds_delta = 2 * (p - 1) * (1 + -(-backlog // cap)) - 1
    outs = {}
    for acked in (False, True):
        outs[acked] = mesh_delta_gossip(
            state, dirty, fctx, mesh, rounds=rounds_delta, cap=cap,
            telemetry=True, ack_window=acked,
        )
    rows_off, rows_on = outs[False][0], outs[True][0]
    steady_identical = all(
        bool(jnp.array_equal(x, y))
        for x, y in zip(jax.tree.leaves(rows_off), jax.tree.leaves(rows_on))
    )
    assert steady_identical, "ack window changed the converged lattice"
    assert int(outs[True][3]) == 0, "heal leg did not certify convergence"
    residue_digest = int(outs[False][3])  # may starve where acked won't
    tel_off, tel_on = outs[False][4], outs[True][4]
    links = p * rounds_delta
    useful_digest = float(tel_off.bytes_useful) / links
    useful_acked = float(tel_on.bytes_useful) / links
    acked_skipped = float(tel_on.bytes_acked_skipped)
    assert useful_acked < useful_digest, (
        "ack window did not beat the digest-only payload baseline"
    )

    # ---- 2. partition heal: drop window, then resync two ways ------------
    synced = jnp.zeros((p, e, a), jnp.uint32).at[:, : e // 2, 0].set(1)
    st2 = ops.empty(e, a, deferred_cap=4, batch=(p,))
    div_rows = jnp.arange(p) + e // 2  # each rank touches ONE row
    ctr2 = synced.at[jnp.arange(p), div_rows, actors].set(3)
    st2 = st2._replace(top=jnp.max(ctr2, axis=1), ctr=ctr2)
    d2 = jnp.zeros((p, e), bool).at[jnp.arange(p), div_rows].set(True)
    f2 = jnp.where(d2[..., None], ctr2, 0)
    since = jax.tree.map(
        lambda x: x[0],
        ops.empty(e, a, deferred_cap=4, batch=(p,))._replace(
            top=jnp.max(synced, axis=1), ctr=synced
        ),
    )
    plan = flt.FaultPlan(
        seed=int(os.environ.get("BENCH_HEAL_SEED", 23)), drop=0.5
    )
    degraded_rows, _, _, residue, _ = mesh_delta_gossip(
        st2, d2, f2, mesh, rounds=rounds_delta, cap=cap, faults=plan
    )
    assert int(residue) >= 1, "the drop window must void the certificate"

    t0 = time.perf_counter()
    healed_full, _, tel_heal = mesh_gossip(
        degraded_rows, mesh, telemetry=True
    )
    full_s = time.perf_counter() - t0
    bytes_full_gossip = float(tel_heal.bytes_exchanged)

    t0 = time.perf_counter()
    healed_dec, report = flt.resync("orswot", degraded_rows, since)
    dec_s = time.perf_counter() - t0
    heal_identical = all(
        bool(jnp.array_equal(x, y))
        for x, y in zip(
            jax.tree.leaves(healed_full), jax.tree.leaves(healed_dec)
        )
    )
    assert heal_identical, (
        "decomposition resync diverged from full-state gossip heal"
    )
    assert report.ratio < 0.25, (
        f"decomposition resync shipped {report.ratio:.1%} of full state"
    )

    log(
        f"config-heal: {p} ranks x {e} elems ({churn:.2%} churn incl. "
        f"removals, cap {cap}): δ useful/link-round digest-only "
        f"{useful_digest:,.0f} B vs +ack-window {useful_acked:,.0f} B "
        f"({useful_acked / useful_digest:.1%}; {acked_skipped:,.0f} B "
        f"masked); post-partition heal: decomposition resync shipped "
        f"{report.bytes_shipped:,.0f} B = {report.ratio:.1%} of "
        f"full-state ({report.bytes_full_state:,.0f} B; gossip wire "
        f"{bytes_full_gossip:,.0f} B) in {dec_s:.2f}s vs {full_s:.2f}s, "
        f"bit-identical both ways"
    )
    return [{
        "config": "heal", "metric": "resync_bytes_ratio",
        "value": round(report.ratio, 4), "unit": "ratio",
        "resync_bytes_shipped": report.bytes_shipped,
        "resync_bytes_full_state": report.bytes_full_state,
        "resync_lanes_shipped": report.lanes_shipped,
        "heal_bytes_full_gossip_wire": bytes_full_gossip,
        "bytes_useful_digest_per_link_round": round(useful_digest, 1),
        "bytes_useful_acked_per_link_round": round(useful_acked, 1),
        "bytes_acked_skipped_total": acked_skipped,
        "ack_vs_digest_useful_ratio": round(
            useful_acked / useful_digest, 4
        ),
        "residue_digest_only": residue_digest,
        "residue_acked": 0,
        "rounds_delta": rounds_delta, "churn": round(churn, 4),
        "cap": cap, "bit_identical": steady_identical and heal_identical,
        "shape": f"{p}x{e}x{a}",
    }]


def bench_recovery():
    """Crash-consistent durability leg (``--recovery`` runs it alone;
    ISSUE 10's acceptance gate), one kill-and-recover story on the
    8-rank δ ring:

    1. **durable run** — δ gossip rounds with ``wal=`` (irreducible δ
       records per round, ``on_round`` fsync), one generational
       snapshot mid-run, more rounds after it (the suffix a recovery
       must replay), then the process "dies" — all in-memory state is
       discarded.
    2. **local recovery** — a fresh WAL open (torn-tail scan) +
       ``recover_state`` (newest valid generation + one jitted
       scan-fold over the log suffix), TIMED, asserted bit-identical
       to the live state at the kill.
    3. **log-suffix rejoin** — the mesh kept converging during a real
       kill window (an extra churn round the dead rank never saw); the
       restarted rank rejoins by shipping the live peer's
       decomposition over its recovered state
       (``durability.recover.rejoin``) instead of receiving full
       state. The decomposition must ship < 25% of full-state resync
       bytes, and the healed state is asserted bit-identical to the
       full-state join."""
    import shutil
    import tempfile

    import jax
    import jax.numpy as jnp

    from crdt_tpu import durability as du
    from crdt_tpu.durability import snapshot as snap
    from crdt_tpu.ops import orswot as ops
    from crdt_tpu.parallel import make_mesh, mesh_delta_gossip

    n_dev = len(jax.devices())
    if n_dev < 2:
        log("recovery leg needs >= 2 devices for a ring; skipping")
        return []
    p = n_dev
    e = int(os.environ.get("BENCH_RECOVERY_ELEMS", 2048))
    a = int(os.environ.get("BENCH_RECOVERY_ACTORS", 8))
    mesh = make_mesh(p, 1)
    root = tempfile.mkdtemp(prefix="bench-recovery-")
    wal_dir = os.path.join(root, "wal")
    snap_dir = os.path.join(root, "snap")

    def churn(state, round_ix):
        rows = (jnp.arange(p) + round_ix * p) % e
        ctr = state.ctr.at[jnp.arange(p), rows, jnp.arange(p) % a].set(
            round_ix + 1
        )
        st = state._replace(
            ctr=ctr, top=jnp.maximum(state.top, jnp.max(ctr, axis=1))
        )
        dirty = jnp.zeros((p, e), bool).at[jnp.arange(p), rows].set(True)
        fctx = jnp.where(dirty[..., None], ctr, 0)
        return st, dirty, fctx

    rec, prev_rec, snap_base = _flight_start()
    try:
        # ---- 1. the durable run --------------------------------------
        # (telemetry= on so the WAL watermarks, fsyncs, snapshot
        # commit, and recovery interleave with per-dispatch telemetry
        # events on the flight recorder's timeline.)
        base = ops.empty(e, a, deferred_cap=2, batch=(p,))
        base = base._replace(
            ctr=base.ctr.at[:, : e // 2, 0].set(1),
            top=base.top.at[:, 0].set(1),
        )
        genesis = base
        w = du.Wal(wal_dir, fsync="on_round")
        st, d, f = churn(base, 1)
        out = mesh_delta_gossip(st, d, f, mesh, wal=w, telemetry=True)
        snap.save_state(
            snap_dir, "orswot", out[0], wal_seq=w.last_seq, retain=2,
        )
        rounds_after_snapshot = int(
            os.environ.get("BENCH_RECOVERY_SUFFIX_ROUNDS", 3)
        )
        for r in range(2, 2 + rounds_after_snapshot):
            st, d, f = churn(out[0], r)
            out = mesh_delta_gossip(st, d, f, mesh, wal=w, telemetry=True)
        final_at_kill = out[0]
        wal_bytes = w.bytes_appended
        wal_fsyncs = w.fsyncs
        w.close()  # the kill: everything in memory is gone

        # ---- 2. local recovery, timed --------------------------------
        t0 = time.perf_counter()
        w2 = du.Wal(wal_dir)
        recovered, rep = du.recover_state(
            snap_dir, w2, genesis, kind="orswot",
        )
        recovery_s = time.perf_counter() - t0
        w2.close()
        recovery_identical = all(
            bool(jnp.array_equal(x, y))
            for x, y in zip(
                jax.tree.leaves(recovered), jax.tree.leaves(final_at_kill)
            )
        )
        assert recovery_identical, (
            "recovery is not bit-identical to the state at the kill"
        )
        assert rep.replayed_records == rounds_after_snapshot

        # ---- 3. the kill window + log-suffix rejoin -------------------
        # The mesh kept converging while the rank was down: one more
        # churn round the dead rank never saw.
        st, d, f = churn(final_at_kill, 2 + rounds_after_snapshot)
        live_rows = mesh_delta_gossip(st, d, f, mesh)[0]
        dead_rank, peer = 0, 1
        live_peer = jax.tree.map(lambda x: x[peer], live_rows)
        rank_state = jax.tree.map(lambda x: x[dead_rank], recovered)
        t0 = time.perf_counter()
        healed, rj = du.rejoin("orswot", live_peer, rank_state)
        rejoin_s = time.perf_counter() - t0
        from crdt_tpu.analysis.registry import get_merge_kind

        full_join = get_merge_kind("orswot").join(live_peer, rank_state)
        full_join = (
            full_join[0] if isinstance(full_join, tuple) else full_join
        )
        rejoin_identical = all(
            bool(jnp.array_equal(x, y))
            for x, y in zip(jax.tree.leaves(healed), jax.tree.leaves(full_join))
        )
        assert rejoin_identical, (
            "log-suffix rejoin diverged from the full-state join"
        )
        assert rj.ratio < 0.25, (
            f"log-based rejoin shipped {rj.ratio:.1%} of full state"
        )
        flight = _flight_finish("recovery", rec, prev_rec, snap_base)
    except BaseException:
        from crdt_tpu import obs as _obs

        _obs.install(prev_rec)
        raise
    finally:
        shutil.rmtree(root, ignore_errors=True)

    log(
        f"config-recovery: {p} ranks x {e} elems: WAL "
        f"{wal_bytes:,.0f} B / {wal_fsyncs} fsyncs over "
        f"{1 + rounds_after_snapshot} durable rounds; recovery (gen "
        f"{rep.generation} + {rep.replayed_records}-record replay) in "
        f"{recovery_s:.3f}s, bit-identical; log-suffix rejoin shipped "
        f"{rj.bytes_shipped:,.0f} B = {rj.ratio:.1%} of full-state "
        f"({rj.bytes_full_state:,.0f} B) in {rejoin_s:.3f}s, "
        f"bit-identical"
    )
    return [{
        "config": "recovery", "metric": "rejoin_bytes_ratio",
        "value": round(rj.ratio, 4), "unit": "ratio",
        "recovery_seconds": round(recovery_s, 4),
        "replayed_records": rep.replayed_records,
        "snapshot_generation": rep.generation,
        "wal_bytes": wal_bytes, "wal_fsyncs": wal_fsyncs,
        "rejoin_bytes_shipped": rj.bytes_shipped,
        "rejoin_bytes_full_state": rj.bytes_full_state,
        "rejoin_lanes_shipped": rj.lanes_shipped,
        "rejoin_seconds": round(rejoin_s, 4),
        "bit_identical": recovery_identical and rejoin_identical,
        "shape": f"{p}x{e}x{a}",
        **flight,
    }]


def bench_scaleout():
    """Elastic mesh scale-out leg (``--scaleout`` runs it alone; ISSUE
    11's acceptance gate), one resize trajectory on the 8-rank axis:

    1. **plateau** — the mesh serves a δ-gossip workload on P-2 live
       ranks (the other two parked — newcomer self-loops), sustained
       replica-join throughput TIMED over warmed runs, every converged
       read asserted bit-identical to the fixed-width oracle.
    2. **scale-out** — a traffic spike drives the Autoscaler's folded
       pressure to 1.0; after the debounce clears it recommends admits,
       and both parked ranks JOIN live: bootstrapped by decomposition
       lanes (cold, from ⊥), ring re-traced under a bumped generation.
       Sustained merges/s is re-measured on the widened mesh and must
       RISE over the pre-admit plateau; reads stay bit-identical.
    3. **warm-start gate** — a separate snapshot-based bootstrap (the
       PR 10 tier as the causal lower bound) must ship < 25% of
       full-state bytes — the log-suffix path, measured, asserted.
    4. **scale-in** — quiet traffic debounces a drain vote; the drained
       rank flushes, its drain-complete certificate must hold
       (residue == 0, nothing lost, zero unacked out-lanes), the row
       parks, and the narrowed mesh still reads bit-identical.

    The damage-free capacity trajectory (merges/s before/after, the
    bootstrap byte ratios, the certificate) is the metric."""
    import random

    import jax
    import jax.numpy as jnp

    from crdt_tpu import elastic, telemetry as tele
    from crdt_tpu.faults.scenarios import genesis_tracking, mint_streams
    from crdt_tpu.models import BatchedOrswot
    from crdt_tpu.ops import orswot as ops
    from crdt_tpu.parallel import make_mesh, mesh_delta_gossip, mesh_gossip
    from crdt_tpu.parallel.mesh import shard_orswot
    from crdt_tpu.scaleout import Autoscaler, ScaleoutMesh, bootstrap, park_row
    from crdt_tpu.utils import Interner

    n_dev = len(jax.devices())
    if n_dev < 4:
        log("scaleout leg needs >= 4 devices; skipping")
        return []
    p = min(n_dev, 8)
    runs = int(os.environ.get("BENCH_SCALEOUT_RUNS", 4))
    seed = int(os.environ.get("BENCH_SCALEOUT_SEED", 23))
    rng = random.Random(seed)
    live0 = p - 2
    sites, _ = mint_streams(rng, live0, 6 * p)
    batched = BatchedOrswot.from_pure(
        sites,
        members=Interner(list(range(5))),
        actors=Interner([f"s{i}" for i in range(p)]),
    )
    mesh = make_mesh(p, 1)
    cur = shard_orswot(batched.state, mesh)
    sm = ScaleoutMesh(p, live=range(live0))
    policy = elastic.ElasticPolicy(
        low_water=0.2, shrink_rounds=2, high_water=0.8, widen_rounds=2
    )
    autoscaler = Autoscaler(sm, policy, min_live=2)
    fix = jax.tree.map(
        lambda x: x[0], mesh_gossip(cur, mesh, local_fold="tree")[0]
    )

    tracking = genesis_tracking

    def identical(rows) -> bool:
        return all(
            all(
                bool(jnp.array_equal(x, y))
                for x, y in zip(
                    jax.tree.leaves(jax.tree.map(lambda v: v[i], rows)),
                    jax.tree.leaves(fix),
                )
            )
            for i in sm.live()
        )

    # One ring round-trip per run; replica joins applied by LIVE ranks
    # only (parked self-loop applies are deselected no-ops), so the
    # honest sustained rate is live_ranks x ring rounds per wall
    # second — the quantity more chips must raise.
    rounds = 2 * (p - 1) - 1  # the pipelined certificate window

    # The whole trajectory records to a flight recorder: generation
    # changes, admits, votes, the drain certificate — plus telemetry
    # events (with in-kernel histograms) from the measured runs.
    rec, prev_rec, base_counters = _flight_start()
    from crdt_tpu import obs as _obs

    try:

        def measure(state):
            # The timed loop stays UN-instrumented (telemetry host drains
            # would flatten the rate comparison); one telemetry'd
            # observation dispatch follows per phase, below.
            plan = sm.plan()
            d, f = tracking(state)  # warmup: compile this membership's ring
            warm = mesh_delta_gossip(state, d, f, mesh, local_fold="tree",
                                     faults=plan)
            jax.block_until_ready(jax.tree.leaves(warm[0]))
            state, res = warm[0], int(warm[3])
            t0 = time.perf_counter()
            for _ in range(runs):
                d, f = tracking(state)
                out = mesh_delta_gossip(state, d, f, mesh, local_fold="tree",
                                        faults=plan)
                state, res = out[0], int(out[3])
            jax.block_until_ready(jax.tree.leaves(state))
            dt = time.perf_counter() - t0
            joins = len(sm.live()) * rounds * runs
            return state, res, joins / dt, dt

        def observe_tel(state):
            # One OFF-the-clock telemetry'd dispatch per phase: the flight
            # recorder gets a per-phase telemetry event (with the in-kernel
            # histograms) and a snapshot delta, the timed numbers stay
            # honest. Joins are idempotent — the converged state is
            # bit-unchanged.
            d, f = tracking(state)
            out = mesh_delta_gossip(state, d, f, mesh, local_fold="tree",
                                    faults=sm.plan(), telemetry=True)
            rec.snapshot_delta()
            return out[0]

        # 1. plateau at P-2.
        cur, res_pre, rate_pre, pre_s = measure(cur)
        assert res_pre == 0, "plateau must certify"
        assert identical(cur), "plateau reads diverged from the oracle"
        cur = observe_tel(cur)

        # 2. spike -> debounced admits -> widened mesh.
        admits = 0
        boot_reports = []
        while sm.parked:
            dec = autoscaler.observe(load=1.0)
            if dec is None:
                continue
            assert dec.action == "admit"
            cur, rep = sm.admit(1, kind="orswot", rows=cur)
            boot_reports.extend(rep.bootstraps)
            admits += 1
        cur, res_post, rate_post, post_s = measure(cur)
        assert res_post == 0, "widened mesh must certify"
        assert identical(cur), "post-admit reads diverged from the oracle"
        cur = observe_tel(cur)
        gain = rate_post / rate_pre if rate_pre else 0.0
        assert rate_post > rate_pre, (
            f"admit must raise sustained merges/s "
            f"({rate_pre:.0f} -> {rate_post:.0f})"
        )

        # 3. warm-start byte gate: snapshot base ships only the log suffix.
        e_w, a_w = 512, 8
        empty_w = ops.empty(e_w, a_w, 2)
        snap_base = empty_w._replace(
            ctr=empty_w.ctr.at[: e_w // 3, 0].set(1)
        )
        live_w = snap_base._replace(
            ctr=snap_base.ctr.at[: e_w // 25, 1].set(2),
            top=snap_base.top.at[0].set(1).at[1].set(2),
        )
        _, warm_rep = bootstrap("orswot", live_w, base=snap_base)
        assert warm_rep.ratio < 0.25, (
            f"warm bootstrap shipped {warm_rep.ratio:.1%} of full-state bytes"
        )

        # 4. quiet -> debounced drain -> certified scale-in.
        dec = None
        while dec is None:
            dec = autoscaler.observe(load=0.0)
        assert dec.action == "drain"
        d, f = tracking(cur)
        flush = mesh_delta_gossip(cur, d, f, mesh, local_fold="tree",
                                  faults=sm.plan())
        cert = sm.drain(dec.rank, kind="orswot", rows=flush[0],
                        residue=int(flush[3]))
        cur = park_row(flush[0], dec.rank)
        cur, res_in, rate_in, _ = measure(cur)
        assert res_in == 0 and identical(cur), (
            "post-drain reads diverged from the oracle"
        )
        cur = observe_tel(cur)

        tel = sm.annotate(tele.zeros())
        tele.record("scaleout", tel)
        flight = _flight_finish("scaleout", rec, prev_rec, base_counters)
    except BaseException:
        _obs.install(prev_rec)
        raise
    cold_ratio = (
        sum(r.ratio for r in boot_reports) / len(boot_reports)
        if boot_reports else 0.0
    )
    log(
        f"config-scaleout: {p}-rank axis {live0}->{p}->{p - 1} live: "
        f"sustained {rate_pre:.0f} -> {rate_post:.0f} joins/s "
        f"({gain:.2f}x) across the admit, warm bootstrap "
        f"{warm_rep.ratio:.1%} of full-state bytes (cold {cold_ratio:.1%}), "
        f"drain rank {dec.rank} certified (residue {cert.residue}, "
        f"unacked {cert.lanes_unacked}) at generation {sm.generation}; "
        f"reads bit-identical in both directions; flight dump replayed "
        f"bit-exact ({flight['flight_events']} events)"
    )
    return [{
        "config": "scaleout", "metric": "scaleout_merge_rate_gain",
        "value": round(gain, 3), "unit": "x",
        "merges_per_s_pre_admit": round(rate_pre, 1),
        "merges_per_s_post_admit": round(rate_post, 1),
        "merges_per_s_post_drain": round(rate_in, 1),
        "live_ranks_trajectory": [live0, p, p - 1],
        "admits": admits, "drains": 1,
        "bootstrap_cold_ratio": round(cold_ratio, 4),
        "bootstrap_warm_ratio": round(warm_rep.ratio, 4),
        "bootstrap_bytes": round(sm.bootstrap_bytes, 1),
        "drain_residue": cert.residue,
        "drain_lanes_unacked": cert.lanes_unacked,
        "drain_packets_lost": cert.packets_lost,
        "generation": sm.generation,
        "bit_identical": True,
        "runs": runs,
        "shape": f"{p}x{cur.ctr.shape[-2]}",
        **flight,
    }]


def bench_serve():
    """Multi-tenant serving front door leg (``--serve`` runs it alone;
    ISSUE 15's acceptance gate, ROADMAP item 1): a ≥1M-tenant live
    population served from a 4×-smaller device-resident lane pool
    through the tenant-packed superblock —

    1. **churn window, timed** — cycles of per-tenant op streams (a
       rotating hot set + a uniform tail over the whole population)
       through the ingest queue's coalesced slab applies
       (``mesh_serve_apply``), per-dispatch wall-clock riding
       ``hist_dispatch_us`` (the p99 apply latency of record).
    2. **evict→restore inside the window** — mid-window, a cohort of
       the coldest dirty tenants moves to the PR 10 snapshot tier
       (persist-then-clear, lanes freed), then a re-touch slice of the
       cohort restores from disk on its next op — the cold-tenant
       cycle the acceptance gate demands.
    3. **oracle bit-identity** — a sampled subset of touched tenants
       (re-touched evictees included) replays its FULL op history
       through the per-tenant sequential oracle and must match the
       served row bit-exactly.

    The SAME committed shape runs on the CPU stand-in mesh — the gate
    is ≥1M live tenants THERE, so there is no cpu_fallback downscale.
    """
    import shutil
    import tempfile

    import jax

    from crdt_tpu import telemetry as tele
    from crdt_tpu.fanout import FanoutPlane
    from crdt_tpu.obs import hist as obs_hist
    from crdt_tpu.obs import trace as obs_trace
    from crdt_tpu.ops import superblock as sb_ops
    from crdt_tpu.parallel import make_mesh
    from crdt_tpu.serve import Evictor, IngestQueue, Superblock

    cfg = bench_configs()["serve"]

    def knob(key, env):
        return int(os.environ.get(env, cfg[key]))

    tenants = knob("tenants", "BENCH_SERVE_TENANTS")
    lanes = knob("lanes", "BENCH_SERVE_LANES")
    slab_lanes = knob("slab_lanes", "BENCH_SERVE_SLAB_LANES")
    slab_depth = knob("slab_depth", "BENCH_SERVE_SLAB_DEPTH")
    cycles = knob("cycles", "BENCH_SERVE_CYCLES")
    ops_per_cycle = knob("ops_per_cycle", "BENCH_SERVE_OPS_PER_CYCLE")
    hot_set = knob("hot_set", "BENCH_SERVE_HOT_SET")
    hot_shift = cfg["hot_shift"]
    evict_cohort = knob("evict_cohort", "BENCH_SERVE_EVICT_COHORT")
    retouch = cfg["retouch"]
    oracle_sample = cfg["oracle_sample"]
    trace_sample = knob("trace_sample", "BENCH_SERVE_TRACE_SAMPLE")
    p = min(cfg["mesh"][0], len(jax.devices()))
    mesh = make_mesh(p, 1)
    caps = dict(
        n_elems=cfg["elems"], n_actors=cfg["actors"],
        deferred_cap=cfg["deferred_cap"],
    )
    e, a = caps["n_elems"], caps["n_actors"]

    sb = Superblock(tenants, mesh, kind="orswot", caps=caps, n_lanes=lanes)
    root = tempfile.mkdtemp(prefix="bench-serve-")
    ev = Evictor(sb, root, pressure_batch=256)
    q = IngestQueue(
        sb, lanes=slab_lanes, depth=slab_depth, max_pending=1 << 20,
        evictor=ev,
    )
    # The trace-completion plane (ISSUE 17): freshness is
    # submit→client-ack, so every SAMPLED tenant gets one thin
    # subscriber and the touched traced tenants' δs are pushed + acked
    # at each cycle's end — sampled journeys complete inside the
    # measured window instead of dying at dispatch.
    traced = np.nonzero(obs_trace.sampled_mask(tenants, trace_sample))[0]
    fan = FanoutPlane(
        sb, evictor=ev, window_cap=4, dispatch_lanes=256,
        capacity=max(len(traced), 1),
    )
    sub_ids = fan.subscribe(traced)
    rng = np.random.default_rng(151)
    next_ctr = np.zeros(tenants, np.uint32)
    history: dict = {}  # tenant -> [(kind, actor, ctr, clock, member)]

    def submit_cycle(cycle, n_ops):
        off = (cycle * hot_shift) % max(tenants - hot_set, 1)
        hot = rng.integers(off, off + hot_set, n_ops)
        uni = rng.integers(0, tenants, n_ops)
        ts = np.where(rng.random(n_ops) < 0.85, hot, uni)
        is_add = rng.random(n_ops) < 0.85
        masks = rng.random((n_ops, e)) < 0.4
        for i in range(n_ops):
            t = int(ts[i])
            act = t % a
            m = masks[i]
            if is_add[i] or next_ctr[t] == 0:
                c = int(next_ctr[t]) + 1
                next_ctr[t] = c
                q.add(t, act, c, m)
                history.setdefault(t, []).append(
                    (sb_ops.ADD, act, c, None, m)
                )
            else:
                # Covered remove (clock at the tenant's applied top):
                # kills dots now, parks nothing — the serving steady
                # state never trips the deferred bound.
                clock = np.zeros(a, np.uint32)
                clock[act] = next_ctr[t]
                q.rm(t, clock, m)
                history.setdefault(t, []).append(
                    (sb_ops.RM, 0, 0, clock, m)
                )
        return np.unique(ts)

    def touch_one(t_):
        """One explicit add (retouch / warmup seeding) that stays in
        the oracle history like every other op."""
        act = t_ % a
        c = int(next_ctr[t_]) + 1
        next_ctr[t_] = c
        m = rng.random(e) < 0.4
        q.add(t_, act, c, m)
        history.setdefault(t_, []).append((sb_ops.ADD, act, c, None, m))

    tr = prev_tr = None
    rec, prev_rec, snap_base = _flight_start(capacity=32768)
    try:
        # Warmup (compiles the apply + telemetry programs AND the
        # trace-completion fan-out dispatch; its ops are real and stay
        # in the oracle histories — only the TIMING is excluded from
        # the measured window).
        submit_cycle(0, 256)
        touch_one(int(traced[0]))  # a dirty traced tenant → push compiles
        q.drain(telemetry=True)
        fan.push(tenants=traced[:1])
        fan.ack(sub_ids)

        # The tracer installs AFTER warmup: every sampled journey it
        # mints belongs to the measured window.
        tr = obs_trace.Tracer(sample=trace_sample)
        prev_tr = obs_trace.install_tracer(tr)

        tel = None
        total_ops = 0
        n_evicted = 0
        restored_in_window = 0
        retouch_set = []
        t0 = time.perf_counter()
        for cycle in range(1, cycles + 1):
            submit_cycle(cycle, ops_per_cycle)
            rep, t = q.drain(telemetry=True)
            total_ops += rep.ops_applied
            if cycle == cycles // 2:
                # The cold-tenant cycle, inside the measured window:
                # evict the coldest dirty cohort, then re-touch a slice
                # so it restores from disk on its next op.
                cold = ev.select_cold(evict_cohort)
                n_evicted = ev.evict(cold)
                retouch_set = cold[:retouch]
                for t_ in retouch_set:
                    touch_one(t_)
                rep2, t2 = q.drain(telemetry=True)
                total_ops += rep2.ops_applied
                restored_in_window = rep2.restored
                if t2 is not None:
                    tel = t2 if tel is None else tele.combine(tel, t2)
                    tele.record("serve", t2)
            # Close the cycle's sampled journeys: push every tenant
            # with an open trace (all sampled, all subscribed) and ack
            # its subscriber — freshness is submit→client-ack.
            open_t = list(tr.open_traces())
            if open_t:
                fan.push(tenants=open_t)
                fan.ack(sub_ids)
            if t is not None:
                # Annotate AFTER the acks so the record carries the
                # cycle's own trace-latency histogram increments.
                t = tr.annotate(t)
                tel = t if tel is None else tele.combine(tel, t)
                tele.record("serve", t)
        window_s = time.perf_counter() - t0
        fresh = obs_hist.summary(tr.freshness_dict())
        skew = obs_trace.skew_report(evictor=ev, queue=q, tracer=tr, k=8)
        traces_minted, traces_completed = tr.minted, tr.completed
        obs_trace.install_tracer(prev_tr)
        assert traces_completed >= 1, (
            "no sampled op journey completed inside the measured window"
        )
        d = tele.to_dict(tel)
        disp = obs_hist.summary(d["hist_dispatch_us"])
        # The flight artifact covers the MEASURED window: finish (and
        # bit-exact-cross-check) it before the oracle phase, whose
        # verification restores page cold tenants in bulk and would
        # roll the ring past the window's telemetry events.
        flight = _flight_finish("serve", rec, prev_rec, snap_base, slo=True)

        # Oracle bit-identity on a sampled subset (re-touched evictees
        # first — they crossed the durable tier inside the window).
        touched = np.asarray(sorted(history))
        sample = list(retouch_set[: oracle_sample // 3])
        rest = rng.choice(
            touched, min(oracle_sample - len(sample), len(touched)),
            replace=False,
        )
        sample += [int(x) for x in rest if int(x) not in set(sample)]
        tk = sb.tk
        mismatches = 0
        for t_ in sample:
            ev.restore(t_)
            want = sb_ops.sequential_oracle(
                tk, tk.empty(**sb.caps), history[t_]
            )
            got = sb.row(t_)
            if not all(
                bool(np.array_equal(np.asarray(x), np.asarray(y)))
                for x, y in zip(
                    jax.tree.leaves(got), jax.tree.leaves(want)
                )
            ):
                mismatches += 1
        bit_identical = mismatches == 0
        assert bit_identical, (
            f"{mismatches}/{len(sample)} sampled tenants diverged from "
            f"the per-tenant sequential oracle"
        )
        assert tenants >= 1_000_000, (
            f"serve leg ran only {tenants} tenants — the gate is 1M+"
        )
        assert n_evicted >= 1 and restored_in_window >= 1, (
            "no cold-tenant evict→restore cycle in the measured window"
        )
    except BaseException:
        from crdt_tpu import obs as _obs

        if tr is not None and obs_trace.get_tracer() is tr:
            obs_trace.install_tracer(prev_tr)
        _obs.install(prev_rec)
        raise
    finally:
        shutil.rmtree(root, ignore_errors=True)

    ratio = lanes / tenants
    log(
        f"config-serve: {tenants:,} live tenants on {lanes:,} lanes "
        f"({ratio:.0%} resident, {sb.nbytes() / 1e6:.0f} MB superblock): "
        f"{total_ops:,} ops in {window_s:.2f}s = "
        f"{total_ops / window_s:,.0f} ops/s sustained; dispatch p50 "
        f"{disp['p50']:,.0f} us / p99 {disp['p99']:,.0f} us; evicted "
        f"{n_evicted} cold tenants, {restored_in_window} restored from "
        f"disk in-window; {len(sample)} tenants oracle-checked "
        f"bit-identical; coalesced {d['ingest_coalesced_ops']:,} ops; "
        f"freshness p50 {fresh['p50']:,.0f} us / p95 "
        f"{fresh['p95']:,.0f} us / p99 {fresh['p99']:,.0f} us over "
        f"{traces_completed} traced journeys (1/{trace_sample} tenants)"
    )
    return [{
        "config": "serve", "metric": "serve_ops_per_sec",
        "value": round(total_ops / window_s, 1), "unit": "ops/s",
        "tenants": tenants, "lanes": lanes,
        "live_tenants": d["live_tenants"],
        "evicted_tenants": d["evicted_tenants"],
        "dispatch_p50_us": round(disp["p50"], 1),
        "dispatch_p99_us": round(disp["p99"], 1),
        "ops_applied": total_ops,
        "window_seconds": round(window_s, 3),
        "ingest_coalesced_ops": d["ingest_coalesced_ops"],
        "peak_resident_bytes": sb.nbytes(),
        "all_resident_equiv_bytes": sb.row_nbytes() * tenants,
        "resident_ratio": round(ratio, 4),
        "evict_cohort": n_evicted,
        "evict_restored_in_window": restored_in_window,
        "widen_events": sb.widen_events,
        "oracle_sampled": len(sample),
        "bit_identical": bit_identical,
        "freshness_p50_us": round(fresh["p50"], 1),
        "freshness_p95_us": round(fresh["p95"], 1),
        "freshness_p99_us": round(fresh["p99"], 1),
        "traces_minted": traces_minted,
        "traces_completed": traces_completed,
        "trace_sample": trace_sample,
        "hot_tenants": skew["tenants"],
        "shape": f"{tenants}x{e}x{a}@{lanes}lanes",
        **flight,
    }]


def bench_serve_zipf():
    """Pipelined always-on serving leg (rides ``--serve``; ISSUE 18's
    acceptance gate): a zipf-popularity op stream through the
    WAL-logged pipelined :class:`ServeLoop` —

    1. **serial baseline, timed** — the SAME pregenerated op schedule
       first runs through PR 15's serial flush loop (assemble → WAL →
       dispatch → wait, one round at a time, its own WAL dir), then
       through the pipelined loop (slab N+1 assembles + WAL-commits
       while slab N's scatter is in flight, cold persists on the
       background drain) — the ops/s ratio is the pipelining win and
       ``overlap_hit`` counts the rounds host work genuinely hid
       device time.
    2. **hot-shard skew event** — the middle third of the window
       multiplies one shard's draw popularity by ``skew_factor`` (10×);
       after its first skewed cycle the evictor's touch stats drive
       ``serve.shard.rebalance`` (placement overrides, minimal-move),
       and the record reports p99 dispatch latency before/during/after
       plus the max/mean host-load ratio at skew onset and
       post-rebalance.
    3. **kill-anywhere durability** — after the window a FRESH
       superblock recovers from the snapshot tier + serve-WAL replay
       (the same bit-identical apply path) and every sampled tenant
       must match the served row bit-exactly — zero acked ops lost.
       The pipelined rows are also checked against the serial
       baseline's AND the per-tenant sequential oracle.
    """
    import shutil
    import tempfile

    import jax

    from crdt_tpu import telemetry as tele
    from crdt_tpu.obs import hist as obs_hist
    from crdt_tpu.ops import superblock as sb_ops
    from crdt_tpu.parallel import make_mesh
    from crdt_tpu.serve import (
        Evictor,
        IngestQueue,
        ServeLoop,
        ServeWal,
        Superblock,
        TenantShardMap,
        host_loads,
        rebalance,
        recover_serve,
    )

    cfg = bench_configs()["serve"]

    def knob(key, env):
        return int(os.environ.get(env, cfg[key]))

    tenants = knob("zipf_tenants", "BENCH_SERVE_ZIPF_TENANTS")
    lanes = knob("zipf_lanes", "BENCH_SERVE_ZIPF_LANES")
    slab_lanes = knob("zipf_slab_lanes", "BENCH_SERVE_ZIPF_SLAB_LANES")
    slab_depth = knob("zipf_slab_depth", "BENCH_SERVE_ZIPF_SLAB_DEPTH")
    cycles = knob("zipf_cycles", "BENCH_SERVE_ZIPF_CYCLES")
    ops_per_cycle = knob(
        "zipf_ops_per_cycle", "BENCH_SERVE_ZIPF_OPS_PER_CYCLE"
    )
    alpha = float(os.environ.get(
        "BENCH_SERVE_ZIPF_ALPHA", cfg["zipf_alpha"]
    ))
    skew_factor = float(cfg["zipf_skew_factor"])
    hosts = int(cfg["zipf_hosts"])
    oracle_sample = int(cfg["zipf_oracle_sample"])
    persist_ahead = knob(
        "zipf_persist_ahead", "BENCH_SERVE_ZIPF_PERSIST_AHEAD"
    )
    rebalance_top = int(cfg["zipf_rebalance_top"])
    p = min(cfg["mesh"][0], len(jax.devices()))
    mesh = make_mesh(p, 1)
    caps = dict(
        n_elems=cfg["elems"], n_actors=cfg["actors"],
        deferred_cap=cfg["deferred_cap"],
    )
    e, a = caps["n_elems"], caps["n_actors"]
    rng = np.random.default_rng(181)

    # Zipf popularity over a shuffled rank order, plus the skewed
    # variant: the hottest tenant's OWN shard gets skew_factor× draw
    # weight for the middle third of the window.
    shard = TenantShardMap(hosts)
    ranks = rng.permutation(tenants).astype(np.float64)
    base_w = 1.0 / (ranks + 1.0) ** alpha
    owner0 = np.asarray([shard.owner(t) for t in range(tenants)])
    hot_host = int(owner0[int(np.argmin(ranks))])
    skew_w = base_w * np.where(owner0 == hot_host, skew_factor, 1.0)
    p_base = base_w / base_w.sum()
    p_skew = skew_w / skew_w.sum()

    # Pregenerate the FULL op schedule (warmup cycle 0 + the window) so
    # the serial baseline and the pipelined loop apply bit-identical
    # streams; the oracle history falls out of the same pass.
    next_ctr = np.zeros(tenants, np.uint32)
    history: dict = {}
    third = max(cycles // 3, 1)
    during = range(third + 1, 2 * third + 1)

    def gen_cycle(n_ops, pv):
        ts = rng.choice(tenants, size=n_ops, p=pv)
        adds = rng.random(n_ops) < 0.85
        masks = rng.random((n_ops, e)) < 0.4
        ops = []
        for i in range(n_ops):
            t = int(ts[i])
            act = t % a
            m = masks[i]
            if adds[i] or next_ctr[t] == 0:
                c = int(next_ctr[t]) + 1
                next_ctr[t] = c
                op = (t, sb_ops.ADD, act, c, None, m)
            else:
                clock = np.zeros(a, np.uint32)
                clock[act] = next_ctr[t]
                op = (t, sb_ops.RM, 0, 0, clock, m)
            ops.append(op)
            history.setdefault(t, []).append(op[1:])
        return ops

    schedule = [gen_cycle(256, p_base)]  # cycle 0 = compile warmup
    for cycle in range(1, cycles + 1):
        schedule.append(gen_cycle(
            ops_per_cycle, p_skew if cycle in during else p_base
        ))

    def submit(q, ops):
        for t, k, act, c, clock, m in ops:
            if k == sb_ops.ADD:
                q.add(t, act, c, m)
            else:
                q.rm(t, clock, m)

    root = tempfile.mkdtemp(prefix="bench-serve-zipf-")
    rec, prev_rec, snap_base = _flight_start(capacity=16384)
    try:
        # ---- serial baseline: PR 15's flush loop, WAL and all -------
        sb_s = Superblock(
            tenants, mesh, kind="orswot", caps=caps, n_lanes=lanes,
        )
        ev_s = Evictor(sb_s, os.path.join(root, "tier_serial"))
        wal_s = ServeWal(os.path.join(root, "wal_serial"))
        q_s = IngestQueue(
            sb_s, lanes=slab_lanes, depth=slab_depth,
            max_pending=1 << 20, evictor=ev_s, wal=wal_s,
        )
        submit(q_s, schedule[0])
        q_s.drain()  # compile outside the timed window
        t0 = time.perf_counter()
        for cycle in range(1, cycles + 1):
            submit(q_s, schedule[cycle])
            q_s.drain(telemetry=True)
        serial_s = time.perf_counter() - t0
        wal_s.close()

        # ---- the pipelined loop over the same schedule --------------
        sb = Superblock(
            tenants, mesh, kind="orswot", caps=caps, n_lanes=lanes,
        )
        ev = Evictor(sb, os.path.join(root, "tier"))
        swal = ServeWal(os.path.join(root, "wal"))
        q = IngestQueue(
            sb, lanes=slab_lanes, depth=slab_depth,
            max_pending=1 << 20, evictor=ev, wal=swal,
        )
        loop = ServeLoop(q, persist_ahead=persist_ahead)
        submit(q, schedule[0])
        loop.drain()  # warmup: compile + settle the pipeline
        phase_tel = {"before": None, "during": None, "after": None}
        moves = 0
        load_ratio_onset = load_ratio_after = 0.0
        total_ops = 0
        dispatches = 0
        t0 = time.perf_counter()
        for cycle in range(1, cycles + 1):
            phase = ("before" if cycle <= third else
                     "during" if cycle in during else "after")
            submit(q, schedule[cycle])
            # Keep stepping while THIS cycle's ops are placeable; the
            # in-flight slab rides across the cycle boundary — the
            # always-on pipeline never drains between cycles.
            while q.n_pending:
                before_p = q.n_pending
                rep, t = loop.step(telemetry=True)
                if rep is not None:
                    total_ops += rep.ops_applied
                    dispatches += rep.dispatches
                if t is not None:
                    phase_tel[phase] = (
                        t if phase_tel[phase] is None
                        else tele.combine(phase_tel[phase], t)
                    )
                    tele.record("serve", t)
                if q.n_pending >= before_p and loop.inflight is None:
                    break  # nothing placeable (cannot happen; guard)
            if cycle == third + 1:
                # First skewed cycle done: the evictor's touch stats
                # ARE the heat signal — plan + land the overrides.
                tc = ev.touch_count
                top = np.argsort(tc)[-rebalance_top:]
                wts = {int(t_): float(tc[t_]) for t_ in top if tc[t_]}
                if wts:
                    lb = host_loads(shard, list(wts), wts)
                    mean = sum(lb.values()) / max(len(lb), 1)
                    load_ratio_onset = max(lb.values()) / max(mean, 1e-9)
                    plan = rebalance(
                        shard, list(wts), wts, threshold=1.25,
                    )
                    moves = len(plan)
                    loop.note_rebalance(moves)
                    la = host_loads(shard, list(wts), wts)
                    load_ratio_after = (
                        max(la.values()) / max(mean, 1e-9)
                    )
        rep, t = loop.flush_inflight(telemetry=True)
        if rep is not None:
            total_ops += rep.ops_applied
            dispatches += rep.dispatches
        if t is not None:
            phase = "after"
            phase_tel[phase] = (
                t if phase_tel[phase] is None
                else tele.combine(phase_tel[phase], t)
            )
            tele.record("serve", t)
        window_s = time.perf_counter() - t0
        wal_bytes = swal.bytes_appended
        wal_fsyncs = swal.fsyncs
        overlap_hits = loop.overlap_hits
        bg_persists = loop.persister.persisted if loop.persister else 0
        swal.sync()

        p99 = {}
        for ph, t in phase_tel.items():
            d = tele.to_dict(t) if t is not None else None
            p99[ph] = (
                obs_hist.summary(d["hist_dispatch_us"])["p99"]
                if d else 0.0
            )
        tel_all = None
        for t in phase_tel.values():
            if t is not None:
                tel_all = t if tel_all is None else tele.combine(tel_all, t)
        d_all = tele.to_dict(tel_all)

        # The flight artifact covers the measured window; finish (and
        # bit-exact-cross-check) before the oracle/recovery phases
        # restore tenants in bulk.
        flight = _flight_finish("serve_zipf", rec, prev_rec, snap_base)

        # ---- oracle + serial-equivalence + recovery bit-identity ----
        touched = np.asarray(sorted(history))
        hot_sample = touched[np.argsort(ranks[touched])][:oracle_sample // 2]
        rest = rng.choice(
            touched, min(oracle_sample, len(touched)), replace=False,
        )
        sample = sorted({int(x) for x in hot_sample} | {
            int(x) for x in rest
        })[:oracle_sample]
        tk = sb.tk
        oracle_mm = serial_mm = 0
        for t_ in sample:
            ev.restore(t_)
            ev_s.restore(t_)
            got = sb.row(t_)
            want = sb_ops.sequential_oracle(
                tk, tk.empty(**sb.caps), history[t_]
            )
            base = sb_s.row(t_)
            leaves = lambda s: [np.asarray(x) for x in jax.tree.leaves(s)]  # noqa: E731
            if not all(
                np.array_equal(x, y)
                for x, y in zip(leaves(got), leaves(want))
            ):
                oracle_mm += 1
            if not all(
                np.array_equal(x, y)
                for x, y in zip(leaves(got), leaves(base))
            ):
                serial_mm += 1
        assert oracle_mm == 0, (
            f"{oracle_mm}/{len(sample)} sampled tenants diverged from "
            f"the per-tenant sequential oracle under the pipelined loop"
        )
        assert serial_mm == 0, (
            f"{serial_mm}/{len(sample)} sampled tenants diverged "
            f"between the pipelined loop and the serial baseline"
        )

        # Kill-anywhere recovery: a FRESH superblock + snapshot tier +
        # serve-WAL replay must land every sampled row bit-identically
        # — the zero-acked-op-loss gate of record.
        swal.close()
        sb_r = Superblock(
            tenants, mesh, kind="orswot", caps=caps, n_lanes=lanes,
        )
        ev_r = Evictor(sb_r, os.path.join(root, "tier"))
        q_r = IngestQueue(
            sb_r, lanes=slab_lanes, depth=slab_depth,
            max_pending=1 << 20, evictor=ev_r,
        )
        with ServeWal(os.path.join(root, "wal")) as swal_r:
            replayed = recover_serve(
                os.path.join(root, "tier"), q_r, swal_r,
            )
        recov_mm = 0
        for t_ in sample:
            ev.restore(t_)
            ev_r.restore(t_)
            leaves = lambda s: [np.asarray(x) for x in jax.tree.leaves(s)]  # noqa: E731
            if not all(
                np.array_equal(x, y)
                for x, y in zip(leaves(sb.row(t_)), leaves(sb_r.row(t_)))
            ):
                recov_mm += 1
        assert recov_mm == 0, (
            f"{recov_mm}/{len(sample)} sampled tenants lost acked ops "
            f"across the kill/recover boundary — the WAL-before-"
            f"dispatch contract is broken"
        )
        bit_identical = oracle_mm == serial_mm == recov_mm == 0
    except BaseException:
        from crdt_tpu import obs as _obs

        _obs.install(prev_rec)
        raise
    finally:
        shutil.rmtree(root, ignore_errors=True)

    serial_ops = total_ops / max(serial_s, 1e-9)
    pipe_ops = total_ops / max(window_s, 1e-9)
    overlap_ratio = overlap_hits / max(dispatches, 1)
    skew_ratio = p99["during"] / max(p99["before"], 1e-9)
    log(
        f"config-serve_zipf: zipf(α={alpha}) over {tenants:,} tenants "
        f"on {lanes:,} lanes, {skew_factor:.0f}× hot-shard skew "
        f"mid-window: {total_ops:,} ops pipelined in {window_s:.2f}s = "
        f"{pipe_ops:,.0f} ops/s (serial baseline {serial_ops:,.0f} "
        f"ops/s, {pipe_ops / max(serial_ops, 1e-9):.2f}×); overlap hit "
        f"{overlap_hits}/{dispatches} dispatches ({overlap_ratio:.0%});"
        f" WAL {wal_bytes:,} bytes / {wal_fsyncs} fsyncs; dispatch p99 "
        f"{p99['before']:,.0f}/{p99['during']:,.0f}/{p99['after']:,.0f}"
        f" us before/during/after skew; {moves} rebalance moves "
        f"(load ratio {load_ratio_onset:.2f}→{load_ratio_after:.2f}); "
        f"{bg_persists} background persists; {replayed.ops:,} ops "
        f"replayed on recovery; {len(sample)} tenants oracle+serial+"
        f"recovery bit-identical"
    )
    return [{
        "config": "serve_zipf", "metric": "serve_zipf_ops_per_sec",
        "value": round(pipe_ops, 1), "unit": "ops/s",
        "tenants": tenants, "lanes": lanes,
        "zipf_alpha": alpha, "skew_factor": skew_factor,
        "hosts": hosts, "hot_host": hot_host,
        "ops_applied": total_ops,
        "window_seconds": round(window_s, 3),
        "serial_ops_per_sec": round(serial_ops, 1),
        "pipeline_speedup": round(pipe_ops / max(serial_ops, 1e-9), 3),
        "dispatches": dispatches,
        "overlap_hits": overlap_hits,
        "overlap_hit_ratio": round(overlap_ratio, 4),
        "serve_wal_bytes": int(wal_bytes),
        "serve_wal_fsyncs": int(wal_fsyncs),
        "background_persists": bg_persists,
        "dispatch_p99_before_us": round(p99["before"], 1),
        "dispatch_p99_during_us": round(p99["during"], 1),
        "dispatch_p99_after_us": round(p99["after"], 1),
        "skew_p99_ratio": round(skew_ratio, 3),
        "rebalance_moves": moves,
        "skew_load_ratio_onset": round(load_ratio_onset, 3),
        "skew_load_ratio_rebalanced": round(load_ratio_after, 3),
        "ingest_coalesced_ops": d_all["ingest_coalesced_ops"],
        "replayed_records": replayed.records,
        "replayed_ops": replayed.ops,
        "oracle_sampled": len(sample),
        "bit_identical": bit_identical,
        "recovered_bit_identical": recov_mm == 0,
        "acked_ops_lost": recov_mm,
        "shape": f"{tenants}x{e}x{a}@{lanes}lanes",
        **flight,
    }]


def bench_fanout():
    """δ-subscription fan-out egress leg (``--fanout`` runs it alone;
    ISSUE 16's acceptance gate): ≥1M subscribers registered over the
    churning 1M-tenant serve superblock, converged updates pushed back
    out as cohort δ payloads —

    1. **push window, timed** — cycles of hot-set writes through the
       ingest queue, then one ``FanoutPlane.push`` per cycle: lagging/
       dirty subscribers bucket into (tenant, acked watermark)
       cohorts, pack into ``mesh_fanout_push`` dispatches (the PR 14
       fused wire kernel over B·E client lanes), and the per-delivery
       byte price rides ``delta_push_bytes`` / ``hist_push_bytes``.
    2. **degradation + churn inside the window** — killed subscribers
       never ack, so the ack window forces snapshot+suffix resyncs
       (``resync_fallbacks``); subscriber churn re-subscribes fresh
       ⊥-watermark clients mid-stream; an evicted cohort of SUBSCRIBED
       tenants re-warms through the evictor on the next push.
    3. **bit-identity** — sampled live client replicas (including
       subscribers sharing tenants with dead ones — split watermark
       buckets) plus one revived dead subscriber must land
       bit-identical to their served rows, and EVERY subscriber's
       acked watermark must converge to its tenant's served version.

    The SAME committed shape runs on the CPU stand-in mesh — the gate
    is ≥1M live subscribers THERE, and the δ price must beat the
    full-state push ≥10×.
    """
    import shutil
    import tempfile

    import jax

    from crdt_tpu import telemetry as tele
    from crdt_tpu.fanout import ClientReplica, FanoutPlane
    from crdt_tpu.obs import hist as obs_hist
    from crdt_tpu.obs import trace as obs_trace
    from crdt_tpu.parallel import make_mesh
    from crdt_tpu.serve import Evictor, IngestQueue, Superblock

    cfg = bench_configs()["fanout"]

    def knob(key, env):
        return int(os.environ.get(env, cfg[key]))

    tenants = knob("tenants", "BENCH_FANOUT_TENANTS")
    lanes = knob("lanes", "BENCH_FANOUT_LANES")
    subscribers = knob("subscribers", "BENCH_FANOUT_SUBSCRIBERS")
    cycles = knob("cycles", "BENCH_FANOUT_CYCLES")
    ops_per_cycle = knob("ops_per_cycle", "BENCH_FANOUT_OPS_PER_CYCLE")
    hot_set = knob("hot_set", "BENCH_FANOUT_HOT_SET")
    dispatch_lanes = knob("dispatch_lanes", "BENCH_FANOUT_DISPATCH_LANES")
    hot_shift = cfg["hot_shift"]
    window_cap = cfg["window_cap"]
    churn = knob("churn", "BENCH_FANOUT_CHURN")
    kill_subscribers = cfg["kill_subscribers"]
    client_sample = cfg["client_sample"]
    evict_cohort = cfg["evict_cohort"]
    trace_sample = knob("trace_sample", "BENCH_FANOUT_TRACE_SAMPLE")
    p = min(cfg["mesh"][0], len(jax.devices()))
    mesh = make_mesh(p, 1)
    caps = dict(
        n_elems=cfg["elems"], n_actors=cfg["actors"],
        deferred_cap=cfg["deferred_cap"],
    )
    e, a = caps["n_elems"], caps["n_actors"]

    sb = Superblock(tenants, mesh, kind="orswot", caps=caps, n_lanes=lanes)
    root = tempfile.mkdtemp(prefix="bench-fanout-")
    ev = Evictor(sb, root, pressure_batch=256)
    q = IngestQueue(
        sb, lanes=cfg["slab_lanes"], depth=cfg["slab_depth"],
        max_pending=1 << 20, evictor=ev,
    )
    plane = FanoutPlane(
        sb, evictor=ev, window_cap=window_cap,
        dispatch_lanes=dispatch_lanes, capacity=subscribers,
    )
    # Subscriber i watches tenant i (every tenant covered); the pinned
    # head tenants are touched EVERY cycle so the sampled replicas and
    # the killed subscribers actually see traffic.
    plane.subscribe(np.arange(subscribers, dtype=np.int64) % tenants)
    pinned = client_sample + kill_subscribers
    clients = {
        s: ClientReplica("orswot", sb.empty_row())
        for s in range(client_sample)
    }
    killed = np.arange(client_sample, pinned)
    dead_sub = client_sample  # the one we revive and verify at the end
    dead_client = ClientReplica("orswot", sb.empty_row())

    rng = np.random.default_rng(163)
    next_ctr = np.zeros(tenants, np.uint32)

    def submit_cycle(cycle, n_ops):
        off = (cycle * hot_shift) % max(tenants - hot_set, 1)
        hot = rng.integers(off, off + hot_set, n_ops)
        uni = rng.integers(0, tenants, n_ops)
        ts = np.where(rng.random(n_ops) < 0.85, hot, uni)
        ts[:pinned] = np.arange(pinned)  # the pinned head, every cycle
        # ~6 touched elements per op regardless of row width — the op
        # sparsity is the workload's, the row width is the tenant's.
        masks = rng.random((n_ops, e)) < (6.0 / e)
        for i in range(n_ops):
            t = int(ts[i])
            c = int(next_ctr[t]) + 1
            next_ctr[t] = c
            q.add(t, t % a, c, masks[i])
        return np.unique(ts)

    def deliver_and_ack(rep, revive=False):
        """Simulate delivery: sampled replicas apply for real, every
        other delivery is assumed received; acks promote everyone
        except the killed set (until ``revive``)."""
        n = 0
        for cp in rep.pushes:
            for s in cp.members:
                s = int(s)
                if s in clients:
                    clients[s].apply_wire(cp.wire, cp.to_ver)
                elif revive and s == dead_sub:
                    dead_client.apply_wire(cp.wire, cp.to_ver)
            n += len(cp.members)
        for rs in rep.resyncs:
            for s in rs.members:
                s = int(s)
                if s in clients:
                    clients[s].adopt(rs.state, rs.to_ver)
                elif revive and s == dead_sub:
                    dead_client.adopt(rs.state, rs.to_ver)
            n += len(rs.members)
        for c in clients.values():
            c.ack()
        if revive:
            dead_client.ack()
        members = [cp.members for cp in rep.pushes + rep.resyncs]
        if members:
            allm = np.concatenate(members)
            if not revive:  # the killed set never acks in the window
                allm = allm[~np.isin(allm, killed)]
            plane.ack(allm)
        return n

    # Warmup: compiles the slab apply + the fan-out dispatch (its ops
    # and pushes are real; only the TIMING is excluded). It runs BEFORE
    # the flight window: the artifact narrates the measured window, and
    # the audit's cohort-conservation check demands every ring
    # fanout_push ride a recorded telemetry — the warmup's never is.
    touched = submit_cycle(0, 512)
    q.drain()
    plane.note_dirty(touched)
    deliver_and_ack(plane.push(telemetry=True))

    tr = prev_tr = None
    rec, prev_rec, snap_base = _flight_start(capacity=32768)
    try:
        # The tracer installs AFTER warmup: every sampled journey it
        # mints belongs to the measured window. The plane's own
        # per-cycle push→ack loop completes the journeys — no extra
        # machinery, freshness falls out of the leg's real traffic.
        tr = obs_trace.Tracer(sample=trace_sample)
        prev_tr = obs_trace.install_tracer(tr)

        tel = None
        push_s = 0.0
        deliveries = 0
        delta_deliveries = 0
        n_evicted = 0
        rewarmed = False
        for cycle in range(1, cycles + 1):
            touched = submit_cycle(cycle, ops_per_cycle)
            q.drain()
            plane.note_dirty(touched)
            if cycle == cycles // 2:
                # Evict SUBSCRIBED (and sampled!) tenants mid-window:
                # the next push must re-warm them through the evictor.
                n_evicted = ev.evict(list(range(evict_cohort)))
            t0 = time.perf_counter()
            rep = plane.push(telemetry=True)
            push_s += time.perf_counter() - t0
            if cycle == cycles // 2:
                rewarmed = all(
                    sb.is_resident(t) for t in range(evict_cohort)
                )
            deliveries += rep.subscribers
            delta_deliveries += sum(len(cp.members) for cp in rep.pushes)
            deliver_and_ack(rep)
            # Annotate AFTER the acks so the record carries the cycle's
            # own trace-latency histogram increments.
            t = tr.annotate(rep.telemetry)
            tel = t if tel is None else tele.combine(tel, t)
            tele.record("fanout", t)
            if churn:
                # Subscriber churn: a random slice (outside the pinned
                # head) leaves; as many fresh ⊥-watermark clients join
                # on random tenants — hot landings re-sync organically.
                drop = rng.integers(pinned, subscribers, churn)
                plane.unsubscribe(np.unique(drop))
                plane.subscribe(rng.integers(0, tenants, len(np.unique(drop))))
        fresh = obs_hist.summary(tr.freshness_dict())
        skew = obs_trace.skew_report(evictor=ev, queue=q, tracer=tr, k=8)
        traces_minted, traces_completed = tr.minted, tr.completed
        obs_trace.install_tracer(prev_tr)
        assert traces_completed >= 1, (
            "no sampled op journey completed inside the push window"
        )
        d = tele.to_dict(tel)
        push_hist = obs_hist.summary(d["hist_push_bytes"])
        flight = _flight_finish("fanout", rec, prev_rec, snap_base,
                                slo=True)

        # Verification: revive the dead subscriber (its catch-up MUST
        # come as a snapshot+suffix resync — its watermark fell out of
        # the ack window long ago), then converge to quiescence.
        for _ in range(window_cap + 2):
            rep = plane.push()
            if rep.cohorts == 0 and not rep.resyncs:
                break
            deliver_and_ack(rep, revive=True)
        st = plane.sub_tenant[:plane._top]
        alive = st >= 0
        watermarks_current = bool(np.all(
            plane.sub_ver[:plane._top][alive]
            == plane.ver[np.where(alive, st, 0)][alive]
        ))
        mismatches = sum(
            0 if c.equals(sb.row(s)) else 1 for s, c in clients.items()
        )
        if not dead_client.equals(sb.row(dead_sub)):
            mismatches += 1
        bit_identical = mismatches == 0 and watermarks_current
        assert bit_identical, (
            f"{mismatches} sampled client replicas diverged "
            f"(watermarks_current={watermarks_current})"
        )
        assert plane.n_live >= 1_000_000, (
            f"fanout leg served only {plane.n_live} subscribers — the "
            f"gate is 1M+"
        )
        assert int(d["resync_fallbacks"]) >= 1 and plane.resyncs_total >= 1, (
            "no dead-subscriber snapshot+suffix resync in the window"
        )
        assert n_evicted >= 1 and rewarmed, (
            "no subscribed-tenant evict→re-warm cycle in the window"
        )
        row_b = sb.row_nbytes()
        bytes_per_delta = d["delta_push_bytes"] / max(delta_deliveries, 1)
        total_bytes = d["delta_push_bytes"] + d["bootstrap_bytes"]
        ratio_delta = row_b / max(bytes_per_delta, 1e-9)
        ratio_overall = deliveries * row_b / max(total_bytes, 1e-9)
        assert ratio_overall >= 10, (
            f"δ fan-out moved 1/{ratio_overall:.1f} of the full-state "
            f"push — the gate is ≥10× (deliveries={deliveries} "
            f"delta_deliveries={delta_deliveries} "
            f"delta_bytes={d['delta_push_bytes']:.0f} "
            f"resync_bytes={d['bootstrap_bytes']:.0f} "
            f"resyncs={int(d['resync_fallbacks'])} row_b={row_b})"
        )
    except BaseException:
        from crdt_tpu import obs as _obs

        if tr is not None and obs_trace.get_tracer() is tr:
            obs_trace.install_tracer(prev_tr)
        _obs.install(prev_rec)
        raise
    finally:
        shutil.rmtree(root, ignore_errors=True)

    log(
        f"config-fanout: {plane.n_live:,} live subscribers over "
        f"{tenants:,} tenants ({lanes:,} lanes): {deliveries:,} δ "
        f"deliveries in {push_s:.2f}s = {deliveries / push_s:,.0f} "
        f"δ-pushes/s; {bytes_per_delta:,.0f} B/subscriber vs "
        f"{row_b:,} B full row = {ratio_delta:.1f}× (overall "
        f"{ratio_overall:.1f}× incl. {int(d['resync_fallbacks'])} "
        f"resyncs); push p50 {push_hist['p50']:,.0f} B / p99 "
        f"{push_hist['p99']:,.0f} B; {int(d['cohorts_per_dispatch']):,} "
        f"cohorts dispatched; {n_evicted} subscribed tenants evicted "
        f"and re-warmed; {len(clients) + 1} client replicas "
        f"bit-identical; freshness p50 {fresh['p50']:,.0f} us / p95 "
        f"{fresh['p95']:,.0f} us / p99 {fresh['p99']:,.0f} us over "
        f"{traces_completed} traced journeys (1/{trace_sample} tenants)"
    )
    return [{
        "config": "fanout", "metric": "fanout_delta_pushes_per_sec",
        "value": round(deliveries / push_s, 1), "unit": "deltas/s",
        "subscribers": plane.n_live, "tenants": tenants, "lanes": lanes,
        "deliveries": deliveries,
        "delta_deliveries": delta_deliveries,
        "bytes_per_subscriber": round(bytes_per_delta, 1),
        "full_row_bytes": row_b,
        "delta_vs_full_ratio": round(ratio_delta, 2),
        "overall_vs_full_ratio": round(ratio_overall, 2),
        "push_bytes_p50": round(push_hist["p50"], 1),
        "push_bytes_p99": round(push_hist["p99"], 1),
        "cohorts_dispatched": int(d["cohorts_per_dispatch"]),
        "resync_fallbacks": int(d["resync_fallbacks"]),
        "subscribers_live": int(d["subscribers_live"]),
        "evicted_rewarmed": n_evicted,
        "window_seconds": round(push_s, 3),
        "subscriber_churn": churn * cycles,
        "clients_verified": len(clients) + 1,
        "bit_identical": bit_identical,
        "freshness_p50_us": round(fresh["p50"], 1),
        "freshness_p95_us": round(fresh["p95"], 1),
        "freshness_p99_us": round(fresh["p99"], 1),
        "traces_minted": traces_minted,
        "traces_completed": traces_completed,
        "trace_sample": trace_sample,
        "hot_tenants": skew["tenants"],
        "shape": f"{subscribers}subs@{tenants}x{e}x{a}@{lanes}lanes",
        **flight,
    }]


def bench_geo():
    """Geo-federation leg (``--geo`` runs it alone; ISSUE 20's
    acceptance gate): a mesh-of-meshes — each region one full serving
    stack (superblock + evictor + WAL-attached ingest queue + fan-out
    interest) federated by rendezvous tenant homing —

    1. **federated traffic window** — per-cycle adds submitted from
       round-robin ORIGIN regions, routed to each tenant's home queue
       (the ack stays the home region's ServeWal group commit), then
       one full cross-region anti-entropy sweep: join-irreducible δ
       lanes over checksum-guarded, retry-wrapped links, mirrors fed
       only where a region holds local interest (partial replication).
    2. **region kill MID-TRAFFIC** — at ``kill_cycle`` the region dies
       with the cycle's ops still pending in its queue (unacked — they
       are legitimately lost); its home shards re-home onto the
       survivors from the durable tier (snapshot rows + WAL-suffix
       replay) plus peer divergence lanes, generation bumped, every
       touching ack window reset to ⊥.
    3. **gates, asserted here** — every checked tenant's home row
       bit-identical to the per-tenant SEQUENTIAL oracle over exactly
       its acked ops (zero acked-op loss, the re-homed cohort checked
       first); every surviving interest mirror bit-identical to its
       home row; cross-region wire bytes ≤ 25% of full-state
       mirroring; per-region resident lanes bounded by the
       home-written ∪ local-interest set — and the federation's total
       residency strictly below written-tenants × regions (partial
       replication proven, not asserted).

    Causal-watermark reads ride the window (stale reads are LABELED —
    the certificate soundness itself is the ``federation`` static-check
    section's gate); the ``watermark_lag_p99`` on the record comes from
    the same histogram the exporter's ``federation`` block surfaces.
    """
    import shutil
    import tempfile

    import jax

    from crdt_tpu import telemetry as tele
    from crdt_tpu.fanout import FanoutPlane
    from crdt_tpu.geo import (
        Federation,
        RegionPlane,
        exchange_all,
        fail_over_region,
        read_local,
    )
    from crdt_tpu.obs import hist as obs_hist
    from crdt_tpu.ops import superblock as sb_ops
    from crdt_tpu.parallel import make_mesh
    from crdt_tpu.serve import Evictor, IngestQueue, Superblock
    from crdt_tpu.serve.wal import ServeWal

    cfg = bench_configs()["geo"]

    def knob(key, env):
        return int(os.environ.get(env, cfg[key]))

    regions = knob("regions", "BENCH_GEO_REGIONS")
    tenants = knob("tenants", "BENCH_GEO_TENANTS")
    lanes = knob("lanes", "BENCH_GEO_LANES")
    cycles = knob("cycles", "BENCH_GEO_CYCLES")
    ops_per_cycle = knob("ops_per_cycle", "BENCH_GEO_OPS_PER_CYCLE")
    hot_set = knob("hot_set", "BENCH_GEO_HOT_SET")
    subscribers = knob("subscribers", "BENCH_GEO_SUBSCRIBERS")
    kill_cycle = knob("kill_cycle", "BENCH_GEO_KILL_CYCLE")
    oracle_sample = cfg["oracle_sample"]
    hot_shift = cfg["hot_shift"]
    evict_cohort = cfg["evict_cohort"]
    assert regions >= 2 and 2 <= kill_cycle <= cycles

    p = min(cfg["mesh"][0], len(jax.devices()))
    mesh = make_mesh(p, 1)
    caps = dict(
        n_elems=cfg["elems"], n_actors=cfg["actors"],
        deferred_cap=cfg["deferred_cap"],
    )
    e, a = caps["n_elems"], caps["n_actors"]

    rng = np.random.default_rng(211)
    roots = []
    planes = {}
    for r in range(regions):
        sb = Superblock(tenants, mesh, kind="orswot", caps=caps,
                        n_lanes=lanes)
        root = tempfile.mkdtemp(prefix=f"bench-geo-r{r}-")
        roots.append(root)
        ev = Evictor(sb, root, pressure_batch=64)
        wal = ServeWal(os.path.join(root, "serve.wal"))
        q = IngestQueue(
            sb, lanes=cfg["slab_lanes"], depth=cfg["slab_depth"],
            max_pending=1 << 18, evictor=ev, wal=wal,
        )
        fan = FanoutPlane(sb, evictor=ev, capacity=max(subscribers, 64))
        planes[r] = RegionPlane(r, sb, q, evictor=ev, wal=wal,
                                fanout=fan)
    fed = Federation(planes)
    # Region-local subscribers: each region watches a random tenant
    # slice — the fan-out half of the partial-replication interest.
    for r in range(regions):
        planes[r].fanout.subscribe(
            rng.integers(0, tenants, max(subscribers // regions, 1))
        )

    dead = regions - 1
    pre_home = np.asarray([fed.rmap.home(t) for t in range(tenants)])
    next_ctr = np.zeros(tenants, np.uint32)
    history = {}  # tenant -> ACKED ops only (sequential-oracle form)

    def submit_cycle(cycle, n_ops, live):
        """One cycle's adds from round-robin origin regions. Returns
        the TENTATIVE (home, tenant, oracle-op) ledger — entries move
        into ``history`` only when the home drain (the WAL group
        commit, i.e. the ack) returns."""
        off = (cycle * hot_shift) % max(tenants - hot_set, 1)
        hot = rng.integers(off, off + hot_set, n_ops)
        uni = rng.integers(0, tenants, n_ops)
        ts = np.where(rng.random(n_ops) < 0.6, hot, uni)
        masks = rng.random((n_ops, e)) < (4.0 / e)
        tent = []
        for i in range(n_ops):
            t = int(ts[i])
            act = t % a
            c = int(next_ctr[t]) + 1
            next_ctr[t] = c
            home = fed.add(int(live[i % len(live)]), t, actor=act,
                           counter=c, member=masks[i])
            tent.append((home, t, (sb_ops.ADD, act, c, None, masks[i])))
        return tent

    def drain_live(tel):
        for p_ in fed.planes.values():
            if not p_.alive:
                continue
            _rep, t_ = p_.queue.drain(telemetry=True)
            if t_ is not None:
                tel = t_ if tel is None else tele.combine(tel, t_)
        return tel

    def ack(tent):
        for _home, t, op in tent:
            history.setdefault(t, []).append(op)

    def roweq(x, y):
        return all(
            bool(np.array_equal(np.asarray(u), np.asarray(v)))
            for u, v in zip(jax.tree.leaves(x), jax.tree.leaves(y))
        )

    failover_rep = None
    kill_s = 0.0
    ops_lost_unacked = 0
    n_spilled = 0
    reads = stale_reads = 0
    rec, prev_rec, snap_base = _flight_start(capacity=16384)
    try:
        # Warmup: compiles the slab apply + the decompose/reconstruct
        # exchange path; its ops are real and stay in the oracle
        # histories — only the TIMING is excluded.
        tel = None
        tent = submit_cycle(0, 64, list(range(regions)))
        tel = drain_live(tel)
        ack(tent)
        exchange_all(fed)

        exchanged_tenants = 0
        t_start = time.perf_counter()
        for cycle in range(1, cycles + 1):
            live = sorted(
                r for r, p_ in fed.planes.items() if p_.alive
            )
            tent = submit_cycle(cycle, ops_per_cycle, live)
            if cycle == kill_cycle:
                # Region kill MID-TRAFFIC: this cycle's ops are still
                # pending, un-drained. The dead region's share was
                # never WAL-committed — unacked, legitimately lost;
                # everything ever ACKED must survive the re-homing.
                lost = [x for x in tent if x[0] == dead]
                tent = [x for x in tent if x[0] != dead]
                ops_lost_unacked = len(lost)
                t0 = time.perf_counter()
                failover_rep = fail_over_region(fed, dead)
                kill_s = time.perf_counter() - t0
            # A pre-sweep mirror read: lag is visible and LABELED.
            if history:
                t_r = int(rng.choice(np.asarray(sorted(history))))
                home = fed.rmap.home(t_r)
                others = [r for r, p_ in fed.planes.items()
                          if p_.alive and r != home]
                if others:
                    _, cert = read_local(
                        fed, int(rng.choice(others)), t_r
                    )
                    reads += 1
                    stale_reads += 0 if cert.fresh else 1
            tel = drain_live(tel)
            ack(tent)
            if cycle == kill_cycle - 1:
                # Spill a cohort of the soon-dead region's home tenants
                # to its durable tier: the failover must recover REAL
                # snapshot rows (plus the WAL suffix replayed
                # idempotently over them), not just replay the log.
                cohort = [t for t in sorted(history)
                          if int(pre_home[t]) == dead][:evict_cohort]
                n_spilled = fed.planes[dead].evictor.evict(cohort)
            for xr in exchange_all(fed):
                exchanged_tenants += xr.tenants_shipped
        window_s = time.perf_counter() - t_start

        # Quiesce: nothing pending, every interest mirror caught up.
        tel = drain_live(tel)
        for _ in range(2):
            for xr in exchange_all(fed):
                exchanged_tenants += xr.tenants_shipped
        total_ops = sum(len(v) for v in history.values())

        # One telemetry record for the whole leg: the federation
        # gauges/counters annotated onto the combined drain telemetry
        # (pytree → schema → exporter → flight recorder).
        assert tel is not None
        t_rec = fed.annotate(jax.tree.map(np.asarray, tel))
        tele.record("geo", t_rec)
        d = tele.to_dict(t_rec)
        wm = obs_hist.summary(d["hist_geo_watermark_lag"])
        flight = _flight_finish("geo", rec, prev_rec, snap_base)

        # ---- gates ----------------------------------------------------
        live = sorted(r for r, p_ in fed.planes.items() if p_.alive)
        written = sorted(history)
        rehomed_written = [
            t for t in written if int(pre_home[t]) == dead
        ]
        sample = list(rehomed_written[:oracle_sample])
        others = [t for t in written if t not in set(sample)]
        if others:
            pick = rng.choice(
                len(others),
                min(max(oracle_sample - len(sample), 16), len(others)),
                replace=False,
            )
            sample += [others[i] for i in pick]

        tk = fed.plane(live[0]).sb.tk
        oracle_mm = recovered_mm = 0
        acked_ops_lost = 0
        for t in sample:
            hp = fed.plane(fed.rmap.home(t))
            if not hp.sb.is_resident(t) and hp.evictor is not None:
                hp.evictor.restore(t)
            want = sb_ops.sequential_oracle(
                tk, tk.empty(**hp.sb.caps), history[t]
            )
            if not roweq(hp.sb.row(t), want):
                oracle_mm += 1
                if int(pre_home[t]) == dead:
                    recovered_mm += 1
                    acked_ops_lost += len(history[t])
        recovered_bit_identical = recovered_mm == 0

        mirror_mm = mirrors_checked = 0
        for r in live:
            pl = fed.plane(r)
            interest = pl.interest_tenants()
            for t in sample:
                home = fed.rmap.home(t)
                if r == home or t not in interest:
                    continue
                mirrors_checked += 1
                if not pl.sb.is_resident(t) or not roweq(
                    pl.sb.row(t), fed.plane(home).sb.row(t)
                ):
                    mirror_mm += 1
        bit_identical = (
            oracle_mm == 0 and mirror_mm == 0 and mirrors_checked >= 1
        )
        assert recovered_bit_identical and acked_ops_lost == 0, (
            f"region-kill failover lost acked ops: {recovered_mm} "
            f"re-homed tenants diverged from their acked-op oracle"
        )
        assert bit_identical, (
            f"{oracle_mm} home rows diverged from the sequential "
            f"oracle, {mirror_mm}/{mirrors_checked} interest mirrors "
            f"diverged from their home rows"
        )
        assert failover_rep is not None and fed.failovers >= 1
        assert n_spilled >= 1 and failover_rep.rows_recovered >= 1, (
            "the failover never touched the durable snapshot tier — "
            f"{n_spilled} rows spilled, "
            f"{failover_rep.rows_recovered} recovered"
        )

        wire_pct = 100.0 * fed.exchange_bytes / max(
            fed.full_mirror_bytes, 1.0
        )
        assert fed.full_mirror_bytes > 0 and wire_pct <= 25.0, (
            f"cross-region δ lanes moved {wire_pct:.1f}% of what "
            f"full-state mirroring would ship — the gate is ≤25%"
        )

        # Partial replication: resident lanes per region bounded by
        # home-written ∪ local-interest (∪ the re-homed cohort — the
        # failover's ⊥-cleared mirrors keep their lane), and the
        # federation total strictly below written × regions.
        rehomed_all = {
            t for t in range(tenants) if int(pre_home[t]) == dead
        }
        resident = {}
        resident_bound_ok = True
        for r in live:
            pl = fed.plane(r)
            allowed = set(pl.interest_tenants())
            allowed |= {t for t in written if fed.rmap.home(t) == r}
            if failover_rep is not None:
                allowed |= rehomed_all
            resident[r] = pl.resident_lanes()
            if resident[r] > len(allowed):
                resident_bound_ok = False
        total_resident = sum(resident.values())
        naive_resident = len(written) * len(live)
        assert resident_bound_ok and total_resident < naive_resident, (
            f"partial replication violated: resident={resident}, "
            f"{total_resident} total vs {naive_resident} for full "
            f"mirroring of {len(written)} written tenants"
        )
    except BaseException:
        from crdt_tpu import obs as _obs

        _obs.install(prev_rec)
        raise
    finally:
        for r, root in zip(range(regions), roots):
            planes[r].wal.close()
            shutil.rmtree(root, ignore_errors=True)

    log(
        f"config-geo: {len(live)}/{regions} regions x {tenants:,} "
        f"tenants: {total_ops:,} acked ops in {window_s:.2f}s "
        f"({total_ops / window_s:,.0f} ops/s incl. a "
        f"{kill_s * 1e3:.0f}ms region-kill failover re-homing "
        f"{failover_rep.tenants_rehomed} tenants, "
        f"{failover_rep.rows_recovered} snapshot rows + "
        f"{failover_rep.ops_replayed} WAL ops, zero acked ops lost, "
        f"{ops_lost_unacked} in-flight unacked dropped); "
        f"{fed.exchange_bytes:,.0f} B cross-region δ vs "
        f"{fed.full_mirror_bytes:,.0f} B full-mirror = "
        f"{wire_pct:.1f}%; residency {resident} of {len(written)} "
        f"written ({total_resident} total vs {naive_resident} naive); "
        f"{stale_reads}/{reads} window reads labeled stale, watermark "
        f"lag p99 {wm['p99']:.1f}; {len(sample)} tenants "
        f"oracle-checked, {mirrors_checked} mirrors bit-identical"
    )
    return [{
        "config": "geo", "metric": "geo_acked_ops_per_sec",
        "value": round(total_ops / window_s, 1), "unit": "ops/s",
        "regions": regions, "regions_live": len(live),
        "tenants": tenants, "lanes": lanes,
        "acked_ops": total_ops,
        "exchanges": int(fed.exchanges),
        "exchanged_tenants": exchanged_tenants,
        "exchange_bytes": round(fed.exchange_bytes, 1),
        "full_mirror_bytes": round(fed.full_mirror_bytes, 1),
        "wire_vs_mirror_pct": round(wire_pct, 2),
        "failovers": int(fed.failovers),
        "failover_ms": round(kill_s * 1e3, 1),
        "tenants_rehomed": failover_rep.tenants_rehomed,
        "rows_spilled": n_spilled,
        "rows_recovered": failover_rep.rows_recovered,
        "ops_replayed": failover_rep.ops_replayed,
        "divergence_lanes": failover_rep.divergence_lanes,
        "mirrors_adopted": failover_rep.mirrors_adopted,
        "acked_ops_lost": acked_ops_lost,
        "unacked_ops_dropped": ops_lost_unacked,
        "recovered_bit_identical": recovered_bit_identical,
        "bit_identical": bit_identical,
        "oracle_sampled": len(sample),
        "mirrors_checked": mirrors_checked,
        "resident_lanes": {str(r): n for r, n in resident.items()},
        "total_resident": total_resident,
        "naive_resident": naive_resident,
        "resident_bound_ok": resident_bound_ok,
        "written_tenants": len(written),
        "reads": reads, "stale_reads_labeled": stale_reads,
        "watermark_lag_p99": round(wm["p99"], 2),
        "window_seconds": round(window_s, 3),
        "shape": f"{regions}regions@{tenants}x{e}x{a}@{lanes}lanes",
        **flight,
    }]


def bench_cpu() -> float:
    from crdt_tpu.pure.orswot import Orswot
    from crdt_tpu.vclock import VClock

    e_cpu = min(E, int(os.environ.get("BENCH_CPU_ELEMS", E)))
    top, ctr = make_arrays(R_CPU, e_cpu)
    reps = []
    for i in range(R_CPU):
        o = Orswot()
        o.clock = VClock({a: int(c) for a, c in enumerate(top[i]) if c})
        for e in range(e_cpu):
            dots = {a: int(c) for a, c in enumerate(ctr[i, e]) if c}
            if dots:
                o.entries[e] = VClock(dots)
        reps.append(o)
    acc = Orswot()
    t0 = time.perf_counter()
    for r in reps:
        acc.merge(r)
    dt = time.perf_counter() - t0
    mps = R_CPU / dt
    log(
        f"CPU oracle fold: {R_CPU} merges over {e_cpu} elems: "
        f"{dt*1e3:.1f} ms -> {mps:,.1f} merges/s"
    )
    return mps


def bench_clocks():
    """Configs 1+2 (diagnostic, stderr): GCounter increment+fold and the
    pairwise VClock merge matrix."""
    import jax
    import jax.numpy as jnp

    from crdt_tpu.ops import vclock as vops

    # Config 1: 64 replicas x 10k increments, converged fold+read. Each
    # replica mints in its own actor lane (an actor never forks), so the
    # converged sum-of-lanes read must equal exactly 10k.
    rng = np.random.default_rng(1)
    counts = rng.multinomial(10_000, np.ones(64) / 64)
    clocks = jnp.asarray(np.diag(counts).astype(np.uint32))
    fold = jax.jit(vops.fold)
    jax.block_until_ready(fold(clocks))
    t0 = time.perf_counter()
    for _ in range(50):
        folded = fold(clocks)
    jax.block_until_ready(folded)
    dt = (time.perf_counter() - t0) / 50
    total = int(np.asarray(folded).sum())
    assert total == 10_000, f"converged gcounter read {total} != 10000"
    log(
        f"config1 gcounter: 64 replicas, 10k incs: fold {dt*1e6:.0f} us, "
        f"read {total} (63 merges -> {63/dt:,.0f} merges/s)"
    )
    records = [{
        "config": 1, "metric": "gcounter_merges_per_sec",
        "value": round(63 / dt, 1), "unit": "merges/s",
        "shape": "64x10000", "read": total,
    }]

    # Config 2: 1k replicas, full pairwise merge matrix — the VClock
    # kernel, then the PNCounter form (p/n = TWO clock matrices per
    # replica, BASELINE names both types for this config).
    clocks2 = jnp.asarray(
        rng.integers(0, 1000, (1000, A)).astype(np.uint32)
    )
    pair = jax.jit(vops.pairwise_merge_matrix)
    jax.block_until_ready(pair(clocks2))
    t0 = time.perf_counter()
    for _ in range(10):
        m = pair(clocks2)
    jax.block_until_ready(m)
    dt = (time.perf_counter() - t0) / 10
    log(
        f"config2 vclock: 1k x 1k pairwise merge matrix: {dt*1e3:.2f} ms "
        f"-> {1e6/dt:,.0f} pair-merges/s"
    )
    records.append({
        "config": 2, "metric": "vclock_pair_merges_per_sec",
        "value": round(1e6 / dt, 1), "unit": "pair-merges/s",
        "shape": f"1000x1000x{A}",
    })

    p2 = jnp.asarray(rng.integers(0, 1000, (1000, A)).astype(np.uint32))
    n2 = jnp.asarray(rng.integers(0, 1000, (1000, A)).astype(np.uint32))

    @jax.jit
    def pn_pair(p, n):
        return vops.pairwise_merge_matrix(p), vops.pairwise_merge_matrix(n)

    jax.block_until_ready(pn_pair(p2, n2))
    t0 = time.perf_counter()
    for _ in range(10):
        pm, nm = pn_pair(p2, n2)
    jax.block_until_ready((pm, nm))
    dt = (time.perf_counter() - t0) / 10
    # Converged read p − n as exact host ints (BigInt-at-the-edge
    # discipline, SURVEY §7.3).
    total = int(np.asarray(vops.fold(p2)).sum()) - int(np.asarray(vops.fold(n2)).sum())
    log(
        f"config2 pncounter: 1k x 1k pairwise merge (p+n): {dt*1e3:.2f} ms "
        f"-> {1e6/dt:,.0f} pair-merges/s; converged read {total}"
    )
    records.append({
        "config": 2, "metric": "pncounter_pair_merges_per_sec",
        "value": round(1e6 / dt, 1), "unit": "pair-merges/s",
        "shape": f"1000x1000x{A}", "read": total,
    })
    return records


def bench_map():
    """Config 4 (diagnostic, stderr): Map<K, MVReg> fold at a large key
    universe (1M keys) — the fused dense-slab Pallas path on TPU
    backends, the jnp log-tree fold elsewhere (``ops.map.fold``'s auto
    dispatch)."""
    import jax
    import jax.numpy as jnp

    from crdt_tpu.ops import map as map_ops

    r = int(os.environ.get("BENCH_MAP_REPLICAS", 8))
    k = int(os.environ.get("BENCH_MAP_KEYS", 1_000_000))
    s, a = 2, 4
    rng = np.random.default_rng(2)
    state = map_ops.empty(k, a, sibling_cap=s, batch=(r,))
    # Valid causal state respecting the per-(key, actor) uniqueness
    # invariant the fused path relies on (pallas_kernels._decode_wide):
    # slot j of replica i writes under actor (i + j) % a with one
    # globally-fixed counter per (key, slot); each replica's top covers
    # exactly the dots it holds.
    cctr = np.zeros((r, k, s), np.uint32)
    cctr[:, :, :] = (np.arange(k)[:, None] * s + np.arange(s) + 1).astype(np.uint32)
    cact = ((np.arange(r)[:, None, None] + np.arange(s)[None, None, :]) % a) * np.ones(
        (r, k, s), np.int32
    )
    cvalid = (np.arange(s) == 0) | (rng.random((r, k, s)) < 0.5)
    cclk = np.zeros((r, k, s, a), np.uint32)
    np.put_along_axis(cclk, cact[..., None].astype(np.int64), cctr[..., None], axis=-1)
    cclk[~cvalid] = 0
    top = np.max(np.where(cvalid[..., None], cclk, 0), axis=(1, 2))
    state = state._replace(
        top=jnp.asarray(top),
        child=state.child._replace(
            wact=jnp.asarray(np.where(cvalid, cact, 0).astype(np.int32)),
            wctr=jnp.asarray(np.where(cvalid, cctr, 0)),
            clk=jnp.asarray(cclk),
            valid=jnp.asarray(cvalid),
        ),
    )
    from crdt_tpu.ops.pallas_kernels import _fused_backend

    path = "fused" if _fused_backend() else "tree"
    # K-vs-2K marginal over a one-dispatch k-pass fold (bench_tpu's
    # methodology: it cancels the fixed per-dispatch overhead).
    passes = int(os.environ.get("BENCH_MAP_PASSES", 4))
    run = _fold_k_runner(map_ops.fold, map_ops.join, state)
    dt_k, degraded = marginal_time(run, passes, "config4 map fold")
    dt = dt_k / passes
    nbytes = sum(x.nbytes for x in jax.tree.leaves(state.child))
    log(
        f"config4 map: {r} replicas x {k} keys fold ({path}): {dt*1e3:.1f} ms "
        f"-> {(r-1)/dt:,.1f} merges/s, {nbytes/dt/1e9:.1f} GB/s child-state"
        + (" [conservative bound]" if degraded else "")
    )
    return {
        "config": 4, "metric": "map_merges_per_sec",
        "value": round((r - 1) / dt, 1), "unit": "merges/s",
        "path": path, "gbps": round(nbytes / dt / 1e9, 1),
        "timing": "conservative" if degraded else "marginal",
        "degraded": degraded,
        "shape": f"{r}x{k}",
    }


def load_automerge_trace(path: str, n_actors: int = 4, limit: int = 0):
    """Load the REAL automerge-perf editing trace (BASELINE config 5;
    github.com/automerge/automerge-perf ``edit-by-index/trace.json``).

    Format: a JSON array of edits, each ``[position, n_deleted,
    inserted_string...]`` — positions are indices into the current text.
    Flattened here to the engine's op stream: ``n_deleted`` DELETEs at
    ``position``, then one INSERT per inserted character. The trace is
    single-author; actors are assigned round-robin per op so the
    replica-batch path still exercises multi-actor minting. ``limit``
    truncates the flattened op stream (0 = everything).

    Offline boxes can't fetch the file, so the synthetic generator below
    stays the fallback — set BENCH_TRACE_PATH when a copy is available
    and ``bench_list`` switches to it (``"trace": "automerge-perf"`` in
    its JSON record)."""
    from crdt_tpu.native import DELETE, INSERT

    with open(path) as f:
        edits = json.load(f)
    kinds, idxs, vals, actors = [], [], [], []
    n = 0
    for edit in edits:
        pos, ndel = int(edit[0]), int(edit[1])
        for _ in range(ndel):
            kinds.append(DELETE)
            idxs.append(pos)
            vals.append(0)
            actors.append(n % n_actors)
            n += 1
            if limit and n >= limit:
                return kinds, idxs, vals, actors
        at = pos
        for chunk in edit[2:]:
            for ch in str(chunk):
                kinds.append(INSERT)
                idxs.append(at)
                vals.append(ord(ch) & 0x7F)
                actors.append(n % n_actors)
                at += 1
                n += 1
                if limit and n >= limit:
                    return kinds, idxs, vals, actors
    return kinds, idxs, vals, actors


def make_edit_trace(n_ops: int, n_actors: int = 4, seed: int = 3):
    """An automerge-perf-shaped editing trace: mostly typing at a moving
    cursor, occasional jumps and deletes (BASELINE config 5)."""
    from crdt_tpu.native import DELETE, INSERT

    rng = np.random.default_rng(seed)
    kinds, idxs, vals, actors = [], [], [], []
    length, cursor = 0, 0
    for _ in range(n_ops):
        roll = rng.random()
        if length == 0 or roll < 0.72:       # type at cursor
            kinds.append(INSERT)
            idxs.append(cursor)
            cursor = min(cursor + 1, length + 1)
            length += 1
        elif roll < 0.87:                     # jump cursor
            cursor = int(rng.integers(0, length + 1))
            kinds.append(INSERT)
            idxs.append(cursor)
            cursor += 1
            length += 1
        else:                                 # backspace
            kinds.append(DELETE)
            victim = max(0, min(cursor - 1, length - 1))
            idxs.append(victim)
            cursor = victim
            length -= 1
        vals.append(int(rng.integers(0, 128)))
        actors.append(int(rng.integers(0, n_actors)))
    return kinds, idxs, vals, actors


def bench_list():
    """Config 5 (diagnostic, stderr): edit-trace ops/sec — pure-Python
    oracle vs native C++ engine vs device batched replicas."""
    from crdt_tpu.native import INSERT, ListEngine
    from crdt_tpu.pure.list import List

    # BASELINE config-5 scale by default (100k-op trace x 1k replicas);
    # the CPU fallback path caps both (main()).
    n_ops = int(os.environ.get("BENCH_LIST_OPS", 100_000))
    r = int(os.environ.get("BENCH_LIST_REPLICAS", 1024))
    trace_path = os.environ.get("BENCH_TRACE_PATH", "")
    if trace_path and os.path.exists(trace_path):
        trace = load_automerge_trace(trace_path, limit=n_ops)
        n_ops = len(trace[0])
        trace_kind = "automerge-perf"
        log(f"config5 list: REAL automerge-perf trace ({n_ops} ops from {trace_path})")
    else:
        trace = make_edit_trace(n_ops)
        trace_kind = "synthetic"

    t0 = time.perf_counter()
    oracle = List()
    for k, ix, v, a in zip(*trace):
        op = (
            oracle.insert_index(ix, v, a)
            if k == INSERT
            else oracle.delete_index(ix, a)
        )
        oracle.apply(op)
    dt_py = time.perf_counter() - t0
    log(f"config5 list: pure oracle {n_ops} ops: {dt_py*1e3:.0f} ms -> {n_ops/dt_py:,.0f} ops/s")

    t0 = time.perf_counter()
    engine = ListEngine()
    engine.apply_trace(*trace)
    dt_native = time.perf_counter() - t0
    log(
        f"config5 list: native engine ({'C++' if engine.is_native else 'fallback'}) "
        f"{n_ops} ops: {dt_native*1e3:.0f} ms -> {n_ops/dt_native:,.0f} ops/s "
        f"({dt_py/dt_native:.1f}x oracle)"
    )

    import jax

    from crdt_tpu.models import BatchedList

    model = BatchedList.from_trace(*trace, n_replicas=r)
    t0 = time.perf_counter()
    model.apply_trace_to_all(chunk=2048)
    jax.block_until_ready(model.alive)
    dt_dev = time.perf_counter() - t0
    total = n_ops * r
    log(
        f"config5 list: device batched {r} replicas x {n_ops} ops: "
        f"{dt_dev*1e3:.0f} ms -> {total/dt_dev:,.0f} replica-ops/s "
        f"({(total/dt_dev)/(n_ops/dt_py):.1f}x oracle rate)"
    )
    return {
        "config": 5, "metric": "list_replica_ops_per_sec",
        "value": round(total / dt_dev, 1), "unit": "replica-ops/s",
        "vs_oracle_rate": round((total / dt_dev) / (n_ops / dt_py), 1),
        "native_ops_per_sec": round(n_ops / dt_native, 1),
        "oracle_ops_per_sec": round(n_ops / dt_py, 1),
        "shape": f"{r}x{n_ops}",
        "trace": trace_kind,
    }


def bench_sparse():
    """Sparse leg (diagnostic, stderr): segment-encoded ORSWOT fold at a
    universe the dense cube could never hold (cost scales by LIVE dots,
    not universe). Shape comes from BENCH_CONFIGS.json's ``sparse``
    entry (env overrides; the CPU stand-in takes the ``cpu_fallback``
    sub-block) — one source of truth with the flagship leg and
    chip_smoke.py."""
    import jax
    import jax.numpy as jnp

    from crdt_tpu.ops import sparse_orswot as sp

    cpu = os.environ.get("BENCH_CPU_FALLBACK") == "1"
    r = _cfg("sparse", "replicas", "BENCH_SPARSE_REPLICAS", cpu)
    cap = _cfg("sparse", "dot_cap", "BENCH_SPARSE_DOTS", cpu)
    universe = _cfg("sparse", "universe", "BENCH_SPARSE_UNIVERSE", cpu)
    rng = np.random.default_rng(7)

    # Random live cells: unique (eid, actor) per replica in canonical
    # segment order, counters covered by the top.
    eid = np.sort(
        rng.choice(universe, size=(r, cap), replace=True).astype(np.int32),
        axis=-1,
    )
    # Cell (eid, actor) must be unique per replica: duplicate eids (rare
    # at 1M) are simply marked dead.
    dup = np.concatenate(
        [np.zeros((r, 1), bool), eid[:, 1:] == eid[:, :-1]], axis=-1
    )
    valid = ~dup
    act = rng.integers(0, A, (r, cap)).astype(np.int32)
    ctr = rng.integers(1, 100, (r, cap)).astype(np.uint32)
    state = sp.empty(cap, A, batch=(r,))
    top = np.zeros((r, A), np.uint32)
    np.maximum.at(top, (np.arange(r)[:, None], act), np.where(valid, ctr, 0))
    # Canonical segment order (valid-first) — join's searchsorted match
    # requires it; dup-killed lanes must not sit interleaved.
    ceid, cact, cctr, cvalid, _ = sp._canon(
        jnp.asarray(np.where(valid, eid, -1)),
        jnp.asarray(np.where(valid, act, 0)),
        jnp.asarray(np.where(valid, ctr, 0)),
        jnp.asarray(valid),
        cap,
    )
    state = state._replace(
        top=jnp.asarray(top), eid=ceid, act=cact, ctr=cctr, valid=cvalid
    )
    live = int(valid.sum())
    nbytes = sum(x.nbytes for x in jax.tree.leaves(state))
    dense_bytes = r * universe * A * 4

    passes = _cfg("sparse", "passes", "BENCH_SPARSE_PASSES", cpu)
    run = _fold_k_runner(sp.fold, sp.join, state)
    dt_k, degraded = marginal_time(run, passes, "config-sparse fold")
    dt = dt_k / passes
    log(
        f"config-sparse: {r} replicas x {cap} dot-cap over a {universe:,}-"
        f"element universe: fold {dt*1e3:.1f} ms -> {(r-1)/dt:,.0f} merges/s "
        f"({live:,} live dots; state {nbytes/1e6:.1f} MB vs dense "
        f"{dense_bytes/1e9:,.0f} GB — {dense_bytes/nbytes:,.0f}x compression)"
        + (" [conservative bound]" if degraded else "")
    )
    return {
        "config": "sparse", "metric": "sparse_merges_per_sec",
        "value": round((r - 1) / dt, 1), "unit": "merges/s",
        "universe": universe, "live_dots": live,
        "state_bytes": nbytes, "dense_equiv_bytes": dense_bytes,
        "compression": round(dense_bytes / nbytes, 1),
        "timing": "conservative" if degraded else "marginal",
        "degraded": degraded,
        "shape": f"{r}x{cap}x{A}",
    }


def bench_sparse_map():
    """Sparse Map<K, MVReg> (diagnostic, stderr): the segment-encoded
    config-4 flavor — fold throughput over a 100M-key universe at
    live-cell-proportional state (``ops/sparse_mvmap.py``)."""
    import jax
    import jax.numpy as jnp

    from crdt_tpu.ops import sparse_mvmap as smv

    cpu = os.environ.get("BENCH_CPU_FALLBACK") == "1"
    r = _cfg("sparse_map", "replicas", "BENCH_SMAP_REPLICAS", cpu)
    cap = _cfg("sparse_map", "cell_cap", "BENCH_SMAP_CELLS", cpu)
    universe = _cfg("sparse_map", "universe", "BENCH_SMAP_UNIVERSE", cpu)
    s_cap = _cfg("sparse_map", "sibling_cap", "BENCH_SMAP_SIBLINGS", cpu)
    rng = np.random.default_rng(11)

    # Causally-consistent cells: unique (kid, act) per replica (dup keys
    # dropped), counters covered by the replica's top, payload clocks
    # witnessing the cell's own dot.
    kid = rng.choice(universe, size=(r, cap), replace=True).astype(np.int32)
    act = rng.integers(0, A, (r, cap)).astype(np.int32)
    # Sort by the packed (kid, act) cell key so EVERY duplicate cell is
    # adjacent (kid-only sorting leaves same-kid different-actor runs
    # unsorted and can hide duplicates), then drop adjacent equals.
    packed = kid.astype(np.int64) * A + act
    order = np.argsort(packed, axis=-1)
    take = lambda x: np.take_along_axis(x, order, axis=-1)
    kid, act, packed = take(kid), take(act), take(packed)
    dup = np.concatenate(
        [np.zeros((r, 1), bool), packed[:, 1:] == packed[:, :-1]], axis=-1
    )
    valid = ~dup
    ctr = rng.integers(1, 100, (r, cap)).astype(np.uint32)
    val = rng.integers(0, 1 << 20, (r, cap)).astype(np.int32)
    clk = np.zeros((r, cap, A), np.uint32)
    np.put_along_axis(clk, act[..., None].astype(np.int64), ctr[..., None], axis=-1)
    clk[~valid] = 0
    top = np.zeros((r, A), np.uint32)
    np.maximum.at(top, (np.arange(r)[:, None], act), np.where(valid, ctr, 0))
    state = smv.empty(cap, A, batch=(r,))
    ckid, cact, cctr, cval, cclk, cvalid, _ = smv._canon(
        jnp.asarray(np.where(valid, kid, -1)),
        jnp.asarray(np.where(valid, act, 0)),
        jnp.asarray(np.where(valid, ctr, 0)),
        jnp.asarray(np.where(valid, val, 0)),
        jnp.asarray(clk),
        jnp.asarray(valid),
        cap,
    )
    state = state._replace(
        top=jnp.asarray(top), kid=ckid, act=cact, ctr=cctr, val=cval,
        clk=cclk, valid=cvalid,
    )
    live = int(valid.sum())
    nbytes = smv.nbytes(state)
    # dense equivalent: the MapState child at this (K, S, A) — int32/u32
    # planes at 4 bytes, the valid plane at 1 (matching smv.nbytes's
    # actual-bytes convention on the sparse side)
    dense_bytes = r * universe * (3 * s_cap * 4 + s_cap * A * 4 + s_cap)

    passes = _cfg("sparse_map", "passes", "BENCH_SMAP_PASSES", cpu)
    run = _fold_k_runner(
        lambda st: smv.fold(st, sibling_cap=s_cap),
        lambda a, b: smv.join(a, b, sibling_cap=s_cap),
        state,
    )
    dt_k, degraded = marginal_time(run, passes, "config-sparse-map fold")
    dt = dt_k / passes
    log(
        f"config-sparse-map: {r} replicas x {cap} cell-cap over a "
        f"{universe:,}-key universe: fold {dt*1e3:.1f} ms -> "
        f"{(r-1)/dt:,.0f} merges/s ({live:,} live cells; state "
        f"{nbytes/1e6:.1f} MB vs dense {dense_bytes/1e12:,.1f} TB)"
        + (" [conservative bound]" if degraded else "")
    )
    return {
        "config": "sparse_map", "metric": "sparse_map_merges_per_sec",
        "value": round((r - 1) / dt, 1), "unit": "merges/s",
        "universe": universe, "live_cells": live,
        "state_bytes": nbytes, "dense_equiv_bytes": dense_bytes,
        "timing": "conservative" if degraded else "marginal",
        "degraded": degraded,
        "shape": f"{r}x{cap}x{A}",
    }


def _flagship_population(c: int, universe: int, n_actors: int, seed: int = 13):
    """The flagship workload's master live-dot table and per-replica
    cut rule — a causally VALID arbitrary-N population with O(C) host
    state, so any replica block can be generated on demand.

    Construction: one global table of ``c`` live (element, actor) cells
    sampled from ``universe``, sorted canonically by (eid, act), with
    counter ``g = eid * A + act + 1`` — strictly increasing along the
    lane order for every actor. Replica ``r`` holds the first
    ``L_r ∈ [c/2, c]`` lanes (a deterministic hash of r): for each
    actor that is a PREFIX of its counter sequence, so per-actor prefix
    closure holds (the state is reachable by applying that actor's add
    ops in order) and the join is a true lattice on the whole
    population — the streamed fold is bit-identical to any co-resident
    or oracle fold order. The converged union is exactly the full
    table, so an accumulator at ``dot_cap == c`` never overflows.

    Returns ``(gen, per_replica_bytes)`` where ``gen(global_row_ids)``
    is a jitted device-side block generator — the stand-in for a real
    stream source (DCN receive, host shards, checkpoint reader)."""
    import jax
    import jax.numpy as jnp

    from crdt_tpu.ops import sparse_orswot as sp

    rng = np.random.default_rng(seed)
    eids = np.sort(rng.choice(universe, size=c, replace=False).astype(np.int64))
    acts = rng.integers(0, n_actors, c).astype(np.int32)
    order = np.lexsort((acts, eids))
    eids, acts = eids[order], acts[order]
    ctrs = (eids * n_actors + acts + 1).astype(np.uint32)
    # Per-lane running top: top of a replica holding lanes [0, L) is
    # cummax[L-1] — one gather per block row.
    cummax = np.zeros((c, n_actors), np.uint32)
    run = np.zeros(n_actors, np.uint32)
    for i in range(c):
        run[acts[i]] = max(run[acts[i]], ctrs[i])
        cummax[i] = run
    m_eid = jnp.asarray(eids.astype(np.int32))
    m_act = jnp.asarray(acts)
    m_ctr = jnp.asarray(ctrs)
    m_top = jnp.asarray(cummax)
    half = c // 2

    @jax.jit
    def gen(row_ids):
        """[B] global replica indices -> canonical SparseOrswotState
        [B, ...] (dead tail, sorted lanes — join-ready as generated)."""
        cut = half + (
            row_ids.astype(jnp.uint32) * jnp.uint32(2654435761)
        ) % jnp.uint32(max(c - half + 1, 1))
        lanes = jnp.arange(c)
        valid = lanes[None, :] < cut[:, None]
        state = sp.empty(c, n_actors, batch=(row_ids.shape[0],))
        return state._replace(
            top=m_top[cut.astype(jnp.int32) - 1],
            eid=jnp.where(valid, m_eid[None], -1),
            act=jnp.where(valid, m_act[None], 0),
            ctr=jnp.where(valid, m_ctr[None], 0),
            valid=valid,
        )

    one = gen(jnp.arange(1))
    per_replica = sum(x.nbytes for x in jax.tree.leaves(one))
    return gen, per_replica


def _flagship_bit_identity(mesh) -> dict:
    """The flagship's correctness gate at a SUBSAMPLED shape: the same
    population construction, small enough for (a) the co-resident
    one-shot fold and (b) the sequential pure-oracle merge chain, both
    compared bit-identically against the streamed fold (and the stream
    re-run at a different block size — block-count invariance). Runs
    before any number is reported; a streamed result that changed the
    lattice would be a bug, not a throughput win."""
    import jax
    import jax.numpy as jnp

    from crdt_tpu.ops import sparse_orswot as sp
    from crdt_tpu.parallel import mesh_stream_fold_sparse

    sub_r, sub_c, sub_uni = 24, 64, 4096
    actors = _cfg("flagship", "actors", "BENCH_FLAGSHIP_ACTORS")
    gen, _ = _flagship_population(sub_c, sub_uni, actors, seed=17)
    blocks8 = [gen(jnp.arange(i, i + 8)) for i in range(0, sub_r, 8)]
    pop = jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=0), *blocks8)

    acc, of, tel = mesh_stream_fold_sparse(
        iter(blocks8), mesh, telemetry=True
    )
    assert not bool(jnp.any(of)), "flagship subsample overflowed"
    acc4, _ = mesh_stream_fold_sparse(
        (jax.tree.map(lambda x: x[i: i + 4], pop) for i in range(0, sub_r, 4)),
        mesh,
    )
    coresident, _ = sp.fold(pop)
    stream_ok = all(
        bool(jnp.array_equal(x, y))
        for x, y in zip(jax.tree.leaves(acc), jax.tree.leaves(coresident))
    )
    invariant = all(
        bool(jnp.array_equal(x, y))
        for x, y in zip(jax.tree.leaves(acc), jax.tree.leaves(acc4))
    )

    # Pure-oracle chain: replica dicts merged sequentially.
    from crdt_tpu.pure.orswot import Orswot
    from crdt_tpu.vclock import VClock

    def pure_of(row) -> Orswot:
        o = Orswot()
        o.clock = VClock({
            a: int(cv) for a, cv in enumerate(np.asarray(row.top)) if cv
        })
        eid = np.asarray(row.eid)
        act = np.asarray(row.act)
        ctr = np.asarray(row.ctr)
        for s in np.nonzero(np.asarray(row.valid))[0]:
            entry = o.entries.setdefault(int(eid[s]), VClock())
            entry.dots[int(act[s])] = int(ctr[s])
        return o

    oracle = Orswot()
    for i in range(sub_r):
        oracle.merge(pure_of(jax.tree.map(lambda x: x[i], pop)))
    oracle_ok = pure_of(acc) == oracle

    return {
        "subsample_shape": f"{sub_r}x{sub_uni}",
        "stream_equals_coresident": stream_ok,
        "block_count_invariant": invariant,
        "stream_equals_pure_oracle": oracle_ok,
        "bit_identical": stream_ok and invariant and oracle_ok,
    }


def bench_flagship():
    """THE flagship leg (``--flagship`` runs it alone): merges/sec
    across 10,240 replicas over a 1M-element universe — BASELINE's
    literal metric of record, never before produced at shape. The
    population streams through the mesh as replica blocks
    (crdt_tpu/parallel/stream.py: donated accumulator aliased in
    place, double-buffered staging), so peak device-resident replica
    state is two blocks plus the accumulator — independent of N —
    while the co-resident equivalent would hold the whole batch.

    Shape comes from BENCH_CONFIGS.json's ``flagship`` entry
    (``chip_smoke.py`` runs it on the chip; env overrides, the CPU run
    takes ``cpu_fallback``). Timing is the K-vs-2K marginal over whole
    stream passes (``marginal_time``) — a conservative-bound timing is
    labeled ``degraded``. Blocks are device-generated per index (a
    real deployment would receive them over DCN/ICI, not from the
    host — the ``bench_tpu`` precedent), each block a DISTINCT replica slice of a
    causally valid population (``_flagship_population``). Before any
    number is reported, the same construction at a subsampled shape is
    gated bit-identical against the co-resident fold, a different
    block size, and the sequential pure-oracle merge chain."""
    import jax
    import jax.numpy as jnp

    from crdt_tpu.parallel import make_mesh, mesh_stream_fold_sparse
    from crdt_tpu.utils.metrics import metrics, state_nbytes

    cpu = os.environ.get("BENCH_CPU_FALLBACK") == "1"
    r_total = _cfg("flagship", "replicas", "BENCH_FLAGSHIP_REPLICAS", cpu)
    universe = _cfg("flagship", "universe", "BENCH_FLAGSHIP_UNIVERSE", cpu)
    cap = _cfg("flagship", "segment_cap", "BENCH_FLAGSHIP_SEGMENT_CAP", cpu)
    actors = _cfg("flagship", "actors", "BENCH_FLAGSHIP_ACTORS", cpu)
    block_rows = _cfg(
        "flagship", "block_rows", "BENCH_FLAGSHIP_BLOCK_ROWS", cpu
    )
    n_dev = len(jax.devices())
    mesh = make_mesh(n_dev, 1)
    block_rows += (-block_rows) % n_dev
    n_blocks = max(-(-r_total // block_rows), 1)
    r_run = n_blocks * block_rows

    gate = _flagship_bit_identity(mesh)
    assert gate["bit_identical"], f"flagship bit-identity gate failed: {gate}"
    log(f"flagship bit-identity gate passed ({gate['subsample_shape']})")

    gen, per_replica_bytes = _flagship_population(cap, universe, actors)

    def blocks():
        for b in range(n_blocks):
            yield gen(jnp.arange(b * block_rows, (b + 1) * block_rows))

    # One telemetry pass (untimed — the flag changes the step program)
    # for the stream counters and the residency accounting.
    acc, of, tel = mesh_stream_fold_sparse(blocks(), mesh, telemetry=True)
    assert not bool(jnp.any(of)), "flagship stream overflowed its caps"
    block_bytes = float(tel.stream_staged_bytes) / max(n_blocks, 1)
    acc_bytes = state_nbytes(acc)
    # Peak residency: the staged block, the double-buffered next block,
    # the generator's output buffer, and the accumulator.
    peak_resident = int(3 * block_bytes + acc_bytes)
    coresident = r_run * per_replica_bytes
    live = int(jnp.sum(acc.valid))

    def run(k: int):
        out = None
        for _ in range(k):
            out = mesh_stream_fold_sparse(blocks(), mesh)
        return out

    dt, degraded = marginal_time(run, 1, "flagship stream", iters=ITERS)
    mps = (r_run - 1) / dt
    metrics.observe("bench.flagship_merges_per_sec", mps)
    log(
        f"config-flagship: {r_run} replicas x {universe:,}-element universe "
        f"streamed as {n_blocks} blocks of {block_rows} (cap {cap}): "
        f"{dt*1e3:.1f} ms/stream -> {mps:,.0f} merges/s; resident "
        f"{peak_resident/1e6:.1f} MB vs co-resident "
        f"{coresident/1e6:.1f} MB ({coresident/max(peak_resident, 1):.1f}x); "
        f"staged {float(tel.stream_staged_bytes)/1e6:.1f} MB, overlap hits "
        f"{int(tel.stream_overlap_hit)}"
        + (" [conservative bound]" if degraded else "")
    )
    return {
        "config": "flagship", "metric": "orswot_merges_per_sec",
        "value": round(mps, 1), "unit": "merges/s",
        "shape": f"{r_total}x{universe}",
        "replicas_run": r_run, "blocks": n_blocks,
        "block_rows": block_rows, "segment_cap": cap, "actors": actors,
        "live_dots": live,
        "path": "stream",
        "block_source": "device-generated (distinct replica slices)",
        "staged_bytes": float(tel.stream_staged_bytes),
        "overlap_hit": int(tel.stream_overlap_hit),
        "peak_device_resident_bytes": peak_resident,
        "coresident_equiv_bytes": coresident,
        "resident_reduction": round(coresident / max(peak_resident, 1), 1),
        "oracle_gate": gate,
        "bit_identical": gate["bit_identical"],
        "timing": "conservative" if degraded else "marginal",
        "degraded": degraded,
    }


def parse_args(argv=None):
    """``--metrics-out`` (or env BENCH_METRICS_OUT): JSONL sink the
    observability drain appends to — registry snapshot, telemetry
    records, bench spans — so metric trajectories persist per run
    instead of dying in stderr (schema:
    tools/telemetry_schema.json)."""
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument(
        "--metrics-out",
        default=os.environ.get("BENCH_METRICS_OUT", ""),
        help="append the metrics snapshot / telemetry / span JSONL here",
    )
    ap.add_argument(
        "--quick-comms",
        action="store_true",
        help="run ONLY the comms leg (full vs digest-gated gossip bytes "
             "per round) and print its record to stdout",
    )
    ap.add_argument(
        "--reclaim",
        action="store_true",
        help="run ONLY the causal-stability reclamation leg (long-churn "
             "add/rm workload with stability= on and the shrink "
             "hysteresis) and print its record to stdout",
    )
    ap.add_argument(
        "--chaos",
        action="store_true",
        help="run ONLY the degraded-mesh fault-tolerance leg (corrupted "
             "+ dropped packets and an evicted-then-rejoined rank on "
             "the δ ring, healed bit-identical; frontier unpinning) and "
             "print its record to stdout",
    )
    ap.add_argument(
        "--heal",
        action="store_true",
        help="run ONLY the optimal-δ-sync leg (ack-window steady-state "
             "payload vs the digest-only baseline, and partition heal "
             "by decomposition resync vs full-state gossip, both "
             "bit-identity gated) and print its record to stdout",
    )
    ap.add_argument(
        "--recovery",
        action="store_true",
        help="run ONLY the crash-consistent durability leg (WAL'd δ "
             "rounds + generational snapshot, kill, timed recovery "
             "asserted bit-identical, log-suffix rejoin bytes vs "
             "full-state resync) and print its record to stdout",
    )
    ap.add_argument(
        "--scaleout",
        action="store_true",
        help="run ONLY the elastic mesh scale-out leg (mid-run admit "
             "raising sustained merges/s, warm-start bootstrap bytes, "
             "certified drain, bit-identical to the fixed-width oracle "
             "in both directions) and print its record to stdout",
    )
    ap.add_argument(
        "--serve",
        action="store_true",
        help="run ONLY the multi-tenant serving leg (1M+ live tenants "
             "with churn through the tenant-packed superblock: "
             "sustained ops/s, p99 apply latency, cold-tenant "
             "evict/restore, sequential-oracle bit-identity) and print "
             "its record to stdout",
    )
    ap.add_argument(
        "--fanout",
        action="store_true",
        help="run ONLY the δ-subscription fan-out leg (1M+ subscribers "
             "over the churning superblock: cohort δ pushes/s, bytes "
             "per subscriber vs full-state push, dead-subscriber "
             "resync, client bit-identity) and print its record to "
             "stdout",
    )
    ap.add_argument(
        "--geo",
        action="store_true",
        help="run ONLY the geo-federation leg (multi-region mesh-of-"
             "meshes: δ anti-entropy over checksum-guarded links, a "
             "mid-traffic region-kill failover with zero acked-op "
             "loss, causal-watermark local reads, partial-replication "
             "residency) and print its record to stdout",
    )
    ap.add_argument(
        "--flagship",
        action="store_true",
        help="run ONLY the flagship replica-streaming leg (10,240 "
             "replicas x 1M elements through parallel/stream.py, shape "
             "from BENCH_CONFIGS.json) and print its record to stdout",
    )
    return ap.parse_args(argv)


# ``--<flag>`` → the legs that mode runs alone (one stdout JSON line).
_SINGLE_LEG_MODES = {
    "flagship": ("flagship",),
    "serve": ("serve", "serve_zipf"),
    "fanout": ("fanout",),
    "geo": ("geo",),
    "scaleout": ("scaleout",),
    "recovery": ("recovery",),
    "heal": ("heal",),
    "chaos": ("chaos",),
    "reclaim": ("reclaim",),
    "quick_comms": ("comms",),
}


def _run_leg(name: str, device: dict, **span_kw) -> list:
    """One leg's records, each stamped with the device it ran on. A
    leg that raises fails the run: no record is ever swallowed."""
    from crdt_tpu.telemetry import span

    with span(f"bench.{name}", **span_kw):
        out = globals()[f"bench_{name}"]()
    recs = out if isinstance(out, list) else [out]
    for rec in recs:
        rec["device"] = device
        rec["degraded"] = bool(
            rec.get("degraded", False) or device["platform"] != "tpu"
        )
    return recs


def main(argv=None):
    global R, E, CHUNK
    args = parse_args(argv)
    device = device_info()
    degraded = device["platform"] != "tpu"
    for flag, legs in _SINGLE_LEG_MODES.items():
        if getattr(args, flag):
            recs = []
            for name in legs:
                recs += _run_leg(name, device, quick=True)
            for rec in recs:
                log(json.dumps(rec))
            # Legs that need more devices than are visible return
            # no record; the mode then says it was skipped.
            print(json.dumps(recs[0] if recs else {
                "config": legs[0], "skipped": True, "device": device}))
            return
    if degraded:
        # The CPU run: scale the shape to something XLA:CPU finishes;
        # the sparse/flagship legs read their cpu_fallback blocks.
        R, E, CHUNK = min(R, 64), min(E, 4096), min(CHUNK, 16)
        for var, cpu_cap in (
            ("BENCH_MAP_KEYS", 20000),
            ("BENCH_LIST_OPS", 5000),
            ("BENCH_LIST_REPLICAS", 64),
        ):
            os.environ[var] = str(min(int(os.environ.get(var, cpu_cap)), cpu_cap))
    records = []
    for name in (
        "clocks", "map", "list", "sparse", "sparse_map", "flagship",
        "elastic", "comms", "reclaim", "chaos", "heal", "recovery",
        "scaleout", "serve", "serve_zipf", "fanout",
    ):
        if os.environ.get(f"BENCH_{name.upper()}", "1") != "0":
            records += _run_leg(name, device, degraded=degraded)
    from crdt_tpu.telemetry import span

    with span("bench.cpu"):
        cpu_mps = bench_cpu()
    with span("bench.tpu", degraded=degraded):
        tpu_mps, path, gbps, bytes_moved, shape, timing_bound = bench_tpu()
    headline = {
        "metric": "orswot_merges_per_sec",
        "value": round(tpu_mps, 1),
        "unit": "merges/s",
        "vs_baseline": round(tpu_mps / cpu_mps, 2),
        "path": path,
        "gbps": round(gbps, 1),
        "bytes_moved": bytes_moved,
        "shape": shape,
        "timing": "conservative" if timing_bound else "marginal",
        "device": device,
        "degraded": timing_bound or degraded,
    }
    from crdt_tpu import exporter
    from crdt_tpu.utils.metrics import metrics

    # Persist the observability trajectory INTO the round artifacts
    # instead of letting it die in stderr: the full registry snapshot
    # rides the headline record and, when --metrics-out is set, the JSONL drain
    # (snapshot + spans; schema-checked by tier-1).
    snapshot = metrics.snapshot()
    headline["metrics"] = snapshot
    # The comms ratio rides the headline record too (the digest-gating
    # win is a round metric, not a diagnostic).
    comms = next((r for r in records if r.get("config") == "comms"), None)
    if comms is not None:
        headline["comms"] = {
            k: comms[k] for k in (
                "value", "bytes_full_per_link_round",
                "bytes_delta_wire_per_link_round",
                "bytes_delta_useful_per_link_round", "churn",
                "bit_identical",
            ) if k in comms
        }
    # The reclamation leg rides the headline record too: the memory
    # trajectory is a round metric of record (ISSUE 5), not a
    # diagnostic.
    rc = next((r for r in records if r.get("config") == "reclaim"), None)
    if rc is not None:
        headline["reclaim"] = {
            k: rc[k] for k in (
                "value", "shrink_events", "peak_occupancy",
                "peak_state_bytes", "end_state_bytes",
                "end_state_bytes_never_reclaimed", "bit_identical",
            ) if k in rc
        }
    # The chaos leg rides the headline record too: the damage the mesh
    # absorbs while staying bit-identical (and the frontier unpinning)
    # is ISSUE 8's metric of record, not a diagnostic.
    ch = next((r for r in records if r.get("config") == "chaos"), None)
    if ch is not None:
        headline["chaos"] = {
            k: ch[k] for k in (
                "value", "packets_rejected", "packets_dropped",
                "evicted_rank", "reclaimed_slots_pinned",
                "reclaimed_slots_evicted", "bit_identical",
            ) if k in ch
        }
    # The heal leg rides the headline record too: the optimal-δ-sync
    # byte wins (ack window vs the digest baseline; decomposition
    # resync vs full-state heal) are ISSUE 9's metrics of record.
    hl = next((r for r in records if r.get("config") == "heal"), None)
    if hl is not None:
        headline["heal"] = {
            k: hl[k] for k in (
                "value", "resync_bytes_shipped",
                "resync_bytes_full_state",
                "bytes_useful_digest_per_link_round",
                "bytes_useful_acked_per_link_round",
                "ack_vs_digest_useful_ratio",
                "bytes_acked_skipped_total", "bit_identical",
            ) if k in hl
        }
    # The recovery leg rides the headline record too: recovery time and
    # the log-rejoin-vs-full-state byte win are ISSUE 10's metrics of
    # record.
    rv = next((r for r in records if r.get("config") == "recovery"), None)
    if rv is not None:
        headline["recovery"] = {
            k: rv[k] for k in (
                "value", "recovery_seconds", "replayed_records",
                "wal_bytes", "wal_fsyncs", "rejoin_bytes_shipped",
                "rejoin_bytes_full_state", "bit_identical",
            ) if k in rv
        }
    # The scaleout leg rides the headline record too: the mid-run
    # capacity trajectory (merges/s across the admit, the bootstrap
    # byte ratios, the drain certificate) is ISSUE 11's metric of
    # record, not a diagnostic.
    sc = next((r for r in records if r.get("config") == "scaleout"), None)
    if sc is not None:
        headline["scaleout"] = {
            k: sc[k] for k in (
                "value", "merges_per_s_pre_admit",
                "merges_per_s_post_admit", "merges_per_s_post_drain",
                "live_ranks_trajectory", "bootstrap_cold_ratio",
                "bootstrap_warm_ratio", "drain_residue",
                "drain_lanes_unacked", "generation", "bit_identical",
            ) if k in sc
        }
    # The serve leg rides the headline record too: sustained ops/s and
    # p99 apply latency at 1M+ live tenants (with the evict/restore
    # cycle and the oracle gate) is ISSUE 15's metric of record.
    sv = next((r for r in records if r.get("config") == "serve"), None)
    if sv is not None:
        headline["serve"] = {
            k: sv[k] for k in (
                "value", "tenants", "lanes", "dispatch_p50_us",
                "dispatch_p99_us", "ingest_coalesced_ops",
                "resident_ratio", "evict_cohort",
                "evict_restored_in_window", "bit_identical",
            ) if k in sv
        }
    # The pipelined zipf serving leg rides the headline too: sustained
    # ops/s vs the serial baseline, overlap-hit ratio, WAL volume, the
    # skew p99 trajectory, and zero-acked-op-loss recovery is ISSUE
    # 18's metric of record.
    sz = next(
        (r for r in records if r.get("config") == "serve_zipf"), None,
    )
    if sz is not None:
        headline["serve_zipf"] = {
            k: sz[k] for k in (
                "value", "serial_ops_per_sec", "pipeline_speedup",
                "overlap_hit_ratio", "serve_wal_bytes",
                "serve_wal_fsyncs", "dispatch_p99_before_us",
                "dispatch_p99_during_us", "dispatch_p99_after_us",
                "skew_p99_ratio", "rebalance_moves", "acked_ops_lost",
                "bit_identical",
            ) if k in sz
        }
    # The fanout leg rides the headline record too: δ-pushes/s and
    # bytes/subscriber vs the full-state push at 1M+ live subscribers
    # (with the resync fallbacks and the client-replica bit-identity
    # gate) is ISSUE 16's metric of record.
    fo = next((r for r in records if r.get("config") == "fanout"), None)
    if fo is not None:
        headline["fanout"] = {
            k: fo[k] for k in (
                "value", "subscribers", "tenants",
                "bytes_per_subscriber", "full_row_bytes",
                "delta_vs_full_ratio", "overall_vs_full_ratio",
                "resync_fallbacks", "cohorts_dispatched",
                "bit_identical",
            ) if k in fo
        }
    # The flagship streaming record rides the headline too: it IS the
    # metric of record at the north-star shape (ROADMAP item 1).
    fl = next((r for r in records if r.get("config") == "flagship"), None)
    if fl is not None:
        headline["flagship"] = {
            k: fl[k] for k in (
                "value", "shape", "blocks", "block_rows", "segment_cap",
                "staged_bytes", "overlap_hit", "peak_device_resident_bytes",
                "coresident_equiv_bytes", "resident_reduction",
                "bit_identical", "timing", "degraded",
            ) if k in fl
        }
    records.append({"config": 3, **headline})
    # Per-config JSON lines (machine-readable) on stderr + a sidecar
    # file; stdout stays EXACTLY one line.
    for rec in records:
        log(json.dumps(rec))
    try:
        # Per-run RESULT records go to BENCH_RECORDS.json (gitignored);
        # BENCH_CONFIGS.json is the COMMITTED shape-config input now —
        # clobbering it with results would destroy the shared source of
        # truth the legs and chip_smoke.py read.
        with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "BENCH_RECORDS.json"), "w") as f:
            json.dump(records, f, indent=1)
    except OSError as exc:
        log(f"could not write BENCH_RECORDS.json: {exc!r}")
    if args.metrics_out:
        try:
            n = exporter.drain_jsonl(args.metrics_out, snapshot=snapshot)
            log(f"metrics drain: {n} records -> {args.metrics_out}")
        except OSError as exc:
            log(f"could not write {args.metrics_out}: {exc!r}")
    log("metrics snapshot: " + json.dumps(snapshot))
    print(json.dumps(headline))


if __name__ == "__main__":
    main()
