"""Driver for the multi-tenant ORSWOT store: ``IngestQueue`` (with its
``ServeWal`` and ``Evictor``) → ``ServeLoop.step`` → ``FanoutPlane.push``.

The traffic file picks the loop: ``closed`` keeps ``pending`` ops queued
and refills before every step; ``open`` submits each op when it falls due
(Poisson arrivals at ``rate``). Both push exactly the tenants whose ops
finished, at most every ``push_interval_s``. The push's gather compiles
once per number of tenants, so an episode ships them in pieces whose
sizes are ``push_buckets`` (powers of two, largest first), each warmed in
set-up: the window compiles nothing and ships no tenant without news.
"""

from __future__ import annotations

import contextlib
import gc
import os
import time
from collections import defaultdict, deque

import numpy as np

from gen import tenant_ops
from reference import orswot as ref

ADD, RM = 1, 2


class _Replay:
    """The queue ``recover_serve`` replays into, passing on only the
    sampled tenants' ops (the replay itself walks every WAL record)."""

    def __init__(self, queue, keep):
        self.q, self.keep = queue, set(int(t) for t in keep)
        self.sb, self.evictor = queue.sb, queue.evictor

    def submit(self, tenant, op):
        if int(tenant) in self.keep:
            self.q.submit(tenant, op)

    def drain(self, **kw):
        return self.q.drain(**kw)


class Store:
    """The system under test plus the benchmark's ledger of every op."""

    def __init__(self, ctx, sched):
        from crdt_tpu.fanout import FanoutPlane
        from crdt_tpu.parallel import make_mesh
        from crdt_tpu.serve import Evictor, IngestQueue, ServeLoop, Superblock
        from crdt_tpu.serve.wal import ServeWal

        cfg, tr = ctx.config, ctx.traffic
        self.ctx, self.cfg, self.tr = ctx, cfg, tr
        self.t, self.a = cfg["tenants"], cfg["actors"]
        self.caps = dict(n_elems=cfg["elems"], n_actors=cfg["actors"],
                         deferred_cap=cfg["deferred_cap"])
        self.snap = os.path.join(ctx.scratch, "snap")
        self.walp = os.path.join(ctx.scratch, "wal")
        self.mesh = make_mesh(1, 1)
        self.sb = Superblock(self.t, self.mesh, kind="orswot", caps=self.caps,
                             n_lanes=cfg["lanes"])
        self.ev = Evictor(self.sb, self.snap,
                          pressure_batch=cfg["evict_pressure_batch"])
        self.wal = ServeWal(self.walp)
        self.q = IngestQueue(self.sb, lanes=cfg["slab_lanes"],
                             depth=cfg["slab_depth"],
                             max_pending=tr["max_pending"], evictor=self.ev,
                             wal=self.wal)
        self.loop = ServeLoop(self.q)
        self.plane = FanoutPlane(self.sb, evictor=self.ev,
                                 window_cap=cfg["window_cap"],
                                 dispatch_lanes=cfg["dispatch_lanes"],
                                 capacity=cfg["subscribers"])
        self.sched = sched
        n = len(sched)
        self.kind = np.zeros(n, np.uint8)
        self.val = np.zeros(n, np.uint32)
        self.next_ctr = np.zeros(self.t, np.uint32)
        self.fifo = defaultdict(deque)       # tenant -> submitted op ids
        self.n_applied = np.zeros(self.t, np.int64)   # ops issued to device
        self.awaiting = defaultdict(list)    # tenant -> finished, unpushed ids
        self.dirty = {}                      # insertion-ordered set
        self.finish_t = np.full(n, np.nan)
        self.due_abs = None
        self.window = (np.inf, np.inf)
        self.fresh_s = []
        self.acked = self.dispatches = self.slabs = self.submitted = 0
        self.pushed = self.pieces = 0        # cohorts shipped, plane.push calls
        self.refused = 0
        self.last_push = -np.inf
        self.clients = {}                    # subscriber -> deliveries

    # ---- ops -----------------------------------------------------------
    def submit(self, i: int) -> bool:
        from crdt_tpu.serve import IngestBackpressure

        t = int(self.sched.tenant[i])
        act = t % self.a
        mask = self.sched.mask[i]
        try:
            if self.sched.is_add[i] or self.next_ctr[t] == 0:
                c = int(self.next_ctr[t]) + 1
                self.q.add(t, act, c, mask)
                self.next_ctr[t] = c
                self.kind[i], self.val[i] = ADD, c
            else:
                clock = np.zeros(self.a, np.uint32)
                clock[act] = self.next_ctr[t]
                self.q.rm(t, clock, mask)
                self.kind[i], self.val[i] = RM, self.next_ctr[t]
        except IngestBackpressure:
            self.refused += 1
            return False
        self.fifo[t].append(i)
        self.submitted += 1
        return True

    def step(self) -> None:
        prev = self.loop.inflight
        rep, _ = self.loop.step()
        now = time.perf_counter()
        if rep is not None:
            self._finished(prev[0], rep, now)
        cur = self.loop.inflight
        if cur is not None and cur is not prev:
            self.slabs += 1
            for t, ops_l in cur[0].taken:
                self.n_applied[t] += len(ops_l)

    def flush_inflight(self) -> None:
        prev = self.loop.inflight
        rep, _ = self.loop.flush_inflight()
        if rep is not None:
            self._finished(prev[0], rep, time.perf_counter())

    def _finished(self, built, rep, now) -> None:
        lo, hi = self.window
        in_window = lo <= now <= hi
        if in_window:
            self.acked += rep.ops_applied
            self.dispatches += rep.dispatches
        for t, ops_l in built.taken:
            ids = [self.fifo[t].popleft() for _ in ops_l]
            self.finish_t[ids] = now
            self.awaiting[t].extend(ids)
            self.dirty[t] = None

    # ---- pushes ----------------------------------------------------------
    def push(self, spans=None) -> None:
        """One push episode: every dirty tenant, in pieces of bucket sizes."""
        take = list(self.dirty)
        pieces, lo = [], 0
        for b in reversed(self.tr["push_buckets"]):
            while len(take) - lo >= b:
                pieces.append(take[lo:lo + b])
                lo += b
        timed = spans.span("span.push", keep=True) if spans else contextlib.nullcontext()
        with timed:
            for piece in pieces:
                self._push_piece(piece)
        done = time.perf_counter()
        self.last_push = done
        lo, hi = self.window
        for t in take:
            del self.dirty[t]
            for i in self.awaiting.pop(t, ()):
                if lo <= self.finish_t[i] <= hi and self.due_abs is not None:
                    self.fresh_s.append(done - self.due_abs[i])

    def _push_piece(self, tenants) -> None:
        applied_at = {t: int(self.n_applied[t]) for t in self.clients}
        rep = self.plane.push(tenants=np.asarray(tenants))
        self.pushed += len(rep.pushes)
        self.pieces += 1
        members = []
        for cp in rep.pushes:
            members.append(cp.members)
            for s in cp.members:
                if int(s) in self.clients:
                    self.clients[int(s)].append(
                        ("wire", cp.wire, cp.to_ver, applied_at[int(s)]))
        for rs in rep.resyncs:
            members.append(rs.members)
            for s in rs.members:
                if int(s) in self.clients:
                    self.clients[int(s)].append(
                        ("state", rs.state, rs.to_ver, applied_at[int(s)]))
        if members:
            self.plane.ack(np.concatenate(members))

    # ---- the reference -------------------------------------------------
    def history(self, t: int, n_ops: int, n_submitted: int):
        out = []
        ids = np.nonzero(self.sched.tenant[:n_submitted] == t)[0]
        ids = ids[self.kind[ids] != 0][:n_ops]
        for i in ids:
            if self.kind[i] == ADD:
                out.append(("add", t % self.a, int(self.val[i]), self.sched.mask[i]))
            else:
                clock = np.zeros(self.a, np.uint32)
                clock[t % self.a] = self.val[i]
                out.append(("rm", clock, self.sched.mask[i]))
        return out


def _leaves(row):
    return [np.asarray(x) for x in (row.top, row.ctr, row.dcl, row.dmask, row.dvalid)]


def run(ctx) -> dict:
    cfg, tr, seed = ctx.config, ctx.traffic, ctx.seed
    open_loop = tr["loop"] == "open"
    seconds = ctx.seconds

    # ---- set-up: traffic, then the store, pre-filled and warmed ----------
    with ctx.phase("generate_s"):
        order = tenant_ops.hot_order(cfg["tenants"], tr["theta"],
                                     draws=tr["warm_draws"], seed=seed)
        warm = order[:tr["push_buckets"][-1]]
        if len(warm) < tr["push_buckets"][-1]:
            raise RuntimeError("warm-up draw found too few distinct tenants")
        rate = tr.get("rate", 0.0)
        n_win = int((rate * 1.5 if open_loop else tr["max_rate"]) * seconds)
        n_warm = tr["warm_ops"]
        sched = tenant_ops.schedule(
            seed, n_warm + n_win, n_keys=cfg["tenants"], elems=cfg["elems"],
            theta=tr["theta"], add_share=tr["add_share"],
            elems_per_op=tr["elems_per_op"], rate=rate)
    with ctx.phase("build_s"):
        st = Store(ctx, sched)
        st.plane.subscribe(np.arange(cfg["subscribers"], dtype=np.int64)
                           % cfg["tenants"])
        rng = np.random.default_rng([seed, 4])
        # Clients: the hottest tenants, and others among those the largest
        # warm-up push reaches, so every client has deliveries to replay.
        for t in list(warm[:tr["client_hot"]]) + list(rng.choice(
                warm[tr["client_hot"]:], tr["client_sample"] - tr["client_hot"],
                replace=False)):
            st.clients[int(t)] = []
    with ctx.phase("warmup_s"):
        i = 0
        while i < n_warm or st.q.n_pending or st.loop.inflight is not None:
            while i < n_warm and st.q.n_pending < tr["max_pending"]:
                st.submit(i)
                i += 1
            st.step()
        # Every shape the window uses: a restore from the durable tier,
        # and a push piece of each bucket size.
        gone = np.nonzero(st.sb.was_evicted & (st.sb.lane_of < 0))[0]
        if len(gone):
            st.ev.restore(int(gone[0]))
        for b in tr["push_buckets"]:
            st._push_piece([int(t) for t in warm[:b]])
        st.dirty.clear()
        st.awaiting.clear()

    # ---- the measured window -------------------------------------------
    if open_loop:  # the window's arrivals, from its start
        due = sched.due[n_warm:] - (sched.due[n_warm - 1] if n_warm else 0.0)
    tracer = None
    if ctx.traced:
        from crdt_tpu.obs import trace as obs_trace

        tracer = obs_trace.Tracer(sample=tr["trace_sample"], keep=1 << 22)
        obs_trace.install_tracer(tracer)
    spans = ctx.spans
    interval = tr["push_interval_s"]
    late = []
    c0 = ctx.compiles.snap()
    pushed0, pieces0 = st.pushed, st.pieces
    with ctx.window() as w:
        end = w.t0 + seconds
        st.window = (w.t0, np.inf)
        if open_loop:
            st.due_abs = np.full(len(sched), np.inf)
            st.due_abs[n_warm:] = w.t0 + due
        while True:
            now = time.perf_counter()
            if now >= end:
                break
            with spans.span("span.submit"):
                if open_loop:
                    while i < len(sched) and st.due_abs[i] <= now:
                        if st.submit(i):
                            late.append(time.perf_counter() - st.due_abs[i])
                        i += 1
                else:
                    while st.q.n_pending < tr["pending"]:
                        if i == len(sched):
                            raise RuntimeError("op schedule exhausted: the store "
                                               "outran max_rate; raise it")
                        st.submit(i)
                        i += 1
            if st.q.n_pending or st.loop.inflight is not None:
                with spans.span("span.step"):
                    st.step()
            elif open_loop and i < len(sched):
                time.sleep(max(0.0, min(st.due_abs[i], end) - time.perf_counter()))
            if st.dirty and time.perf_counter() - st.last_push >= interval:
                st.push(spans)
    window_s = w.seconds
    st.window = (w.t0, w.t1)
    pending_end = st.q.n_pending
    pushed, pieces = st.pushed - pushed0, st.pieces - pieces0
    c1 = ctx.compiles.snap()
    peak = ctx.memory_peak()
    if tracer is not None:
        from crdt_tpu.obs import trace as obs_trace

        obs_trace.install_tracer(None)

    # ---- after the window: settle, push what the window acked ------------
    attempted = (st.submitted + st.refused) - n_warm if not open_loop else (
        int(np.sum(due <= window_s)))
    st.flush_inflight()
    deadline = time.perf_counter() + 60.0
    while open_loop and any(
            np.any((st.finish_t[ids] >= st.window[0]) & (st.finish_t[ids] <= st.window[1]))
            for ids in st.awaiting.values()) and time.perf_counter() < deadline:
        time.sleep(max(0.0, st.last_push + interval - time.perf_counter()))
        st.push()
    n_sub = i
    out = {
        "e2e": {"ops_per_s": st.acked / window_s},
        "attempted": int(attempted),
        "failed": int(st.refused),
        "window_s": window_s,
        "memory_peak_bytes": peak,
        "counters": {
            "ops_acked": st.acked, "dispatches": st.dispatches,
            "slabs": st.slabs, "submitted": st.submitted - n_warm,
            "refused": st.refused, "window_compiles": c1["compiles"] - c0["compiles"],
            "window_cache_misses": c1["cache_misses"] - c0["cache_misses"],
            "wal_fsyncs": st.wal.fsyncs, "wal_bytes": st.wal.bytes_appended,
            "overlap_hits": st.loop.overlap_hits, "steps": st.loop.steps,
            "widen_events": st.sb.widen_events,
            "evicted_now": int(np.sum(st.sb.was_evicted & (st.sb.lane_of < 0))),
            "pending_end": pending_end,
            "push_cohorts": pushed, "push_pieces": pieces,
        },
        "samples": {},
    }
    if open_loop:
        out["e2e"]["fresh_p99_ms"] = (
            float(np.percentile(st.fresh_s, 99)) * 1e3 if st.fresh_s else None)
        out["counters"]["fresh_ops"] = len(st.fresh_s)
        out["samples"]["gen_late_s"] = late
    out["samples"]["push_s"] = spans.samples.get("span.push", [])
    if tracer is not None:
        out["samples"]["durable_s"] = _durable(tracer)

    # ---- correctness: served rows, recovery, subscribers ------------------
    checks = _check(ctx, st, n_sub)
    out["checks"] = checks
    return out


def _durable(tracer):
    """Submit → durable seconds of every sampled op, from the tracer's raw
    stamps (completed and open traces). The tracer is installed for the
    window alone, so every trace is of an op submitted in it."""
    stamp_lists = [rec["stamps"] for rec in tracer.recent]
    for lst in tracer.open_traces().values():
        stamp_lists += [stamps for _tid, stamps in lst]
    out = []
    for stamps in stamp_lists:
        first = {}
        for stage, t in stamps:
            first.setdefault(stage, t)
        if "submit" in first and "durable" in first:
            out.append((first["durable"] - first["submit"]) * 1e-9)
    return out


def _sample(ctx, st):
    rng = np.random.default_rng([ctx.seed, 5])
    tr = ctx.traffic
    counts = st.n_applied
    touched = np.nonzero(counts > 0)[0]
    hot = touched[np.argsort(-counts[touched], kind="stable")][:tr["row_hot"]]
    pick = list(hot)
    evicted = np.nonzero(st.sb.was_evicted)[0]   # parked in the durable tier
    if len(evicted):
        pick += list(rng.choice(evicted, min(tr["row_evicted"], len(evicted)), replace=False))
    pick += list(rng.choice(touched, min(tr["row_sample"], len(touched)), replace=False))
    return list(dict.fromkeys(int(t) for t in pick))


def _check(ctx, st, n_sub) -> list:
    import jax

    caps = st.caps
    sample = _sample(ctx, st)
    want = {t: ref.tenant_row(caps, st.history(t, int(st.n_applied[t]), n_sub))
            for t in sample}
    # Served rows (a tenant parked in the durable tier comes back first).
    # The control takes the program's place here: the reference with each
    # tenant's ops after the first applied in reverse order, which breaks
    # "per-tenant state equals the sequential application of its ops".
    served = 0
    for t in sample:
        if ctx.control:
            h = st.history(t, int(st.n_applied[t]), n_sub)
            got = ref.tenant_row(caps, h[:1] + h[1:][::-1]).leaves()
        else:
            if not st.sb.is_resident(t):
                st.ev.restore(t)
            got = _leaves(st.sb.row(t))
        served += ref.words_differing(got, want[t].leaves())
    # Subscribers: each sampled client replays what it was delivered.
    clients = 0
    cpu = jax.devices("cpu")[0]
    from crdt_tpu.fanout import ClientReplica

    with jax.default_device(cpu):
        empty = jax.tree.map(np.asarray, st.sb.tk.empty(**caps))
        for s, deliveries in st.clients.items():
            c = ClientReplica("orswot", empty)
            n_at = 0
            for how, payload, ver, applied in deliveries:
                if how == "wire":
                    c.apply_wire(payload, ver)
                else:
                    c.adopt(payload, ver)
                c.ack()
                n_at = applied
            got = ref.tenant_row(
                caps, st.history(s, n_at, n_sub) if deliveries else [])
            clients += ref.words_differing(
                [np.asarray(x) for x in jax.tree.leaves(c.state)], got.leaves())
    widens = st.sb.widen_events
    # Recovery: a fresh store rebuilt from the snapshot tier and the WAL.
    recovered = _recover(ctx, st, sample, want)
    checks = [
        ("served_row_words_off", served, 0),
        ("recovered_row_words_off", recovered, 0),
        ("client_row_words_off", clients, 0),
        ("rows_checked", len(sample), None),
        ("clients_checked", len(st.clients), None),
        ("widen_events", widens, 0),
    ]
    return checks


def _recover(ctx, st, sample, want) -> int:
    from crdt_tpu.serve import Evictor, IngestQueue, Superblock
    from crdt_tpu.serve.wal import ServeWal, recover_serve

    st.wal.close()
    caps, mesh, snap, walp = st.caps, st.mesh, st.snap, st.walp
    t_total = st.t
    for name in ("plane", "loop", "q", "ev", "sb"):
        setattr(st, name, None)
    gc.collect()
    sb = Superblock(t_total, mesh, kind="orswot", caps=caps,
                    n_lanes=ctx.traffic["recover_lanes"])
    ev = Evictor(sb, snap, pressure_batch=16)
    q = IngestQueue(sb, lanes=ctx.traffic["recover_lanes"],
                    depth=ctx.config["slab_depth"], max_pending=1 << 20,
                    evictor=ev)
    with ServeWal(walp) as wal:
        recover_serve(snap, _Replay(q, sample), wal)
    off = 0
    for t in sample:
        if not sb.is_resident(t):
            ev.restore(t)
        off += ref.words_differing(_leaves(sb.row(t)), want[t].leaves())
    return off
