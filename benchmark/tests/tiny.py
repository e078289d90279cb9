"""Tiny shapes for CPU rehearsals of every cell: the same drivers and
readers, cut to what the CPU test run can hold."""

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)

TENANTS = dict(tenants=4096, lanes=4096, elems=16, slab_lanes=256,
               dispatch_lanes=256, subscribers=4096, evict_pressure_batch=32,
               max_rate=60000, warm_draws=65536, warm_ops=1024,
               recover_lanes=128, row_hot=4, row_evicted=4, row_sample=12,
               client_hot=2, client_sample=6)
TINY = {
    "tenants_resident_sat": dict(TENANTS, pending=2048,
                                 push_buckets=[1 << k for k in range(9)]),
    "tenants_resident_rate": dict(TENANTS, rate=400,
                                  push_buckets=[1 << k for k in range(9)]),
    "replicas_fold_1chip": dict(replicas=64, elems=512, writer_elems=4,
                                check_span=8, check_rounds=2, ref_block_rows=16),
    "replicas_ring_4chip": dict(replicas=64, elems=512, writer_elems=4,
                                check_span=8, check_rounds=2, ref_block_rows=16),
}


def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def rehearse(cell, seed=7, seconds=1.0, trace=0, control=0, **extra):
    """One run of ``cell`` at the tiny size, in this process."""
    for p in (BENCH, ROOT):
        if p not in sys.path:
            sys.path.insert(0, p)
    import run

    args = run.parse(["--workload", cell, "--seed", str(seed), "--seconds",
                      str(seconds), "--trace", str(trace), "--control", str(control)])
    return run.run_cell(args, bench(), overrides=dict(TINY[cell], **extra))
