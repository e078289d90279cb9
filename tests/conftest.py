"""Test config: force JAX onto CPU with 8 virtual devices so the multi-chip
sharding paths (crdt_tpu.parallel) compile and run without TPU hardware.

NOTE: this OVERRIDES any ``--xla_force_host_platform_device_count`` you
set in XLA_FLAGS — the suite's mesh-shape tests assume exactly 8 virtual
devices. Edit the ``pin_cpu(virtual_devices=8)`` call below if you need a
different count.

The pin-CPU recipe lives in ``crdt_tpu.utils.cpu_pin``.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from crdt_tpu.utils.cpu_pin import pin_cpu

pin_cpu(virtual_devices=8)

import jax

from crdt_tpu.utils.compile_cache import enable_compile_cache

# Persistent XLA compilation cache: the suite is compile-bound on CPU
# (hundreds of shard_map/jit programs), and the cache is keyed on the
# HLO so it is safe across reruns. The helper exports the env vars, so
# the multihost/example worker subprocesses hit the same cache.
enable_compile_cache()

assert jax.default_backend() == "cpu", jax.default_backend()
assert len(jax.devices()) == 8, jax.devices()

import pytest

try:
    import hypothesis  # noqa: F401  (the real package, when installed)
except ModuleNotFoundError:
    # Some CI images ship jax but not hypothesis; the property suite
    # still runs on the deterministic fallback sampler (_hyposhim.py).
    from _hyposhim import _install

    _install()

from hypothesis import settings

# One CPU core in CI: keep example counts modest by default (24 keeps
# the full tier-1 suite inside its wall-clock budget on this box; crank
# locally with an explicit @settings(max_examples=...) on the test).
settings.register_profile("ci", max_examples=24, deadline=None)
settings.load_profile("ci")
# Quick-iteration profile for the smoke subset (selected below).
settings.register_profile("smoke", max_examples=8, deadline=None)


# ---- smoke subset ---------------------------------------------------------
# ``pytest -m smoke`` runs ONE representative A/B gate per family (~1 min on
# the 1-core box) instead of the full ~13-minute suite. Curated here rather
# than as scattered decorators so the subset is auditable in one place; only
# the FIRST collected instance of a parameterized prefix is marked.
SMOKE_PREFIXES = (
    "test_vclock.py::",                     # first law test
    "test_models_counters.py::test_gcounter_fold_read_matches_oracle",
    "test_models_counters.py::test_pncounter_fold_read_matches_oracle",
    "test_models_registers.py::test_gset_join_and_fold_match_oracle",
    "test_models_registers.py::test_lww_updates_and_fold_match_oracle",
    "test_models_registers.py::test_mvreg_join_and_fold_match_oracle",
    "test_models_orswot.py::test_join_bit_identical_to_oracle_merge",
    "test_sparse_orswot.py::test_sparse_join_matches_dense_join",
    "test_models_map.py::test_join_bit_identical_to_oracle_merge",
    "test_models_map3.py::test_join_bit_identical_to_oracle_merge",
    "test_models_map_nested.py::test_nested_join_bit_identical",
    "test_sequences.py::test_list_concurrent_inserts_converge",
    "test_native_list.py::",
    "test_merkle.py::",
    "test_serde.py::test_orswot_round_trip_including_deferred",
    "test_checkpoint.py::test_orswot_resume_then_merge",
    "test_parallel.py::test_mesh_fold_bit_identical",
    "test_delta.py::test_delta_gossip_matches_fold",
)


# ---- slow tier ------------------------------------------------------------
# Tier-1 CI runs ``-m 'not slow'`` under a hard wall-clock budget. These
# are the heaviest gates whose law/path each has a faster cousin that
# stays in tier-1 (named alongside); run the full set with plain
# ``pytest tests/``. Curated here, like SMOKE_PREFIXES, to stay auditable.
SLOW_NODEIDS = (
    # deep-nesting demo; 01/03 cover the example harness, nest laws in
    # test_models_map_nested / test_sparse_nested_map
    "test_examples.py::test_example_runs[06_deep_nesting_and_sparse.py]",
    # 2-process fold; examples/04_multihost_dcn.py drives the same
    # worker pair, and test_two_process_list_sync keeps the runtime gate
    "test_multihost.py::test_two_process_mesh_fold_bit_identical",
    # depth-3 sparse laws; depth-2 laws in test_sparse_mvmap.py, dense
    # depth-3 in test_models_map3 / test_delta_map3
    "test_sparse_mvmap_depth3.py::test_depth3_join_laws",
    "test_sparse_mvmap_depth3.py::test_depth3_fold_equals_sequential_joins",
    # deep sparse-nest folds; depth-2 fold in test_sparse_nest.py,
    # depth-4 single-shot fold gate stays (test_nest_depth4)
    "test_sparse_nest3.py::test_sparse_depth3_fold_matches_oracle",
    "test_nest_depth4.py::test_depth4_delta_exchange_converges",
    # heaviest churn-reclamation gate (also @mark.slow in-file); the
    # three per-kind churn legs in test_reclaim.py stay tier-1
    "test_reclaim.py::test_churn_reclaim_long_mixed",
    # heaviest streaming gate (widen + reclaim + telemetry over 24
    # replicas); block-count invariance, widen, reclaim, and counter
    # laws each have a faster in-tier cousin in test_stream.py
    "test_stream.py::test_stream_combined_widen_reclaim_large",
    # ---- second curation round (ISSUE 7: wall-clock crept past the
    # 870 s tier-1 budget; ROADMAP item-5 satellite). Same contract:
    # every promotion names its faster in-tier cousin.
    # replica-fold mesh-shape sweep: (8,1) pow2 replica-only, (4,2)
    # gate mesh, and (3,1) non-pow2 all_gather fallback stay tier-1;
    # the remaining element-shard permutations move here
    "test_parallel.py::test_mesh_fold_bit_identical[mesh_shape2]",
    "test_parallel.py::test_mesh_fold_bit_identical[mesh_shape3]",
    "test_parallel.py::test_mesh_fold_bit_identical[mesh_shape5]",
    # compiled-HLO aliasing sweep over every donated entry (~25 s): the
    # registry-discovery failures stay tier-1 (test_check_aliasing /
    # test_analysis), the jaxpr-level donation-alias lint runs in-tier,
    # and tools/run_static_checks.py `aliasing` runs the full compiled
    # gate on every chain invocation
    "test_check_aliasing.py::test_every_donated_entry_point_aliases",
    # example demos with dedicated in-tier suites: 07 (lifecycle) is
    # covered by test_lifecycle.py, 05 (δ sync) by test_delta.py +
    # test_zero_copy_ring.py; 01/03/04 stay (harness + multihost cousins)
    "test_examples.py::test_example_runs[07_lifecycle_and_certificates.py]",
    "test_examples.py::test_example_runs[05_delta_sync.py]",
    # depth-4 replica-multiplied fold; the depth-4 op path and join
    # gates stay tier-1 (test_nest_depth4), depth-2 folds in
    # test_sparse_nest
    "test_nest_depth4.py::test_depth4_fold_bit_identical_to_oracle_fold",
    # one of three per-kind churn-reclaim legs; dense + sparse_orswot
    # legs stay tier-1, the mixed long gate was already slow
    "test_reclaim.py::test_churn_reclaim_sparse_map",
    # map3 replica fold vs oracle; map3 op path (test_models_map3) and
    # the δ drain gates (test_delta_map3) stay tier-1
    "test_models_map3.py::test_fold_bit_identical_to_oracle_fold",
    # one of four donated==undonated bit-identity properties; the
    # dense, sparse-set, and δ flavors stay tier-1 (test_donation.py)
    "test_donation.py::test_donated_sparse_map_gossip_bit_identical",
    # lattice laws for the single heaviest kind; the other 11 kinds
    # stay tier-1 and run_static_checks `laws` checks all 12 per chain
    "test_analysis.py::test_registered_kind_passes_lattice_laws[sparse_nested_map]",
    # digest-gating A/B with pipeline=False; the default-flags
    # (pipeline=True) twin stays tier-1 (test_zero_copy_ring.py)
    "test_zero_copy_ring.py::test_digest_gating_bit_identical_and_fewer_useful_bytes[False]",
    # sparse-vs-dense replica fold A/B; the join-level twin
    # (test_sparse_join_matches_dense_join), the ring-gossip A/B
    # (test_sparse_ring_gossip_matches_dense_fold), and the model-level
    # gate (test_sparse_model_ab_gate) stay tier-1
    "test_sparse_orswot.py::test_sparse_fold_matches_dense_fold",
    # sparse-map faulty-delivery convergence; the dense device-dropout
    # gate (test_device_anti_entropy_with_dropouts_converges) and the
    # pure drop/dup/reorder property stay tier-1
    "test_fault_injection.py::test_sparse_map_faulty_delivery_converges",
    # ---- third curation round (ISSUE 8: the chaos soak must not push
    # tier-1 past the 870 s budget). The 8-rank mixed
    # drop/corrupt/evict/rejoin soak moves here; its 4-rank in-tier
    # cousin (test_chaos_soak_dense_quick) runs the same machinery —
    # eviction trigger included — on a shorter schedule, and the map-δ
    # and sparse-stream chaos legs stay tier-1.
    "test_chaos.py::test_chaos_soak_dense_long",
    # ---- fourth curation round (ISSUE 9: the decomposition property
    # gates). The 5 heaviest per-kind decomposition-law params move
    # here; the cheap representatives (orswot, sparse_orswot, gset,
    # lwwreg, mvreg, vclock, map_orswot) stay tier-1, and
    # tools/run_static_checks.py `decomp` runs ALL 12 kinds on every
    # chain invocation regardless — the same split the schedule
    # checker uses.
    "test_delta_opt.py::test_decomposition_laws_clean[sparse_nested_map]",
    "test_delta_opt.py::test_decomposition_laws_clean[sparse_mvmap]",
    "test_delta_opt.py::test_decomposition_laws_clean[map]",
    "test_delta_opt.py::test_decomposition_laws_clean[map_map]",
    "test_delta_opt.py::test_decomposition_laws_clean[map3]",
    # ---- fifth curation round (ISSUE 11: the scale-out suite lands
    # ~28 s of new tests with tier-1 already at ~845 s against the
    # 870 s budget). Same contract: every promotion names its faster
    # in-tier cousin.
    # the 8-rank chaos scale-out soak; its 4-rank in-tier cousins run
    # the same machinery — bootstrap, certificate, generation stamps —
    # (test_admit_bootstraps_newcomer_from_bottom_bit_identical,
    # test_drain_cycle_certified_and_survivors_serve), and the
    # faults-composed gates stay tier-1 in test_fault_injection.py
    "test_scaleout.py::test_scaleout_soak_under_chaos_8rank",
    # heaviest example demo (~28 s); 02/03/04 keep the example-harness
    # and multihost coverage, and the tags workload's CRDT content is
    # the orswot/map model suites' bread and butter
    "test_examples.py::test_example_runs[01_collaborative_tags.py]",
    # heaviest per-kind op-path A/B (~20 s, depth-3 sparse); the
    # depth-2 sparse op paths (test_sparse_nest.py::
    # test_sparse_op_path_bit_identical, test_sparse_mvmap.py::
    # test_op_path_bit_identical) and this kind's fold/join/law gates
    # stay tier-1
    "test_sparse_nested_map.py::test_op_path_bit_identical",
    # heaviest fused-fold A/B (~14 s, map3); the orswot-chain and
    # nested-map fused folds (test_fused_fold_matches_tree_fold,
    # test_fused_nested_map_fold_matches_tree_fold) stay tier-1, and
    # map3's tree-fold oracle gate lives in test_models_map3
    "test_pallas_fold.py::test_fused_map3_fold_matches_tree_fold",
    # heaviest elastic-recovery leg (~13 s, nested key rm_width); the
    # flat rm_width and nested span recoveries
    # (test_elastic_call_recovers_rm_width_overflow,
    # test_elastic_call_recovers_span_overflow) stay tier-1
    "test_elastic.py::test_elastic_call_recovers_nested_key_rm_width_overflow",
    # (8,1) replica-only fold A/B (~29 s — mostly the suite's first
    # trace); the (4,2) gate-mesh and (3,1) non-pow2 params stay
    # tier-1, and the 8x1 replica axis is exercised end-to-end by the
    # gossip/δ/faults/scaleout suites every run
    "test_parallel.py::test_mesh_fold_bit_identical[mesh_shape0]",
    # sparse nested replica fold vs oracle (~12 s); the mesh-vs-host
    # fold gate (test_mesh_fold_matches_host_fold) and the dense
    # nested fold (test_models_map_nested) stay tier-1
    "test_sparse_nested_map.py::test_fold_bit_identical_to_oracle_fold",
    # second of three per-kind churn-reclaim legs (~16 s); the dense
    # leg stays tier-1 as the in-tier churn representative (mixed and
    # sparse_map moved in earlier rounds), and sparse_orswot's
    # join/fold/compaction gates stay in-tier elsewhere
    "test_reclaim.py::test_churn_reclaim_sparse_orswot",
    # ---- sixth curation round (ISSUE 12: the observability suite
    # lands ~40 new tests with a contended tier-1 run already at the
    # 870 s wall on this 2-core box; idle-box wall clock 737 s). Same
    # contract: every promotion names its faster in-tier cousin, and
    # nothing promised as a cousin by an earlier round moves.
    # streamed-list chunked-vs-one-shot A/B (~14 s); the
    # element-sharded list A/B (test_element_sharded_list_matches
    # _unsharded) and the native one-shot list gates
    # (test_native_list.py) stay tier-1
    "test_streamed_lists.py::test_streamed_chunks_match_one_shot",
    # depth-3 sparse-vs-dense MODEL A/B (~12 s); the depth-2
    # sparse-vs-dense gates (test_sparse_mvmap.py) and this kind's
    # gossip/law/coverage gates stay tier-1
    "test_sparse_nested_map.py::test_sparse_matches_dense_model",
    # nested-model checkpoint round-trip (~10 s); the flat-model
    # checkpoint round-trips (test_checkpoint.py) and the durability
    # snapshot/model round-trips (test_durability.py) stay tier-1
    "test_checkpoint.py::test_nested_models_checkpoint_round_trip",
    # one of four per-kind stream-vs-fold invariance gates (~9 s);
    # the dense, sparse, and sharded stream gates stay tier-1
    # (test_stream.py), and mvmap's fold oracle lives in
    # test_sparse_mvmap.py
    "test_stream.py::test_mvmap_stream_matches_fold",
    # compaction invariance for the single heaviest kind (~9 s); the
    # other 11 kinds stay tier-1 and run_static_checks `laws` checks
    # all 12 per chain invocation (the round-2 laws[sparse_nested_map]
    # split, applied to the compaction law)
    "test_analysis.py::test_registered_kind_passes_compaction_invariance[sparse_nested_map]",
    # sparse jitted-gossip telemetry replay (~7 s); the dense twin
    # (test_jitted_dense_gossip_telemetry_matches_host_recompute)
    # runs the same host-recompute machinery in-tier, and the sparse
    # gossip path keeps its convergence gates in test_sparse_orswot.py
    "test_telemetry.py::test_jitted_sparse_gossip_telemetry_matches_host_recompute",
    # ---- seventh curation round (ISSUE 15: the serving front door).
    # Same contract: every promotion names its faster in-tier cousin.
    # sparse coalesced-vs-sequential A/B (~2.5 s): the dense param
    # stays tier-1 and the `serve` static-check section runs a
    # coalesced==sequential micro A/B on every chain invocation
    "test_serve.py::test_coalesced_apply_matches_sequential_oracle[sparse_orswot-caps1]",
    # mid-evict kills at the two SNAPSHOT-owned boundaries: the three
    # serve.* crashpoint params stay tier-1, and the `durability`
    # static-check section kill-and-recovers at EVERY snapshot
    # boundary (the serve persist/restore crossings included) per
    # chain invocation
    "test_serve.py::test_mid_evict_crash_recovers_last_durable_record[snapshot.pre_rename-False]",
    "test_serve.py::test_mid_evict_crash_recovers_last_durable_record[snapshot.post_commit_pre_prune-True]",
    # ---- eighth curation round (ISSUE 19: the interleaving explorer).
    # Same contract: every promotion names its faster in-tier cousin.
    # full 2-preemption serve matrix, 2 tenants × 3 ops, both kinds
    # (also @mark.slow in-file): the 1-preemption closures in
    # test_concur.py stay tier-1 and the `concurrency` static-check
    # section explores the dense serve world + the full fanout world
    # on every chain invocation
    "test_concur.py::test_explorer_serve_full_matrix[orswot]",
    "test_concur.py::test_explorer_serve_full_matrix[sparse_orswot]",
)


# ---- address-space guard (map-count cliff on long single-process runs) ----
# Every compiled XLA:CPU executable holds a handful of anonymous
# mappings for its code pages; a full tier-1 run accumulates tens of
# thousands of executables in one process, and once the kernel's
# vm.max_map_count (default 65530) is exhausted the NEXT mmap inside
# backend_compile dies as a SIGSEGV — the suite crashes mid-run at
# whatever innocent test happens to cross the line, with no Python
# traceback naming the real cause (found live in PR 14: the fused-wire
# A/B suites pushed the count over the cliff at ~64 980 maps, killing a
# plain shard_orswot device_put in test_telemetry). Dropping the jit
# caches releases the executables' mappings (verified: 300 executables
# ≈ 1 800 maps, fully reclaimed by jax.clear_caches()); the persistent
# XLA compilation cache above makes the recompiles cheap disk loads, so
# the guard costs nothing until it actually fires — and firing beats a
# segfault every time.
_MAP_GUARD_EVERY = 25       # tests between /proc/self/maps checks
_MAP_GUARD_LIMIT = 45_000   # clear well before the 65 530 kernel cliff
_map_guard_tick = 0


def _map_count() -> int:
    try:
        with open("/proc/self/maps", "rb") as f:
            return sum(1 for _ in f)
    except OSError:  # non-Linux: no /proc, and no known cliff either
        return 0


def pytest_runtest_teardown(item, nextitem):
    global _map_guard_tick
    _map_guard_tick += 1
    if _map_guard_tick % _MAP_GUARD_EVERY:
        return
    if _map_count() < _MAP_GUARD_LIMIT:
        return
    import gc

    import jax

    jax.clear_caches()
    gc.collect()


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "smoke: one fast A/B gate per CRDT family (~1 min subset)"
    )
    config.addinivalue_line(
        "markers", "slow: heavyweight gates excluded from tier-1 CI"
    )
    if (config.getoption("-m") or "").strip() == "smoke":
        settings.load_profile("smoke")


def pytest_collection_modifyitems(config, items):
    seen = set()
    for item in items:
        nodeid = item.nodeid.split("/")[-1]
        if nodeid in SLOW_NODEIDS:
            item.add_marker(pytest.mark.slow)
        for p in SMOKE_PREFIXES:
            if nodeid.startswith(p) and p not in seen:
                seen.add(p)
                item.add_marker(pytest.mark.smoke)
                break
