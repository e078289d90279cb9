"""crdt_tpu.parallel — the distributed anti-entropy layer.

The reference has no communication backend at all: every type derives
serde and the *caller* ships bytes (SURVEY.md §3 row 17, §3.1). This
package is the TPU-native replacement — the single biggest new piece vs
the reference (SURVEY.md §6.8): replica state lives sharded over a
``jax.sharding.Mesh`` and anti-entropy runs as XLA collectives over
ICI/DCN instead of caller-transported bytes.

Mesh axes (SURVEY.md §3.1 mapping):

- ``replica`` — data-parallel analog: one lane per CRDT replica.
- ``element`` — tensor/sequence-parallel analog: the member universe of
  an ORSWOT (or key space of a Map) sharded across devices.

Collectives provided (all usable inside ``jax.shard_map``):

- :func:`collectives.all_reduce_join` — full-mesh anti-entropy collapsed
  into one all-reduce with the ORSWOT lattice-join monoid (recursive
  doubling over ICI; the north star's ``lax.all_reduce``).
- :func:`collectives.all_reduce_clock` — the same for plain vector
  clocks / counters (``lax.pmax``).
- :func:`collectives.ring_round` — one ``ppermute`` gossip round
  (pairwise anti-entropy; the ring-attention-shaped component).

Top-level entry points (:mod:`.anti_entropy`) wrap these in
``jax.shard_map`` over a mesh and are what models/bench/driver call.
"""

from .mesh import (
    REPLICA_AXIS,
    ELEMENT_AXIS,
    make_mesh,
    map_specs,
    map_out_specs,
    map3_specs,
    map_orswot_specs,
    nested_map_specs,
    orswot_specs,
    orswot_out_specs,
    shard_map3,
    shard_map_orswot,
    shard_map_state,
    shard_nested_map,
    shard_orswot,
)
from .collectives import (
    all_reduce_clock,
    all_reduce_join,
    all_reduce_lattice,
    ring_round,
)
from .anti_entropy import (
    gossip_elastic,
    mesh_fold,
    mesh_fold_clocks,
    mesh_fold_gset,
    mesh_fold_lww,
    mesh_fold_map,
    mesh_fold_map3,
    mesh_fold_map_orswot,
    mesh_fold_mvreg,
    mesh_fold_nested_map,
    mesh_fold_sparse,
    mesh_fold_sparse_mvmap,
    mesh_fold_sparse_nested,
    mesh_gossip_sparse_mvmap,
    mesh_gossip_sparse_nested,
    mesh_gossip,
    mesh_gossip_sparse,
    mesh_gossip_map,
    mesh_gossip_map3,
    mesh_gossip_map_orswot,
    mesh_gossip_nested_map,
)
from .sparse_shard import (
    mesh_fold_sparse_map,
    mesh_fold_sparse_mvmap_sharded,
    mesh_fold_sparse_nested_sharded,
    mesh_fold_sparse_sharded,
    split_cells,
    split_nested,
    split_segments,
)
from .stream import (
    StreamFaultReport,
    StreamInterrupted,
    iter_blocks,
    mesh_stream_fold,
    mesh_stream_fold_sparse,
    mesh_stream_fold_sparse_mvmap,
    mesh_stream_fold_sparse_sharded,
)
from .delta_ring import delta_gossip_elastic
from .fanout_push import mesh_fanout_push
from .serve_apply import mesh_serve_apply
from .delta import (
    DeltaPacket,
    apply_delta,
    dirty_between,
    extract_delta,
    interval_accumulate,
    mesh_delta_gossip,
)
from .delta_map import (
    MapDeltaPacket,
    apply_delta_map,
    extract_delta_map,
    interval_accumulate_map,
    mesh_delta_gossip_map,
)
from .delta_map_orswot import (
    MapOrswotDeltaPacket,
    apply_delta_mo,
    extract_delta_mo,
    interval_accumulate_mo,
    mesh_delta_gossip_map_orswot,
)
from .delta_map3 import (
    Map3DeltaPacket,
    apply_delta_m3,
    extract_delta_m3,
    interval_accumulate_m3,
    mesh_delta_gossip_map3,
)
from . import multihost

__all__ = [
    "multihost",
    "delta_gossip_elastic",
    "gossip_elastic",
    "StreamFaultReport",
    "StreamInterrupted",
    "iter_blocks",
    "mesh_stream_fold",
    "mesh_stream_fold_sparse",
    "mesh_stream_fold_sparse_mvmap",
    "mesh_stream_fold_sparse_sharded",
    "mesh_fanout_push",
    "mesh_serve_apply",
    "DeltaPacket",
    "apply_delta",
    "dirty_between",
    "interval_accumulate",
    "MapDeltaPacket",
    "apply_delta_map",
    "extract_delta_map",
    "interval_accumulate_map",
    "mesh_delta_gossip_map",
    "MapOrswotDeltaPacket",
    "apply_delta_mo",
    "extract_delta_mo",
    "interval_accumulate_mo",
    "mesh_delta_gossip_map_orswot",
    "Map3DeltaPacket",
    "apply_delta_m3",
    "extract_delta_m3",
    "interval_accumulate_m3",
    "mesh_delta_gossip_map3",
    "extract_delta",
    "mesh_delta_gossip",
    "map3_specs",
    "map_orswot_specs",
    "nested_map_specs",
    "shard_map3",
    "shard_map_orswot",
    "shard_nested_map",
    "mesh_fold_map3",
    "mesh_fold_map_orswot",
    "mesh_fold_nested_map",
    "mesh_fold_gset",
    "mesh_fold_lww",
    "mesh_fold_mvreg",
    "mesh_fold_sparse_map",
    "mesh_fold_sparse_mvmap",
    "mesh_fold_sparse_mvmap_sharded",
    "mesh_fold_sparse_nested_sharded",
    "mesh_fold_sparse_nested",
    "mesh_gossip_sparse_mvmap",
    "mesh_gossip_sparse_nested",
    "mesh_fold_sparse_sharded",
    "split_cells",
    "split_nested",
    "split_segments",
    "mesh_gossip_map",
    "mesh_gossip_sparse",
    "mesh_gossip_map3",
    "mesh_gossip_map_orswot",
    "mesh_gossip_nested_map",
    "REPLICA_AXIS",
    "ELEMENT_AXIS",
    "make_mesh",
    "map_specs",
    "map_out_specs",
    "orswot_specs",
    "orswot_out_specs",
    "shard_map_state",
    "shard_orswot",
    "all_reduce_join",
    "all_reduce_clock",
    "all_reduce_lattice",
    "ring_round",
    "mesh_fold",
    "mesh_fold_clocks",
    "mesh_fold_map",
    "mesh_gossip",
]
