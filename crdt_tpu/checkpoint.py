"""Checkpoint / resume for device-resident replica state.

Reference story (SURVEY.md §6.4): serde bytes on disk ARE the checkpoint;
a resumed replica merges back in. Device form: the struct-of-arrays
state goes into one ``.npz`` (host-synced numpy), the host-side tables
(interners, capacities) ride along as a canonical-JSON sidecar inside
the same file. ``load`` reconstructs the model; the resume path is then
ordinary anti-entropy — ``merge``/``fold`` with the live replicas (the
resume-then-merge test in tests/test_checkpoint.py).

Interned actors/members/keys/values are serialized with
``crdt_tpu.serde`` so arbitrary payload types survive the round trip.
"""

from __future__ import annotations

import io
import json
import os
import zlib
from typing import Union

import jax
import numpy as np

from . import serde
from .models.glist import BatchedGList
from .models.list import CHUNK_WIDTH, LANES, BatchedList, slot_width
from .models.map import BatchedMap
from .models.map3 import BatchedMap3
from .models.map_nested import BatchedMapOrswot, BatchedNestedMap
from .models.orswot import BatchedOrswot
from .models.sparse_orswot import BatchedSparseOrswot
from .native import DELETE, INSERT
from .ops import map as map_ops
from .ops import mvreg as mv_ops
from .ops import orswot as orswot_ops
from .utils import Interner


class CheckpointCorrupt(RuntimeError):
    """A checkpoint's stored bytes fail their recorded content checksum.
    ``array`` names the offending array so the operator knows WHAT
    rotted, not just that something did. Raised by :func:`load` instead
    of silently reconstructing a model from rotten bytes; recovery is a
    matter for the generational snapshot tier
    (``crdt_tpu.durability.snapshot`` falls back one generation)."""

    def __init__(self, path, array: str, expect: int, got=None):
        detail = (
            "is MISSING from the file" if got is None
            else f"fails its content checksum (recorded {expect:#010x}, "
                 f"stored bytes hash to {got:#010x})"
        )
        super().__init__(
            f"checkpoint {os.fspath(path)!r}: array {array!r} {detail} — "
            f"the file is corrupt; restore from an older generation "
            f"instead of loading rotten state"
        )
        self.path = os.fspath(path)
        self.array = array


def array_checksum(v: np.ndarray) -> int:
    """CRC-32 of one array's dtype, shape, and content bytes — the
    per-array integrity unit ``save`` records and ``load`` verifies
    (also the manifest unit of ``durability.snapshot``)."""
    v = np.ascontiguousarray(v)
    crc = zlib.crc32(f"{v.dtype.str}:{v.shape}".encode("ascii"))
    # crc32 takes any buffer: hash the array's memory in place instead
    # of a tobytes() copy (flagship-scale content planes are GBs).
    return zlib.crc32(v.reshape(-1).view(np.uint8).data, crc) & 0xFFFFFFFF


def fsync_dir(path) -> None:
    """fsync a DIRECTORY so a just-renamed/created entry inside it is
    durable across power loss (write-then-rename alone only orders the
    data, not the directory entry). Best-effort on platforms whose
    directories refuse O_RDONLY opens."""
    flags = os.O_RDONLY | getattr(os, "O_DIRECTORY", 0)
    try:
        fd = os.open(os.fspath(path), flags)
    except OSError:
        return
    try:
        os.fsync(fd)
    finally:
        os.close(fd)


def _interner_items(interner: Interner):
    return [serde.encode(item) for item in interner.items()]


def _interner_from(items) -> Interner:
    return Interner(serde.decode(item) for item in items)


def _state_arrays(state) -> dict:
    """Flatten any NamedTuple state pytree to numbered host arrays (the
    leaf order of ``jax.tree`` is deterministic for a fixed pytree
    type, so load can unflatten through a template of the same type)."""
    return {f"a_{i}": np.asarray(x) for i, x in enumerate(jax.tree.leaves(state))}


def _state_from_arrays(template, arrays):
    n = sum(1 for k in arrays if k.startswith("a_"))
    leaves = [jax.device_put(arrays[f"a_{i}"]) for i in range(n)]
    return jax.tree.unflatten(jax.tree.structure(template), leaves)


def _engine_dump(engine) -> dict:
    """Host-side identifier-tree state: every minted identifier's path
    (ragged, as counts + flat components) plus the live handle set —
    enough to re-ingest into a fresh engine via ``apply_remote``."""
    total = engine.total_ids()
    counts, cidx, cactor, cctr = [], [], [], []
    for h in range(total):
        path = engine.identifier_path(h)
        counts.append(len(path))
        for ix, a, c in path:
            cidx.append(ix)
            cactor.append(a)
            cctr.append(c)
    live, _ = engine.read()
    clk_actors, clk_ctrs = engine.clock_dump()
    return {
        "e_counts": np.asarray(counts, np.int64),
        "e_cidx": np.asarray(cidx, np.int64),
        "e_cactor": np.asarray(cactor, np.int32),
        "e_cctr": np.asarray(cctr, np.uint64),
        "e_live": np.asarray(live, np.int64),
        # The mint clock rides separately: deletes consume counters no
        # surviving identifier path records, and a resumed engine must
        # not re-mint spent dots.
        "e_clk_actors": clk_actors,
        "e_clk_ctrs": clk_ctrs,
    }


def _engine_restore(engine, arrays, values: np.ndarray) -> None:
    """Re-ingest a dumped identifier tree: INSERT every identifier by
    path (in mint order, reproducing handle numbering), then DELETE the
    ones that were dead. ``values[h]`` is identifier ``h``'s payload."""
    for actor, ctr in zip(arrays["e_clk_actors"], arrays["e_clk_ctrs"]):
        engine.clock_seed(int(actor), int(ctr))
    counts = arrays["e_counts"]
    paths, pos = [], 0
    for c in counts:
        c = int(c)
        paths.append(
            [
                (int(arrays["e_cidx"][i]), int(arrays["e_cactor"][i]), int(arrays["e_cctr"][i]))
                for i in range(pos, pos + c)
            ]
        )
        pos += c
    if not paths:
        return
    kinds = np.full(len(paths), INSERT, np.uint8)
    handles = engine.apply_remote(kinds, paths, np.asarray(values, np.int32))
    assert (handles == np.arange(len(paths))).all(), "handle order drifted"
    live = set(int(h) for h in arrays["e_live"])
    dead = [h for h in range(len(paths)) if h not in live]
    if dead:
        engine.apply_remote(
            np.full(len(dead), DELETE, np.uint8),
            [paths[h] for h in dead],
            np.zeros(len(dead), np.int32),
        )


def _is_sparse_map(model) -> bool:
    from .models.sparse_map import BatchedSparseMapOrswot

    return isinstance(model, BatchedSparseMapOrswot)


def _is_sparse_mvmap(model) -> bool:
    from .models.sparse_mvmap import BatchedSparseMap

    return isinstance(model, BatchedSparseMap)


def _is_sparse_nested_map(model) -> bool:
    from .models.sparse_nested_map import BatchedSparseNestedMap

    return isinstance(model, BatchedSparseNestedMap)


def _dump(model) -> tuple:
    """``(meta, arrays)`` for any checkpointable model — the type
    dispatch :func:`save` serializes and ``durability.snapshot`` layers
    generations on. ``arrays`` values are host numpy; ``meta`` is
    JSON-serializable."""
    if isinstance(model, BatchedOrswot):
        meta = {
            "kind": "orswot",
            "members": _interner_items(model.members),
            "actors": _interner_items(model.actors),
        }
        arrays = {f"s_{k}": np.asarray(v) for k, v in model.state._asdict().items()}
    elif isinstance(model, BatchedSparseOrswot):
        meta = {
            "kind": "sparse_orswot",
            "members": _interner_items(model.members),
            "actors": _interner_items(model.actors),
        }
        arrays = {f"s_{k}": np.asarray(v) for k, v in model.state._asdict().items()}
    elif _is_sparse_map(model):
        meta = {
            "kind": "sparse_map_orswot",
            "span": model.span,
            "keys": _interner_items(model.keys),
            "members": _interner_items(model.members),
            "actors": _interner_items(model.actors),
        }
        arrays = {
            **{f"c_{k}": np.asarray(v)
               for k, v in model.state.core._asdict().items()},
            **{f"s_{k}": np.asarray(v)
               for k, v in model.state._asdict().items() if k != "core"},
        }
    elif _is_sparse_nested_map(model):
        meta = {
            "kind": "sparse_map_map",
            "span": model.span,
            "sibling_cap": model.sibling_cap,
            "n_keys1": model.n_keys1,
            "keys1": _interner_items(model.keys1),
            "keys2": _interner_items(model.keys2),
            "actors": _interner_items(model.actors),
            "values": _interner_items(model.values),
        }
        arrays = {
            **{f"c_{k}": np.asarray(v)
               for k, v in model.state.core._asdict().items()},
            **{f"s_{k}": np.asarray(v)
               for k, v in model.state._asdict().items() if k != "core"},
        }
    elif _is_sparse_mvmap(model):
        meta = {
            "kind": "sparse_map",
            "n_keys": model.n_keys,
            "sibling_cap": model.sibling_cap,
            "keys": _interner_items(model.keys),
            "actors": _interner_items(model.actors),
            "values": _interner_items(model.values),
        }
        arrays = {f"s_{k}": np.asarray(v) for k, v in model.state._asdict().items()}
    elif isinstance(model, BatchedMap):
        meta = {
            "kind": "map",
            "keys": _interner_items(model.keys),
            "actors": _interner_items(model.actors),
            "values": _interner_items(model.values),
        }
        arrays = {
            f"s_{k}": np.asarray(v)
            for k, v in model.state._asdict().items()
            if k != "child"
        }
        arrays.update(
            {f"c_{k}": np.asarray(v) for k, v in model.state.child._asdict().items()}
        )
    elif isinstance(model, BatchedMapOrswot):
        meta = {
            "kind": "map_orswot",
            "keys": _interner_items(model.keys),
            "members": _interner_items(model.members),
            "actors": _interner_items(model.actors),
            "dims": [
                model.n_replicas, model.n_keys, model.n_members,
                int(model.state.core.top.shape[-1]),
                int(model.state.kdcl.shape[-2]),
            ],
        }
        arrays = _state_arrays(model.state)
    elif isinstance(model, BatchedNestedMap):
        meta = {
            "kind": "map_map",
            "keys1": _interner_items(model.keys1),
            "keys2": _interner_items(model.keys2),
            "actors": _interner_items(model.actors),
            "values": _interner_items(model.values),
            "dims": [
                model.n_replicas, model.n_keys1, model.n_keys2,
                int(model.state.m.top.shape[-1]),
                int(model.state.m.child.wact.shape[-1]),
                int(model.state.odcl.shape[-2]),
            ],
        }
        arrays = _state_arrays(model.state)
    elif isinstance(model, BatchedMap3):
        meta = {
            "kind": "map3",
            "keys1": _interner_items(model.keys1),
            "keys2": _interner_items(model.keys2),
            "members": _interner_items(model.members),
            "actors": _interner_items(model.actors),
            "dims": [
                model.n_replicas, model.n_keys1, model.n_keys2,
                model.n_members,
                int(model.state.mo.core.top.shape[-1]),
                int(model.state.odcl.shape[-2]),
            ],
        }
        arrays = _state_arrays(model.state)
    elif isinstance(model, BatchedList):
        ins = model.op_kinds == INSERT
        values = np.zeros(model.engine.total_ids(), np.int32)
        values[model.op_handles[ins]] = model.op_vals[ins]
        # Mesh placement is NOT persisted (a mesh names live devices;
        # the restoring host's topology may differ). ``placed`` records
        # that the caller should re-``place`` after load.
        meta = {
            "kind": "list",
            "n_replicas": model.n_replicas,
            "chunk_width": model.chunk_width,
            "applied": model._applied,
            "placed": model._mesh is not None,
        }
        arrays = {
            "slots": model.slots,
            "vals": np.asarray(model.vals),
            "alive": np.asarray(model.alive),
            "op_handles": model.op_handles,
            "op_kinds": model.op_kinds,
            "op_vals": model.op_vals,
            "id_values": values,
            **_engine_dump(model.engine),
        }
    elif isinstance(model, BatchedGList):
        meta = {"kind": "glist", "n_replicas": model.n_replicas}
        arrays = {
            "slots": model.slots,
            "uvals": model.uvals,
            "alive": np.asarray(model.alive),
            **_engine_dump(model.engine),
        }
    else:
        raise TypeError(f"cannot checkpoint {type(model).__name__}")
    return meta, {k: np.asarray(v) for k, v in arrays.items()}


def to_npz_bytes(meta: dict, arrays: dict) -> bytes:
    """One .npz image of ``(meta, arrays)`` with per-array content
    checksums recorded in the meta — the byte format ``save`` writes
    and ``durability.snapshot`` frames into generations."""
    meta = dict(meta)
    meta["checksums"] = {k: array_checksum(v) for k, v in arrays.items()}
    buf = io.BytesIO()
    np.savez(
        buf,
        meta=np.frombuffer(
            json.dumps(meta, sort_keys=True).encode("utf-8"), dtype=np.uint8
        ),
        **arrays,
    )
    return buf.getvalue()


def from_npz_bytes(path, raw) -> tuple:
    """Parse + integrity-check one .npz image: returns ``(meta,
    arrays)`` or raises :class:`CheckpointCorrupt` naming the first
    array whose stored bytes fail their recorded checksum. Checkpoints
    predating the checksums load with a one-shot warning — their
    integrity is UNKNOWN, not verified."""
    global _WARNED_NO_CHECKSUMS
    with np.load(io.BytesIO(raw) if isinstance(raw, bytes) else raw) as z:
        meta = json.loads(bytes(z["meta"]).decode("utf-8"))
        arrays = {k: z[k] for k in z.files if k != "meta"}
    sums = meta.get("checksums")
    if sums is None:
        if not _WARNED_NO_CHECKSUMS:
            _WARNED_NO_CHECKSUMS = True
            import warnings

            warnings.warn(
                f"checkpoint {os.fspath(path)!r} predates per-array "
                f"content checksums — integrity NOT verified (re-save to "
                f"upgrade). Warned once per process.",
                stacklevel=3,
            )
        from .utils.metrics import metrics

        metrics.count("checkpoint.loaded_unverified")
        return meta, arrays
    # Iterate the RECORDED set, not the stored one: a rotten file that
    # dropped an array entirely must fail here with its name, not leak
    # a bare KeyError out of the restore dispatch.
    missing = sorted(set(sums) - set(arrays))
    if missing:
        raise CheckpointCorrupt(path, missing[0], int(sums[missing[0]]))
    for name, v in arrays.items():
        got = array_checksum(v)
        expect = int(sums.get(name, -1))
        if got != expect:
            raise CheckpointCorrupt(path, name, expect, got)
    return meta, arrays


_WARNED_NO_CHECKSUMS = False


def save(path: Union[str, os.PathLike], model, compact: bool = False) -> None:
    """Checkpoint a device model to ``path`` (one .npz file) with
    per-array content checksums, atomically AND durably: the tmp file
    (and its directory) is fsynced BEFORE the rename — write-then-rename
    without the fsync orders nothing across power loss, so a crash
    could leave the renamed file empty.

    ``compact=True`` runs causal-stability compaction against the
    model's OWN replica rows first (``reclaim.compact_model`` — sound
    because the checkpointed batch is the replica set the frontier is
    computed over): retired parked slots and stale dead payload never
    reach disk, and a model shrunk after restore starts from the
    compacted occupancy. Models outside the compactable family (lists,
    counters) save as-is with ``reclaim.compact_on_save_unsupported``
    counted — compact-on-save must never make a checkpoint impossible."""
    if compact:
        from . import elastic
        from .reclaim import compact_model
        from .utils.metrics import metrics

        # Only the family check may soften to a counter — a TypeError
        # raised INSIDE a registered compaction kernel is a kernel bug
        # and must surface, not be miscounted as "unsupported".
        try:
            elastic.kind_of(model)
        except TypeError:
            metrics.count("reclaim.compact_on_save_unsupported")
        else:
            compact_model(model)
    meta, arrays = _dump(model)
    # Write-then-fsync-then-rename: a crash mid-checkpoint never
    # corrupts the last good checkpoint (the reference's bytes-on-disk
    # story, made atomic and durable).
    tmp = f"{os.fspath(path)}.tmp"
    with open(tmp, "wb") as f:
        f.write(to_npz_bytes(meta, arrays))
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)
    fsync_dir(os.path.dirname(os.path.abspath(os.fspath(path))))


def load(path: Union[str, os.PathLike]):
    """Restore a device model checkpointed by ``save``; raises
    :class:`CheckpointCorrupt` (naming the array) when the stored bytes
    fail their recorded content checksums instead of silently
    reconstructing from rotten state."""
    with open(path, "rb") as f:
        meta, arrays = from_npz_bytes(path, f.read())
    return _restore(meta, arrays)


def _restore(meta: dict, arrays: dict):
    """Rebuild the model from a parsed ``(meta, arrays)`` image (the
    inverse of :func:`_dump`; shared with ``durability.snapshot``)."""
    dev = lambda a: jax.device_put(a)
    if meta["kind"] == "orswot":
        state = orswot_ops.OrswotState(
            **{k[2:]: dev(v) for k, v in arrays.items() if k.startswith("s_")}
        )
        model = BatchedOrswot(
            state.top.shape[0],
            state.ctr.shape[-2],
            state.ctr.shape[-1],
            state.dcl.shape[-2],
            members=_interner_from(meta["members"]),
            actors=_interner_from(meta["actors"]),
        )
        model.state = state
        return model
    if meta["kind"] == "sparse_orswot":
        from .ops import sparse_orswot as sparse_ops

        state = sparse_ops.SparseOrswotState(
            **{k[2:]: dev(v) for k, v in arrays.items() if k.startswith("s_")}
        )
        model = BatchedSparseOrswot(
            state.top.shape[0],
            state.eid.shape[-1],
            state.top.shape[-1],
            state.dcl.shape[-2],
            state.didx.shape[-1],
            members=_interner_from(meta["members"]),
            actors=_interner_from(meta["actors"]),
        )
        model.state = state
        return model
    if meta["kind"] == "sparse_map_orswot":
        from .models.sparse_map import BatchedSparseMapOrswot
        from .ops import sparse_nest as nest_ops
        from .ops import sparse_orswot as sparse_ops

        core = sparse_ops.SparseOrswotState(
            **{k[2:]: dev(v) for k, v in arrays.items() if k.startswith("c_")}
        )
        state = nest_ops.SparseNestState(
            core=core,
            **{k[2:]: dev(v) for k, v in arrays.items() if k.startswith("s_")},
        )
        model = BatchedSparseMapOrswot(
            core.top.shape[0],
            int(meta["span"]),
            core.eid.shape[-1],
            core.top.shape[-1],
            core.dcl.shape[-2],
            core.didx.shape[-1],
            state.kcl.shape[-2],
            state.kidx.shape[-1],
            keys=_interner_from(meta["keys"]),
            members=_interner_from(meta["members"]),
            actors=_interner_from(meta["actors"]),
        )
        model.state = state
        return model
    if meta["kind"] == "sparse_map_map":
        from .models.sparse_nested_map import BatchedSparseNestedMap
        from .ops import sparse_mvmap as smv_ops
        from .ops import sparse_nest as nest_ops

        core = smv_ops.SparseMVMapState(
            **{k[2:]: dev(v) for k, v in arrays.items() if k.startswith("c_")}
        )
        state = nest_ops.SparseNestState(
            core=core,
            **{k[2:]: dev(v) for k, v in arrays.items() if k.startswith("s_")},
        )
        model = BatchedSparseNestedMap(
            core.top.shape[0],
            int(meta["span"]),
            core.kid.shape[-1],
            core.top.shape[-1],
            int(meta["sibling_cap"]),
            core.dcl.shape[-2],
            core.kidx.shape[-1],
            state.kcl.shape[-2],
            state.kidx.shape[-1],
            # Older checkpoints predate the persisted bound; 0 falls back
            # to the packing-max default (their save-time value).
            n_keys1=int(meta.get("n_keys1", 0)),
            keys1=_interner_from(meta["keys1"]),
            keys2=_interner_from(meta["keys2"]),
            actors=_interner_from(meta["actors"]),
            values=_interner_from(meta["values"]),
        )
        model.state = state
        return model
    if meta["kind"] == "sparse_map":
        from .models.sparse_mvmap import BatchedSparseMap
        from .ops import sparse_mvmap as smv_ops

        state = smv_ops.SparseMVMapState(
            **{k[2:]: dev(v) for k, v in arrays.items() if k.startswith("s_")}
        )
        model = BatchedSparseMap(
            state.top.shape[0],
            int(meta["n_keys"]),
            state.top.shape[-1],
            state.kid.shape[-1],
            int(meta["sibling_cap"]),
            state.dcl.shape[-2],
            state.kidx.shape[-1],
            keys=_interner_from(meta["keys"]),
            actors=_interner_from(meta["actors"]),
            values=_interner_from(meta["values"]),
        )
        model.state = state
        return model
    if meta["kind"] == "map":
        child = mv_ops.MVRegState(
            **{k[2:]: dev(v) for k, v in arrays.items() if k.startswith("c_")}
        )
        state = map_ops.MapState(
            child=child,
            **{k[2:]: dev(v) for k, v in arrays.items() if k.startswith("s_")},
        )
        model = BatchedMap(
            state.top.shape[0],
            state.dkeys.shape[-1],
            state.top.shape[-1],
            state.child.wact.shape[-1],
            state.dcl.shape[-2],
            keys=_interner_from(meta["keys"]),
            actors=_interner_from(meta["actors"]),
            values=_interner_from(meta["values"]),
        )
        model.state = state
        return model
    if meta["kind"] == "map_orswot":
        r, nk, nm, na, d = meta["dims"]
        model = BatchedMapOrswot(
            r, nk, nm, na, d,
            keys=_interner_from(meta["keys"]),
            members=_interner_from(meta["members"]),
            actors=_interner_from(meta["actors"]),
        )
        model.state = _state_from_arrays(model.state, arrays)
        return model
    if meta["kind"] == "map_map":
        r, nk1, nk2, na, s, d = meta["dims"]
        model = BatchedNestedMap(
            r, nk1, nk2, na, s, d,
            keys1=_interner_from(meta["keys1"]),
            keys2=_interner_from(meta["keys2"]),
            actors=_interner_from(meta["actors"]),
            values=_interner_from(meta["values"]),
        )
        model.state = _state_from_arrays(model.state, arrays)
        return model
    if meta["kind"] == "map3":
        r, nk1, nk2, nm, na, d = meta["dims"]
        model = BatchedMap3(
            r, nk1, nk2, nm, na, d,
            keys1=_interner_from(meta["keys1"]),
            keys2=_interner_from(meta["keys2"]),
            members=_interner_from(meta["members"]),
            actors=_interner_from(meta["actors"]),
        )
        model.state = _state_from_arrays(model.state, arrays)
        return model
    if meta["kind"] == "list":
        model = BatchedList(meta["n_replicas"], meta.get("chunk_width", CHUNK_WIDTH))
        if meta.get("placed"):
            import warnings

            warnings.warn(
                "checkpointed BatchedList was mesh-placed; placement is "
                "not persisted — call place(mesh) again on the restored "
                "model before large-scale use",
                stacklevel=2,
            )
        _engine_restore(model.engine, arrays, arrays["id_values"])
        model.slots = arrays["slots"]
        assert (model.engine.total_order() == model.slots).all(), (
            "restored identifier order drifted from the checkpoint"
        )
        vals, alive = arrays["vals"], arrays["alive"]
        if vals.ndim == 2:
            # Written while the state was [R, N]: lay it out in LANES-slot rows.
            pad = ((0, 0), (0, slot_width(vals.shape[1]) - vals.shape[1]))
            vals, alive = (np.pad(x, pad).reshape(len(x), -1, LANES) for x in (vals, alive))
        model.vals = jax.device_put(vals)
        model.alive = jax.device_put(alive)
        model.op_handles = arrays["op_handles"]
        model.op_kinds = arrays["op_kinds"]
        model.op_vals = arrays["op_vals"]
        model._applied = int(meta["applied"])
        model.build_chunks()
        return model
    if meta["kind"] == "glist":
        model = BatchedGList(meta["n_replicas"])
        _engine_restore(model.engine, arrays, arrays["uvals"])
        model.slots = arrays["slots"]
        assert (model.engine.total_order() == model.slots).all(), (
            "restored identifier order drifted from the checkpoint"
        )
        model.uvals = arrays["uvals"]
        model.alive = jax.device_put(arrays["alive"])
        return model
    raise ValueError(f"unknown checkpoint kind {meta['kind']!r}")


__all__ = [
    "CheckpointCorrupt", "array_checksum", "fsync_dir", "from_npz_bytes",
    "load", "save", "to_npz_bytes",
]
