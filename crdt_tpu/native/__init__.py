"""crdt_tpu.native — compiled host-side runtime components.

The reference is a native (Rust) crate; per the build rule its host-side
hot loops get native equivalents, not Python stand-ins. Today that is
``listseq`` (listseq.cpp): dense identifier allocation + ordered-sequence
maintenance for List/GList — the inherently sequential part of BASELINE
config 5 that cannot ride the TPU (SURVEY.md §4.5, §7.1 "identifier
allocation on host").

The shared library is built on demand with g++ (no pip, no pybind11 —
plain ctypes over an ``extern "C"`` surface) and cached next to the
source. ``ListEngine`` is the Python face; if the toolchain is missing
the pure-Python fallback (``_PyEngine``, driving ``crdt_tpu.pure.list``)
keeps the API alive at oracle speed.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import sys
import tempfile
from typing import Optional, Sequence, Tuple

import numpy as np

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "listseq.cpp")
with open(_SRC, "rb") as _f:
    # The library is named after the source it was built from, so a
    # stale .so copied along with the tree (whatever its mtime) is never
    # loaded: any edit to listseq.cpp names a new library.
    _LIB = os.path.join(
        _DIR, f"_listseq-{hashlib.sha256(_f.read()).hexdigest()[:16]}.so"
    )


_BUILD_FAILED = False


def _build() -> Optional[str]:
    """Compile listseq.cpp → _listseq-<hash>.so if missing. Returns the
    library path, or None if no toolchain is available. A failure is
    cached so repeated engine constructions don't re-spawn g++."""
    global _BUILD_FAILED
    if _BUILD_FAILED:
        return None
    try:
        if os.path.exists(_LIB):
            return _LIB
        # Build to a temp name then rename: atomic for concurrent pytest
        # workers sharing the checkout.
        fd, tmp = tempfile.mkstemp(suffix=".so", dir=_DIR)
        os.close(fd)
        cmd = ["g++", "-O2", "-shared", "-fPIC", "-std=c++17", _SRC, "-o", tmp]
        proc = subprocess.run(cmd, capture_output=True, text=True)
        if proc.returncode != 0:
            os.unlink(tmp)
            print(f"crdt_tpu.native: g++ failed:\n{proc.stderr}", file=sys.stderr)
            _BUILD_FAILED = True
            return None
        os.replace(tmp, _LIB)
        return _LIB
    except (OSError, FileNotFoundError) as exc:
        print(f"crdt_tpu.native: build unavailable ({exc})", file=sys.stderr)
        _BUILD_FAILED = True
        return None


_lib = None


def _load():
    global _lib
    if _lib is not None:
        return _lib
    path = _build()
    if path is None:
        return None
    try:
        lib = ctypes.CDLL(path)
    except OSError:
        # Stale or wrong-arch binary (e.g. a cached .so from another
        # platform): rebuild from source once, else fall back.
        try:
            os.unlink(path)
        except OSError:
            pass
        path = _build()
        if path is None:
            return None
        try:
            lib = ctypes.CDLL(path)
        except OSError as exc:
            print(f"crdt_tpu.native: load failed ({exc})", file=sys.stderr)
            return None
    lib.ls_new.restype = ctypes.c_void_p
    lib.ls_free.argtypes = [ctypes.c_void_p]
    lib.ls_apply_trace.restype = ctypes.c_int64
    lib.ls_apply_trace.argtypes = [
        ctypes.c_void_p,
        np.ctypeslib.ndpointer(np.uint8, flags="C"),
        np.ctypeslib.ndpointer(np.int64, flags="C"),
        np.ctypeslib.ndpointer(np.int32, flags="C"),
        np.ctypeslib.ndpointer(np.int32, flags="C"),
        ctypes.c_int64,
        np.ctypeslib.ndpointer(np.int64, flags="C"),
    ]
    lib.ls_apply_remote.restype = ctypes.c_int64
    lib.ls_apply_remote.argtypes = [
        ctypes.c_void_p,
        np.ctypeslib.ndpointer(np.uint8, flags="C"),
        np.ctypeslib.ndpointer(np.int64, flags="C"),
        np.ctypeslib.ndpointer(np.int64, flags="C"),
        np.ctypeslib.ndpointer(np.int32, flags="C"),
        np.ctypeslib.ndpointer(np.uint64, flags="C"),
        np.ctypeslib.ndpointer(np.int32, flags="C"),
        ctypes.c_int64,
        np.ctypeslib.ndpointer(np.int64, flags="C"),
    ]
    lib.ls_len.restype = ctypes.c_int64
    lib.ls_len.argtypes = [ctypes.c_void_p]
    lib.ls_total_ids.restype = ctypes.c_int64
    lib.ls_total_ids.argtypes = [ctypes.c_void_p]
    lib.ls_read.argtypes = [
        ctypes.c_void_p,
        np.ctypeslib.ndpointer(np.int64, flags="C"),
        np.ctypeslib.ndpointer(np.int32, flags="C"),
    ]
    lib.ls_total_order.argtypes = [
        ctypes.c_void_p,
        np.ctypeslib.ndpointer(np.int64, flags="C"),
    ]
    lib.ls_id_len.restype = ctypes.c_int64
    lib.ls_id_len.argtypes = [ctypes.c_void_p, ctypes.c_int64]
    lib.ls_id_path.argtypes = [
        ctypes.c_void_p,
        ctypes.c_int64,
        np.ctypeslib.ndpointer(np.int64, flags="C"),
        np.ctypeslib.ndpointer(np.int32, flags="C"),
        np.ctypeslib.ndpointer(np.uint64, flags="C"),
    ]
    lib.ls_clock_get.restype = ctypes.c_int64
    lib.ls_clock_get.argtypes = [ctypes.c_void_p, ctypes.c_int32]
    lib.ls_clock_count.restype = ctypes.c_int64
    lib.ls_clock_count.argtypes = [ctypes.c_void_p]
    lib.ls_clock_dump.argtypes = [
        ctypes.c_void_p,
        np.ctypeslib.ndpointer(np.int32, flags="C"),
        np.ctypeslib.ndpointer(np.uint64, flags="C"),
    ]
    lib.ls_clock_seed.argtypes = [ctypes.c_void_p, ctypes.c_int32, ctypes.c_uint64]
    _lib = lib
    return lib


def native_available() -> bool:
    return _load() is not None


INSERT, DELETE = 0, 1


class ListEngine:
    """Native sequence engine: the host half of the device List.

    Actors are dense int ids (callers intern, as everywhere else); for
    bit-identical identifier parity with the pure oracle the interned id
    order must agree with the actors' natural ordering (OrdDot markers
    compare by actor first).
    """

    def __init__(self):
        lib = _load()
        if lib is None:
            self._impl = _PyEngine()
            self._e = None
        else:
            self._impl = None
            self._e = ctypes.c_void_p(lib.ls_new())

    def __del__(self):
        if getattr(self, "_e", None) is not None and _lib is not None:
            _lib.ls_free(self._e)
            self._e = None

    @property
    def is_native(self) -> bool:
        return self._e is not None

    # ---- local edit trace (mint + apply) ------------------------------
    def apply_trace(
        self,
        kinds: Sequence[int],
        indices: Sequence[int],
        values: Sequence[int],
        actors: Sequence[int],
    ) -> np.ndarray:
        """Apply a local edit trace (INSERT at index with value / DELETE
        at index), minting identifiers; returns each op's identifier
        handle (the stable device slot)."""
        kinds = np.ascontiguousarray(kinds, np.uint8)
        indices = np.ascontiguousarray(indices, np.int64)
        values = np.ascontiguousarray(values, np.int32)
        actors = np.ascontiguousarray(actors, np.int32)
        n = len(kinds)
        out = np.empty(n, np.int64)
        if self._impl is not None:
            self._impl.apply_trace(kinds, indices, values, actors, out)
            return out
        rc = _lib.ls_apply_trace(self._e, kinds, indices, values, actors, n, out)
        if rc < 0:
            raise IndexError(f"trace op {-rc - 1}: index out of range")
        return out

    # ---- remote op delivery (CmRDT apply by identifier) ----------------
    def apply_remote(self, kinds, paths, values) -> np.ndarray:
        """Apply remote ops: each op is (kind, identifier path, value).
        Paths are sequences of (index, actor, counter) components.
        Duplicate inserts / absent deletes are idempotent no-ops."""
        n = len(kinds)
        counts = np.asarray([len(p) for p in paths], np.int64)
        flat = [c for p in paths for c in p]
        cidx = np.asarray([c[0] for c in flat], np.int64)
        cactor = np.asarray([c[1] for c in flat], np.int32)
        cctr = np.asarray([c[2] for c in flat], np.uint64)
        if (counts <= 0).any():
            bad = int(np.argmax(counts <= 0))
            raise ValueError(f"remote op {bad}: empty identifier path")
        kinds = np.ascontiguousarray(kinds, np.uint8)
        values = np.ascontiguousarray(values, np.int32)
        out = np.empty(n, np.int64)
        if self._impl is not None:
            self._impl.apply_remote(kinds, counts, cidx, cactor, cctr, values, out)
            return out
        rc = _lib.ls_apply_remote(
            self._e, kinds, counts, cidx, cactor, cctr, values, n, out
        )
        if rc < 0:
            raise ValueError(f"remote op {-rc - 1}: malformed identifier path")
        return out

    # ---- reads ---------------------------------------------------------
    def __len__(self) -> int:
        if self._impl is not None:
            return len(self._impl)
        return int(_lib.ls_len(self._e))

    def total_ids(self) -> int:
        if self._impl is not None:
            return self._impl.total_ids()
        return int(_lib.ls_total_ids(self._e))

    def read(self) -> Tuple[np.ndarray, np.ndarray]:
        """(handles, value ids) of the live sequence, in order."""
        n = len(self)
        handles = np.empty(n, np.int64)
        vals = np.empty(n, np.int32)
        if self._impl is not None:
            self._impl.read(handles, vals)
        else:
            _lib.ls_read(self._e, handles, vals)
        return handles, vals

    def total_order(self) -> np.ndarray:
        """rank[handle] over ALL allocated identifiers (live or dead) —
        the device order-maintenance permutation."""
        out = np.empty(self.total_ids(), np.int64)
        if self._impl is not None:
            self._impl.total_order(out)
        else:
            _lib.ls_total_order(self._e, out)
        return out

    def identifier_path(self, handle: int):
        """The (index, actor, counter) components of a handle's path."""
        if self._impl is not None:
            return self._impl.identifier_path(handle)
        n = int(_lib.ls_id_len(self._e, handle))
        if n < 0:
            raise IndexError(f"no identifier with handle {handle}")
        idx = np.empty(n, np.int64)
        act = np.empty(n, np.int32)
        ctr = np.empty(n, np.uint64)
        _lib.ls_id_path(self._e, handle, idx, act, ctr)
        return [(int(i), int(a), int(c)) for i, a, c in zip(idx, act, ctr)]

    def clock_get(self, actor: int) -> int:
        if self._impl is not None:
            return self._impl.clock_get(actor)
        return int(_lib.ls_clock_get(self._e, int(actor)))

    def clock_dump(self) -> Tuple[np.ndarray, np.ndarray]:
        """(actors, counters) of the mint clock — checkpoint payload
        (deletes consume counters no identifier path records)."""
        if self._impl is not None:
            return self._impl.clock_dump()
        n = int(_lib.ls_clock_count(self._e))
        actors = np.empty(n, np.int32)
        ctrs = np.empty(n, np.uint64)
        _lib.ls_clock_dump(self._e, actors, ctrs)
        return actors, ctrs

    def clock_seed(self, actor: int, ctr: int) -> None:
        """Raise an actor's mint clock to at least ``ctr`` (resume)."""
        if self._impl is not None:
            self._impl.clock_seed(actor, ctr)
        else:
            _lib.ls_clock_seed(self._e, int(actor), int(ctr))


class _PyEngine:
    """Pure-Python fallback with the same surface, driving the oracle
    types — correctness-equal, oracle-speed."""

    def __init__(self):
        from ..pure.identifier import Identifier, between

        self._between = between
        self._Identifier = Identifier
        self.ids = []       # handle -> Identifier
        self.vals = []
        self.alive = []
        self.seq = []       # handles in order
        self.clock = {}

    def apply_trace(self, kinds, indices, values, actors, out):
        from ..dot import OrdDot

        for i in range(len(kinds)):
            p = int(indices[i])
            actor = int(actors[i])
            self.clock[actor] = self.clock.get(actor, 0) + 1
            if kinds[i] == INSERT:
                if p < 0 or p > len(self.seq):
                    raise IndexError(f"trace op {i}: index out of range")
                lo = self.ids[self.seq[p - 1]] if p > 0 else None
                hi = self.ids[self.seq[p]] if p < len(self.seq) else None
                ident = self._between(lo, hi, OrdDot(actor, self.clock[actor]))
                handle = len(self.ids)
                self.ids.append(ident)
                self.vals.append(int(values[i]))
                self.alive.append(True)
                self.seq.insert(p, handle)
                out[i] = handle
            else:
                if p < 0 or p >= len(self.seq):
                    raise IndexError(f"trace op {i}: index out of range")
                handle = self.seq.pop(p)
                self.alive[handle] = False
                out[i] = handle

    def apply_remote(self, kinds, counts, cidx, cactor, cctr, values, out):
        import bisect
        from ..dot import OrdDot

        off = 0
        for i in range(len(kinds)):
            comps = tuple(
                (int(cidx[off + c]), OrdDot(int(cactor[off + c]), int(cctr[off + c])))
                for c in range(int(counts[i]))
            )
            off += int(counts[i])
            ident = self._Identifier(comps)
            marker = comps[-1][1]
            self.clock[marker.actor] = max(
                self.clock.get(marker.actor, 0), marker.counter
            )
            keys = [self.ids[h] for h in self.seq]
            pos = bisect.bisect_left(keys, ident)
            present = pos < len(self.seq) and keys[pos] == ident
            if kinds[i] == INSERT:
                if not present:
                    handle = len(self.ids)
                    self.ids.append(ident)
                    self.vals.append(int(values[i]))
                    self.alive.append(True)
                    self.seq.insert(pos, handle)
                    out[i] = handle
                else:
                    out[i] = self.seq[pos]
            else:
                if present:
                    handle = self.seq.pop(pos)
                    self.alive[handle] = False
                    out[i] = handle
                else:
                    out[i] = -1

    def __len__(self):
        return len(self.seq)

    def total_ids(self):
        return len(self.ids)

    def read(self, handles, vals):
        for i, h in enumerate(self.seq):
            handles[i] = h
            vals[i] = self.vals[h]

    def total_order(self, out):
        order = sorted(range(len(self.ids)), key=lambda h: self.ids[h])
        for r, h in enumerate(order):
            out[h] = r

    def identifier_path(self, handle):
        return [(i, m.actor, m.counter) for i, m in self.ids[handle].path]

    def clock_get(self, actor):
        return self.clock.get(int(actor), 0)

    def clock_dump(self):
        actors = np.asarray(list(self.clock.keys()), np.int32)
        ctrs = np.asarray(list(self.clock.values()), np.uint64)
        return actors, ctrs

    def clock_seed(self, actor, ctr):
        actor, ctr = int(actor), int(ctr)
        if ctr > self.clock.get(actor, 0):
            self.clock[actor] = ctr


__all__ = ["ListEngine", "native_available", "INSERT", "DELETE"]
