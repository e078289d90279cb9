"""Op schedules for the tenant store, vectorised in numpy from the seed.

Key popularity is YCSB's scrambled zipfian (``requestdistribution=zipfian``,
``ScrambledZipfianGenerator``; Cooper et al., SoCC 2010): a zipfian draw
over 10^10 items with constant 0.99, hashed by 64-bit FNV-1a onto the
tenant space. The op mix (adds vs covered removes, elements per op) is
the one ``bench.py``'s ``serve``/``fanout`` legs used; copied here so that
a later change to the program cannot move the yardstick.
"""

from __future__ import annotations

import numpy as np

# YCSB ScrambledZipfianGenerator: ITEM_COUNT = 10^10 and its precomputed
# zeta(10^10, 0.99).
YCSB_ITEMS = 10_000_000_000
YCSB_ZETAN = 26.46902820178302
FNV_OFFSET = np.uint64(0xCBF29CE484222325)
FNV_PRIME = np.uint64(1099511628211)


def _zeta(n: int, theta: float) -> float:
    return float(np.sum(1.0 / np.arange(1, n + 1, dtype=np.float64) ** theta))


def zipfian_ranks(rng: np.random.Generator, n: int, theta: float = 0.99,
                  items: int = YCSB_ITEMS, zetan: float = YCSB_ZETAN) -> np.ndarray:
    """YCSB ``ZipfianGenerator.nextValue`` for ``n`` draws (0 = hottest)."""
    zeta2 = _zeta(2, theta)
    alpha = 1.0 / (1.0 - theta)
    eta = (1.0 - (2.0 / items) ** (1.0 - theta)) / (1.0 - zeta2 / zetan)
    u = rng.random(n)
    uz = u * zetan
    tail = (items * (eta * u - eta + 1.0) ** alpha).astype(np.int64)
    out = np.where(uz < 1.0, 0, np.where(uz < 1.0 + 0.5 ** theta, 1, tail))
    return np.minimum(out, items - 1).astype(np.int64)


def fnv1a64(values: np.ndarray) -> np.ndarray:
    """YCSB ``Utils.fnvhash64`` over the 8 little-endian bytes of each
    value, then ``Math.abs`` of the signed result."""
    v = values.astype(np.uint64)
    h = np.full(v.shape, FNV_OFFSET, np.uint64)
    with np.errstate(over="ignore"):
        for i in range(8):
            octet = (v >> np.uint64(8 * i)) & np.uint64(0xFF)
            h = (h ^ octet) * FNV_PRIME
    return np.abs(h.view(np.int64)).astype(np.int64)


def scrambled_zipf(rng: np.random.Generator, n: int, n_keys: int,
                   theta: float = 0.99) -> np.ndarray:
    return fnv1a64(zipfian_ranks(rng, n, theta)) % n_keys


def hot_order(n_keys: int, theta: float = 0.99, draws: int = 1 << 21,
              seed: int = 0) -> np.ndarray:
    """Keys in order of first appearance in a long draw (hottest first)."""
    keys = scrambled_zipf(np.random.default_rng([seed, 3]), draws, n_keys, theta)
    _, first = np.unique(keys, return_index=True)
    return keys[np.sort(first)]


class Schedule:
    """``n`` ops: ``tenant[n]``, ``is_add[n]`` (False = covered remove of
    the tenant's applied top), ``mask[n, elems]`` and, for an open loop,
    ``due[n]`` seconds from the window's start (Poisson arrivals)."""

    def __init__(self, tenant, is_add, mask, due=None):
        self.tenant, self.is_add, self.mask, self.due = tenant, is_add, mask, due

    def __len__(self):
        return len(self.tenant)


def schedule(seed: int, n: int, *, n_keys: int, elems: int, theta: float,
             add_share: float, elems_per_op: float, rate: float = 0.0) -> Schedule:
    """The op stream for one run. Every seed draws the same number of ops
    with the same mix; only the keys, members and arrival gaps differ."""
    rng = np.random.default_rng([seed, 1])
    tenant = scrambled_zipf(rng, n, n_keys, theta)
    is_add = rng.random(n) < add_share
    mask = np.empty((n, elems), bool)
    for lo in range(0, n, 1 << 16):   # float32 draws, in blocks of rows
        rows = mask[lo:lo + (1 << 16)]
        rows[:] = rng.random(rows.shape, dtype=np.float32) < (elems_per_op / elems)
    due = None
    if rate > 0:
        due = np.cumsum(rng.exponential(1.0 / rate, n))
    return Schedule(tenant, is_add, mask, due)
