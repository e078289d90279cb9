"""Kernels: the ``Map<K, MVReg>`` replica fold as a share of its HBM
roofline: the bytes each round's fold must move (``roofline_map``), times
the window's rounds, over the device's busy seconds in the window."""

from roofline_map import map_fold_bytes


def read(c):
    red, peaks = c["trace"], c["peaks"]
    if red is None or peaks is None or red["busy_s"] <= 0:
        return None
    k = c["res"]["counters"]
    one = map_fold_bytes(k["rows_per_device"], k["keys"], k["actors"], k["sibling_cap"],
                         k["deferred_cap"])
    return 100.0 * one * k["rounds"] / red["busy_s"] / peaks["hbm_bytes_per_s"]
