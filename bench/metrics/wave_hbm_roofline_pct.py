"""Kernels: the least HBM traffic the window's enumerations need, over the
chip's peak bandwidth, as a share of the device's busy time.

The work is the algorithm's, whatever implements it: each of the P paths
the algorithm extends (the traffic file's ``paths_extended``, counted by
``bench/reference.py``) is written once and read once, as its vertex set
of ``nw = ceil(n / 32)`` words plus v1, v2 and v_last, 4 bytes each; a
stored cycle adds its vertex set once. That is the traffic of a round
that streams its frontier through HBM; a kernel that keeps several rounds
in VMEM moves less and can pass 100% of this bound only by doing so. The
time is every device operation of the traced window, kernels and XLA
alike.
"""
import math


def wave_bytes(paths: int, n: int, stored_cycles: int = 0) -> int:
    nw = math.ceil(n / 32)
    return 2 * paths * (4 * nw + 12) + 4 * nw * stored_cycles


def read(r):
    c, tr = r["counters"], r["trace"]
    enums = c.get("enumerations")
    if not enums or not tr.busy_s:
        return None
    stored = sum(c.get("stored_cycles") or [])
    total = wave_bytes(c["paths_extended"] * len(enums), c["n_vertices"],
                       stored)
    return 100.0 * total / r["peaks"]["hbm_bytes_per_s"] / tr.busy_s
