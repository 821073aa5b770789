"""Kernels: ``wave_hbm_roofline_pct`` of a cell on several chips. The
work is that metric's (``wave_bytes``, imported from its reader so that it
is defined in one place), over the peak bandwidth of all the cell's chips
(``chips`` of the configuration) and the device's busy time, the mean
over the chips."""
import os

from bench.run import load_module

_base = load_module(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                 "wave_hbm_roofline_pct.py"))


def read(r):
    c, tr = r["counters"], r["trace"]
    enums = c.get("enumerations")
    if not enums or not tr.busy_s:
        return None
    total = _base.wave_bytes(c["paths_extended"] * len(enums),
                             c["n_vertices"])
    peak = r["config"]["chips"] * r["peaks"]["hbm_bytes_per_s"]
    return 100.0 * total / peak / tr.busy_s
