"""Round: how full the sharded rounds run. Every round of the sharded
superstep works on a device's whole ``local_capacity``; this is the mean,
over the window's rounds and devices, of the live rows a device holds
after the round over that capacity (``live_rows_sum`` of each result's
stats, the sum of the superstep's per-round per-device live counts)."""


def read(r):
    enums = r["counters"].get("enumerations", [])
    keys = ("live_rows_sum", "rounds", "n_devices", "local_capacity")
    if not enums or any(k not in s for s in enums for k in keys):
        return None
    slots = sum(s["rounds"] * s["n_devices"] * s["local_capacity"]
                for s in enums)
    return 100.0 * sum(s["live_rows_sum"] for s in enums) / slots \
        if slots else None
