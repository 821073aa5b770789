"""Round: how full the sharded rounds run at the buckets they ran at.
Every round of the sharded superstep works on a device's whole bucket;
this is the mean, over the window's rounds and devices, of the live rows a
device holds after the round over that round's bucket (``live_rows_sum``
over ``slot_rows_sum`` of each result's stats, the sum of the buckets the
rounds ran at over rounds and devices). Nothing where the program does not
count ``slot_rows_sum``."""


def read(r):
    enums = r["counters"].get("enumerations", [])
    keys = ("live_rows_sum", "slot_rows_sum")
    if not enums or any(k not in s for s in enums for k in keys):
        return None
    slots = sum(s["slot_rows_sum"] for s in enums)
    return 100.0 * sum(s["live_rows_sum"] for s in enums) / slots \
        if slots else None
