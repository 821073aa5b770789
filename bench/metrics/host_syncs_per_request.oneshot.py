"""Driver: host syncs per enumeration of the window (``n_host_syncs`` of
each result's stats)."""


def read(r):
    syncs = [s.get("n_host_syncs") for s in
             r["counters"].get("enumerations", [])]
    if not syncs or None in syncs:
        return None
    return sum(syncs) / len(syncs)
