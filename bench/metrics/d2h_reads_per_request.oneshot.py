"""Driver: arrays copied from the device to the host per enumeration of
the window (``n_d2h_arrays`` of each result's stats). A ``device_get`` of
a tuple reads each of its arrays on its own, so this counts the reads
that ``host_syncs_per_request.oneshot`` folds into one sync each."""


def read(r):
    reads = [s.get("n_d2h_arrays") for s in
             r["counters"].get("enumerations", [])]
    if not reads or None in reads:
        return None
    return sum(reads) / len(reads)
