"""Driver: how unevenly the sharded frontier spreads over the devices.
Per enumeration, 100 x (the busiest device's peak live rows over the mean
of the devices' peaks - 1), from ``per_device_peak_rows`` of each
result's stats; the mean over the window's enumerations. 0 is an even
spread; the capacity rule of ``table1_sharded4`` leaves room for 100."""


def read(r):
    peaks = [s.get("per_device_peak_rows") for s in
             r["counters"].get("enumerations", [])]
    if not peaks or any(not p or not sum(p) for p in peaks):
        return None
    return sum(100.0 * (max(p) * len(p) / sum(p) - 1.0)
               for p in peaks) / len(peaks)
