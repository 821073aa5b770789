"""Scheduler: mean share of the pool's lanes that held a request, per
superstep dispatch (``occupancy_sum / supersteps`` of the session)."""


def read(r):
    s = r["counters"].get("session", {})
    if not s.get("supersteps"):
        return None
    return 100.0 * s["occupancy_sum"] / s["supersteps"]
