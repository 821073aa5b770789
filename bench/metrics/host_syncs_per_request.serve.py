"""Driver: host syncs of the serving session per completed request
(``n_host_syncs / completed`` of the session)."""


def read(r):
    s = r["counters"].get("session", {})
    if not s.get("completed") or s.get("n_host_syncs") is None:
        return None
    return s["n_host_syncs"] / s["completed"]
