"""Scheduler: 95th percentile of the time a request waited between its
arrival and its admission to a lane, over every request of the session
(``queue_wait_ms`` of the scheduler's session stats)."""
from bench import traffic


def read(r):
    waits = r["counters"].get("session", {}).get("queue_wait_ms")
    return traffic.percentile(waits, 95) if waits else None
