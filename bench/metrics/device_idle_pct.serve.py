"""Device: share of the traced window in which no operation ran on the
chip (1 - busy / window, busy the union of the trace's XLA Ops)."""


def read(r):
    tr = r["trace"]
    return 100.0 * (1.0 - tr.busy_s / tr.window_s) if tr.window_s else None
