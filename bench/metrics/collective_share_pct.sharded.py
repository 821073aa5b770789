"""Device: share of the traced window's device time, summed over the
chips, spent in collectives: the ``all-reduce`` of each round's live
count and the ``collective-permute`` of balancing (operations of the
trace whose name holds either, their ``-start`` and ``-done`` halves
included)."""
import re

_COLLECTIVE = re.compile(r"all-reduce|collective-permute")


def read(r):
    ops = r["trace"].op_seconds
    total = sum(ops.values())
    if not total:
        return None
    coll = sum(s for key, s in ops.items()
               if _COLLECTIVE.search(key.split(" ", 1)[0].rsplit("/", 1)[-1]))
    return 100.0 * coll / total
