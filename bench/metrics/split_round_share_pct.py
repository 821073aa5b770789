"""Round: share of the window's expansion rounds that took the split path
(flag kernel plus XLA compaction) rather than one fused kernel."""


def read(r):
    enums = r["counters"].get("enumerations", [])
    split = sum(s.get("split_rounds", 0) for s in enums)
    total = split + sum(s.get("fused_rounds", 0) for s in enums)
    return 100.0 * split / total if total else None
