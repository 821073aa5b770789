"""Persistent tuning cache — the memory layer of ``repro.tune``.

A tuned knob set is a property of a *workload class*, not of one graph:
any graph with the same shape class (power-of-two buckets of n / m / Δ),
run through the same backend × formulation × engine on the same device
kind, chops into near-identical dispatch sequences. ``TuneKey`` names that
class; ``TuneStore`` maps it to the winning knobs in a versioned on-disk
JSON file so a warm service skips the search (and the profiling run that
feeds it) entirely.

The store carries the same LRU bound as the ``ProgramCache`` it feeds
(``max_entries`` ↔ ``max_plans``): long-lived services tuning many
workload classes evict the least-recently-used entry instead of growing
without bound. Writes are atomic (tmp + ``os.replace``, the
``repro/checkpoint`` idiom); a version mismatch on load drops the stale
file's entries rather than misapplying old-schema knobs; the
read→merge→replace window of ``save`` is serialized by an ``fcntl``
advisory lock on ``<path>.lock`` so concurrent writers sharing one path
cannot interleave inside it and lose each other's updates (falls back to
lock-free merge-on-save where ``fcntl`` does not exist).
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import json
import os

try:
    import fcntl
except ImportError:  # pragma: no cover - non-POSIX: lock-free fallback
    fcntl = None

SCHEMA_VERSION = 1


@contextlib.contextmanager
def _file_lock(path: str | None):
    """Exclusive advisory lock on ``<path>.lock`` (no-op without fcntl or
    path). Guards the whole read→merge→replace window of ``save`` — two
    racing writers serialize, so neither can lose the other's entries."""
    if fcntl is None or not path:
        yield
        return
    lock_path = path + ".lock"
    os.makedirs(os.path.dirname(os.path.abspath(lock_path)), exist_ok=True)
    with open(lock_path, "w") as lf:
        fcntl.flock(lf, fcntl.LOCK_EX)
        try:
            yield
        finally:
            fcntl.flock(lf, fcntl.LOCK_UN)


def _p2(x: int) -> int:
    """Round up to a power of two (the shape-class bucket)."""
    x = max(int(x), 1)
    p = 1
    while p < x:
        p <<= 1
    return p


def shape_class(n: int, m: int, delta: int) -> str:
    """Workload shape class: pow2 buckets of |V|, |E|, Δ."""
    return f"n{_p2(n)}-m{_p2(m)}-d{_p2(delta)}"


@dataclasses.dataclass(frozen=True)
class TuneKey:
    """Identity of one tuned workload class:
    graph-shape class × backend × formulation × engine × mode × device
    (× device count for mesh-routed classes — the sharded knobs scale with
    how many devices split the frontier; × batch-size class for batched
    requests — lane imbalance changes which round budget wins)."""
    shape: str            # shape_class(n, m, Δ)
    store: bool           # store vs count-only mode
    formulation: str
    backend: str
    engine: str           # 'wave' | 'dist' (mesh-routed) | 'sched'
    device_kind: str      # jax platform: 'cpu' | 'gpu' | 'tpu'
    ndev: int = 0         # TOTAL device count H·D (0: unsharded)
    batch: int = 0        # batch-size class (pow2 bucket of B; 0: unbatched)
    nhost: int = 0        # host tier size of a 2-level mesh (0: flat) — a
    #                       2×4 mesh tunes apart from a flat 8: cross-host
    #                       knobs only exist (and pay off) on the former

    def as_str(self) -> str:
        mode = "store" if self.store else "count"
        parts = [self.shape, mode, self.formulation, self.backend,
                 self.engine, self.device_kind]
        if self.ndev:     # unsharded keys keep the pre-dist string format
            parts.append(f"x{self.ndev}")
        if self.batch:    # unbatched keys keep the pre-batch string format
            parts.append(f"b{self.batch}")
        if self.nhost:    # flat-mesh keys keep the pre-hierarchy format
            parts.append(f"h{self.nhost}")
        return "|".join(parts)

    @classmethod
    def from_str(cls, s: str) -> "TuneKey":
        shape, mode, formulation, backend, engine, device, *rest = \
            s.split("|")
        ndev = batch = nhost = 0
        for tok in rest:   # legacy strings carry neither token; order-free
            if tok.startswith("x"):
                ndev = int(tok[1:])
            elif tok.startswith("b"):
                batch = int(tok[1:])
            elif tok.startswith("h"):
                nhost = int(tok[1:])
        return cls(shape=shape, store=(mode == "store"),
                   formulation=formulation, backend=backend, engine=engine,
                   device_kind=device, ndev=ndev, batch=batch, nhost=nhost)


class TuneStore:
    """Versioned JSON store of tuned knob sets, LRU-bounded.

    ``path=None`` keeps the store in memory (tests, one-off scripts); with a
    path, every ``put`` persists atomically and a warm process re-loads the
    file on construction. Entry schema::

        {"version": 1,
         "entries": {"<TuneKey str>": {"knobs": {...}, "meta": {...},
                                       "hits": N}}}
    """

    def __init__(self, path: str | None = None,
                 max_entries: int | None = None):
        self.path = path
        self.max_entries = max_entries
        self._entries: "collections.OrderedDict[str, dict]" = \
            collections.OrderedDict()
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.puts = 0
        self.stale_drops = 0
        if path:
            self.load()

    # -- persistence -----------------------------------------------------

    def load(self) -> None:
        if not self.path or not os.path.exists(self.path):
            return
        try:
            with open(self.path) as f:
                doc = json.load(f)
        except (OSError, json.JSONDecodeError):
            self.stale_drops += 1
            return
        if doc.get("version") != SCHEMA_VERSION:
            # old-schema knobs must not be misapplied — start fresh
            self.stale_drops += 1
            return
        for k, v in doc.get("entries", {}).items():
            self._entries[k] = v
        self._shed()

    def save(self) -> None:
        if not self.path:
            return
        os.makedirs(os.path.dirname(os.path.abspath(self.path)),
                    exist_ok=True)
        # merge-on-save: re-read the file so entries tuned by OTHER
        # processes sharing this path survive our write (our entries win on
        # key conflict). The fcntl lock serializes the whole
        # read→merge→replace window, so a racing writer can no longer lose
        # an update inside it (lock-free platforms keep merge-on-save,
        # which still prevents whole-store clobbering). The merged file may
        # transiently exceed max_entries (the bound is enforced on the
        # in-memory LRU).
        with _file_lock(self.path):
            merged: dict = {}
            if os.path.exists(self.path):
                try:
                    with open(self.path) as f:
                        doc = json.load(f)
                    if doc.get("version") == SCHEMA_VERSION:
                        merged.update(doc.get("entries", {}))
                except (OSError, json.JSONDecodeError):
                    pass
            merged.update(self._entries)
            tmp = self.path + ".tmp"
            with open(tmp, "w") as f:
                json.dump(dict(version=SCHEMA_VERSION, entries=merged), f,
                          indent=2)
            os.replace(tmp, self.path)

    # -- LRU dict --------------------------------------------------------

    def _shed(self) -> None:
        while (self.max_entries is not None
               and len(self._entries) > self.max_entries):
            self._entries.popitem(last=False)
            self.evictions += 1

    def get(self, key: "TuneKey | str") -> dict | None:
        """Tuned knobs for ``key``, or None. A hit refreshes LRU order."""
        k = key.as_str() if isinstance(key, TuneKey) else key
        entry = self._entries.get(k)
        if entry is None:
            self.misses += 1
            return None
        self.hits += 1
        entry["hits"] = entry.get("hits", 0) + 1
        self._entries.move_to_end(k)
        return dict(entry["knobs"])

    def put(self, key: "TuneKey | str", knobs: dict,
            meta: dict | None = None) -> None:
        k = key.as_str() if isinstance(key, TuneKey) else key
        self._entries[k] = dict(knobs=dict(knobs), meta=dict(meta or {}),
                                hits=0)
        self._entries.move_to_end(k)
        self.puts += 1
        self._shed()
        self.save()

    def __len__(self) -> int:
        return len(self._entries)

    def __contains__(self, key) -> bool:
        k = key.as_str() if isinstance(key, TuneKey) else key
        return k in self._entries

    def keys(self):
        return list(self._entries.keys())

    def stats(self) -> dict:
        return dict(entries=len(self._entries), store_hits=self.hits,
                    store_misses=self.misses, evictions=self.evictions,
                    puts=self.puts, stale_drops=self.stale_drops,
                    max_entries=self.max_entries,
                    persistent=self.path is not None)
