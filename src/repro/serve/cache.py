"""Shared bounded caches for the serving layer (DESIGN.md §9).

Two pieces, both import-light so the core engines can use them without
pulling the server in:

* :class:`LRUCache` — a thread-safe least-recently-used map with
  hit/miss/eviction counters.  It replaces every unbounded (or
  clear-on-overflow) memoization dict in the execution stack: the
  plan-keyed einsum/jit program memos in :mod:`repro.core.jax_engine`,
  and the per-``Prepared`` compiled-program memo the distributed path
  keeps (:attr:`repro.core.prepare.Prepared._program_cache`).  Long-lived
  server processes otherwise accumulate compiled programs without bound.
* :class:`PlanCache` + :func:`plan_shape_key` — prepared-statement
  semantics for the query server: compiled :class:`~repro.api.plan.Plan`
  objects keyed on query *shape* (relations, rewrites, group attrs,
  aggregate kinds, engine, execution options) plus the server's data
  generation, so a repeat query skips ``prepare`` + plan compile + jit
  entirely and runs straight on the cached physical plan.
"""
from __future__ import annotations

import threading
import time
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Any, Callable

from repro import obs


@dataclass
class CacheStats:
    """Counters of one cache's lifetime; ``snapshot()`` for reporting."""

    hits: int = 0
    misses: int = 0
    evictions: int = 0
    inserts: int = 0

    def snapshot(self) -> dict[str, int]:
        return {
            "hits": self.hits,
            "misses": self.misses,
            "evictions": self.evictions,
            "inserts": self.inserts,
        }


class LRUCache:
    """Thread-safe bounded LRU map with hit/miss/eviction counters.

    ``get_or_create(key, factory)`` gives once-per-key construction: the
    factory for a given key runs at most once at a time (concurrent
    callers of the *same* key block on a per-key latch and then share the
    produced value; distinct keys never block each other).  This is what
    lets the server compile a plan exactly once under a thundering herd
    of identical queries.
    """

    def __init__(self, maxsize: int = 128, name: str = "lru"):
        if maxsize < 1:
            raise ValueError(f"maxsize must be >= 1, got {maxsize}")
        self.maxsize = maxsize
        self.name = name
        self.stats = CacheStats()  # guarded-by: _lock
        self._data: OrderedDict[Any, Any] = OrderedDict()  # guarded-by: _lock
        self._lock = threading.RLock()
        self._building: dict[Any, threading.Event] = {}  # guarded-by: _lock

    # -- dict-ish surface (used by the engine memos) -------------------
    def __len__(self) -> int:
        with self._lock:
            return len(self._data)

    def __contains__(self, key) -> bool:
        with self._lock:
            return key in self._data

    def get(self, key, default=None):
        with self._lock:
            if key in self._data:
                self._data.move_to_end(key)
                self.stats.hits += 1
                return self._data[key]
            self.stats.misses += 1
            return default

    def put(self, key, value) -> None:
        with self._lock:
            if key in self._data:
                self._data.move_to_end(key)
                self._data[key] = value
                return
            self._data[key] = value
            self.stats.inserts += 1
            while len(self._data) > self.maxsize:
                self._data.popitem(last=False)
                self.stats.evictions += 1

    __setitem__ = put

    def setdefault(self, key, value):
        """Insert-if-absent; returns the stored value.  A present key
        counts as a hit; an absent key counts only the insert (callers
        pair this with a ``get`` that already counted the miss)."""
        with self._lock:
            if key in self._data:
                self._data.move_to_end(key)
                self.stats.hits += 1
                return self._data[key]
            self.put(key, value)
            return value

    def clear(self) -> None:
        with self._lock:
            self._data.clear()

    def keys(self) -> list:
        with self._lock:
            return list(self._data)

    # -- once-per-key construction -------------------------------------
    def get_or_create(self, key, factory: Callable[[], Any]):
        """Return the cached value, or build it with ``factory`` exactly
        once even under concurrent callers of the same key."""
        while True:
            with self._lock:
                if key in self._data:
                    self._data.move_to_end(key)
                    self.stats.hits += 1
                    return self._data[key]
                latch = self._building.get(key)
                if latch is None:
                    self.stats.misses += 1
                    latch = self._building[key] = threading.Event()
                    mine = True
                else:
                    mine = False
            if not mine:
                latch.wait()
                # the builder may have failed — loop to retry/observe
                with self._lock:
                    if key in self._data:
                        self._data.move_to_end(key)
                        self.stats.hits += 1
                        return self._data[key]
                continue
            try:
                value = factory()
            except BaseException:
                with self._lock:
                    self._building.pop(key, None)
                latch.set()
                raise
            with self._lock:
                self.put(key, value)
                self._building.pop(key, None)
            latch.set()
            return value


# ----------------------------------------------------------------------
# plan-shape keys & the prepared-plan cache
# ----------------------------------------------------------------------


def plan_shape_key(spec, generation: int = 0, stats_generation: int = 0):
    """Hashable shape of a :class:`~repro.api.builder.Q` spec, or ``None``
    when the query cannot be cached safely.

    The key captures everything the compiled plan depends on: relations
    (with aliases), column renames, pushed-down predicate labels, group
    attributes, the named-aggregate bundle (name, kind, measure), engine
    name, memory budget / stream options, the mesh shard count, the
    server's data ``generation`` (bumped on every relation registration,
    so stale plans become unreachable and age out of the LRU), and the
    ``stats_generation`` of the statistics layer plus the spec's own
    stats toggle — a stats bump invalidates every cached plan whose root
    / split choices were made on the old sketches (DESIGN.md §10).

    Uncacheable shapes — ``None`` is returned — are those whose identity
    the label cannot prove: callable predicates (the label is just the
    function's ``__name__``, so two distinct lambdas — or two different
    closures that happen to share a name — would collide), engine
    *instances* (no stable name), and mesh objects (only plain shard
    counts are keyed).  Declarative comparison/equality predicates carry
    ``"attr op value"`` labels (always containing a space) and key fine —
    they are the only predicate form the wire protocol admits, so every
    remote query is cacheable.
    """
    engine = spec.engine_name
    if not isinstance(engine, str):
        return None
    mesh = getattr(spec, "mesh_opt", None)
    if mesh is not None and not isinstance(mesh, int):
        return None
    preds = []
    for p in spec.predicates:
        if " " not in p.label:
            return None  # callable-form predicate: label is only a name
        preds.append((p.relation, p.label))
    return (
        generation,
        spec.relations,
        spec.renames,
        tuple(preds),
        spec.group_attrs,
        tuple((name, a.kind, a.measure) for name, a in spec.aggs),
        engine,
        spec.budget,
        spec.stream_opt,
        mesh,
        stats_generation,
        bool(getattr(spec, "stats_opt", True)),
    )


@dataclass
class PlanCacheStats:
    """Plan-cache counters: LRU traffic plus actual compiles/bypasses."""

    compiles: int = 0  # times compile_plan actually ran
    bypasses: int = 0  # uncacheable shapes compiled outside the cache
    lru: CacheStats = field(default_factory=CacheStats)

    def snapshot(self) -> dict[str, int]:
        return {
            "compiles": self.compiles,
            "bypasses": self.bypasses,
            **self.lru.snapshot(),
        }


class PlanCache:
    """Prepared-plan cache: ``Q`` shape → compiled ``Plan``.

    ``lookup(spec, db, generation)`` returns a ready-to-execute plan; a
    warm hit skips logical rewrites, encoding, root search / GHD
    compilation, and (via the plan's cached ``Prepared``) the CSR-view
    sorts and jitted-program traces of every engine memo hanging off it.
    """

    def __init__(self, maxsize: int = 64):
        self._lru = LRUCache(maxsize, name="plans")
        # counters see concurrent lookup() callers, and build() runs
        # OUTSIDE the LRU's per-key latch lock — they need their own lock
        self._stats_lock = threading.Lock()
        self.stats = PlanCacheStats(lru=self._lru.stats)  # guarded-by: _stats_lock

    def __len__(self) -> int:
        return len(self._lru)

    def lookup(self, spec, db, generation: int = 0, stats_generation: int = 0):
        key = plan_shape_key(spec, generation, stats_generation)
        if key is None:
            with self._stats_lock:
                self.stats.bypasses += 1
                self.stats.compiles += 1
            return _build_plan(spec, db)

        def build():
            with self._stats_lock:
                self.stats.compiles += 1
            return _build_plan(spec, db)

        return self._lru.get_or_create(key, build)

    def clear(self) -> None:
        self._lru.clear()


def _build_plan(spec, db):
    """Compile one plan, counted in ``plan.builds`` and ``plan.build_s``."""
    from repro.api.plan import compile_plan

    t = time.perf_counter()
    plan = compile_plan(spec, db)
    obs.count("plan.builds")
    obs.count("plan.build_s", time.perf_counter() - t)
    return plan
