"""Compiled-executable cache for the FCT runtime.

One entry per (program kind, shape signature, backend, mesh) key; the value
is a ``jax.jit``-wrapped program.  Because the key pins every dimension the
program's shapes depend on (see batch.PlanSignature), a cache hit can never
retrace: JAX sees the same callable with the same input shapes.

``traces`` counts actual (re)traces — the wrapped Python body only runs while
JAX is tracing, so the counter moves exactly once per compiled specialization.
Tests assert warm queries leave it untouched.

``max_entries`` bounds the cache for long-lived serving processes: entries
are kept in LRU order (a ``get_or_build`` hit refreshes recency) and the
least-recently-used executable is dropped once the cap is exceeded.
Dropping the jit wrapper releases its compiled executable; a later request
for that signature simply recompiles (a miss + trace, counted as usual).

Each jitted program is named after its key's kind (``program_name``), so
profiles tell ``fct_store`` from ``fct_topk`` by module name.

``LruDict`` is the shared bounded-LRU primitive — the session-level caches
in ``repro/api`` (tuple sets, routing plans) reuse it rather than re-rolling
the eviction bookkeeping.
"""
from __future__ import annotations

import threading
from collections import OrderedDict
from typing import Any, Callable, Dict, Hashable, Optional

import jax

from repro.obs import default_registry


class LruDict(OrderedDict):
    """OrderedDict with LRU semantics and an optional size bound.

    ``hit(key)`` returns the value (or None) and refreshes its recency;
    ``put(key, value)`` inserts — first writer wins if the key raced in —
    refreshes, evicts past ``max_entries`` (None = unbounded) and returns
    the kept value.  ``evictions`` counts drops.  Callers provide their own
    locking and hit/miss counters.
    """

    def __init__(self, max_entries: Optional[int] = None) -> None:
        super().__init__()
        self.max_entries = max_entries
        self.evictions = 0

    def hit(self, key: Hashable):
        value = self.get(key)
        if value is not None:
            try:
                self.move_to_end(key)
            except KeyError:  # concurrently evicted; the value stays valid
                pass
        return value

    def put(self, key: Hashable, value):
        value = self.setdefault(key, value)
        self.move_to_end(key)
        while self.max_entries is not None and len(self) > self.max_entries:
            self.popitem(last=False)
            # fct-lint: waive[R3] -- externally-locked primitive (docstring): every caller holds its own lock around put/hit
            self.evictions += 1
        return value


def program_name(key: Hashable) -> str:
    """The jitted program's name: the key's kind (its first element, e.g.
    ``fct_store``), so a profile's XLA module reads ``jit_fct_store(...)``
    and its host line ``PjitFunction(fct_store)``."""
    return str(key[0] if isinstance(key, tuple) else key)


class ExecutableCache:
    """Hashable-key -> jitted callable, with LRU eviction and hit/miss/
    trace/eviction counters."""

    def __init__(self, max_entries: Optional[int] = None,
                 metrics=None) -> None:
        if max_entries is not None and max_entries < 1:
            raise ValueError(f"max_entries must be >= 1, got {max_entries}")
        self._fns = LruDict(max_entries)
        self._lock = threading.Lock()
        self.metrics = metrics if metrics is not None else default_registry()
        self._c_hits = self.metrics.counter("executable_cache.hits")
        self._c_misses = self.metrics.counter("executable_cache.misses")
        self._c_traces = self.metrics.counter("executable_cache.traces")

    @property
    def max_entries(self) -> Optional[int]:
        return self._fns.max_entries

    @property
    def evictions(self) -> int:
        return self._fns.evictions

    # legacy attribute views: the counters now live in the metrics registry
    # (registry lock = the consistent-read owner), these read-only ints keep
    # every existing caller and test working
    @property
    def hits(self) -> int:
        return self._c_hits.value

    @property
    def misses(self) -> int:
        return self._c_misses.value

    @property
    def traces(self) -> int:
        return self._c_traces.value

    def get_or_build(self, key: Hashable, builder: Callable[[], Callable]):
        """Return the cached executable for ``key``, building (and jitting)
        it on first use.  ``builder`` returns the un-jitted program.

        The cache is shared process-wide across sessions and serving
        tenants, so all bookkeeping happens under ``_lock``.  ``builder``
        runs outside the lock (it may be slow); if two threads race the
        same cold key, ``LruDict.put``'s first-writer-wins keeps exactly
        one executable and the loser's build is discarded.
        """
        with self._lock:
            fn = self._fns.hit(key)
        if fn is not None:
            self._c_hits.inc()
            return fn
        self._c_misses.inc()
        inner = builder()

        def program(*args: Any):
            self._c_traces.inc()  # runs only under tracing, not per call
            return inner(*args)

        program.__name__ = program.__qualname__ = program_name(key)
        with self._lock:
            return self._fns.put(key, jax.jit(program))

    def __contains__(self, key: Hashable) -> bool:
        return key in self._fns

    def __len__(self) -> int:
        return len(self._fns)

    def clear(self) -> None:
        with self._lock:
            self._fns.clear()
            self._fns.evictions = 0
        self._c_hits.reset()
        self._c_misses.reset()
        self._c_traces.reset()

    def stats(self) -> Dict[str, int]:
        # one registry-lock cut for the counters, then the LRU bookkeeping
        # under its own lock — each group internally consistent
        hits, misses, traces = self.metrics.values(
            self._c_hits, self._c_misses, self._c_traces)
        with self._lock:
            return {"entries": len(self), "hits": hits, "misses": misses,
                    "traces": traces, "evictions": self.evictions}


_GLOBAL_CACHE = ExecutableCache()


def default_cache() -> ExecutableCache:
    """Process-wide cache shared by the default engine and the two-job path."""
    return _GLOBAL_CACHE
