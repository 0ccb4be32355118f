"""Design cache — LRU of ``PreparedDesign`` handles for repeated-X traffic.

Serving workloads are dominated by repeated design matrices (the same
feature matrix queried with many targets: probes, ablations, per-user
heads).  Everything about a solve that depends only on ``x`` therefore lives
on one ``repro.core.prepare.PreparedDesign`` per design, cached across
requests and keyed by the design fingerprint:

  * the padded device-resident copy of ``x`` (skips re-pad + host→device
    transfer on every request);
  * the squared column norms (the O(obs·vars) pass of Algorithm 1 line 3)
    and their per-``thr`` padded layouts;
  * the per-block Gram Cholesky factors for ``mode="gram"``, computed once
    per (thr, ridge) and reused by every later request;
  * per-placement sharded device copies — a bucket routed to a mesh-sharded
    backend (see ``repro.serve.placement``) reuses its resident reshard;
  * each tenant's last solved coefficients (warm starts), LRU-bounded.

This module used to carry its own ``DesignEntry`` dataclass with exactly
that state; PR 4 promoted it to the public ``PreparedDesign`` handle, and
the cache now stores handles directly — ``DesignEntry`` remains as an alias
so existing callers and tests keep working.  The per-entry lock semantics
(every mutable accessor guarded, per-design so one slow Cholesky build never
blocks another design's lookups) moved with the state and are unchanged.

Entries are LRU-evicted so memory is bounded by ``max_entries`` designs;
per-entry warm coefficients are themselves LRU-bounded by ``max_tenants``.
The cache-level lock only covers the LRU map itself.

PR 9: with a ``repro.store.DesignStore`` attached (``store=``), the cache
becomes a *view over the store's device tier* — eviction turns into
demotion (device → host → disk, warm-start state preserved), lookups that
miss the device tier try a promotion before rebuilding from source, and
designs too large for the device byte budget come back as non-resident
streaming handles served by the ``"bakp_stream"`` method.  Without a store
the behaviour is bit-identical to before.
"""
from __future__ import annotations

import threading
from collections import OrderedDict
from dataclasses import dataclass
from typing import Optional, Tuple

import numpy as np

from repro import obs
from repro.core.prepare import PreparedDesign, prepare
from repro.core.spec import SolverSpec

# Backwards-compatible name: per-design cached state IS the public handle.
DesignEntry = PreparedDesign


@dataclass
class CacheStats:
    """Per-cache counters (convenience mirror of the ``serve_cache_*``
    families this cache records into its ``repro.obs`` registry — see
    ``ServeStats`` for the pattern)."""

    hits: int = 0
    misses: int = 0
    evictions: int = 0

    @property
    def hit_rate(self) -> float:
        total = self.hits + self.misses
        return self.hits / total if total else 0.0

    def as_dict(self) -> dict:
        return {"hits": self.hits, "misses": self.misses,
                "evictions": self.evictions, "hit_rate": self.hit_rate}


class DesignCache:
    """LRU cache: design key → ``PreparedDesign``.

    Thread-safe: the async dispatcher pre-warms entries from its dispatch
    thread (overlapping padding + host→device transfer + method-state
    warming with in-flight solves) while the solver thread reads them, so
    the LRU bookkeeping is guarded by a lock.  Entry *construction* runs
    outside the lock; on a build race the first ``put`` wins and the
    loser's entry is dropped.
    """

    def __init__(self, max_entries: int = 64, max_tenants: int = 64,
                 registry: Optional[obs.MetricsRegistry] = None,
                 store=None):
        self.max_entries = max_entries
        self.max_tenants = max_tenants
        self.store = store  # Optional[repro.store.DesignStore]
        self.stats = CacheStats()
        reg = registry or obs.default_registry()
        self._m_hits = reg.counter(
            "serve_cache_hits_total", "design-cache lookups served resident")
        self._m_misses = reg.counter(
            "serve_cache_misses_total", "design-cache lookups that built")
        self._m_evictions = reg.counter(
            "serve_cache_evictions_total", "designs LRU-evicted")
        self._m_resident = reg.gauge(
            "serve_cache_entries", "designs currently resident")
        self._lock = threading.Lock()
        self._entries: "OrderedDict[str, PreparedDesign]" = OrderedDict()

    def __len__(self) -> int:
        if self.store is not None:
            return len(self.store)  # device-tier resident count
        return len(self._entries)

    def _record_lookup(self, hit: bool, record_stats: bool) -> None:
        if not record_stats:
            return
        with self._lock:
            if hit:
                self.stats.hits += 1
                self._m_hits.inc()
            else:
                self.stats.misses += 1
                self._m_misses.inc()

    def _sync_evictions(self, demotions_before: int) -> None:
        """Mirror store demotions into the historical eviction counters —
        dashboards keyed on ``serve_cache_evictions_total`` keep reading
        the device tier's turnover."""
        delta = self.store.stats.demotions_device - demotions_before
        if delta > 0:
            with self._lock:
                self.stats.evictions += delta
                self._m_evictions.inc(delta)
        self._m_resident.set(len(self.store))

    def get(self, key: str,
            record_stats: bool = True) -> Optional[PreparedDesign]:
        """Fetch (and LRU-touch) an entry.  ``record_stats=False`` makes the
        lookup invisible to hit/miss accounting — used by the dispatcher's
        pre-warm so each request still logs exactly one cache event, at
        flush time.  Store-backed: returns the device-resident entry or the
        non-resident streaming handle; never promotes (that is
        ``get_or_build``'s job, so plain lookups stay O(1))."""
        if self.store is not None:
            entry = self.store.get(key)
            self._record_lookup(entry is not None, record_stats)
            return entry
        with self._lock:
            entry = self._entries.get(key)
            if entry is None:
                if record_stats:
                    self.stats.misses += 1
                    self._m_misses.inc()
                return None
            self._entries.move_to_end(key)
            if record_stats:
                self.stats.hits += 1
                self._m_hits.inc()
            return entry

    def put(self, key: str, entry: PreparedDesign) -> PreparedDesign:
        if self.store is not None:
            before = self.store.stats.demotions_device
            out = self.store.admit(key, entry)
            self._sync_evictions(before)
            return out
        with self._lock:
            existing = self._entries.get(key)
            if existing is not None:  # build race: first writer wins
                self._entries.move_to_end(key)
                return existing
            self._entries[key] = entry
            while len(self._entries) > self.max_entries:
                self._entries.popitem(last=False)
                self.stats.evictions += 1
                self._m_evictions.inc()
            self._m_resident.set(len(self._entries))
            return entry

    def get_or_build(self, key: str, build_x_pad,
                     spec: Optional[SolverSpec] = None,
                     record_stats: bool = True,
                     placement=None, mesh=None
                     ) -> Tuple[PreparedDesign, bool]:
        """Fetch the ``PreparedDesign`` for ``key``, preparing it on miss.

        ``build_x_pad`` is a zero-arg callable returning the bucket-padded
        design matrix — only invoked on a miss, so hits skip the host-side
        padding entirely.  ``spec`` (optional) additionally warms the
        method's derived state (thr-padded column norms, block-Gram
        Cholesky, the fused kernel's resident tiers) on hit AND miss — the
        dispatcher's pre-warm passes it so those builds run off the lane
        threads; idempotent + per-entry locked, so racing with a lane
        thread is safe.  ``placement``/``mesh`` extend the warm to the
        lane-resident sharded copy and bind the entry's home placement
        (``PreparedDesign.bind_home`` — first-wins).  Returns
        (entry, cache_hit).

        Store-backed: a device-tier miss first tries ``store.promote`` —
        climbing the design back from its host/disk snapshot (with warm
        coefficients and Cholesky state restored) counts as a *hit*, since
        ``build_x_pad`` never runs.  The dispatcher's pre-warm routes
        through here, so promotion overlaps queue wait by construction.
        Only a design unknown to every tier rebuilds from source.
        """
        if self.store is not None:
            entry = self.store.get(key)
            hit = entry is not None
            if not hit:
                before = self.store.stats.demotions_device
                promoted = self.store.promote(key)
                if promoted is not None:
                    entry, hit = promoted, True
                self._sync_evictions(before)
            self._record_lookup(hit, record_stats)
            if not hit:
                before = self.store.stats.demotions_device
                entry = self.store.build(
                    key, np.asarray(build_x_pad(), np.float32),
                    max_tenants=self.max_tenants)
                self._sync_evictions(before)
        else:
            entry = self.get(key, record_stats)
            hit = entry is not None
            if not hit:
                # A sharded placement builds x in its layout directly: a
                # design one device cannot hold never lands on device 0.
                built = prepare(np.asarray(build_x_pad(), np.float32),
                                mesh=mesh, fingerprint=key,
                                max_tenants=self.max_tenants,
                                placement=placement)
                entry = self.put(key, built)
        if spec is not None:
            entry.warm_lane_state(spec, placement=placement, mesh=mesh)
        else:
            entry.bind_home(placement)
            if (placement is not None and placement.sharded
                    and mesh is not None and entry.x_pad is not None):
                entry.x_for_placement(placement, mesh)
        return entry, hit
