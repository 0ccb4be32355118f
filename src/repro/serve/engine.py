"""Batched multi-tenant solver-serving engine.

``SolverServeEngine`` turns a stream of per-tenant ``SolveRequest``s into a
small number of compiled batch solves:

  1. **Bucketing** — requests are grouped by padded power-of-two shape (and
     solver config), so the jit compile cache is bounded by the number of
     buckets seen, not the number of distinct request shapes.
  2. **Same-design coalescing** — requests whose design matrix fingerprints
     match are merged into ONE multi-RHS solve: ``y`` becomes (obs, k) and a
     single stream of ``x`` (the solver's entire memory traffic) serves all
     k tenants.  k is itself padded to a power of two to bound recompiles.
  3. **Same-bucket vmap batching** — leftover single-design requests in a
     bucket are stacked and solved with one vmapped call (batch padded to a
     power of two by replicating the last system; replicas are discarded).
  4. **Design caching** — everything that depends only on ``x`` lives on a
     ``repro.core.PreparedDesign`` handle (device copy, column norms,
     block-Gram Cholesky factors, sharded copies, warm coefficients),
     memoised across flushes in an LRU ``DesignCache``.  Solves dispatch
     through ``PreparedDesign.solve`` with the request's effective
     ``SolverSpec`` (see ``spec_for``), so the engine is a consumer of the
     public core API — methods registered via ``repro.core.register_method``
     are servable without engine changes.
  5. **Warm starts** — a request may carry initial coefficients
     (``SolveRequest.a0``), or name a ``tenant_id`` whose last solved
     coefficients the design cache retained; the iterative solvers then
     start from that point instead of zeros.  Warm and cold requests
     coalesce freely: cold members of a group ride a zero column/row of the
     stacked ``a0``, which is bit-identical to the cold path.
  6. **Mesh placement** — an engine constructed with a ``ServeMesh`` routes
     buckets onto the mesh-sharded SolveBakP backends
     (``repro.core.distributed``) by size: big buckets shard their design
     rows over the data axes (``obs_sharded``), giant same-design multi-RHS
     groups shard the k axis instead (``rhs_sharded`` — one stream of ``x``
     per device serves k/D tenants), and optionally pod-scale buckets go
     2-D.  The placement is part of the grouping key, so one compiled
     program never mixes mesh layouts; vmap batching stays single-device
     (vmapping over shard_map is not a thing), so sharded buckets solve
     their leftover singles individually.

  7. **Execution lanes** — ``flush()`` is a pure batch-builder: it groups,
     resolves design entries and routes each batch to its execution lane
     (``repro.serve.lanes`` — a (device set, kernel path) executor thread
     per placement/kernel family), then waits for all units.  Batches on
     different lanes (single-device xla, fused Pallas, each mesh
     placement) overlap; batches on one lane keep their submission order,
     so results are bit-identical to the sequential engine
     (``ServeConfig.lane_execution=False`` collapses everything onto one
     serial lane — the pre-lane architecture).

Results come back as per-request ``ServedSolve``s, in submission order, with
padding stripped and per-request SSE recomputed from the stripped residual.

Spans (``repro.obs.span``; on the device trace while profiling):
``engine.flush`` around a whole flush; ``engine.build`` around the host
work before a solver call (the flush's grouping and design lookups, then
each unit's padding of y and a0); ``solve/<method>`` around the solver
launch (``solve/vmap/<method>`` for a vmapped batch, and
``solve/<placement kind>/<method>`` on a mesh placement, such as
``solve/obs_sharded/bakp_gram``); ``engine.fetch`` from the result being
ready on the device to the finished ``ServedSolve``.  Each request's
telemetry carries its unit's ``build_s``, ``solve_s`` and ``fetch_s``.

Flushing is exception-safe: a batch whose solver raises is isolated — every
request in it gets an error result (``ServedSolve.error`` set, zero
coefficients) and the remaining batches still run, so one poisoned request
can never wedge the engine or starve its co-tenants.

Example::

    engine = SolverServeEngine()
    for x, y in workload:
        engine.submit(SolveRequest(x=x, y=y, method="bakp_gram", rtol=1e-8))
    for served in engine.flush():
        use(served.coef)
"""
from __future__ import annotations

import contextlib
import functools
import itertools
import logging
import math
import threading
import time
from dataclasses import dataclass
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import obs
from repro.core.distributed import placement_shardings
from repro.core.prepare import PreparedDesign
from repro.core.spec import SolverSpec, solver_method
from repro.kernels.fused_solve import fused_fits
from repro.resilience import faults, ladder
from repro.serve.batching import (group_requests, next_pow2, pad_x, pad_y,
                                  prepare_request, request_bucket)
from repro.serve.cache import DesignCache
from repro.serve.lanes import LaneKey, LanePool, LaneWork, current_lane
from repro.serve.placement import (Placement, PlacementPolicy, ServeMesh,
                                   placement_for_bucket, placement_for_group)
from repro.serve.types import ServedSolve, SolveRequest
from repro.store.store import TileCorruptionError

_log = logging.getLogger(__name__)

# BAK-family methods a store-backed engine rewrites to "bakp_stream" when a
# request's bucket exceeds the device byte budget (spec_for): same
# block-Jacobi mathematics, served through the store's streaming path
# instead of a resident X copy that could never be admitted.
_STREAM_REROUTE = frozenset(
    {"bak", "bakp", "bakp_gram", "bakp_fused", "bak_fused"})


@functools.lru_cache(maxsize=32)
def _transpose_to(sharding: Optional[NamedSharding]):
    """A jitted 2-D transpose whose result lands in ``sharding`` (None:
    on the operand's own device)."""
    return jax.jit(jnp.transpose, out_shardings=sharding)


def _transposed(sharding) -> Optional[NamedSharding]:
    """The sharding of a 2-D mesh array's transpose that keeps every shard
    on the device that holds it, so the transpose moves nothing between
    devices; None for a single-device array."""
    if not isinstance(sharding, NamedSharding):
        return None
    spec = tuple(sharding.spec) + (None,) * (2 - len(sharding.spec))
    return NamedSharding(sharding.mesh, P(spec[1], spec[0]))


def _request_rows(residual) -> np.ndarray:
    """A group's (obs_p, k_pad) residual on the host as request-major
    (k_pad, obs_p) rows, transposed on the device where each shard lies:
    each request's residual is then one contiguous row."""
    return np.asarray(_transpose_to(_transposed(residual.sharding))(residual))


class _HostTime:
    """The host time one solve unit spends around its solver call, for its
    requests' telemetry: wall seconds under its ``engine.build`` and
    ``engine.fetch`` spans."""

    __slots__ = ("build_s", "fetch_s")

    def __init__(self, build_s: float = 0.0):
        self.build_s = build_s
        self.fetch_s = 0.0

    def unit(self) -> "_HostTime":
        """A unit's account, starting from its flush's build."""
        return _HostTime(self.build_s)

    def build(self):
        return self._phase("engine.build", "build_s")

    def fetch(self):
        return self._phase("engine.fetch", "fetch_s")

    @contextlib.contextmanager
    def _phase(self, name: str, field: str):
        with obs.span(name) as rec:
            yield
        if rec is not None:
            setattr(self, field, getattr(self, field) + rec.duration_s)

    def stamp(self, served: Sequence[ServedSolve]) -> None:
        """Write the unit's times into each result's telemetry."""
        for r in served:
            if r.telemetry is not None:
                r.telemetry.build_s = self.build_s
                r.telemetry.fetch_s = self.fetch_s


@dataclass
class ServeConfig:
    """Engine-level knobs (per-request solver knobs live on SolveRequest)."""

    omega: float = 1.0
    ridge: float = 1e-6
    min_obs: int = 8
    min_vars: int = 8
    coalesce: bool = True        # same-design requests → one multi-RHS solve
    vmap_batch: bool = True      # same-bucket singles → one vmapped solve
    max_vmap_batch: int = 64     # cap on vmapped batch size (memory bound)
    cache_entries: int = 64      # LRU design-cache capacity
    warm_cache: bool = True      # retain per-tenant coefs for warm starts
    warm_tenants: int = 64       # per-design LRU cap on retained tenants
    prefer_fused: bool = False   # upgrade "bakp" requests to the fused
    # whole-solve megakernel ("bakp_fused") when the bucket fits VMEM.
    # Same algorithm/results; trades cross-design vmap batching for the
    # fused kernel's one-launch solves, so it pays off on coalescing-heavy
    # (repeated-design) traffic.  Mesh engines keep "bakp" (the fused
    # kernel is single-device; upgrading would defeat sharded placement).
    placement_policy: Optional[PlacementPolicy] = None  # None → defaults
    omega_2d: float = 0.5        # damping for the 2-D mesh placement (its
    # cross-device Jacobi block is D·thr wide — see core.distributed)
    precision: Optional[str] = None  # engine-level X-stream precision policy
    # ("bf16"/"bf16_fp32acc"): applied to legacy per-field requests exactly
    # like omega/ridge (an explicit SolveRequest.spec stays authoritative).
    # Requests whose effective method lacks the precision downgrade to
    # "fp32" with a solver_fallback_total{reason="precision"} count instead
    # of erroring their batch (see spec_for).
    lane_execution: bool = True  # run flush batches on per-placement
    # execution lanes (repro.serve.lanes) so single-device xla/fused and
    # mesh-sharded solves overlap.  False collapses every lane onto ONE
    # serial executor thread — the pre-lane architecture, kept as the
    # benchmark baseline and a conservative fallback.  Results are
    # bit-identical either way (batch composition and per-batch execution
    # are unchanged; only cross-batch overlap differs).
    store_device_bytes: Optional[int] = None  # device-tier byte budget for
    # the design store (repro.store).  With any store_* knob set, the
    # design cache becomes a view over a tiered DesignStore: eviction
    # demotes (device → host RAM → disk) instead of deleting, demoted
    # designs promote back with warm-start/Cholesky state intact, and
    # requests whose bucket exceeds this budget are rewritten to the
    # streaming "bakp_stream" method (counted as solver_fallback_total
    # {reason="over_hbm"}).  All three None (default) = no store; behaviour
    # and results are bit-identical to the plain LRU cache.
    store_host_bytes: Optional[int] = None    # host-tier budget; overflow
    # spills LRU host snapshots to disk (or drops X bytes, state kept,
    # when store_dir is unset)
    store_dir: Optional[str] = None           # disk-tier directory for the
    # memmapped design tile files; None disables the disk tier
    fault_plan: Optional[object] = None  # chaos harness (repro.resilience):
    # a FaultPlan, a {site: rule} dict, inline JSON text or a JSON file
    # path.  Installed process-wide at engine construction; None (default)
    # leaves injection disarmed — the hooks are a single None-check, so
    # behaviour is bit-identical to a build without them.
    retry_ladder: bool = True    # retry failed/diverged solves down the
    # capability-aware degradation ladder (repro.resilience.ladder): cold
    # restart when a warm start is implicated, fp32 when reduced precision
    # is, then MethodEntry.fallback hops (fused → persweep → stream →
    # lstsq).  False restores the pre-ladder behaviour: first error fails
    # the batch.
    max_retries: int = 3         # ladder steps per request (not per rung)
    retry_backoff_s: float = 0.002  # jittered exponential backoff base
    # between ladder steps; 0 disables the sleep (tests)
    lane_max_restarts: int = 3   # consecutive lane worker-thread deaths
    # before that lane's circuit breaker trips and its work reroutes to
    # the serial fallback executor (repro.serve.lanes)


@dataclass
class ServeStats:
    """Per-engine counters.

    A convenience view: the same events stream into the engine's
    ``repro.obs`` ``MetricsRegistry`` (``serve_*`` families, richer —
    labelled by method/kernel path/placement and with latency and sweep
    histograms the plain ints here cannot carry), which is what the
    exporters read.  These fields stay per-instance ints so multiple
    engines in one process (tests, benchmarks) keep independent tallies
    with zero-cost reads.
    """

    requests: int = 0
    solver_calls: int = 0
    multi_rhs_groups: int = 0
    multi_rhs_requests: int = 0
    vmap_batches: int = 0
    vmap_requests: int = 0
    single_solves: int = 0
    warm_starts: int = 0
    failures: int = 0
    sharded_solves: int = 0      # solver calls routed to a mesh placement
    retries: int = 0             # retry-ladder steps taken (all reasons)

    def as_dict(self) -> dict:
        return dict(self.__dict__)


@functools.lru_cache(maxsize=32)
def _vmapped_solver(spec: SolverSpec, warm: bool):
    """jit(vmap(...)) batch solver for one static solver config.

    ``spec`` must be canonical with ``atol`` zeroed (the engine passes
    ``spec.canonical().replace(atol=0.0)``): ``atol`` is a *traced
    per-element* argument, not part of the cache key — requests in one
    bucket can have different real obs, so each gets its own
    padding-corrected absolute tolerance without recompiling.  The
    per-system callable comes from the method's registry entry
    (``MethodEntry.vmap_one``), so registered backends become batchable by
    providing one.  Module-level lru_cache keeps the function object (and
    therefore the jit compile cache) stable across engine instances and
    flushes; the bounded maxsize caps memory when tenants send many
    distinct knob combinations.  ``warm`` selects the variant that threads
    a batched ``a0`` through — kept out of the cold signature so all-cold
    batches keep their original program.
    """
    entry = solver_method(spec.method)
    if entry.vmap_one is None:
        raise ValueError(f"method {spec.method!r} is not vmap-batchable")
    one = entry.vmap_one(spec)
    if warm:
        return jax.jit(jax.vmap(one))
    return jax.jit(jax.vmap(functools.partial(one, a0=None)))


class SolverServeEngine:
    """Multi-tenant batched serving front-end for the BAK solver family.

    ``mesh`` (optional) is a ``repro.serve.placement.ServeMesh`` (or a raw
    ``jax.sharding.Mesh``, wrapped with its first axis as data); with one,
    the placement policy routes big buckets/groups onto the mesh-sharded
    solvers.  Without one (default) every solve is single-device, exactly
    as before.
    """

    def __init__(self, config: Optional[ServeConfig] = None, mesh=None,
                 registry: Optional[obs.MetricsRegistry] = None):
        self.config = config or ServeConfig()
        if mesh is not None and not isinstance(mesh, ServeMesh):
            axes = tuple(mesh.axis_names)
            model = "model" if "model" in axes and len(axes) > 1 else None
            data = tuple(a for a in axes if a != model)
            mesh = ServeMesh(mesh=mesh, data_axes=data, model_axis=model)
        self.mesh: Optional[ServeMesh] = mesh
        self.policy = self.config.placement_policy or PlacementPolicy()
        # One registry for the whole serving stack: the cache and (in the
        # async path) the dispatcher record into this same instance, so one
        # exporter snapshot covers intake → cache → solve.  Defaults to the
        # process-global registry; pass a fresh MetricsRegistry to isolate
        # (benchmarks comparing engine variants do).
        self.registry = registry or obs.default_registry()
        cfg = self.config
        if cfg.fault_plan is not None:
            # Chaos harness: arm the process-wide plan.  Engines without
            # one never touch the module global, so a fresh engine does not
            # disarm a plan a test installed directly.
            faults.install(faults.FaultPlan.coerce(cfg.fault_plan))
        if (cfg.store_device_bytes is not None
                or cfg.store_host_bytes is not None
                or cfg.store_dir is not None):
            from repro.store import DesignStore
            self.store = DesignStore(device_bytes=cfg.store_device_bytes,
                                     host_bytes=cfg.store_host_bytes,
                                     disk_dir=cfg.store_dir,
                                     max_entries=cfg.cache_entries,
                                     registry=self.registry)
        else:
            self.store = None
        self.cache = DesignCache(max_entries=self.config.cache_entries,
                                 max_tenants=self.config.warm_tenants,
                                 registry=self.registry,
                                 store=self.store)
        # The engine owns its lane pool: the synchronous flush and the
        # async dispatcher submit into the same executors, so per-lane
        # program affinity (and the per-lane gauges) cover both paths.
        self.lanes = LanePool(registry=self.registry,
                              serial=not self.config.lane_execution,
                              max_restarts=self.config.lane_max_restarts)
        # Work units on different lanes mutate ServeStats concurrently.
        self._stats_lock = threading.Lock()
        self._warned_unshardable_fused = False
        self.stats = ServeStats()
        reg = self.registry
        self._m_requests = reg.counter(
            "serve_requests_total", "requests accepted into flush windows")
        self._m_solves = reg.counter(
            "serve_solves_total",
            "solver calls by batch kind / method / kernel path / placement")
        self._m_served = reg.counter(
            "serve_requests_served_total",
            "requests answered, by batch kind and warm/cold start")
        self._m_errors = reg.counter(
            "serve_errors_total",
            "requests failed, by exception type / method / bucket")
        self._m_latency = reg.histogram(
            "serve_solve_latency_seconds",
            "wall time of one batched solver call (kernel path, X-stream "
            "precision and execution lane labelled)",
            buckets=obs.LATENCY_BUCKETS)
        # Same family the eager dispatch shims (obs.record_dispatch) feed —
        # the engine's precision downgrade is one more fallback cause, and
        # sharing the family keeps one dashboard query covering both.
        self._m_fallback = reg.counter(
            "solver_fallback_total",
            "solves re-routed off their requested kernel path")
        self._m_retries = reg.counter(
            "solver_retries_total",
            "retry-ladder steps taken, by reason and from/to rung")
        self._m_sweeps = reg.histogram(
            "serve_sweeps",
            "solver sweeps per request (warm label isolates warm-start "
            "savings)", buckets=obs.COUNT_BUCKETS)
        self._m_group = reg.histogram(
            "serve_group_size", "requests per solver call, by batch kind",
            buckets=obs.COUNT_BUCKETS)
        # Bound-series children for the hot label combos: the per-request
        # and per-solve record sites run on the flush path, and rebuilding
        # a sorted label key every call is measurable there.  Only a
        # handful of combos exist, so the caches stay tiny.
        self._c_served: dict = {}
        self._c_sweeps: dict = {}
        self._c_solve: dict = {}
        self._pending: List[SolveRequest] = []
        # Atomic id source: serve() runs concurrently on lane threads (the
        # async dispatcher), and ``itertools.count`` advances under the GIL
        # so ids never duplicate.
        self._seq = itertools.count()

    def placement_for(self, bucket, method: str) -> Optional[Placement]:
        """Bucket-level placement (None when the engine has no mesh, so
        mesh-less grouping keys stay identical to the pre-placement ones)."""
        if self.mesh is None:
            return None
        return placement_for_bucket(bucket, method, self.policy, self.mesh)

    def spec_for(self, req: SolveRequest, *, record: bool = False
                 ) -> SolverSpec:
        """The effective ``SolverSpec`` a request solves under.

        An explicit ``SolveRequest.spec`` is authoritative; legacy
        per-field requests get the engine-level ``omega``/``ridge``/
        ``precision`` (``ServeConfig``) applied, preserving the pre-spec
        behaviour where those knobs were engine configuration.

        A precision the effective method cannot run (``MethodEntry.
        precisions``) downgrades to "fp32" here — the engine serves the
        request at full precision rather than erroring its whole batch —
        counting ``solver_fallback_total{reason="precision"}``.  The count
        fires only under ``record=True``: ``spec_for`` runs several times
        per request on the flush path (grouping, then each solve body), and
        only the grouping pass (``_flush``'s ``spec_fn``) is once-per-
        request.
        """
        spec = req.solver_spec()
        if req.spec is None:
            spec = spec.replace(omega=self.config.omega,
                                ridge=self.config.ridge)
            if (self.config.precision is not None
                    and spec.precision != self.config.precision):
                spec = spec.replace(precision=self.config.precision)
        # Over-HBM rewrite (store engines): a bucket whose padded X alone
        # exceeds the device byte budget can never be served resident — the
        # store builds it as a non-resident streaming handle — so reroute
        # the BAK-family request to the streaming method up front (same
        # block-Jacobi algorithm; parity-tested against "bakp"), before
        # prefer_fused could upgrade it onto a resident-only path.
        if (self.store is not None and self.store.device_bytes is not None
                and spec.method in _STREAM_REROUTE):
            bucket = request_bucket(req, min_obs=self.config.min_obs,
                                    min_vars=self.config.min_vars)
            if bucket[0] * bucket[1] * 4 > self.store.device_bytes:
                if record:
                    self._m_fallback.inc(1, method=spec.method,
                                         reason="over_hbm")
                spec = spec.replace(method="bakp_stream")
        # The bf16 X stream halves the resident itemsize, so the fit check
        # (and therefore the upgrade) sees twice the VMEM headroom.
        itemsize = 2 if spec.precision != "fp32" else 4
        if (self.config.prefer_fused and spec.method == "bakp"
                and spec.max_iter >= 1):
            if self.mesh is not None:
                # The fused megakernel is single-device; upgrading on a
                # mesh engine would defeat sharded placement, so "bakp"
                # stays — but audibly: the skip counts as a fallback and
                # logs once, instead of the prefer_fused knob silently
                # doing nothing.
                if record:
                    self._m_fallback.inc(1, method="bakp_fused",
                                         reason="unshardable_fused")
                    if not self._warned_unshardable_fused:
                        self._warned_unshardable_fused = True
                        _log.warning(
                            "prefer_fused is a no-op on this mesh engine: "
                            "the fused megakernel is single-device, so "
                            "'bakp' requests keep their sharded-eligible "
                            "method (counted as solver_fallback_total"
                            "{reason=\"unshardable_fused\"})")
            else:
                # Fused eligibility mirrors the method's own dispatch check
                # (nrhs estimated at 1 — the method kernel re-checks with
                # the real coalesced k and falls back when it grew past the
                # budget, so the upgrade is always safe).
                bucket = request_bucket(req, min_obs=self.config.min_obs,
                                        min_vars=self.config.min_vars)
                vars_pb = -(-bucket[1] // spec.thr) * spec.thr
                if fused_fits(vars_pb, bucket[0], 1, itemsize,
                              max_iter=spec.max_iter):
                    spec = spec.replace(method="bakp_fused")
        if (spec.precision != "fp32"
                and spec.precision not in
                solver_method(spec.method).precisions):
            if record:
                self._m_fallback.inc(1, method=spec.method,
                                     reason="precision")
            spec = spec.replace(precision="fp32")
        return spec

    # ------------------------------------------------------------- intake
    def _intake(self, request: SolveRequest) -> str:
        """Normalise one request and assign its id (if absent).

        ``x``/``y``/``a0`` are normalised to host numpy here, once — every
        later ``np.asarray`` in the flush path is then a free view, even
        when the caller handed us device arrays.
        """
        prepare_request(request)
        if request.request_id is None:
            request.request_id = f"req-{next(self._seq)}"
        return request.request_id

    def submit(self, request: SolveRequest) -> str:
        """Queue a request for the next flush(); returns its id.

        submit()/flush() are a single-caller API: the shared pending list
        is deliberately unlocked.  Concurrent callers (the dispatcher's
        lane threads) must use serve(), which never touches it.
        """
        rid = self._intake(request)
        self._pending.append(request)
        return rid

    def serve(self, requests: Sequence[SolveRequest]) -> List[ServedSolve]:
        """Solve ``requests`` in one flush window; results in order.

        Thread-safe: the batch stays local to this call — it never passes
        through the shared submit()/flush() intake — so overlapping
        serve() calls from different lane threads cannot steal each
        other's requests.
        """
        batch = list(requests)
        for r in batch:
            self._intake(r)
        return self._serve(batch)

    # -------------------------------------------------------------- flush
    def flush(self) -> List[ServedSolve]:
        """Execute all pending requests; results in submission order.

        Exception-safe: a solver failure poisons only its own batch — the
        affected requests get error results and every other batch still
        runs, so the returned list always covers all pending requests.
        """
        requests, self._pending = self._pending, []
        return self._serve(requests)

    def _serve(self, requests: List[SolveRequest]) -> List[ServedSolve]:
        if not requests:
            return []
        with self._stats_lock:
            self.stats.requests += len(requests)
        self._m_requests.inc(len(requests))
        with obs.span("engine.flush", requests=len(requests)):
            return self._flush(requests)

    def _flush(self, requests: List[SolveRequest]) -> List[ServedSolve]:
        """Pure batch-builder: grouping, design-cache lookups and lane
        routing happen here on the calling thread (``engine.build``); the
        actual solves are work units submitted to the engine's lane pool
        (``_run_units``), so batches bound for different lanes
        (single-device xla/fused vs each mesh placement) overlap instead of
        serialising."""
        results: List[Optional[ServedSolve]] = [None] * len(requests)
        flush_time = _HostTime()
        with flush_time.build():
            units = self._build_units(requests, results, flush_time)
        self._run_units(units, requests, results)
        assert all(r is not None for r in results)
        return results

    def _build_units(self, requests, results, flush_time):
        """Group ``requests`` and resolve their design entries into solve
        units for ``_run_units``: (lane, size, run, fail_idxs, bucket) —
        the last two let ``_run_units`` fail a unit's unanswered requests
        when the unit never ran to completion (lane worker-thread death /
        shutdown)."""
        units: List[Tuple[LaneKey, int, object, List[int], tuple]] = []
        cfg = self.config

        def unit(lane, fail_idxs, bucket, size, fn):
            # Exception isolation rides inside the unit: a solver failure
            # poisons only its own batch, exactly as the inline path did.
            def run(fn=fn, fail_idxs=fail_idxs, bucket=bucket):
                try:
                    fn()
                except Exception as exc:
                    self._fail(requests, fail_idxs, bucket, exc, results)
            units.append((lane, size, run, fail_idxs, bucket))

        groups = group_requests(
            requests, min_obs=cfg.min_obs, min_vars=cfg.min_vars,
            placement_fn=self.placement_for,
            # The grouping pass is the once-per-request spec resolution, so
            # it is where a precision downgrade gets counted.
            spec_fn=lambda r: self.spec_for(r, record=True))
        for outer, designs in groups.items():
            bucket = outer[0]
            method = outer[1]
            mentry = solver_method(method)
            placement = self.placement_for(bucket, method)
            singles = []  # (idx, entry, cache_hit, design_key)
            for key, idxs in designs.items():
                try:
                    entry, hit = self._design_entry(key, requests[idxs[0]],
                                                    bucket, placement)
                except Exception as exc:  # bad design: fail just this group
                    self._fail(requests, idxs, bucket, exc, results)
                    continue
                if cfg.coalesce and len(idxs) > 1 and mentry.multi_rhs:
                    # The k-sharded group upgrade is decided here (k is
                    # known after coalescing) so the unit routes to its
                    # real lane, not the bucket's.
                    gplacement = placement
                    if self.mesh is not None and mentry.shardable:
                        gplacement = placement_for_group(
                            placement or Placement(), next_pow2(len(idxs)),
                            self.policy, self.mesh)
                    unit(self.lanes.lane_for(method, gplacement, self.mesh),
                         idxs, bucket, len(idxs),
                         functools.partial(self._solve_multi_rhs, requests,
                                           idxs, entry, hit, bucket,
                                           results, gplacement, key,
                                           flush_time))
                else:
                    singles.extend((i, entry, hit, key) for i in idxs)
            # vmap batching is single-device only (a vmapped shard_map would
            # nest meshes); sharded buckets solve leftovers individually.
            use_vmap = (cfg.vmap_batch and len(singles) > 1
                        and mentry.batchable
                        and (placement is None or not placement.sharded))
            if use_vmap:
                for lo in range(0, len(singles), cfg.max_vmap_batch):
                    chunk = singles[lo:lo + cfg.max_vmap_batch]
                    if len(chunk) > 1:
                        # The vmapped program is a single-device stack —
                        # it rides the method's single-device lane.
                        unit(self.lanes.lane_for(method),
                             [i for i, _, _, _ in chunk], bucket,
                             len(chunk),
                             functools.partial(self._solve_vmapped,
                                               requests, chunk, bucket,
                                               results, flush_time))
                    else:
                        idx, entry, hit, key = chunk[0]
                        unit(self.lanes.lane_for(method, placement,
                                                 self.mesh),
                             [idx], bucket, 1,
                             functools.partial(self._solve_one, requests,
                                               idx, entry, hit, bucket,
                                               results, placement, key,
                                               flush_time))
            else:
                for idx, entry, hit, key in singles:
                    unit(self.lanes.lane_for(method, placement, self.mesh),
                         [idx], bucket, 1,
                         functools.partial(self._solve_one, requests, idx,
                                           entry, hit, bucket, results,
                                           placement, key, flush_time))
        return units

    def _run_units(self, units, requests, results) -> None:
        """Execute flush work units on their lanes and wait for all.

        Nested flushes (``serve``/``flush`` called from a lane work — the
        dispatcher's per-batch submission path) run inline on the current
        lane thread: the batch was already routed to its lane, and
        re-submitting from inside a lane could deadlock a lane on itself.

        Units swallow solver errors via ``_fail``; a work coming back with
        ``error`` set means the unit never completed — lane worker-thread
        death (``LaneWorkerDeath``) or a shutdown race.  Its unanswered
        requests get error results and the flush still returns a full
        result list: the engine keeps serving through a dying lane.
        """
        if not units:
            return
        if current_lane() is not None:
            for _, _, fn, _, _ in units:
                fn()
            return
        works = [self.lanes.submit(lane, LaneWork(fn, size=size,
                                                  tag=lane.label))
                 for lane, size, fn, _, _ in units]
        for w in works:
            w.wait()
        for w, (_, _, _, fail_idxs, bucket) in zip(works, units):
            if w.error is not None:
                missing = [i for i in fail_idxs if results[i] is None]
                if missing:
                    self._fail(requests, missing, bucket, w.error, results)

    def shutdown(self, drain: bool = True) -> None:
        """Stop the engine's lane executor threads (idempotent; the engine
        keeps working afterwards only via fresh lane threads on the next
        flush, so call this at teardown)."""
        self.lanes.shutdown(drain=drain)

    # ---------------------------------------------------------- internals
    def _design_entry(self, key, req, bucket, placement=None):
        return self.cache.get_or_build(
            key, lambda: pad_x(np.asarray(req.x), bucket),
            placement=placement, mesh=self.mesh)

    def _fail(self, requests, idxs, bucket, exc, results):
        """Error results for a poisoned batch (engine keeps serving).

        Failures are structured, not just stringly: each request bumps
        ``serve_errors_total{exception_type,method,bucket}`` and carries a
        telemetry record naming the failing bucket/method, so a poisoned
        batch is diagnosable from a metrics scrape alone.
        """
        exc_type = type(exc).__name__
        msg = f"{exc_type}: {exc}"
        obs.consume_dispatch()  # drop any path a partial dispatch recorded
        for idx in idxs:
            req = requests[idx]
            n_obs, nvars = np.asarray(req.x).shape
            tel = None
            if obs.enabled():
                tel = obs.SolveTelemetry(
                    request_id=req.request_id, tenant_id=req.tenant_id,
                    bucket=bucket, method=req.method, kernel_path="none",
                    batch_kind="error", group_size=len(idxs),
                    batch_size=len(idxs), error_type=exc_type)
            results[idx] = ServedSolve(
                request_id=req.request_id,
                coef=np.zeros((nvars,), np.float32),
                residual=np.asarray(req.y, np.float32).copy(),
                sse=float(np.dot(req.y, req.y)),
                n_sweeps=0,
                converged=False,
                bucket=bucket,
                batch_kind="error",
                group_size=len(idxs),
                error=msg,
                telemetry=tel,
            )
            with self._stats_lock:
                self.stats.failures += 1
            self._m_errors.inc(1, exception_type=exc_type,
                               method=req.method,
                               bucket=f"{bucket[0]}x{bucket[1]}")

    def _resolve_a0(self, req: SolveRequest, entry: PreparedDesign):
        """Warm-start coefficients for a request: explicit ``a0`` wins,
        then the design handle's per-tenant store; None means cold."""
        if req.a0 is not None:
            return np.asarray(req.a0, np.float32)
        if self.config.warm_cache:
            return entry.warm_coef(req.tenant_id)
        return None

    @staticmethod
    def _pad_a0(a0: np.ndarray, vars_p: int) -> np.ndarray:
        """Zero-pad (vars,) warm-start coefficients to the bucket width.

        Zero entries for padded columns are exact: those columns are zero,
        so their coefficients stay pinned at 0 either way.
        """
        if a0.shape[0] == vars_p:
            return a0
        out = np.zeros((vars_p,), np.float32)
        out[: a0.shape[0]] = a0
        return out

    @staticmethod
    def _padded_atol(atol: float, n_real: int, n_padded: int) -> float:
        """Correct an absolute RMSE tolerance for zero padding.

        The solvers compare total SSE against ``n_padded * atol²``, but only
        ``n_real`` of those elements carry signal (padding rows/RHS hold
        exactly zero residual), so the raw threshold would be inflated by
        n_padded/n_real.  Scaling atol by sqrt(n_real/n_padded) makes the
        padded criterion equal the unpadded one.  ``rtol`` needs no
        correction (padding contributes 0 to both sides of the ratio).
        """
        if atol <= 0.0 or n_real == n_padded:
            return atol
        return atol * math.sqrt(n_real / n_padded)

    def _call_solver(self, spec: SolverSpec, entry: PreparedDesign, y_dev,
                     atol: float, a0=None, placement=None, donate=None):
        """One (possibly multi-RHS) solve on the prepared design.

        Everything dispatches through ``PreparedDesign.solve`` — the
        engine's only job here is the serving-side corrections: ``atol`` is
        the padding-corrected absolute tolerance (see ``_padded_atol``;
        ``spec.atol`` itself must not be used), and a 2-D mesh placement
        gets the engine's ``omega_2d`` damping (its cross-device Jacobi
        block is D·thr wide).  ``a0`` is the bucket-padded warm start (or
        None for the cold program — a separate jit signature, so cold
        solves don't pay the warm path's extra residual matmul).
        ``donate`` goes to ``PreparedDesign.solve`` as is.
        """
        eff = spec.replace(atol=atol)
        if placement is not None and placement.kind == "mesh_2d":
            eff = eff.replace(omega=self.config.omega_2d)
        where = (f"{placement.kind}/" if placement is not None
                 and placement.sharded else "")
        with obs.span(f"solve/{where}{eff.method}"):
            return entry.solve(y_dev, a0, spec=eff, placement=placement,
                               mesh=self.mesh, donate=donate)

    # ------------------------------------------------------- retry ladder
    @staticmethod
    def _rung_label(spec: SolverSpec, warm: bool = False) -> str:
        """Metrics label for one ladder rung: method, ':<precision>' when
        reduced, '+warm' when warm-started."""
        lbl = spec.method
        if spec.precision != "fp32":
            lbl += f":{spec.precision}"
        if warm:
            lbl += "+warm"
        return lbl

    @staticmethod
    def _diverged(res, sse0: Optional[float] = None) -> bool:
        """Whether a completed solve net-diverged (see
        ``core.types.warm_retention_ok`` for the history semantics): not
        converged AND the recorded SSE rose materially above its own start
        — or above the caller-supplied cold baseline ``sse0`` (= |y|², the
        SSE of the zero solution), which catches a warm start that blew up
        from its very first sweep."""
        try:
            conv = np.asarray(res.converged)
            if conv.ndim != 0 or bool(conv):
                return False
            h = np.asarray(res.history, np.float32).ravel()
            h = h[np.isfinite(h)]
            if h.size == 0:
                return False
            if h.size >= 2 and float(h[-1]) > 1.01 * float(h[0]):
                return True
            if sse0 is not None and float(h[-1]) > 1.01 * sse0:
                return True
        except Exception:
            return False
        return False

    @staticmethod
    def _is_corruption(exc: BaseException) -> bool:
        """Did this solve die because the design's store tier is damaged?
        (Quarantine already happened inside the store; the ladder's job is
        to rebuild the entry from the request's ``x`` and retry.)"""
        if isinstance(exc, TileCorruptionError):
            return True
        return isinstance(exc, KeyError) and "store tier" in str(exc)

    def _rung_ok(self, spec: SolverSpec, entry, need_multi: bool) -> bool:
        """Can this entry/batch actually run on the given rung?"""
        m = solver_method(spec.method)
        if entry.x_pad is None and not m.streams:
            return False  # non-resident design: streaming rungs only
        if need_multi and not m.multi_rhs:
            return False  # coalesced (obs, k) batch stays coalesced
        return True

    def _attempt_solve(self, spec: SolverSpec, entry, y_for, atol: float,
                       a0, placement, host: _HostTime, *,
                       deadline_at: Optional[float] = None,
                       rebuild=None, sse0: Optional[float] = None,
                       need_multi: bool = False, donate=None):
        """One solve with the retry/degradation ladder wrapped around it.

        Runs ``_call_solver`` and retries on a raised exception or a
        *diverged* result, stepping down a capability-aware ladder:

          1. store corruption → rebuild the design entry from the request's
             ``x`` (``rebuild``) and retry the SAME rung;
          2. warm start present → cold retry on the same rung (a poisoned
             ``a0`` is the usual suspect);
          3. reduced precision → fp32, same method;
          4. ``MethodEntry.fallback`` hops (fused → persweep → stream →
             lstsq), skipping rungs the entry/batch cannot run
             (``_rung_ok``); a method change drops the mesh placement (the
             fallback method may not be shardable).

        Bounded by ``ServeConfig.max_retries``, the request deadline
        (``deadline_at``, obs.now() clock) and the ladder floor; each step
        sleeps a jittered exponential backoff and counts
        ``solver_retries_total{reason,from_path,to_path}``.  When the
        ladder is exhausted the last exception re-raises (→ ``_fail``) or
        the last diverged result returns as-is (flagged so ``_strip``
        skips warm retention).

        ``y_for(placement)`` gives each attempt's ``y`` for that attempt's
        placement, so a rung that drops the mesh placement never receives
        a mesh-sharded operand; with ``donate`` each attempt's solve may
        consume it, so ``y_for`` makes a consumed one anew.  Each attempt's
        divergence check reads the result back to the host, so it counts as
        fetch time (``host``), not solve time.

        Returns ``(res, spec, entry, placement, retries, diverged,
        a0_used)`` — the rung that finally served, so the caller records
        the method/path that actually ran.
        """
        cfg = self.config
        cur, cur_entry, cur_a0, cur_place = spec, entry, a0, placement
        retries = 0
        while True:
            exc = None
            res = None
            try:
                faults.maybe_raise("solver.raise", cur.method)
                res = self._call_solver(cur, cur_entry, y_for(cur_place),
                                        atol, a0=cur_a0, placement=cur_place,
                                        donate=donate)
                jax.block_until_ready(res.coef)
            except Exception as e:
                exc = e
            with host.fetch():
                forced = (exc is None
                          and faults.hit("solver.diverge", cur.method)
                          is not None)
                diverged = forced or (exc is None and self._diverged(res, sse0))
            if exc is None and not diverged:
                return (res, cur, cur_entry, cur_place, retries, False,
                        cur_a0)
            out_of_time = (deadline_at is not None
                           and obs.now() >= deadline_at)
            if (not cfg.retry_ladder or retries >= cfg.max_retries
                    or out_of_time):
                if exc is not None:
                    raise exc
                return (res, cur, cur_entry, cur_place, retries, True,
                        cur_a0)
            # Pick the next rung (the first applicable recovery, in order).
            frm = self._rung_label(cur, cur_a0 is not None)
            if (exc is not None and self._is_corruption(exc)
                    and rebuild is not None):
                reason, nxt = "corruption", cur
                try:
                    cur_entry = rebuild()
                except Exception:
                    raise exc  # design is gone for good — report the solve
            elif cur_a0 is not None:
                reason, nxt, cur_a0 = "warm_poison", cur, None
            else:
                reason = "raise" if exc is not None else (
                    "forced_diverge" if forced else "diverge")
                nxt = ladder.next_rung(cur)
                while nxt is not None and not self._rung_ok(
                        nxt, cur_entry, need_multi):
                    nxt = ladder.next_rung(nxt)
                if nxt is None:  # ladder floor reached
                    if exc is not None:
                        raise exc
                    return (res, cur, cur_entry, cur_place, retries, True,
                            cur_a0)
                if nxt.method != cur.method:
                    cur_place = None  # fallback may not be shardable
            retries += 1
            to = self._rung_label(nxt, cur_a0 is not None)
            self._m_retries.inc(1, reason=reason, from_path=frm,
                                to_path=to)
            # The counter has no room for the cause; the log keeps it.
            _log.warning("solve on %s failed (%s: %s); retrying on %s", frm,
                         reason, exc if exc is not None else "diverged", to)
            with self._stats_lock:
                self.stats.retries += 1
            delay = ladder.backoff_s(retries - 1, cfg.retry_backoff_s)
            if delay > 0.0:
                if deadline_at is not None:
                    delay = min(delay, max(0.0, deadline_at - obs.now()))
                time.sleep(delay)
            cur = nxt

    def _record_solve(self, spec: SolverSpec, placement, kind: str,
                      group_size: int, dt: float, path=None) -> str:
        """Record one solver call's metrics; returns the kernel path that
        actually executed.

        The path comes off the thread-local relay the eager dispatch shims
        filled (``obs.record_dispatch`` in ``repro.core.methods`` /
        ``repro.kernels.ops``) — a ``bakp_fused`` request that outgrew VMEM
        reports "xla" here, not what the spec asked for.  ``path`` forces
        it where the engine knows better (the vmapped batch program).
        """
        if path is None:
            path = obs.consume_dispatch(
                "sharded" if placement is not None and placement.sharded
                else "xla")
        if obs.enabled():
            placement_kind = (placement.kind if placement is not None
                              else "single")
            lk = current_lane()
            lane = lk.label if lk is not None else "inline"
            ck = (kind, spec.method, path, placement_kind, spec.precision,
                  lane)
            bound = self._c_solve.get(ck)
            if bound is None:
                bound = self._c_solve[ck] = (
                    self._m_solves.labels(kind=kind, method=spec.method,
                                          path=path,
                                          placement=placement_kind),
                    self._m_latency.labels(kind=kind, method=spec.method,
                                           path=path,
                                           precision=spec.precision,
                                           lane=lane),
                    self._m_group.labels(kind=kind))
            bound[0].inc(1)
            bound[1].observe(dt)
            bound[2].observe(group_size)
        return path

    def _strip(self, req: SolveRequest, coef, residual, *, bucket, kind,
               group_size, latency, hit, n_sweeps, converged, entry=None,
               warm=False, placement=None, method="", path="xla",
               retain_warm=True, retries=0) -> ServedSolve:
        n_obs, nvars = np.asarray(req.x).shape
        coef = np.asarray(coef)[:nvars]
        residual = np.asarray(residual)[:n_obs]
        # ``retain_warm=False`` = the solve diverged: its coefficients are
        # worse than zero, and retaining them would poison the tenant's
        # next warm start into starting from the blown-up point.
        if entry is not None and self.config.warm_cache and retain_warm:
            entry.store_coef(req.tenant_id, coef)
        if warm:
            with self._stats_lock:
                self.stats.warm_starts += 1
        sse = float(np.dot(residual, residual))
        n_sweeps = int(n_sweeps)
        converged = bool(converged)
        placement_kind = placement.kind if placement is not None else "single"
        lk = current_lane()
        lane = lk.label if lk is not None else "inline"
        tel = None
        if obs.enabled():
            warm_lbl = "1" if warm else "0"
            sk = (kind, warm_lbl)
            served_c = self._c_served.get(sk)
            if served_c is None:
                served_c = self._c_served[sk] = self._m_served.labels(
                    kind=kind, warm=warm_lbl)
            sweeps_c = self._c_sweeps.get(warm_lbl)
            if sweeps_c is None:
                sweeps_c = self._c_sweeps[warm_lbl] = self._m_sweeps.labels(
                    warm=warm_lbl)
            served_c.inc(1)
            sweeps_c.observe(n_sweeps)
            tel = obs.SolveTelemetry(
                request_id=req.request_id, tenant_id=req.tenant_id,
                bucket=bucket, method=method or req.method,
                kernel_path=path, placement=placement_kind, lane=lane,
                batch_kind=kind,
                group_size=group_size, batch_size=group_size,
                warm_start=warm, cache_hit=hit, n_sweeps=n_sweeps, sse=sse,
                converged=converged, retries=retries, solve_s=latency)
        return ServedSolve(
            request_id=req.request_id,
            coef=coef,
            residual=residual,
            sse=sse,
            n_sweeps=n_sweeps,
            converged=converged,
            bucket=bucket,
            batch_kind=kind,
            group_size=group_size,
            latency_s=latency,
            cache_hit=hit,
            warm_start=warm,
            placement=placement_kind,
            retries=retries,
            telemetry=tel,
        )

    def _solve_multi_rhs(self, requests, idxs, entry, hit, bucket, results,
                         placement=None, key=None, flush_time=None):
        """Coalesce same-design requests into one (obs, k_pad) solve.

        Warm and cold members coalesce: if any member warm-starts, the
        group solve gets a stacked ``a0`` whose cold columns are zero
        (identical to those members' cold path).

        ``placement`` is final here — the k-sharded group upgrade (one
        stream of ``x`` per device serves k/D tenants, group-global SSE
        stopping) is decided by ``_flush`` at unit-build time, where the
        lane is chosen — except that the retry ladder drops it when a
        fallback rung changes the method (see ``_attempt_solve``).
        """
        host = (flush_time or _HostTime()).unit()
        obs_p, vars_p = bucket
        k = len(idxs)
        k_pad = next_pow2(k)
        req0 = requests[idxs[0]]
        with host.build():
            spec = self.spec_for(req0)
            mentry = solver_method(spec.method)
            # Request-major: each target is one contiguous row, and only
            # the padding is zeroed; the device transposes the group into
            # the solvers' (obs_p, k_pad) layout (``_group_y``).
            ys_t = np.empty((k_pad, obs_p), np.float32)
            ys_t[k:] = 0.0
            sse0 = 0.0
            for c, idx in enumerate(idxs):
                y = np.asarray(requests[idx].y, np.float32)
                ys_t[c, : y.shape[0]] = y
                ys_t[c, y.shape[0]:] = 0.0
                sse0 += float(np.dot(y, y))
            if mentry.iterative:
                a0s = [self._resolve_a0(requests[idx], entry) for idx in idxs]
            else:  # direct methods don't iterate, so warm starts are meaningless
                a0s = [None] * k
            a0_mat = None
            if any(a is not None for a in a0s):
                a0_mat = np.zeros((vars_p, k_pad), np.float32)
                for c, a in enumerate(a0s):
                    if a is not None:
                        a0_mat[:, c] = self._pad_a0(a, vars_p)
            # Same design => same real obs for every member of the group.
            obs_real = np.asarray(req0.x).shape[0]
            atol = self._padded_atol(spec.atol, obs_real * k, obs_p * k_pad)
            deadlines = [requests[i].deadline_at for i in idxs
                         if requests[i].deadline_at is not None]
        rebuild = None
        if key is not None:
            rebuild = lambda: self._design_entry(  # noqa: E731
                key, req0, bucket, placement)[0]
        operands = {}

        def y_for(place):
            # One device operand per placement the ladder tries, made
            # after t0: the transfer belongs to the solve stretch.  The
            # solve consumes it (donated), so a retry makes it anew.
            if place not in operands or operands[place].is_deleted():
                operands[place] = self._group_y(ys_t, place)
            return operands[place]

        t0 = obs.now()
        try:
            res, fspec, fentry, fplace, retries, diverged, a0_used = \
                self._attempt_solve(
                    spec, entry, y_for, atol, a0_mat, placement, host,
                    deadline_at=min(deadlines) if deadlines else None,
                    rebuild=rebuild, sse0=sse0, need_multi=True,
                    donate=True)
        finally:
            # The solve is done with y: free it before the residual's
            # transpose takes a buffer of the same size.
            operands.clear()
        dt = obs.now() - t0 - host.fetch_s
        with host.fetch():
            path = self._record_solve(fspec, fplace, "multi_rhs", k, dt)
            coef = np.asarray(res.coef)
            resid_t = _request_rows(res.residual)
            for c, idx in enumerate(idxs):
                results[idx] = self._strip(
                    requests[idx], coef[:, c], resid_t[c], bucket=bucket,
                    kind="multi_rhs", group_size=k, latency=dt, hit=hit,
                    n_sweeps=res.n_sweeps, converged=res.converged,
                    entry=fentry,
                    warm=a0_used is not None and a0s[c] is not None,
                    placement=fplace, method=fspec.method, path=path,
                    retain_warm=not diverged, retries=retries)
        host.stamp([results[idx] for idx in idxs])
        with self._stats_lock:
            self.stats.solver_calls += 1
            self.stats.multi_rhs_groups += 1
            self.stats.multi_rhs_requests += k
            if fplace is not None and fplace.sharded:
                self.stats.sharded_solves += 1

    def _group_y(self, ys_t: np.ndarray, placement) -> jax.Array:
        """A group's request-major host targets ``ys_t`` (k_pad, obs_p) as
        the (obs_p, k_pad) operand the solvers take, placed for
        ``placement``: the rows go to the devices in the transpose of the
        placement's ``y`` sharding, and each device transposes its own
        slab.  A single-device placement transposes on the default device.
        The transpose is exact, so the solve sees the same values."""
        if placement is None or not placement.sharded:
            return _transpose_to(None)(jnp.asarray(ys_t))
        y_sharding = placement_shardings(placement.kind, self.mesh)[1]
        return _transpose_to(y_sharding)(
            jax.device_put(ys_t, _transposed(y_sharding)))

    def _solve_vmapped(self, requests, singles, bucket, results,
                       flush_time=None):
        """Stack same-bucket single-design requests into one vmapped solve.

        Degradation (retry ladder): a raised vmapped batch is not retried
        as a stack — there is no batched ladder — it degrades to
        per-request ``_solve_one`` calls, each with its own full ladder;
        a member whose own ladder also exhausts fails alone.  Counted as
        ``solver_retries_total{reason=...,from_path="vmap:...",
        to_path="single"}`` once per member.
        """
        try:
            self._solve_vmapped_inner(requests, singles, bucket, results,
                                      flush_time)
            return
        except Exception as exc:
            if not self.config.retry_ladder:
                raise
            spec = self.spec_for(requests[singles[0][0]])
            reason = ("raise" if isinstance(exc, faults.FaultInjected)
                      else type(exc).__name__)
            self._m_retries.inc(len(singles), reason=reason,
                                from_path=f"vmap:{spec.method}",
                                to_path="single")
            with self._stats_lock:
                self.stats.retries += len(singles)
        for idx, entry, hit, key in singles:
            if results[idx] is not None:
                continue
            try:
                self._solve_one(requests, idx, entry, hit, bucket, results,
                                None, key, flush_time)
            except Exception as exc:
                self._fail(requests, [idx], bucket, exc, results)

    def _solve_vmapped_inner(self, requests, singles, bucket, results,
                             flush_time=None):
        host = (flush_time or _HostTime()).unit()
        obs_p, vars_p = bucket
        req0 = requests[singles[0][0]]
        b = len(singles)
        b_pad = next_pow2(b)
        # Pad the batch by replicating the last system (discarded below) so
        # the vmapped program only ever compiles for power-of-two batches.
        padded = singles + [singles[-1]] * (b_pad - b)
        with host.build():
            spec = self.spec_for(req0)
            mentry = solver_method(spec.method)
            xs = jnp.stack([entry.x_pad for _, entry, _, _ in padded])
            ys = jnp.asarray(np.stack(
                [pad_y(np.asarray(requests[i].y, np.float32), obs_p)
                 for i, _, _, _ in padded]))
            a0s = [self._resolve_a0(requests[i], e) for i, e, _, _ in padded]
            warm = any(a is not None for a in a0s)
            solver = _vmapped_solver(spec.canonical().replace(atol=0.0), warm)
            # Per-element padding-corrected atol (real obs varies within a
            # bucket); traced, so it never forces a recompile.
            atols = jnp.asarray([
                self._padded_atol(spec.atol, np.asarray(requests[i].x).shape[0],
                                  obs_p)
                for i, _, _, _ in padded], dtype=jnp.float32)
            if mentry.blocked:
                cns = jnp.stack(
                    [e.cn_for_thr(spec.thr) for _, e, _, _ in padded])
            else:
                cns = jnp.stack([e.cn for _, e, _, _ in padded])
            if mentry.needs_chol:
                chols = jnp.stack(
                    [e.chol_for(spec.thr, spec.ridge) for _, e, _, _ in padded])
                args = (xs, ys, cns, atols, chols)
            else:
                args = (xs, ys, cns, atols)
            if warm:
                a0_mat = np.zeros((b_pad, vars_p), np.float32)
                for row, a in enumerate(a0s):
                    if a is not None:
                        a0_mat[row] = self._pad_a0(a, vars_p)
                args = args + (jnp.asarray(a0_mat),)
        t0 = obs.now()
        faults.maybe_raise("solver.raise", f"vmap:{spec.method}")
        with obs.span(f"solve/vmap/{spec.method}"):
            res = solver(*args)
            jax.block_until_ready(res.coef)
        dt = obs.now() - t0
        with host.fetch():
            forced = faults.hit("solver.diverge", f"vmap:{spec.method}")
            # The vmapped program is one jit'd stack — the eager dispatch shims
            # never run inside it, so the path is "vmap" by construction.
            obs.consume_dispatch()
            path = self._record_solve(spec, None, "vmap", b, dt, path="vmap")
            coef = np.asarray(res.coef)
            resid = np.asarray(res.residual)
            conv_b = np.asarray(res.converged)
            hist_b = np.asarray(res.history, np.float32)

            def row_retain(row: int) -> bool:
                # Per-row warm retention: the batched analogue of
                # core.types.warm_retention_ok (which is scalar-only).
                if forced is not None:
                    return False
                if bool(conv_b[row]):
                    return True
                h = hist_b[row][np.isfinite(hist_b[row])]
                return not (h.size >= 2 and float(h[-1]) > 1.01 * float(h[0]))

            for row, (idx, entry, hit, _) in enumerate(singles):
                results[idx] = self._strip(
                    requests[idx], coef[row], resid[row], bucket=bucket,
                    kind="vmap", group_size=b, latency=dt, hit=hit,
                    n_sweeps=res.n_sweeps[row], converged=res.converged[row],
                    entry=entry, warm=a0s[row] is not None,
                    method=spec.method, path=path,
                    retain_warm=row_retain(row))
        host.stamp([results[idx] for idx, _, _, _ in singles])
        with self._stats_lock:
            self.stats.solver_calls += 1
            self.stats.vmap_batches += 1
            self.stats.vmap_requests += b

    def _solve_one(self, requests, idx, entry, hit, bucket, results,
                   placement=None, key=None, flush_time=None):
        host = (flush_time or _HostTime()).unit()
        req = requests[idx]
        with host.build():
            spec = self.spec_for(req)
            y_real = np.asarray(req.y, np.float32)
            y_pad = pad_y(y_real, bucket[0])
            atol = self._padded_atol(spec.atol, y_real.shape[0], bucket[0])
            a0 = None
            if solver_method(spec.method).iterative:
                a0 = self._resolve_a0(req, entry)
            a0_pad = None
            if a0 is not None:
                a0_pad = self._pad_a0(a0, bucket[1])
        rebuild = None
        if key is not None:
            rebuild = lambda: self._design_entry(  # noqa: E731
                key, req, bucket, placement)[0]
        t0 = obs.now()
        # Host buffers in: the solver entries donate their fresh in-jit
        # transfers on accelerator backends (see types.donate_default).
        res, fspec, fentry, fplace, retries, diverged, a0_used = \
            self._attempt_solve(spec, entry, lambda place: y_pad, atol,
                                a0_pad, placement, host,
                                deadline_at=req.deadline_at,
                                rebuild=rebuild,
                                sse0=float(np.dot(y_real, y_real)))
        dt = obs.now() - t0 - host.fetch_s
        with host.fetch():
            path = self._record_solve(fspec, fplace, "single", 1, dt)
            results[idx] = self._strip(
                req, res.coef, res.residual, bucket=bucket, kind="single",
                group_size=1, latency=dt, hit=hit, n_sweeps=res.n_sweeps,
                converged=res.converged, entry=fentry,
                warm=a0_used is not None, placement=fplace,
                method=fspec.method, path=path, retain_warm=not diverged,
                retries=retries)
        host.stamp([results[idx]])
        with self._stats_lock:
            self.stats.solver_calls += 1
            self.stats.single_solves += 1
            if fplace is not None and fplace.sharded:
                self.stats.sharded_solves += 1
