"""Solver-serving driver: pump a synthetic multi-tenant request stream
through ``repro.serve`` and report throughput.

Synchronous windows (the original engine-level driver):

    PYTHONPATH=src python -m repro.launch.solver_serve \
        --requests 256 --obs 2048 --vars 256 --designs 8 \
        --method bakp_gram --flush-every 32

Async deadline-aware dispatch (Poisson arrivals through AsyncDispatcher):

    PYTHONPATH=src python -m repro.launch.solver_serve --mode async \
        --requests 256 --rate 200 --deadline-ms 500 --max-batch 16 \
        --tenants 32

Fused-megakernel serving (whole solves on one Pallas launch; oversized
designs fall back to the XLA path automatically):

    PYTHONPATH=src python -m repro.launch.solver_serve \
        --method bakp_fused --requests 256 --designs 8
    # or upgrade eligible 'bakp' requests in place:
    PYTHONPATH=src python -m repro.launch.solver_serve \
        --method bakp --prefer-fused

Mesh-sharded placement (route big buckets / giant same-design groups onto
the sharded SolveBakP backends; on CPU this forces virtual host devices
before jax loads, so it must be a fresh process):

    PYTHONPATH=src python -m repro.launch.solver_serve --mesh 4x2 \
        --requests 256 --obs 2048 --vars 256 --designs 4 \
        --shard-min-cells 65536 --rhs-shard-min-k 32

``--designs D`` controls design-matrix reuse: requests cycle over D distinct
matrices, so every flush window sees same-design groups (coalesced into
multi-RHS solves) and, across windows, warm design-cache hits.  ``--designs``
equal to ``--requests`` gives a worst-case all-unique stream (pure vmap
batching); ``--designs 1`` gives the best case (everything rides one
multi-RHS solve).  ``--tenants T`` tags requests with recurring tenant ids,
so repeated (design, tenant) pairs warm-start from their previous
coefficients; in async mode each request also carries a deadline and the
driver reports the deadline hit rate.
"""
from __future__ import annotations

import argparse
import os
import time

import numpy as np

from repro import obs


def ensure_mesh_devices(spec: str) -> None:
    """Force enough virtual CPU devices for ``spec`` BEFORE jax imports.

    XLA reads ``--xla_force_host_platform_device_count`` at backend init, so
    this only works from a fresh process that has not touched jax yet — which
    is why the driver defers every ``repro.serve`` import into ``main``.  On
    a real accelerator platform (JAX_PLATFORMS set to tpu/gpu) the flag is
    left alone: the mesh uses the physical devices.
    """
    platforms = os.environ.get("JAX_PLATFORMS", "cpu")
    flags = os.environ.get("XLA_FLAGS", "")
    if "cpu" in platforms and "xla_force_host_platform_device_count" not in flags:
        n = 1  # inline product: importing repro.serve here would pull in jax
        for part in spec.lower().split("x"):
            n *= int(part)
        os.environ["XLA_FLAGS"] = \
            f"{flags} --xla_force_host_platform_device_count={n}".strip()


def build_requests(rng, xs, n, method, max_iter, rtol, thr, noise=0.0,
                   tenants=0, deadline_s=None, precision="fp32",
                   refine_sweeps=None):
    """Requests cycling over the shared design matrices ``xs``.

    ``design_key`` is trusted identity — it must only be reused for the SAME
    matrix, which is why the designs are drawn once and shared between the
    warmup and the timed stream.
    """
    from repro.serve import SolveRequest, SolverSpec

    kw = {} if refine_sweeps is None else {"refine_sweeps": refine_sweeps}
    spec = SolverSpec(method=method, max_iter=max_iter, rtol=rtol, thr=thr,
                      precision=precision, **kw)
    designs = len(xs)
    nvars = xs[0].shape[1]
    reqs = []
    for i in range(n):
        d = i % designs
        a = rng.normal(size=(nvars,)).astype(np.float32)
        y = xs[d] @ a
        if noise:
            y = y + noise * rng.normal(size=y.shape[0]).astype(np.float32)
        reqs.append(SolveRequest(
            x=xs[d], y=y, spec=spec,
            design_key=f"design-{d}", request_id=f"req-{i}",
            tenant_id=f"tenant-{i % tenants}" if tenants else None,
            deadline_s=deadline_s))
    return reqs


def report_engine(engine):
    s = engine.stats
    print(f"solver calls: {s.solver_calls} "
          f"(multi_rhs groups={s.multi_rhs_groups} "
          f"covering {s.multi_rhs_requests} reqs; "
          f"vmap batches={s.vmap_batches} covering {s.vmap_requests} reqs; "
          f"singles={s.single_solves}; warm starts={s.warm_starts}; "
          f"failures={s.failures}; sharded={s.sharded_solves})")
    c = engine.cache.stats
    print(f"design cache: {c.hits} hits / {c.misses} misses "
          f"(hit rate {c.hit_rate:.1%}), {len(engine.cache)} resident")
    lanes = engine.lanes.stats()
    if lanes:
        mix = "; ".join(
            f"{label}: {ls['batches']} batches/{ls['requests']} reqs "
            f"busy {ls['busy_s']*1e3:.0f}ms"
            for label, ls in sorted(lanes.items()))
        print(f"execution lanes: {mix}")
    if engine.mesh is not None:
        print(f"mesh: {engine.mesh.describe()}")


def run_sync(args, engine, reqs):
    results = []
    t0 = time.perf_counter()
    for lo in range(0, len(reqs), args.flush_every):
        for r in reqs[lo:lo + args.flush_every]:
            engine.submit(r)
        results.extend(engine.flush())
    wall = time.perf_counter() - t0

    lat = np.array([r.latency_s for r in results])
    kinds = {k: sum(r.batch_kind == k for r in results)
             for k in ("multi_rhs", "vmap", "single", "error")}
    placements = {}
    for r in results:
        placements[r.placement] = placements.get(r.placement, 0) + 1
    print(f"served {len(results)} requests in {wall:.3f}s "
          f"-> {len(results)/wall:.1f} solves/s")
    print(f"latency p50={np.percentile(lat, 50)*1e3:.2f}ms "
          f"p95={np.percentile(lat, 95)*1e3:.2f}ms "
          f"max={lat.max()*1e3:.2f}ms (batch wall time per request)")
    print(f"batch mix: {kinds}")
    print(f"placement mix: {placements}")
    report_engine(engine)
    return reqs, results


def run_async(args, engine, reqs):
    """Poisson arrival stream through the deadline-aware dispatcher."""
    from repro.serve import AsyncDispatcher, DispatchConfig

    rng = np.random.default_rng(args.seed + 1)
    arrivals = np.cumsum(rng.exponential(1.0 / args.rate, size=len(reqs)))
    deadline_s = args.deadline_ms / 1e3
    cfg = DispatchConfig(
        max_queue=args.max_queue,
        backpressure=args.backpressure,
        max_batch=args.max_batch,
        deadline_margin_s=args.deadline_margin_ms / 1e3,
        idle_timeout_s=args.idle_timeout_ms / 1e3,
        default_deadline_s=deadline_s,
    )
    tickets = []
    rejected = 0
    with AsyncDispatcher(engine, cfg) as disp:
        t0 = time.perf_counter()
        base = obs.now()  # same clock as every SolveTicket timestamp
        for i, req in enumerate(reqs):
            now = time.perf_counter() - t0
            if arrivals[i] > now:
                time.sleep(arrivals[i] - now)
            try:
                tickets.append((i, disp.submit(req)))
            except Exception:  # QueueFullError under "reject"
                rejected += 1
        disp.drain()
        wall = time.perf_counter() - t0
        results = [t.result(timeout=60.0) for _, t in tickets]
        stats = disp.stats

    lat = np.array([t.completed_at - base - arrivals[i]
                    for i, t in tickets])
    misses = sum(t.deadline_met is False for _, t in tickets)
    served = len(tickets)
    print(f"served {served}/{len(reqs)} requests in {wall:.3f}s "
          f"-> {served/wall:.1f} solves/s "
          f"(arrival rate {args.rate:.0f}/s, {rejected} rejected)")
    print(f"request latency p50={np.percentile(lat, 50)*1e3:.2f}ms "
          f"p95={np.percentile(lat, 95)*1e3:.2f}ms "
          f"max={lat.max()*1e3:.2f}ms (arrival -> completion)")
    print(f"deadlines: {misses} missed / {served} "
          f"(hit rate {1 - misses/served:.1%} at "
          f"{args.deadline_ms:.0f}ms)")
    print(f"batches fired: full={stats.fired_full} "
          f"deadline={stats.fired_deadline} idle={stats.fired_idle} "
          f"drain={stats.fired_drain}; max inflight={stats.max_inflight}")
    report_engine(engine)
    # Pair results with the requests actually accepted: under "reject"
    # backpressure some submissions never got a ticket, and --check must
    # not verify a solve against a shifted request's system.
    return [reqs[i] for i, _ in tickets], results


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=["sync", "async"], default="sync")
    ap.add_argument("--requests", type=int, default=256)
    ap.add_argument("--obs", type=int, default=2048)
    ap.add_argument("--vars", type=int, default=256)
    ap.add_argument("--designs", type=int, default=8)
    ap.add_argument("--method", default="bakp_gram",
                    help="solver method; any name in the core method "
                         "registry (repro.core.method_names()) — validated "
                         "after jax loads so --mesh device forcing works")
    ap.add_argument("--max-iter", type=int, default=40)
    ap.add_argument("--rtol", type=float, default=1e-10)
    ap.add_argument("--thr", type=int, default=128)
    ap.add_argument("--flush-every", type=int, default=32,
                    help="sync mode: requests per flush window")
    ap.add_argument("--tenants", type=int, default=0,
                    help="recurring tenant ids (0 = off; enables warm starts)")
    ap.add_argument("--precision", default="fp32",
                    choices=["fp32", "bf16", "bf16_fp32acc"],
                    help="X-stream storage precision (SolverSpec.precision): "
                         "bf16 halves HBM traffic with fp32 accumulators; "
                         "bf16_fp32acc adds fp32 polish sweeps recovering "
                         "full precision.  Methods without bf16 support are "
                         "downgraded to fp32 by the engine (counted in "
                         "solver_fallback_total{reason='precision'})")
    ap.add_argument("--refine-sweeps", type=int, default=None,
                    help="fp32 polish-sweep cap for --precision "
                         "bf16_fp32acc (default: SolverSpec's)")
    ap.add_argument("--prefer-fused", action="store_true",
                    help="upgrade 'bakp' requests to the fused whole-solve "
                         "Pallas megakernel (method 'bakp_fused') when the "
                         "bucket fits VMEM; request --method bakp_fused "
                         "directly to force it for all sizes (oversized "
                         "designs fall back to the XLA path)")
    ap.add_argument("--mesh", default=None, metavar="DxM",
                    help="route big buckets onto a device mesh, e.g. '8' or "
                         "'4x2' (data[xmodel]); on CPU forces that many "
                         "virtual host devices")
    ap.add_argument("--shard-min-cells", type=int, default=None,
                    help="bucket obs_p*vars_p at which solves go obs-sharded "
                         "(default: PlacementPolicy's 2^21)")
    ap.add_argument("--rhs-shard-min-k", type=int, default=32,
                    help="same-design group size at which the k axis shards "
                         "across data devices")
    ap.add_argument("--no-lanes", action="store_true",
                    help="disable per-placement execution lanes: run every "
                         "batch on one serial executor thread (the pre-lane "
                         "architecture; results are bit-identical)")
    ap.add_argument("--store-device-bytes", type=int, default=None,
                    help="device-tier byte budget for the tiered design "
                         "store (repro.store): eviction demotes designs to "
                         "host RAM/disk instead of deleting them, and "
                         "over-budget designs serve via the streaming "
                         "'bakp_stream' method.  Unset (with the other "
                         "--store-* flags) = plain LRU cache, bit-identical "
                         "behaviour")
    ap.add_argument("--store-host-bytes", type=int, default=None,
                    help="host-tier byte budget; overflow spills LRU host "
                         "snapshots to --store-dir (or drops X bytes, "
                         "keeping warm/Cholesky state, when unset)")
    ap.add_argument("--store-dir", default=None, metavar="DIR",
                    help="disk-tier directory for memmapped design tile "
                         "files (unset = no disk tier)")
    ap.add_argument("--fault-plan", default=None, metavar="JSON",
                    help="chaos harness (repro.resilience): inline JSON or "
                         "a path to a JSON file mapping fault sites to "
                         "rules, e.g. '{\"solver.raise\": {\"count\": 3}}'. "
                         "Sites: lane.worker, lane.delay, solver.raise, "
                         "solver.diverge, store.tile_corrupt, "
                         "store.read_delay.  Unset = injection disarmed "
                         "(zero-cost, bit-identical)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--check", action="store_true",
                    help="verify every request vs numpy lstsq (slow)")
    # async-mode knobs
    ap.add_argument("--rate", type=float, default=200.0,
                    help="async: Poisson arrival rate (requests/s)")
    ap.add_argument("--deadline-ms", type=float, default=500.0)
    ap.add_argument("--deadline-margin-ms", type=float, default=100.0)
    ap.add_argument("--idle-timeout-ms", type=float, default=20.0)
    ap.add_argument("--max-batch", type=int, default=16)
    ap.add_argument("--max-queue", type=int, default=1024)
    ap.add_argument("--backpressure", choices=["reject", "block"],
                    default="block")
    # observability (repro.obs)
    ap.add_argument("--metrics-json", default=None, metavar="PATH",
                    help="write the final metrics-registry snapshot (solve "
                         "counts, per-kernel-path latency histograms, cache "
                         "hit/miss, deadline hit rate, ...) to PATH as JSON")
    ap.add_argument("--metrics-port", type=int, default=None,
                    help="serve Prometheus /metrics (+ /metrics.json, "
                         "/healthz) on this port for the run's duration "
                         "(0 = ephemeral; the resolved port is printed)")
    ap.add_argument("--trace-dir", default=None, metavar="DIR",
                    help="capture a jax profiler trace of the run into DIR "
                         "(view in TensorBoard/Perfetto; every obs.span - "
                         "dispatcher waits, flushes, batch build, solver "
                         "calls, result fetch - appears by name)")
    args = ap.parse_args()

    if args.mesh:
        ensure_mesh_devices(args.mesh)  # must precede any jax import

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from repro.core import method_names
    from repro.serve import (PlacementPolicy, ServeConfig, SolverServeEngine,
                             build_serve_mesh)

    if args.method not in method_names():
        raise SystemExit(
            f"--method must be one of {method_names()}, got {args.method!r}")
    rng = np.random.default_rng(args.seed)
    smesh = build_serve_mesh(args.mesh) if args.mesh else None
    policy = None
    if args.mesh:
        defaults = PlacementPolicy()
        policy = PlacementPolicy(
            obs_shard_min_cells=(args.shard_min_cells
                                 if args.shard_min_cells is not None
                                 else defaults.obs_shard_min_cells),
            rhs_shard_min_k=args.rhs_shard_min_k)
    engine = SolverServeEngine(
        ServeConfig(placement_policy=policy,
                    prefer_fused=args.prefer_fused,
                    lane_execution=not args.no_lanes,
                    precision=(args.precision if args.precision != "fp32"
                               else None),
                    store_device_bytes=args.store_device_bytes,
                    store_host_bytes=args.store_host_bytes,
                    store_dir=args.store_dir,
                    fault_plan=args.fault_plan),
        mesh=smesh)
    xs = [rng.normal(size=(args.obs, args.vars)).astype(np.float32)
          for _ in range(args.designs)]
    req_kw = dict(tenants=args.tenants, precision=args.precision,
                  refine_sweeps=args.refine_sweeps)
    reqs = build_requests(rng, xs, args.requests, args.method, args.max_iter,
                          args.rtol, args.thr,
                          deadline_s=(args.deadline_ms / 1e3
                                      if args.mode == "async" else None),
                          **req_kw)

    # Warmup: compile every (bucket, k, B) program this stream will need.
    # Async batch compositions vary with arrival timing, so warm a range of
    # window sizes (1, 2, 4, ... max_batch), not just one; with tenants the
    # warm-start (a0) program variants are separate jit signatures, so each
    # size runs twice — the second pass warm-starts off the first.
    if args.mode == "sync":
        warm_sizes = [min(args.flush_every, args.requests)]
    else:
        warm_sizes = sorted({1, 2, 4, args.max_batch, args.designs,
                             2 * args.designs})
    for n in warm_sizes:
        for _ in range(2 if args.tenants else 1):
            engine.serve(build_requests(
                rng, xs, min(n, args.requests), args.method, args.max_iter,
                args.rtol, args.thr, **req_kw))

    server = None
    if args.metrics_port is not None:
        server = obs.start_metrics_server(args.metrics_port,
                                          registry=engine.registry)
        print(f"metrics: http://localhost:{server.port}/metrics")
    if args.trace_dir:
        obs.start_profiling(args.trace_dir)

    try:
        if args.mode == "sync":
            served_reqs, results = run_sync(args, engine, reqs)
        else:
            served_reqs, results = run_async(args, engine, reqs)
    finally:
        if args.trace_dir:
            obs.stop_profiling()
            print(f"profiler trace written to {args.trace_dir}")
        if args.metrics_json:
            obs.write_metrics_json(
                args.metrics_json, registry=engine.registry,
                extra={"mode": args.mode, "method": args.method,
                       "requests": args.requests, "obs": args.obs,
                       "vars": args.vars, "designs": args.designs,
                       "mesh": args.mesh})
            print(f"metrics snapshot written to {args.metrics_json}")
        if server is not None:
            server.close()

    lat_h = engine.registry.get("serve_solve_latency_seconds")
    if lat_h is not None and lat_h.count():
        print("solver-call latency (registry): "
              f"p50={lat_h.percentile(50)*1e3:.2f}ms "
              f"p95={lat_h.percentile(95)*1e3:.2f}ms "
              f"p99={lat_h.percentile(99)*1e3:.2f}ms "
              f"over {lat_h.count()} calls")

    if args.check:
        mapes = []
        for r, q in zip(results, served_reqs):
            ref = np.linalg.lstsq(np.asarray(q.x, np.float64),
                                  np.asarray(q.y, np.float64), rcond=None)[0]
            denom = np.maximum(np.abs(ref), 1e-12)
            mapes.append(float(np.mean(np.abs(r.coef - ref) / denom)))
        print(f"MAPE vs lstsq: mean={np.mean(mapes):.2e} "
              f"worst={np.max(mapes):.2e}")


if __name__ == "__main__":
    main()
