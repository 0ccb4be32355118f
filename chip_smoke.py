"""On-chip smoke test of the served solve path.

    python chip_smoke.py [--seed N]        # one TPU chip, phases (a)-(d)
    python chip_smoke.py --chips 4         # four chips: the sharded placements

Every phase is served the way a user's requests are: ``SolveRequest``s go
through ``AsyncDispatcher`` into ``SolverServeEngine``, which routes them
through its execution lanes to the kernels.  Each design is a consistent,
well-conditioned Gaussian system ``y = x @ a`` made from ``--seed``; the
served coefficients are checked against the planted ``a`` (MAPE, on the
host).  A phase fails — and the script exits non-zero with no result line —
when any request fails, when its MAPE is over the limit, or when the
engine's metrics show a retry, a fallback, or a kernel path other than the
phase's own: a silently degraded solve is a failure here, not an answer.

Phases on one chip:
  (a) tall design on the XLA path: obs 1,000,000 x vars 1,000 fp32 (padded
      to 1,048,576 x 1,024, 4.3 GB of HBM), 16 coalesced requests,
      ``bakp_gram``;
  (b) the fused megakernel: obs 16,384 x vars 512 fp32 ``bakp_fused``;
      obs 16,384 x vars 1,024 with the bf16 tier and fp32 polish, 64
      coalesced requests; one ``bak_fused`` (Algorithm 1) request at
      obs 16,384 x vars 256;
  (c) the streaming kernel: ``bakp_stream`` fp32 on obs 32,768 x
      vars 8,192 (1 GiB of HBM, 64 MiB of double-buffered VMEM tiles);
  (d) a multi-tenant mix: 64 requests over 8 designs of obs 65,536 x
      vars 512 on ``bakp_gram``, cold, then warm re-solves, with deadlines.

With ``--chips 4`` only the sharded placements run: an ``obs_sharded``
solve of obs 4,000,000 x vars 1,000 fp32 (17.2 GB padded, more than one
chip holds), and an ``rhs_sharded`` group of 128 requests over a 1 GiB
design, compared with the same requests on the single-device engine.

One line per phase, then as the last line
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Without a TPU it exits 2 before any work.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import pathlib
import sys
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

from repro import obs  # noqa: E402
from repro.core import SolverSpec  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.serve import (AsyncDispatcher, DispatchConfig,  # noqa: E402
                         PlacementPolicy, ServeConfig, SolveRequest,
                         SolverServeEngine)

MAPE_LIMIT = 1e-4
# Batches fire when full or at drain, never on an idle gap: a design's
# first request pre-warms (pads, transfers, factors) on the dispatch thread
# for seconds at these sizes, and a partial batch would change k.
_IDLE_S = 600.0


class PhaseError(RuntimeError):
    """A phase served something other than what it was asked to serve."""


# ------------------------------------------------------------------ data
def gaussian(seed: int, shape, *, chunks: int = 16) -> np.ndarray:
    """Standard normal float32 array, filled in ``chunks`` row ranges on a
    thread pool (numpy's generators release the GIL).  Deterministic for a
    seed: each chunk has its own spawned generator, whatever the pool."""
    out = np.empty(shape, np.float32)
    bounds = np.linspace(0, shape[0], chunks + 1).astype(int)
    seqs = np.random.SeedSequence(seed).spawn(chunks)

    def fill(i):
        np.random.default_rng(seqs[i]).standard_normal(
            dtype=np.float32, out=out[bounds[i]:bounds[i + 1]])

    with ThreadPoolExecutor(min(chunks, os.cpu_count() or 1)) as pool:
        list(pool.map(fill, range(chunks)))
    return out


def planted(seed: int, nvars: int, k: int) -> np.ndarray:
    """(nvars, k) coefficients with |a| in [1, 2]: MAPE stays meaningful."""
    rng = np.random.default_rng(seed)
    return (rng.uniform(1.0, 2.0, (nvars, k))
            * rng.choice([-1.0, 1.0], (nvars, k))).astype(np.float32)


def mape(coef, a) -> float:
    return float(np.mean(np.abs(np.asarray(coef, np.float64) - a)
                         / np.abs(a)))


# --------------------------------------------------------------- serving
def _counters(name: str) -> dict:
    """Label string -> value of one counter family in the default registry
    (the engines here record there, as does the kernel-path relay)."""
    fam = obs.default_registry().snapshot().get(name)
    return dict(fam["values"]) if fam else {}


def _delta(before: dict, after: dict) -> dict:
    out = {k: v - before.get(k, 0.0) for k, v in after.items()}
    return {k: v for k, v in out.items() if v}


def _label(lbl: str, key: str) -> str:
    return dict(kv.split("=", 1) for kv in lbl.split(",") if kv)[key]


def serve(engine: SolverServeEngine, requests, *, max_batch: int,
          ) -> tuple:
    """Serve ``requests`` through a fresh dispatcher on ``engine``; returns
    (results, tickets, wall seconds)."""
    cfg = DispatchConfig(max_batch=max_batch, max_queue=len(requests) + 1,
                         idle_timeout_s=_IDLE_S, backpressure="block")
    t0 = time.perf_counter()
    with AsyncDispatcher(engine, cfg) as disp:
        tickets = [disp.submit(r) for r in requests]
        disp.drain()
        results = [t.result() for t in tickets]
    return results, tickets, time.perf_counter() - t0


def served_checked(name: str, engine, make_requests, coefs, *, path: str,
                   max_batch: int, passes: int = 2, placement=None):
    """Serve the phase ``passes`` times (the first compiles), and hold it
    to: every request of every pass ok, no retry, no fallback, every
    dispatch on ``path``, the last pass's MAPE within the limit.  Returns
    (results, tickets, seconds per pass, worst MAPE)."""
    before = {n: _counters(n) for n in ("solver_retries_total",
                                        "solver_fallback_total",
                                        "solver_dispatch_total")}
    walls = []
    for _ in range(passes):
        results, tickets, wall = serve(engine, make_requests(),
                                       max_batch=max_batch)
        walls.append(wall)
        failed = [r for r in results if not r.ok]
        if failed:
            raise PhaseError(f"{name}: {len(failed)} requests failed: "
                             f"{failed[0].error}")
    retries = _delta(before["solver_retries_total"],
                     _counters("solver_retries_total"))
    fallbacks = _delta(before["solver_fallback_total"],
                       _counters("solver_fallback_total"))
    dispatch = _delta(before["solver_dispatch_total"],
                      _counters("solver_dispatch_total"))
    paths = {_label(lbl, "path") for lbl in dispatch}
    if retries or fallbacks or paths != {path}:
        raise PhaseError(f"{name}: expected path {path!r} with no retry or "
                         f"fallback; got dispatch={dispatch} "
                         f"retries={retries} fallbacks={fallbacks}")
    if placement is not None:
        kinds = {r.placement for r in results}
        if kinds != {placement}:
            raise PhaseError(f"{name}: placements {kinds}, "
                             f"expected {placement!r}")
    worst = max(mape(r.coef, coefs[:, i]) for i, r in enumerate(results))
    if not worst <= MAPE_LIMIT:
        raise PhaseError(f"{name}: MAPE {worst:.3e} > {MAPE_LIMIT:.0e}")
    return results, tickets, walls, worst


def phase_line(name, shapes, method, path, results, walls, worst, kind,
               **extra) -> str:
    """One phase's report: what ran, on which path, how well, how long
    (the first pass compiles; the last is steady)."""
    rec = {"phase": name, "shapes": shapes, "method": method, "path": path,
           "sweeps": sorted({int(r.n_sweeps) for r in results}),
           "mape": worst, "compile_s": walls[0], "steady_s": walls[-1],
           "device_kind": kind, **extra}
    return json.dumps(rec)


def _engine(**kw) -> SolverServeEngine:
    return SolverServeEngine(ServeConfig(**kw.pop("config", {})), **kw)


def _requests(x, ys, spec, key, **kw):
    return lambda: [SolveRequest(x=x, y=ys[:, i], spec=spec, design_key=key,
                                 request_id=f"{key}-{i}", **kw)
                    for i in range(ys.shape[1])]


def coalesced_phase(name, kind, seed, *, obs_, nvars, k, spec, path,
                    engine=None, placement=None):
    """k requests over one design, coalesced into one multi-RHS solve."""
    x = gaussian(seed, (obs_, nvars))
    a = planted(seed + 1, nvars, k)
    ys = x @ a
    eng = engine or _engine()
    try:
        results, _, walls, worst = served_checked(
            name, eng, _requests(x, ys, spec, name), a, path=path,
            max_batch=k, placement=placement)
    finally:
        if engine is None:
            eng.shutdown()
    return phase_line(name, f"{obs_}x{nvars} k={k} {spec.precision}",
                      spec.method, path, results, walls, worst,
                      kind), results


# ---------------------------------------------------------------- phases
def phase_tall(kind, seed, *, obs_=1_000_000, nvars=1_000, k=16):
    """(a) paper Table 1 grid point on the XLA exact-block path."""
    spec = SolverSpec(method="bakp_gram", rtol=1e-8, max_iter=50)
    return [coalesced_phase("a_tall_xla", kind, seed, obs_=obs_,
                            nvars=nvars, k=k, spec=spec, path="xla")[0]]


def phase_fused(kind, seed, *, obs_=16_384, nvars=(512, 1_024, 256),
                k=(4, 64, 1)):
    """(b) the fused megakernel: fp32, the bf16 tier with fp32 polish,
    and Algorithm 1."""
    specs = (SolverSpec(method="bakp_fused", thr=256, rtol=1e-8,
                        max_iter=200),
             SolverSpec(method="bakp_fused", thr=256, rtol=1e-8,
                        max_iter=200, precision="bf16_fp32acc",
                        refine_sweeps=32),
             SolverSpec(method="bak_fused", thr=256, rtol=1e-8,
                        max_iter=200))
    names = ("b_fused_fp32", "b_fused_bf16", "b_bak_fused")
    return [coalesced_phase(n, kind, seed + 10 * i, obs_=obs_, nvars=v,
                            k=kk, spec=s, path="fused")[0]
            for i, (n, v, kk, s) in enumerate(zip(names, nvars, k, specs))]


def phase_stream(kind, seed, *, obs_=32_768, nvars=8_192, k=1):
    """(c) the streaming kernel on an HBM-resident design."""
    spec = SolverSpec(method="bakp_stream", thr=256, rtol=1e-8,
                      max_iter=300)
    return [coalesced_phase("c_stream", kind, seed, obs_=obs_, nvars=nvars,
                            k=k, spec=spec, path="stream")[0]]


def phase_tenants(kind, seed, *, obs_=65_536, nvars=512, designs=8,
                  tenants=8, deadline_s=5.0):
    """(d) tenants over several designs: a cold round, then warm re-solves
    of slightly drifted targets, each request with a deadline."""
    xs = [gaussian(seed + d, (obs_, nvars)) for d in range(designs)]
    a0 = planted(seed + 100, nvars, designs * tenants)
    drift = planted(seed + 101, nvars, designs * tenants) * 1e-2
    rounds = (a0, a0 + drift, a0 + 2 * drift)
    spec = SolverSpec(method="bakp_gram", rtol=1e-8, max_iter=50)

    def make(a):
        return lambda: [
            SolveRequest(x=xs[i % designs],
                         y=xs[i % designs] @ a[:, i], spec=spec,
                         design_key=f"d_tenants-{i % designs}",
                         tenant_id=f"t{i}", deadline_s=deadline_s,
                         request_id=f"d-{i}")
            for i in range(designs * tenants)]

    eng = _engine()
    walls = []
    try:
        for a in rounds:   # cold, warm (compiles the a0 programs), warm
            results, tickets, w, worst = served_checked(
                "d_tenants", eng, make(a), a, path="xla", max_batch=32,
                passes=1)
            walls += w
    finally:
        eng.shutdown()
    warm = sum(r.warm_start for r in results)
    if warm != len(results):
        raise PhaseError(f"d_tenants: {warm}/{len(results)} warm starts in "
                         f"the last round")
    hit = sum(bool(t.deadline_met) for t in tickets) / len(tickets)
    return [phase_line(
        "d_tenants", f"{designs}x({obs_}x{nvars}) tenants={tenants}",
        spec.method, "xla", results, [sum(walls[:-1]), walls[-1]], worst,
        kind, deadline_s=deadline_s, deadline_hit_rate=hit,
        served=len(results))]


def _design_devices(engine, key, placement):
    """Devices holding the design's buffers in ``placement``'s layout."""
    entry = engine.cache.get(key, record_stats=False)
    arr = entry.x_for_placement(placement, engine.mesh)
    return arr, {s.device for s in arr.addressable_shards}


def phase_sharded(kind, seed, smesh, *, obs_=4_000_000, nvars=1_000, k=4,
                  rhs_obs=262_144, rhs_vars=1_024, rhs_k=128):
    """Four chips: an obs-sharded design larger than one chip, and a
    rhs-sharded group checked against the single-device engine.  Prints
    its own lines: the design-placement checks sit between the solves."""
    import jax

    from repro.serve.batching import next_pow2
    from repro.serve.placement import OBS_SHARDED, RHS_SHARDED

    ndev = smesh.data_size
    # The rhs design stays a single-device bucket (its k=128 group then
    # shards k); the larger obs design shards its rows.
    rhs_cells = next_pow2(rhs_obs) * next_pow2(rhs_vars)
    assert next_pow2(obs_) * next_pow2(nvars) > rhs_cells
    policy = PlacementPolicy(obs_shard_min_cells=2 * rhs_cells,
                             rhs_shard_min_k=rhs_k)
    eng = _engine(config=dict(placement_policy=policy), mesh=smesh)
    try:
        spec = SolverSpec(method="bakp_gram", rtol=1e-8, max_iter=50)
        line, _ = coalesced_phase(
            "s_obs_sharded", kind, seed, obs_=obs_, nvars=nvars, k=k,
            spec=spec, path="sharded", engine=eng, placement="obs_sharded")
        arr, devs = _design_devices(eng, "s_obs_sharded", OBS_SHARDED)
        if len(devs) != ndev:
            raise PhaseError(f"obs_sharded design on {len(devs)} devices")
        # memory_stats() is None on the CPU backend (the rehearsal).
        in_use = {str(d.id): (d.memory_stats() or {}).get("bytes_in_use")
                  for d in jax.devices()[:ndev]}
        rec = json.loads(line)
        rec.update(shard_shape=list(arr.addressable_shards[0].data.shape),
                   bytes_in_use=in_use)
        print(json.dumps(rec), flush=True)

        line, sharded = coalesced_phase(
            "s_rhs_sharded", kind, seed + 1, obs_=rhs_obs, nvars=rhs_vars,
            k=rhs_k, spec=spec, path="sharded", engine=eng,
            placement="rhs_sharded")
        _, devs = _design_devices(eng, "s_rhs_sharded", RHS_SHARDED)
        if len(devs) != ndev:
            raise PhaseError(f"rhs_sharded design on {len(devs)} devices")
    finally:
        eng.shutdown()
    _, single = coalesced_phase(
        "s_rhs_single", kind, seed + 1, obs_=rhs_obs, nvars=rhs_vars,
        k=rhs_k, spec=spec, path="xla")
    diff = max(float(np.max(np.abs(s.coef - r.coef))
                   / np.max(np.abs(r.coef)))
               for s, r in zip(sharded, single))
    if not diff <= MAPE_LIMIT:
        raise PhaseError(f"rhs_sharded differs from the single-device "
                         f"engine by {diff:.3e}")
    rec = json.loads(line)
    rec.update(max_rel_diff_vs_single=diff,
               sweeps_single=sorted({int(r.n_sweeps) for r in single}))
    print(json.dumps(rec), flush=True)


# ------------------------------------------------------------------ main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded placements on 4 chips")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform is "
              f"{dev.platform!r}); nothing was run", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2
    enable_compile_cache()
    obs.set_enabled(True)
    kind = dev.device_kind
    try:
        if args.chips == 4:
            from repro.serve import build_serve_mesh
            phase_sharded(kind, args.seed, build_serve_mesh("4"))
        else:
            for i, phase in enumerate((phase_tall, phase_fused, phase_stream,
                                       phase_tenants)):
                for line in phase(kind, args.seed + 1000 * i):
                    print(line, flush=True)
                # The phase's engine and designs are garbage now; free
                # their device buffers before the next phase allocates.
                gc.collect()
    except Exception as exc:
        print(f"chip_smoke: FAILED: {type(exc).__name__}: {exc}",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
