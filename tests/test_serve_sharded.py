"""Sharded serving: placement routing, mesh parity, cache thread-safety.

The engine-level parity check runs in a subprocess with 8 forced virtual
CPU devices (the main test process keeps the single-device view, see
tests/conftest.py); placement policy and cache-locking tests run in-process
— they don't touch device state.
"""
import json
import os
import subprocess
import sys
import textwrap
import threading

import numpy as np
import pytest

from repro.serve import (Placement, PlacementPolicy, SolveRequest,
                         mesh_device_count, placement_for_group)
from repro.serve.batching import config_key
from repro.serve.cache import DesignCache

# Parity workload + assertions, executed under an 8-device mesh.  The same
# requests go through a mesh-routed engine and a plain single-device engine;
# results must line up in submission order with MAPE <= 1e-5 per request.
PARITY_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np
    from repro.serve import (PlacementPolicy, ServeConfig, SolveRequest,
                            SolverServeEngine, build_serve_mesh)

    K = 32  # same-design group size: exercises the k-sharded multi-RHS path

    def workload(seed):
        rng = np.random.default_rng(seed)
        reqs = []
        # big-bucket designs (pad to 512x64 >= policy threshold)
        # -> obs-sharded singles on the mesh engine
        for i in range(3):
            x = rng.normal(size=(500, 60)).astype(np.float32)
            a = rng.normal(size=(60,)).astype(np.float32)
            reqs.append(SolveRequest(
                x=x, y=x @ a, thr=16, max_iter=40, rtol=0.0,
                design_key=f"big-{i}", request_id=f"big-{i}",
                tenant_id=f"big-t{i}"))
        # giant same-design group, small bucket -> rhs-sharded multi-RHS
        xs = rng.normal(size=(200, 24)).astype(np.float32)
        A = rng.normal(size=(24, K)).astype(np.float32)
        for i in range(K):
            reqs.append(SolveRequest(
                x=xs, y=xs @ A[:, i], thr=16, max_iter=40, rtol=0.0,
                design_key="grp", request_id=f"grp-{i}",
                tenant_id=f"grp-t{i}"))
        # distinct small designs -> vmap batch (single-device on BOTH)
        for i in range(4):
            x = rng.normal(size=(100, 12)).astype(np.float32)
            a = rng.normal(size=(12,)).astype(np.float32)
            reqs.append(SolveRequest(
                x=x, y=x @ a, thr=8, max_iter=40, rtol=0.0,
                design_key=f"sm-{i}", request_id=f"sm-{i}"))
        return reqs

    policy = PlacementPolicy(obs_shard_min_cells=512 * 64, rhs_shard_min_k=32)
    eng_mesh = SolverServeEngine(ServeConfig(placement_policy=policy),
                                 mesh=build_serve_mesh("4x2"))
    eng_single = SolverServeEngine(ServeConfig())

    for rnd in range(2):  # round 2 = warm starts via tenant_id on both sides
        r_mesh = eng_mesh.serve(workload(7))
        r_single = eng_single.serve(workload(7))
        assert [r.request_id for r in r_mesh] == \\
            [r.request_id for r in r_single], "submission order diverged"
        assert not [r.error for r in r_mesh + r_single if r.error]
        placements = {r.request_id: r.placement for r in r_mesh}
        for i in range(3):
            assert placements[f"big-{i}"] == "obs_sharded", placements
        for i in range(K):
            assert placements[f"grp-{i}"] == "rhs_sharded", placements
        for i in range(4):
            assert placements[f"sm-{i}"] == "single", placements
        kinds = {r.request_id: r.batch_kind for r in r_mesh}
        assert all(kinds[f"grp-{i}"] == "multi_rhs" for i in range(K))
        assert all(kinds[f"sm-{i}"] == "vmap" for i in range(4))
        assert all(r.placement == "single" for r in r_single)
        worst = 0.0
        for m, s in zip(r_mesh, r_single):
            denom = np.maximum(np.abs(s.coef), 1e-12)
            worst = max(worst, float(np.mean(np.abs(m.coef - s.coef)
                                             / denom)))
        assert worst <= 1e-5, f"round {rnd}: parity MAPE {worst}"
        print(f"round {rnd}: worst parity MAPE {worst:.2e}")
    assert eng_mesh.stats.sharded_solves >= 8   # 3 obs + 1 rhs per round
    assert eng_mesh.stats.warm_starts > 0       # round 2 warm-started
    assert eng_single.stats.sharded_solves == 0
    print("PARITY_OK")
""")


@pytest.mark.slow
def test_sharded_engine_parity_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    p = subprocess.run([sys.executable, "-c", PARITY_SCRIPT],
                       capture_output=True, text=True, env=env, timeout=600)
    assert p.returncode == 0, p.stdout + "\n" + p.stderr
    assert "PARITY_OK" in p.stdout


# The served path of the obs-sharded deployment (``bench/configs/
# sharded_4m.json``) at a tiny size: eight single-RHS ``bakp_gram``
# requests, outstanding at once as eight closed-loop callers leave them,
# through ``AsyncDispatcher`` on a four-device mesh engine with the
# configuration's placement policy (its cell threshold lowered to this
# shape).  8,192 x 300 pads to the bucket 8,192 x 512: four column blocks
# of thr 128, each a psum of a (128, 8) partial over the four row shards.
# Prints one JSON line for the tests below to judge.
CLOSED8_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json
    import threading
    import numpy as np
    from repro import obs
    from repro.core import SolverSpec
    from repro.serve import (AsyncDispatcher, DispatchConfig, PlacementPolicy,
                             ServeConfig, SolveRequest, SolverServeEngine,
                             build_serve_mesh)

    OBS, VARS, K = 8192, 300, 8
    rng = np.random.default_rng(15)
    x = rng.standard_normal((OBS, VARS), dtype=np.float32)
    a = rng.uniform(1.0, 2.0, (K, VARS)) * rng.choice([-1.0, 1.0], (K, VARS))
    clean = (a.astype(np.float32) @ x.T)
    noise = rng.standard_normal((K, OBS)).astype(np.float32)
    ys = clean + (1e-3 * np.linalg.norm(clean, axis=1, keepdims=True)
                  / np.linalg.norm(noise, axis=1, keepdims=True)) * noise
    spec = SolverSpec(method="bakp_gram", rtol=1e-8, max_iter=50)

    obs.set_enabled(True)
    obs.get_tracer().clear()
    policy = PlacementPolicy(obs_shard_min_cells=OBS * 512, rhs_shard_min_k=32)
    mesh_eng = SolverServeEngine(ServeConfig(placement_policy=policy),
                                 mesh=build_serve_mesh("4"))
    # max_batch = K fires the group once the eighth request joins it.
    cfg = DispatchConfig(max_batch=K, idle_timeout_s=5.0)
    results = [None] * K
    start = threading.Barrier(K)
    with AsyncDispatcher(mesh_eng, cfg) as disp:
        def client(i):
            start.wait()
            ticket = disp.submit(SolveRequest(
                x=x, y=ys[i], spec=spec, design_key="sharded",
                request_id=f"c{i}"))
            results[i] = ticket.result(timeout=300)
        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(K)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    mesh_spans = [s.name for s in obs.get_tracer().spans()
                  if s.name.startswith("solve/")]

    obs.get_tracer().clear()
    single = SolverServeEngine(ServeConfig()).serve([SolveRequest(
        x=x, y=ys[0], spec=spec, design_key="single", request_id="s0")])
    single_spans = [s.name for s in obs.get_tracer().spans()
                    if s.name.startswith("solve/")]

    x64 = x.astype(np.float64)
    y64 = ys.astype(np.float64)
    ref = np.linalg.lstsq(x64, y64.T, rcond=None)[0].T
    coef = np.stack([np.asarray(r.coef, np.float64) for r in results])
    resid = np.stack([np.asarray(r.residual, np.float64) for r in results])
    coef_err = (np.linalg.norm(coef - ref, axis=1)
                / np.linalg.norm(ref, axis=1))
    resid_err = (np.linalg.norm(resid - (y64 - coef @ x64.T), axis=1)
                 / np.linalg.norm(y64, axis=1))
    print(json.dumps({
        "ok": [r.ok for r in results],
        "group_size": [r.group_size for r in results],
        "batch_kind": [r.batch_kind for r in results],
        "placement": [r.placement for r in results],
        "kernel_path": [r.telemetry.kernel_path for r in results],
        "retries": [r.retries for r in results],
        "coef_rel_err": coef_err.tolist(),
        "resid_rel_err": resid_err.tolist(),
        "mesh_spans": mesh_spans,
        "single_spans": single_spans,
        "single_placement": single[0].placement,
    }))
""")


@pytest.fixture(scope="module")
def closed8_run():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    p = subprocess.run([sys.executable, "-c", CLOSED8_SCRIPT],
                       capture_output=True, text=True, env=env, timeout=600)
    assert p.returncode == 0, p.stdout + "\n" + p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_closed8_group_is_one_obs_sharded_solve(closed8_run):
    r = closed8_run
    assert all(r["ok"]), r
    assert r["group_size"] == [8] * 8
    assert r["batch_kind"] == ["multi_rhs"] * 8
    assert r["placement"] == ["obs_sharded"] * 8
    assert r["kernel_path"] == ["sharded"] * 8
    assert r["retries"] == [0] * 8


def test_closed8_answers_match_float64_lstsq(closed8_run):
    """Against ``numpy.linalg.lstsq`` in float64 of the same data.

    Coefficients within 1e-6 relative: the solve stops at rtol 1e-8 on the
    SSE, which on this well-conditioned Gaussian design leaves a
    coefficient error of order 1e-7, and float32 sums over 8,192 rows add
    rounding of the same order.  The targets carry noise of 1e-3 of
    ``|x a|``, so a solve that left a shard's rows out of the psum would
    be off by about 1e-4, a hundred times the tolerance.  Residuals within
    1e-6 of ``|y|``: the served residual is the solve's own float32 running
    residual, against float64 ``y - x a`` of the served coefficients."""
    assert max(closed8_run["coef_rel_err"]) < 1e-6, closed8_run
    assert max(closed8_run["resid_rel_err"]) < 1e-6, closed8_run


def test_solve_span_is_named_by_placement(closed8_run):
    """A mesh placement's solve span carries its kind; a single-device
    solve keeps ``solve/<method>``."""
    assert closed8_run["mesh_spans"] == ["solve/obs_sharded/bakp_gram"]
    assert closed8_run["single_placement"] == "single"
    assert closed8_run["single_spans"] == ["solve/bakp_gram"]


# ----------------------------------------------------------- policy (pure)
class _FakeMesh:
    """Shape-only stand-in so policy tests never touch jax device state."""

    def __init__(self, shape):
        self.shape = dict(shape)
        self.axis_names = tuple(shape)


def _smesh(data=4, model=2):
    from repro.serve import ServeMesh
    shape = {"data": data}
    if model:
        shape["model"] = model
    return ServeMesh(mesh=_FakeMesh(shape), data_axes=("data",),
                     model_axis="model" if model else None)


class TestPlacementPolicy:
    def test_no_mesh_is_single(self):
        from repro.serve import placement_for_bucket
        p = placement_for_bucket((1 << 12, 1 << 12), "bakp_gram",
                                 PlacementPolicy(), None)
        assert p.kind == "single"

    def test_threshold_routes_obs_sharded(self):
        from repro.serve import placement_for_bucket
        pol = PlacementPolicy(obs_shard_min_cells=1 << 16)
        sm = _smesh()
        assert placement_for_bucket((512, 128), "bakp_gram", pol,
                                    sm).kind == "obs_sharded"
        assert placement_for_bucket((128, 128), "bakp_gram", pol,
                                    sm).kind == "single"
        # non-shardable methods stay single at any size
        for m in ("bak", "lstsq", "normal"):
            assert placement_for_bucket((512, 128), m, pol, sm).kind == \
                "single"

    def test_divisibility_guard(self):
        from repro.serve import placement_for_bucket
        pol = PlacementPolicy(obs_shard_min_cells=1)
        sm = _smesh(data=8, model=None)
        # obs_p=4 not divisible by 8 data devices -> single
        assert placement_for_bucket((4, 1 << 10), "bakp", pol, sm).kind == \
            "single"

    def test_mesh_2d_opt_in(self):
        from repro.serve import placement_for_bucket
        sm = _smesh()
        off = PlacementPolicy(obs_shard_min_cells=1)
        assert placement_for_bucket((512, 128), "bakp_gram", off,
                                    sm).kind == "obs_sharded"
        on = PlacementPolicy(obs_shard_min_cells=1, mesh_2d_min_cells=1 << 16)
        assert placement_for_bucket((512, 128), "bakp_gram", on,
                                    sm).kind == "mesh_2d"

    def test_group_upgrade(self):
        pol = PlacementPolicy(rhs_shard_min_k=32)
        sm = _smesh()
        single = Placement("single")
        assert placement_for_group(single, 32, pol, sm).kind == "rhs_sharded"
        assert placement_for_group(single, 16, pol, sm).kind == "single"
        # k not divisible by the data axes -> stays single
        pol2 = PlacementPolicy(rhs_shard_min_k=2)
        assert placement_for_group(single, 2, pol2, sm).kind == "single"
        # already-sharded buckets keep their placement
        obs = Placement("obs_sharded")
        assert placement_for_group(obs, 64, pol, sm).kind == "obs_sharded"

    def test_config_key_carries_placement(self, rng):
        x = rng.normal(size=(40, 6)).astype(np.float32)
        req = SolveRequest(x=x, y=x[:, 0])
        bucket = (64, 8)
        base = config_key(req, bucket)
        assert config_key(req, bucket, None) == base
        keyed = config_key(req, bucket, Placement("obs_sharded"))
        assert keyed != base
        assert keyed[:len(base)] == base

    def test_mesh_device_count(self):
        assert mesh_device_count("8") == 8
        assert mesh_device_count("4x2") == 8


# ------------------------------------------------- cache thread-safety
class TestDesignEntryLocking:
    def test_concurrent_entry_mutation(self, rng):
        """Regression: per-entry state (warm-coef OrderedDict, chol/cn_thr
        dicts) was mutated from the dispatcher pre-warm thread and the
        solver thread with no lock.  Hammer every accessor from several
        threads; under the old code this intermittently corrupted the
        OrderedDict / raised RuntimeError."""
        cache = DesignCache(max_entries=4, max_tenants=8)
        x = rng.normal(size=(64, 24)).astype(np.float32)
        entry, _ = cache.get_or_build("d0", lambda: x)
        stop = threading.Event()
        errors = []

        def hammer(tid):
            try:
                i = 0
                while not stop.is_set():
                    t = f"tenant-{tid}-{i % 13}"
                    entry.store_coef(t, np.full((24,), float(i), np.float32))
                    entry.warm_coef(t)
                    entry.warm_coef(f"tenant-{(tid + 1) % 4}-{i % 13}")
                    entry.cn_for_thr(5 + (i % 3))
                    entry.chol_for(8, 1e-6)
                    i += 1
            except Exception as exc:  # pragma: no cover - the regression
                errors.append(exc)

        threads = [threading.Thread(target=hammer, args=(t,))
                   for t in range(4)]
        for t in threads:
            t.start()
        import time
        time.sleep(1.0)
        stop.set()
        for t in threads:
            t.join(timeout=10)
        assert not errors, errors
        # LRU bound survived the stampede
        assert len(entry._warm) <= 8

    def test_store_coef_copies(self, rng):
        cache = DesignCache()
        x = rng.normal(size=(16, 4)).astype(np.float32)
        entry, _ = cache.get_or_build("d0", lambda: x)
        coef = np.ones((4,), np.float32)
        entry.store_coef("t", coef)
        coef[:] = -1.0  # caller mutates the returned ServedSolve.coef
        np.testing.assert_array_equal(entry.warm_coef("t"),
                                      np.ones((4,), np.float32))
