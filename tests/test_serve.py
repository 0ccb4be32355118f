"""repro.serve: bucketing, padding, caching, coalescing + multi-RHS solves."""
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import make_system
from repro.core import solve, solvebak, solvebakp
from repro.serve import (ServeConfig, ServedSolve, SolveRequest,
                         SolverServeEngine, bucket_shape, design_fingerprint,
                         group_requests, next_pow2)


def _lstsq(x, y):
    return np.linalg.lstsq(np.asarray(x, np.float64),
                           np.asarray(y, np.float64), rcond=None)[0]


# --------------------------------------------------------------- multi-RHS
class TestMultiRhsSolvers:
    """Multi-RHS core solves vs a column-by-column fp32 oracle."""

    @pytest.mark.parametrize("solver_kw", [
        dict(fn="bak"),
        dict(fn="bakp", mode="jacobi", thr=16),
        dict(fn="bakp", mode="gram", thr=16),
    ])
    def test_matches_column_by_column(self, rng, solver_kw):
        obs, nvars, k = 400, 32, 6
        x = rng.normal(size=(obs, nvars)).astype(np.float32)
        a_true = rng.normal(size=(nvars, k)).astype(np.float32)
        ys = x @ a_true
        if solver_kw["fn"] == "bak":
            multi = solvebak(jnp.array(x), jnp.array(ys), max_iter=60)
            cols = [solvebak(jnp.array(x), jnp.array(ys[:, i]), max_iter=60)
                    for i in range(k)]
        else:
            kw = dict(thr=solver_kw["thr"], mode=solver_kw["mode"],
                      max_iter=60)
            multi = solvebakp(jnp.array(x), jnp.array(ys), **kw)
            cols = [solvebakp(jnp.array(x), jnp.array(ys[:, i]), **kw)
                    for i in range(k)]
        assert multi.coef.shape == (nvars, k)
        assert multi.residual.shape == (obs, k)
        for i, c in enumerate(cols):
            # Multi-RHS sweeps are the single-RHS sweeps run side by side;
            # only the (shared) stopping decision may differ.  With a fixed
            # sweep budget the iterates are identical.
            np.testing.assert_allclose(np.array(multi.coef[:, i]),
                                       np.array(c.coef), rtol=1e-5,
                                       atol=1e-5)
        np.testing.assert_allclose(np.array(multi.coef), a_true, rtol=1e-3,
                                   atol=1e-3)

    def test_multi_rhs_via_solve_api(self, rng):
        x, _, _ = make_system(rng, 300, 20)
        a_true = rng.normal(size=(20, 3)).astype(np.float32)
        ys = x @ a_true
        for method in ("bak", "bakp", "bakp_gram", "lstsq", "normal"):
            res = solve(jnp.array(x), jnp.array(ys), method=method,
                        max_iter=60, thr=8)
            assert res.coef.shape == (20, 3), method
            np.testing.assert_allclose(np.array(res.coef), a_true, rtol=1e-3,
                                       atol=1e-3, err_msg=method)

    def test_multi_rhs_kernels_vs_ref(self, rng):
        from repro.core.types import column_norms_sq, safe_inv
        from repro.kernels import bakp_sweep, block_update, cd_sweep
        from repro.kernels.ref import (ref_bakp_sweep, ref_block_update,
                                       ref_cd_sweep)
        obs, nvars, k, blk = 128, 16, 4, 8
        x = rng.normal(size=(obs, nvars)).astype(np.float32)
        e = rng.normal(size=(k, obs)).astype(np.float32)
        x_t = jnp.array(x.T)
        inv_cn = safe_inv(column_norms_sq(jnp.array(x)))
        for kern, ref, kw in ((cd_sweep, ref_cd_sweep, {}),
                              (bakp_sweep, ref_bakp_sweep,
                               dict(block=blk))):
            da_k, e_k = kern(x_t, jnp.array(e), inv_cn, block=blk)
            da_r, e_r = ref(x_t, jnp.array(e), inv_cn, **kw)
            np.testing.assert_allclose(np.array(da_k), np.array(da_r),
                                       rtol=1e-5, atol=1e-5)
            np.testing.assert_allclose(np.array(e_k), np.array(e_r),
                                       rtol=1e-5, atol=1e-5)
        da = rng.normal(size=(blk, k)).astype(np.float32)
        out = block_update(x_t[:blk], jnp.array(e), jnp.array(da), obs_tile=64)
        np.testing.assert_allclose(
            np.array(out),
            np.array(ref_block_update(x_t[:blk], jnp.array(e), jnp.array(da))),
            rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------- dispatch errors
class TestDispatchErrors:
    def test_unknown_method_raises(self, rng):
        x, y, _ = make_system(rng, 50, 4)
        with pytest.raises(ValueError, match="method must be one of"):
            solve(jnp.array(x), jnp.array(y), method="cholesky_qr")

    def test_random_order_requires_key(self, rng):
        x, y, _ = make_system(rng, 50, 4)
        with pytest.raises(ValueError, match="requires a PRNG key"):
            solvebak(jnp.array(x), jnp.array(y), order="random")

    def test_engine_rejects_unknown_method(self, rng):
        x, y, _ = make_system(rng, 50, 4)
        with pytest.raises(ValueError, match="method must be one of"):
            SolverServeEngine().submit(SolveRequest(x=x, y=y, method="qr"))

    def test_engine_rejects_bad_shapes(self, rng):
        x, y, _ = make_system(rng, 50, 4)
        eng = SolverServeEngine()
        with pytest.raises(ValueError, match="x must be 2D"):
            eng.submit(SolveRequest(x=y, y=y))
        with pytest.raises(ValueError, match="y must be"):
            eng.submit(SolveRequest(x=x, y=y[:-1]))


# ----------------------------------------------------------------- batching
class TestBucketing:
    def test_pow2(self):
        assert next_pow2(1) == 1
        assert next_pow2(5) == 8
        assert next_pow2(8) == 8
        assert next_pow2(9) == 16
        assert next_pow2(3, floor=8) == 8
        assert bucket_shape(300, 24) == (512, 32)
        assert bucket_shape(4, 4) == (8, 8)

    def test_fingerprint_content_keyed(self, rng):
        x = rng.normal(size=(20, 4)).astype(np.float32)
        assert design_fingerprint(x) == design_fingerprint(x.copy())
        x2 = x.copy()
        x2[3, 2] += 1.0
        assert design_fingerprint(x) != design_fingerprint(x2)
        # same bytes, different shape must differ
        assert design_fingerprint(x) != design_fingerprint(x.reshape(4, 20))

    def test_grouping_deterministic(self, rng):
        xs = [rng.normal(size=(30, 6)).astype(np.float32) for _ in range(3)]
        reqs = [SolveRequest(x=xs[i % 3], y=xs[i % 3][:, 0])
                for i in range(9)]
        g1 = group_requests(reqs)
        g2 = group_requests(reqs)
        assert list(g1) == list(g2)
        (outer, designs), = g1.items()
        assert outer[0] == (32, 8)
        assert [idx for lst in designs.values() for idx in lst] == \
            [0, 3, 6, 1, 4, 7, 2, 5, 8]


# ------------------------------------------------------------------- engine
class TestEngine:
    def test_padding_strip_correctness(self, rng):
        """Non-pow2 shapes through every batch path match unpadded lstsq."""
        eng = SolverServeEngine()
        reqs = []
        x_shared = rng.normal(size=(300, 24)).astype(np.float32)
        for i in range(3):  # same-design -> multi_rhs
            a = rng.normal(size=(24,)).astype(np.float32)
            reqs.append(SolveRequest(x=x_shared, y=x_shared @ a, thr=16,
                                     max_iter=60, rtol=1e-12))
        for i in range(2):  # unique designs, same bucket -> vmap
            x = rng.normal(size=(290, 20)).astype(np.float32)
            a = rng.normal(size=(20,)).astype(np.float32)
            reqs.append(SolveRequest(x=x, y=x @ a, thr=16, max_iter=60,
                                     rtol=1e-12))
        x = rng.normal(size=(100, 5)).astype(np.float32)  # own bucket
        reqs.append(SolveRequest(x=x, y=x @ np.ones(5, np.float32), thr=16,
                                 max_iter=60, rtol=1e-12))
        results = eng.serve(reqs)
        assert [r.batch_kind for r in results] == \
            ["multi_rhs"] * 3 + ["vmap"] * 2 + ["single"]
        for req, res in zip(reqs, results):
            assert isinstance(res, ServedSolve)
            assert res.coef.shape == (req.x.shape[1],)
            assert res.residual.shape == (req.x.shape[0],)
            np.testing.assert_allclose(res.coef, _lstsq(req.x, req.y),
                                       rtol=1e-3, atol=1e-3)
            assert res.sse == pytest.approx(
                float(np.sum(res.residual ** 2)), rel=1e-5, abs=1e-8)

    def test_multi_rhs_group_matches_row_major_solve_bitwise(self, rng):
        """A single-device group of 3 (k_pad 4) is built request-major and
        transposed on the device: each answer equals, to the bit, a direct
        ``PreparedDesign.solve`` on the row-major (obs_p, 4) numpy ``y``,
        and each residual is a contiguous row."""
        x = rng.normal(size=(300, 24)).astype(np.float32)
        reqs = [SolveRequest(x=x, y=x @ rng.normal(size=(24,)).astype(
                    np.float32), method="bakp_gram", thr=8, max_iter=60,
                    rtol=1e-10, design_key="rows") for _ in range(3)]
        eng = SolverServeEngine()
        served = eng.serve(reqs)
        assert [r.batch_kind for r in served] == ["multi_rhs"] * 3
        entry = eng.cache.get("rows", record_stats=False)
        y_rows = np.zeros((512, 4), np.float32)
        for c, req in enumerate(reqs):
            y_rows[:300, c] = req.y
        direct = entry.solve(y_rows, spec=eng.spec_for(reqs[0]))
        for c, r in enumerate(served):
            assert np.array_equal(r.coef, np.asarray(direct.coef)[:24, c])
            assert np.array_equal(r.residual,
                                  np.asarray(direct.residual)[:300, c])
            assert r.residual.flags.c_contiguous
            assert r.residual.shape == (300,)
        eng.shutdown()

    def test_multi_rhs_padding_is_zeroed_after_a_larger_group(self, rng):
        """Consecutive groups of 4 then 3: the second group's padding
        right-hand side and rows reach the solver zeroed, its device ``y``
        is donated to the solve (consumed), and its answers equal a fresh
        engine's to the bit."""
        x = rng.normal(size=(300, 24)).astype(np.float32)

        def group(n):
            return [SolveRequest(x=x, y=x @ rng.normal(size=(24,)).astype(
                np.float32), method="bakp_gram", thr=8, max_iter=60,
                rtol=1e-10, design_key="rows") for _ in range(n)]
        first, second = group(4), group(3)
        eng = SolverServeEngine()
        seen, operands = [], []
        call_solver = eng._call_solver

        def recording(spec, entry, y_dev, atol, a0=None, placement=None,
                      donate=None):
            seen.append((np.array(y_dev), donate))
            operands.append(y_dev)
            return call_solver(spec, entry, y_dev, atol, a0=a0,
                               placement=placement, donate=donate)
        eng._call_solver = recording
        eng.serve(first)
        later = eng.serve(second)
        (y1, donate1), (y2, donate2) = seen
        assert donate1 is donate2 is True
        assert all(op.is_deleted() for op in operands)
        assert y1.shape == y2.shape == (512, 4)
        assert np.array_equal(y2[:300, :3],
                              np.stack([r.y for r in second], axis=1))
        assert not y2[300:].any() and not y2[:, 3].any()
        fresh = SolverServeEngine().serve(second)
        for r, f in zip(later, fresh):
            assert r.batch_kind == "multi_rhs" and r.group_size == 3
            assert np.array_equal(r.coef, f.coef)
            assert np.array_equal(r.residual, f.residual)
        eng.shutdown()

    def test_multi_rhs_group_serves_a_method_without_donate(self, rng):
        """A registered method whose kernel has no ``donate`` keyword still
        serves a coalesced group: the engine's ``donate=True`` is not passed
        to it, and its answers equal the method it wraps."""
        import dataclasses

        from repro.core import register_method, solver_method
        from repro.core import spec as spec_mod

        lstsq = solver_method("lstsq")

        def plain(p, y, spec, *, a0=None, key=None, placement=None,
                  mesh=None):
            return lstsq.solve(p, y, spec, a0=a0, key=key,
                               placement=placement, mesh=mesh)
        register_method(dataclasses.replace(lstsq, name="lstsq_plain",
                                            solve=plain))
        try:
            x = rng.normal(size=(300, 24)).astype(np.float32)
            ys = [x @ rng.normal(size=(24,)).astype(np.float32)
                  for _ in range(3)]

            def group(method):
                return SolverServeEngine().serve([
                    SolveRequest(x=x, y=y, method=method, design_key="plain")
                    for y in ys])
            served, wrapped = group("lstsq_plain"), group("lstsq")
            for r, w in zip(served, wrapped):
                assert r.batch_kind == "multi_rhs" and r.group_size == 3
                assert np.array_equal(r.coef, w.coef)
                assert np.array_equal(r.residual, w.residual)
        finally:
            spec_mod._REGISTRY.pop("lstsq_plain", None)

    def test_results_in_submission_order(self, rng):
        eng = SolverServeEngine()
        reqs = []
        for i in range(6):
            x = rng.normal(size=(40 + i, 4)).astype(np.float32)
            reqs.append(SolveRequest(x=x, y=x @ np.ones(4, np.float32),
                                     request_id=f"tag-{i}", thr=4,
                                     max_iter=40, rtol=1e-12))
        out = eng.serve(reqs)
        assert [r.request_id for r in out] == [f"tag-{i}" for i in range(6)]

    def test_cache_hits_for_repeated_design(self, rng):
        eng = SolverServeEngine()
        x = rng.normal(size=(200, 16)).astype(np.float32)

        def mk():
            a = rng.normal(size=(16,)).astype(np.float32)
            return SolveRequest(x=x, y=x @ a, thr=8, max_iter=40, rtol=1e-12)

        first = eng.serve([mk()])
        assert not first[0].cache_hit
        assert eng.cache.stats.hits == 0
        second = eng.serve([mk(), mk()])
        assert all(r.cache_hit for r in second)
        assert eng.cache.stats.hits == 1  # one lookup per design group
        assert len(eng.cache) == 1

    def test_cache_lru_eviction(self, rng):
        from repro.serve import ServeConfig
        eng = SolverServeEngine(ServeConfig(cache_entries=2))
        for i in range(4):
            x = rng.normal(size=(50, 4)).astype(np.float32)
            eng.serve([SolveRequest(x=x, y=x[:, 0], thr=4, max_iter=20)])
        assert len(eng.cache) == 2
        assert eng.cache.stats.evictions == 2

    def test_coalescing_off_falls_back(self, rng):
        eng = SolverServeEngine(ServeConfig(coalesce=False, vmap_batch=False))
        x = rng.normal(size=(64, 8)).astype(np.float32)
        out = eng.serve([SolveRequest(x=x, y=x[:, 0], thr=8, max_iter=30,
                                      rtol=1e-12) for _ in range(3)])
        assert all(r.batch_kind == "single" for r in out)
        np.testing.assert_allclose(out[0].coef, _lstsq(x, x[:, 0]),
                                   rtol=1e-3, atol=1e-3)

    def test_atol_corrected_for_padding(self, rng):
        """atol through the engine must match the unpadded criterion.

        obs=300 pads to 512; an uncorrected atol would inflate the SSE
        threshold by 512/300 and stop early.  The engine's solve must take
        exactly as many sweeps as the direct unpadded solve.
        """
        x, y, _ = make_system(rng, 300, 24, noise=0.3)
        atol = 0.35
        direct = solvebak(jnp.array(x), jnp.array(y), max_iter=50, atol=atol)
        eng = SolverServeEngine()
        served, = eng.serve([SolveRequest(x=x, y=y, method="bak",
                                          max_iter=50, atol=atol)])
        assert served.n_sweeps == int(direct.n_sweeps)
        assert served.converged == bool(direct.converged)
        # sanity: the tolerance actually fires mid-run, so the test bites
        assert 1 <= int(direct.n_sweeps) < 50

    def test_direct_methods_served_singly(self, rng):
        eng = SolverServeEngine()
        x = rng.normal(size=(60, 6)).astype(np.float32)
        a = rng.normal(size=(6,)).astype(np.float32)
        out = eng.serve([SolveRequest(x=x, y=x @ a, method="lstsq")
                         for _ in range(2)])
        # lstsq isn't batchable -> per-request solves, still cache-backed.
        assert all(r.batch_kind in ("single", "multi_rhs") for r in out)
        for r in out:
            np.testing.assert_allclose(r.coef, a, rtol=1e-3, atol=1e-3)
