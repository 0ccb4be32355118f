"""Fused whole-solve megakernel: interpret-mode parity + dispatch + fallback.

Parity targets (ISSUE 5 acceptance): <= 1e-5 vs the XLA ``solvebak`` /
``solvebakp`` solvers across single/multi-RHS x warm-start x early-exit, and
``n_sweeps`` equality vs the unfused per-sweep kernel launch loop (the fused
kernel reproduces its SSE reduction bit-for-bit in interpret mode, so the
on-chip stopping decisions match the host-side ones sweep-for-sweep).

The VMEM-budget tests monkeypatch ``repro.kernels.cd_sweep.
VMEM_BUDGET_BYTES`` (the shared budget ``fused_fits`` reads at call time):
the raw kernel must refuse with the VMEM error message, while every dispatch
route (method registry, ``PreparedDesign.solve``, the serving engine) must
fall back to the XLA path and still serve the request.
"""
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import SolverSpec, prepare, solve, solvebak, solvebakp
from repro.core.spec import solver_method
from repro.core.types import donate_default
from repro.kernels import (fused_fits, fused_solve, fused_vmem_bytes,
                           solvebakp_kernel, solvebakp_persweep_kernel)

# The package attribute ``cd_sweep`` is the *function*; the module (owner of
# VMEM_BUDGET_BYTES) is reached through sys.modules (see test_kernels.py).
_CD = sys.modules["repro.kernels.cd_sweep"]


def _system(rng, obs=512, nvars=64, k=None, consistent=True):
    x = rng.normal(size=(obs, nvars)).astype(np.float32)
    shape = (nvars,) if k is None else (nvars, k)
    a = rng.normal(size=shape).astype(np.float32)
    y = (x @ a).astype(np.float32)
    if not consistent:
        y = y + 0.1 * rng.normal(size=y.shape).astype(np.float32)
    return x, a, y


class TestFusedParity:
    @pytest.mark.parametrize("k", [None, 4])
    @pytest.mark.parametrize("warm", [False, True])
    def test_bakp_vs_xla(self, rng, k, warm):
        x, a, y = _system(rng, k=k)
        a0 = None
        if warm:
            a0 = (0.8 * a).astype(np.float32)
        rf = fused_solve(jnp.asarray(x.T), jnp.asarray(y),
                         a0=None if a0 is None else jnp.asarray(a0),
                         block=16, max_iter=40)
        rx = solvebakp(jnp.asarray(x), jnp.asarray(y), thr=16, max_iter=40,
                       a0=None if a0 is None else jnp.asarray(a0))
        np.testing.assert_allclose(np.asarray(rf.coef), np.asarray(rx.coef),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(rf.residual),
                                   np.asarray(rx.residual),
                                   rtol=1e-5, atol=1e-5)
        assert int(rf.n_sweeps) == int(rx.n_sweeps) == 40

    @pytest.mark.parametrize("k", [None, 3])
    def test_bak_vs_solvebak(self, rng, k):
        x, _, y = _system(rng, obs=256, nvars=32, k=k)
        rf = fused_solve(jnp.asarray(x.T), jnp.asarray(y), variant="bak",
                         block=8, max_iter=12)
        rx = solvebak(jnp.asarray(x), jnp.asarray(y), max_iter=12)
        np.testing.assert_allclose(np.asarray(rf.coef), np.asarray(rx.coef),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(rf.sse), np.asarray(rx.sse),
                                   rtol=1e-4, atol=1e-6)

    @pytest.mark.parametrize("k", [None, 4])
    @pytest.mark.parametrize("warm", [False, True])
    @pytest.mark.parametrize("variant", ["bak", "bakp"])
    def test_early_exit_n_sweeps_matches_unfused(self, rng, k, warm,
                                                 variant):
        """atol early exit: fused must stop at the same sweep as the
        per-sweep launch loop, well before max_iter, on cold AND warm
        starts, single- AND multi-RHS."""
        x, a, y = _system(rng, k=k)
        a0 = jnp.asarray(0.5 * a) if warm else None
        kw = dict(block=16, max_iter=100, atol=1e-3, variant=variant)
        rf = fused_solve(jnp.asarray(x.T), jnp.asarray(y), a0=a0, **kw)
        ru = solvebakp_persweep_kernel(jnp.asarray(x.T), jnp.asarray(y),
                                       a0=a0, **kw)
        assert int(rf.n_sweeps) == int(ru.n_sweeps) < 100
        assert bool(rf.converged) and bool(ru.converged)
        np.testing.assert_allclose(np.asarray(rf.coef), np.asarray(ru.coef),
                                   rtol=1e-5, atol=1e-5)

    def test_rtol_stall_matches_unfused(self, rng):
        """rtol stall: the fused kernel's on-chip SSE reduction reproduces
        the host jnp.vdot bit-for-bit, so even the razor-edge rtol stopping
        sweep matches the unfused launch loop, and the histories are
        identical."""
        x, _, y = _system(rng, obs=1024, nvars=128)
        kw = dict(block=32, max_iter=80, rtol=1e-9)
        rf = fused_solve(jnp.asarray(x.T), jnp.asarray(y), **kw)
        ru = solvebakp_persweep_kernel(jnp.asarray(x.T), jnp.asarray(y),
                                       **kw)
        assert int(rf.n_sweeps) == int(ru.n_sweeps) < 80
        np.testing.assert_array_equal(np.asarray(rf.history),
                                      np.asarray(ru.history))

    def test_precomputed_norms_match_recomputed(self, rng):
        from repro.core.types import column_norms_sq, safe_inv

        x, _, y = _system(rng)
        cn = column_norms_sq(jnp.asarray(x))
        base = fused_solve(jnp.asarray(x.T), jnp.asarray(y), block=16,
                           max_iter=10)
        via_cn = fused_solve(jnp.asarray(x.T), jnp.asarray(y), cn=cn,
                             block=16, max_iter=10)
        via_inv = fused_solve(jnp.asarray(x.T), jnp.asarray(y),
                              inv_cn=safe_inv(cn), block=16, max_iter=10)
        np.testing.assert_array_equal(np.asarray(base.coef),
                                      np.asarray(via_cn.coef))
        np.testing.assert_array_equal(np.asarray(base.coef),
                                      np.asarray(via_inv.coef))

    def test_solvebakp_kernel_shim_dispatches_fused(self, rng):
        """The public kernel entry runs fused for VMEM-fitting designs and
        matches the per-sweep path it replaced."""
        x, _, y = _system(rng)
        assert fused_fits(64, 512, 1, 4, max_iter=40)
        ks = solvebakp_kernel(jnp.asarray(x.T), jnp.asarray(y), block=16,
                              max_iter=40)
        ps = solvebakp_persweep_kernel(jnp.asarray(x.T), jnp.asarray(y),
                                       block=16, max_iter=40)
        np.testing.assert_allclose(np.asarray(ks.coef), np.asarray(ps.coef),
                                   rtol=1e-5, atol=1e-5)
        assert int(ks.n_sweeps) == int(ps.n_sweeps)


class TestVmemBudget:
    def test_fused_solve_raises_over_budget(self, rng, monkeypatch):
        x, _, y = _system(rng, obs=128, nvars=16)
        monkeypatch.setattr(_CD, "VMEM_BUDGET_BYTES", 1024)
        with pytest.raises(ValueError, match="VMEM"):
            fused_solve(jnp.asarray(x.T), jnp.asarray(y), block=8)

    def test_budget_accounting(self):
        # Mosaic's padded layout: lanes pad to 128, sublanes to 8 rows, so
        # the (k=2, obs) residuals take 8 rows and the (vars, k) / (vars, 1)
        # / (50, 1) columns take 128 lanes.  x (128, 1024) + residual in
        # and out + a0/coef + inv_cn + history.
        b = fused_vmem_bytes(128, 1024, 2, 4, max_iter=50)
        assert b == (128 * 1024 * 4 + 2 * 8 * 1024 * 4 + 2 * 128 * 128 * 4
                     + 128 * 128 * 4 + 56 * 128 * 4)
        assert fused_fits(128, 1024, 2, 4, max_iter=50)
        # A bf16 x halves only the resident design (16-row sublane tiles).
        assert (fused_vmem_bytes(128, 1024, 2, 2, max_iter=50)
                == b - 128 * 1024 * 2)

    def test_kernel_shim_falls_back_to_persweep(self, rng, monkeypatch):
        """Over budget, solvebakp_kernel silently uses the per-sweep loop
        (whose own smaller working set still fits) instead of raising."""
        x, _, y = _system(rng, obs=128, nvars=16)
        # fused needs the whole x resident (~50 KiB padded); the per-sweep
        # loop only two (block, obs) tiles + the residual (~26 KiB).
        monkeypatch.setattr(_CD, "VMEM_BUDGET_BYTES", 32 * 1024)
        r = solvebakp_kernel(jnp.asarray(x.T), jnp.asarray(y), block=8,
                             max_iter=30)
        ref = solvebakp(jnp.asarray(x), jnp.asarray(y), thr=8, max_iter=30)
        np.testing.assert_allclose(np.asarray(r.coef), np.asarray(ref.coef),
                                   rtol=1e-5, atol=1e-5)

    def test_method_falls_back_to_xla(self, rng, monkeypatch):
        """The registry method never raises on oversized designs — it runs
        the XLA path of the same algorithm."""
        x, a, y = _system(rng, obs=128, nvars=16)
        monkeypatch.setattr(_CD, "VMEM_BUDGET_BYTES", 128)
        r = solve(x, y, method="bakp_fused", thr=8, max_iter=60, rtol=1e-9)
        ref = solvebakp(jnp.asarray(x), jnp.asarray(y), thr=8, max_iter=60,
                        rtol=1e-9)
        np.testing.assert_allclose(np.asarray(r.coef), np.asarray(ref.coef),
                                   rtol=1e-6, atol=1e-6)

    def test_engine_falls_back_instead_of_raising(self, rng, monkeypatch):
        """A bakp_fused request on an over-budget bucket is served (XLA
        fallback), not failed."""
        from repro.serve import SolveRequest, SolverServeEngine

        x, a, y = _system(rng, obs=128, nvars=16)
        monkeypatch.setattr(_CD, "VMEM_BUDGET_BYTES", 128)
        engine = SolverServeEngine()
        spec = SolverSpec(method="bakp_fused", thr=8, max_iter=60,
                          rtol=1e-9)
        [served] = engine.serve([SolveRequest(x=x, y=y, spec=spec)])
        assert served.error is None
        np.testing.assert_allclose(served.coef, a, rtol=1e-3, atol=1e-3)


class TestMethodDispatch:
    @pytest.mark.parametrize("method,variant", [("bakp_fused", "bakp"),
                                                ("bak_fused", "bak")])
    def test_registry_entry(self, method, variant):
        e = solver_method(method)
        assert e.iterative and e.multi_rhs and e.blocked
        assert not e.shardable and not e.batchable
        assert e.prepare is not None

    def test_solve_shim(self, rng):
        x, a, y = _system(rng)
        r = solve(x, y, method="bakp_fused", thr=16, max_iter=60, rtol=1e-9)
        np.testing.assert_allclose(np.asarray(r.coef), a, rtol=1e-3,
                                   atol=1e-3)

    def test_prepared_design_handle(self, rng):
        """prepare() warms the transposed design + inv_cn caches; repeated
        handle solves reuse them and match the XLA bakp path."""
        x, _, y = _system(rng, k=4)
        spec = SolverSpec(method="bakp_fused", thr=16, max_iter=40)
        design = prepare(x, spec)
        assert 16 in design._x_t and 16 in design._inv_cn  # prepare hook ran
        r = design.solve(y)
        ref = solvebakp(jnp.asarray(x), jnp.asarray(y), thr=16, max_iter=40)
        np.testing.assert_allclose(np.asarray(r.coef), np.asarray(ref.coef),
                                   rtol=1e-5, atol=1e-5)
        # x_t cache: padded to a thr multiple (64 -> 72), transposed layout
        x_t = design.x_t_for(24)
        assert x_t.shape == (72, x.shape[0])
        np.testing.assert_array_equal(np.asarray(x_t[:64]), x.T)
        assert float(jnp.abs(x_t[64:]).max()) == 0.0

    def test_engine_coalesces_fused_requests(self, rng):
        from repro.serve import SolveRequest, SolverServeEngine

        x, _, _ = _system(rng, obs=256, nvars=32)
        coefs = rng.normal(size=(32, 3)).astype(np.float32)
        spec = SolverSpec(method="bakp_fused", thr=16, max_iter=60,
                          rtol=1e-9)
        engine = SolverServeEngine()
        served = engine.serve([
            SolveRequest(x=x, y=(x @ coefs[:, i]).astype(np.float32),
                         spec=spec, design_key="d0")
            for i in range(3)])
        assert all(s.batch_kind == "multi_rhs" for s in served)
        assert all(s.error is None for s in served)
        for i, s in enumerate(served):
            np.testing.assert_allclose(s.coef, coefs[:, i], rtol=1e-3,
                                       atol=1e-3)

    def test_engine_prefer_fused_upgrade(self, rng):
        """prefer_fused upgrades eligible 'bakp' requests to the megakernel
        and serves identical results."""
        from repro.serve import (ServeConfig, SolveRequest,
                                 SolverServeEngine)

        x, a, y = _system(rng, obs=256, nvars=32)
        req = SolveRequest(x=x, y=y, spec=SolverSpec(
            method="bakp", thr=16, max_iter=60, rtol=1e-9))
        engine = SolverServeEngine(ServeConfig(prefer_fused=True))
        assert engine.spec_for(req).method == "bakp_fused"
        plain = SolverServeEngine()
        assert plain.spec_for(req).method == "bakp"
        [served] = engine.serve([req])
        assert served.error is None
        np.testing.assert_allclose(served.coef, a, rtol=1e-3, atol=1e-3)


class TestValidationAndDonation:
    def test_rejects_bad_shapes(self, rng):
        x, _, y = _system(rng, obs=64, nvars=16)
        with pytest.raises(ValueError, match="multiple of block"):
            fused_solve(jnp.asarray(x.T), jnp.asarray(y), block=10)
        with pytest.raises(ValueError, match="a0"):
            fused_solve(jnp.asarray(x.T), jnp.asarray(y), block=8,
                        a0=jnp.zeros((7,)))
        with pytest.raises(ValueError, match="variant"):
            fused_solve(jnp.asarray(x.T), jnp.asarray(y), block=8,
                        variant="nope")
        with pytest.raises(ValueError, match="max_iter"):
            fused_solve(jnp.asarray(x.T), jnp.asarray(y), block=8,
                        max_iter=0)

    def test_donate_flag_accepted(self, rng):
        """donate is a no-op on CPU but must be accepted on every solver
        entry, and an explicit donate=False must never invalidate inputs."""
        x, _, y = _system(rng, obs=64, nvars=16)
        xd, yd = jnp.asarray(x), jnp.asarray(y)
        r1 = fused_solve(jnp.asarray(x.T), yd, block=8, max_iter=5,
                         donate=False)
        r2 = solvebak(xd, yd, max_iter=5, donate=False)
        r3 = solvebakp(xd, yd, thr=8, max_iter=5, donate=False)
        r4 = solvebakp_kernel(jnp.asarray(x.T), yd, block=8, max_iter=5,
                              donate=False)
        assert float(yd[0]) == y[0]  # y still alive after all four solves
        assert r2.coef.shape == (16,)  # solvebak ran (Algorithm 1)
        np.testing.assert_allclose(np.asarray(r1.residual),
                                   np.asarray(r3.residual), rtol=1e-4,
                                   atol=1e-4)
        np.testing.assert_array_equal(np.asarray(r1.coef),
                                      np.asarray(r4.coef))

    def test_donate_default_on_an_accelerator(self, monkeypatch):
        """The accelerator branch of the donation default (never taken on
        the CPU backend): host operands donate at top level only, device
        arrays never."""
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        host = np.zeros(4, np.float32)
        assert donate_default(None, host, None)
        assert not donate_default(None, jnp.asarray(host))
        assert not donate_default(False, host)
        inside = []
        jax.jit(lambda v: inside.append(donate_default(None, host)) or v)(1.0)
        assert inside == [False]
