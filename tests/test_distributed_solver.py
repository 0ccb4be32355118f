"""Distributed (shard_map) solvers.

The 8-device checks run in a subprocess with forced virtual host devices so
the main test process keeps the single-device view; the regression tests
for the convergence-flag and history bugs run in-process on a trivial
(1, 1) mesh — the sharding machinery is identical, only the axis sizes
differ, so they exercise the exact while_loop state layout that was broken.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import make_system
from repro.core import (solvebakp, solvebakp_rhs_sharded,
                        solvebakp_vars_sharded)
from repro.launch.mesh import make_debug_mesh

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np, jax, jax.numpy as jnp
    from repro.core import (solvebakp_obs_sharded, solvebakp_vars_sharded,
                            solvebakp_2d, solvebakp_rhs_sharded, solvebakp)
    from repro.launch.mesh import make_debug_mesh

    mesh = make_debug_mesh((4, 2), ("data", "model"))
    rng = np.random.default_rng(1)
    x = rng.normal(size=(512, 64)).astype(np.float32)
    a_true = rng.normal(size=(64,)).astype(np.float32)
    y = x @ a_true

    r = solvebakp_obs_sharded(jnp.array(x), jnp.array(y), mesh, thr=16,
                              max_iter=50, mode="gram")
    err = float(np.abs(np.array(r.coef) - a_true).max())
    assert err < 1e-3, f"obs-sharded err {err}"

    # must agree with the single-device gram solver sweep-for-sweep
    r1 = solvebakp(jnp.array(x), jnp.array(y), thr=16, max_iter=5,
                   mode="gram")
    r2 = solvebakp_obs_sharded(jnp.array(x), jnp.array(y), mesh, thr=16,
                               max_iter=5, mode="gram")
    h1, h2 = np.array(r1.history)[:5], np.array(r2.history)[:5]
    np.testing.assert_allclose(h1, h2, rtol=1e-3)

    r = solvebakp_vars_sharded(jnp.array(x), jnp.array(y), mesh, thr=16,
                               max_iter=100, mode="gram", omega=0.5)
    err = float(np.abs(np.array(r.coef) - a_true).max())
    assert err < 1e-3, f"vars-sharded err {err}"

    r = solvebakp_2d(jnp.array(x), jnp.array(y), mesh, thr=16,
                     max_iter=100, mode="gram", omega=0.5)
    err = float(np.abs(np.array(r.coef) - a_true).max())
    assert err < 1e-3, f"2d err {err}"

    # jacobi mode distributed
    r = solvebakp_obs_sharded(jnp.array(x), jnp.array(y), mesh, thr=8,
                              max_iter=80, mode="jacobi")
    err = float(np.abs(np.array(r.coef) - a_true).max())
    assert err < 1e-3, f"obs-sharded jacobi err {err}"

    # ---- multi-RHS + warm starts through every sharded variant ----
    k = 32
    A = rng.normal(size=(64, k)).astype(np.float32)
    Y = x @ A
    ref = solvebakp(jnp.array(x), jnp.array(Y), thr=16, max_iter=20,
                    mode="gram")
    robs = solvebakp_obs_sharded(jnp.array(x), jnp.array(Y), mesh, thr=16,
                                 max_iter=20, mode="gram")
    np.testing.assert_allclose(np.array(robs.coef), np.array(ref.coef),
                               rtol=1e-4, atol=1e-5)
    # rhs-sharded: identical iterates AND identical (global-SSE) history —
    # per-RHS coordinate updates never interact across the k shards.
    rrhs = solvebakp_rhs_sharded(jnp.array(x), jnp.array(Y), mesh, thr=16,
                                 max_iter=20, mode="gram")
    np.testing.assert_allclose(np.array(rrhs.coef), np.array(ref.coef),
                               rtol=1e-5, atol=1e-6)
    # The histories agree to rtol while the SSE carries signal.  Once the
    # solve reaches the fp32 floor, each residual entry is rounding noise of
    # size ~eps·|y|, so the SSE there is ~|Y|²·eps² whatever the reduction
    # order: that is the absolute agreement fp32 can give.
    floor = float(np.sum(Y.astype(np.float64) ** 2)) \\
        * float(np.finfo(np.float32).eps) ** 2
    np.testing.assert_allclose(np.array(rrhs.history)[:20],
                               np.array(ref.history)[:20], rtol=1e-4,
                               atol=floor)

    # warm start from the exact solution: first-sweep residual ~ 0
    for fn, kw in ((solvebakp_obs_sharded, {}),
                   (solvebakp_rhs_sharded, {}),
                   (solvebakp_vars_sharded, dict(omega=0.5)),
                   (solvebakp_2d, dict(omega=0.5))):
        rw = fn(jnp.array(x), jnp.array(Y), mesh, thr=16, max_iter=3,
                mode="gram", a0=jnp.array(A), **kw)
        assert float(rw.sse) < 1e-4, f"{fn.__name__} warm sse {float(rw.sse)}"
    # (vars,) a0 broadcasts across all k right-hand sides
    a1 = rng.normal(size=(64,)).astype(np.float32)
    rb = solvebakp_rhs_sharded(jnp.array(x), jnp.array(Y), mesh, thr=16,
                               max_iter=20, mode="gram", a0=jnp.array(a1))
    rs = solvebakp(jnp.array(x), jnp.array(Y), thr=16, max_iter=20,
                   mode="gram", a0=jnp.array(a1))
    np.testing.assert_allclose(np.array(rb.coef), np.array(rs.coef),
                               rtol=1e-5, atol=1e-6)
    print("DISTRIBUTED_OK")
""")


@pytest.mark.slow
def test_distributed_solvers_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    p = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                       text=True, env=env, timeout=600)
    assert p.returncode == 0, p.stdout + "\n" + p.stderr
    assert "DISTRIBUTED_OK" in p.stdout


# --------------------------------------------------- in-process regressions
@pytest.fixture(scope="module")
def mesh1():
    """Trivial (1, 1) mesh on the test process's single CPU device."""
    return make_debug_mesh((1, 1), ("data", "model"))


class TestVarsShardedHistory:
    def test_history_holds_sse_trace(self, rng, mesh1):
        """Regression: the while_loop state was unpacked as
        ``... converged_h, converged`` and the *converged flag* landed in
        ``SolveResult.history`` (correct only by positional coincidence).
        The history slot must hold the per-sweep SSE trace."""
        x, y, _ = make_system(rng, 128, 32)
        n = 6
        r = solvebakp_vars_sharded(jnp.array(x), jnp.array(y), mesh1, thr=8,
                                   max_iter=n, mode="gram", omega=0.5)
        h = np.array(r.history)
        assert h.shape == (n,)
        assert np.all(np.isfinite(h[:n]))
        # a real SSE trace: positive, non-increasing, starting below ||y||²
        assert h[0] <= float(np.dot(y, y)) + 1e-3
        assert np.all(np.diff(h) <= 1e-5 * np.maximum(h[:-1], 1.0))
        # on a 1-device mesh vars-sharding is the single-device solver
        ref = solvebakp(jnp.array(x), jnp.array(y), thr=8, max_iter=n,
                        mode="gram", omega=0.5)
        np.testing.assert_allclose(h, np.array(ref.history), rtol=1e-5)


def _diverging_system(rng, obs=256, nvars=32):
    """Strongly correlated columns: Jacobi-within-block with thr=nvars
    diverges at ω=1 (the paper's remedy is small thr; we *want* the blowup
    here)."""
    base = rng.normal(size=(obs, 1)).astype(np.float32)
    x = base + 0.01 * rng.normal(size=(obs, nvars)).astype(np.float32)
    return x, (x @ np.ones(nvars, np.float32))


class TestDivergenceFlag:
    """Regression: ``(sse_prev - sse) <= rtol * sse_prev`` is trivially true
    when SSE *increases*, so a diverging solve used to stop after one sweep
    with ``converged=True``.  It must still stop early, but say False."""

    def test_single_device(self, rng):
        x, y = _diverging_system(rng)
        r = solvebakp(jnp.array(x), jnp.array(y), thr=32, max_iter=50,
                      mode="jacobi", rtol=1e-8)
        h = np.array(r.history)
        assert h[0] > float(np.dot(y, y))       # genuinely diverging
        assert not bool(r.converged)
        assert int(r.n_sweeps) < 50             # early exit retained

    def test_sharded(self, rng, mesh1):
        x, y = _diverging_system(rng)
        r = solvebakp_vars_sharded(jnp.array(x), jnp.array(y), mesh1,
                                   thr=32, max_iter=50, mode="jacobi",
                                   omega=1.0, rtol=1e-8)
        assert not bool(r.converged)
        assert int(r.n_sweeps) < 50

    def test_converging_still_reports_true(self, rng):
        x, y, _ = make_system(rng, 200, 16)
        r = solvebakp(jnp.array(x), jnp.array(y), thr=8, max_iter=100,
                      mode="gram", rtol=1e-10)
        assert bool(r.converged)
        assert int(r.n_sweeps) < 100

    def test_warm_start_at_optimum_is_converged(self):
        """A warm start already at the fixed point sits AT the accuracy
        floor, so the first sweep's float-noise SSE wobble may land a hair
        above sse0 — that is a stall (converged=True), not divergence.
        Several seeds: the wobble's sign is seed-dependent."""
        for seed in range(8):
            r = np.random.default_rng(seed)
            x = r.normal(size=(256, 32)).astype(np.float32)
            y = (x @ r.normal(size=(32,)).astype(np.float32)
                 + 0.1 * r.normal(size=(256,)).astype(np.float32))
            a_opt = np.linalg.lstsq(x.astype(np.float64),
                                    y.astype(np.float64), rcond=None)[0]
            res = solvebakp(jnp.array(x), jnp.array(y), thr=8, max_iter=50,
                            mode="gram", rtol=1e-6,
                            a0=jnp.array(a_opt.astype(np.float32)))
            assert bool(res.converged), f"seed {seed}"
            assert int(res.n_sweeps) <= 2, f"seed {seed}"


class TestRhsShardedApi:
    def test_requires_multi_rhs(self, rng, mesh1):
        x, y, _ = make_system(rng, 64, 8)
        with pytest.raises(ValueError, match="multi-RHS"):
            solvebakp_rhs_sharded(jnp.array(x), jnp.array(y), mesh1, thr=8)

    def test_one_device_matches_single(self, rng, mesh1):
        x, _, _ = make_system(rng, 96, 12)
        A = rng.normal(size=(12, 4)).astype(np.float32)
        Y = jnp.array(x @ A)
        r1 = solvebakp_rhs_sharded(jnp.array(x), Y, mesh1, thr=8,
                                   max_iter=15, mode="gram")
        r2 = solvebakp(jnp.array(x), Y, thr=8, max_iter=15, mode="gram")
        np.testing.assert_allclose(np.array(r1.coef), np.array(r2.coef),
                                   rtol=1e-5, atol=1e-6)

    def test_bad_a0_shape_raises(self, rng, mesh1):
        x, _, _ = make_system(rng, 64, 8)
        Y = jnp.array(rng.normal(size=(64, 2)).astype(np.float32))
        with pytest.raises(ValueError, match="a0 must be"):
            solvebakp_rhs_sharded(jnp.array(x), Y, mesh1, thr=8,
                                  a0=jnp.zeros((5,)))

    def test_tolerances_do_not_retrace(self, rng, mesh1):
        """atol/rtol are traced operands of the sharded programs: the
        serving engine's padding-corrected atol varies with real group
        size, and must never force a shard_map recompile."""
        from repro.core.distributed import _sharded_program
        x, _, _ = make_system(rng, 96, 12)
        Y = jnp.array(rng.normal(size=(96, 4)).astype(np.float32))
        before = _sharded_program.cache_info().currsize
        for atol, rtol in ((0.0, 0.0), (0.013, 1e-7), (0.250, 1e-9)):
            solvebakp_rhs_sharded(jnp.array(x), Y, mesh1, thr=8,
                                  max_iter=5, mode="gram", atol=atol,
                                  rtol=rtol)
        after = _sharded_program.cache_info().currsize
        assert after - before <= 1  # one program serves every tolerance


def test_mesh_builder_no_axistype_needed():
    """make_debug_mesh builds a mesh over the visible devices."""
    m = make_debug_mesh((1,), ("data",))
    assert m.shape["data"] == 1
    assert jax.devices()[0] in list(m.devices.flat)
