"""Algorithm 2 (SolveBakP) — block CD, gram mode, property tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

# hypothesis is optional (requirements-dev.txt): only the property test
# skips without it; the deterministic solver tests always run.
try:
    from hypothesis import given, settings, strategies as st
    HAS_HYPOTHESIS = True
except ImportError:
    HAS_HYPOTHESIS = False

from conftest import blocked_system, make_system
from repro.core import SolverSpec, solvebakp
from repro.core.methods import _bakp_vmap_one
from repro.core.solvebakp import block_gram_cholesky


class TestSolveBakP:
    @pytest.mark.parametrize("thr", [1, 4, 16, 64])
    def test_thr_sweep(self, rng, thr):
        x, y, a_true = make_system(rng, 600, 48)
        res = solvebakp(jnp.array(x), jnp.array(y), thr=thr, max_iter=80,
                        mode="jacobi")
        np.testing.assert_allclose(np.array(res.coef), a_true, rtol=1e-3,
                                   atol=1e-3)

    @pytest.mark.parametrize("thr", [4, 16, 48])
    def test_gram_mode(self, rng, thr):
        x, y, a_true = make_system(rng, 600, 48)
        res = solvebakp(jnp.array(x), jnp.array(y), thr=thr, max_iter=40,
                        mode="gram")
        np.testing.assert_allclose(np.array(res.coef), a_true, rtol=1e-3,
                                   atol=1e-3)

    def test_gram_beats_jacobi_on_correlated(self, rng):
        """Beyond-paper claim: exact block CD converges faster on systems
        with correlated columns inside a block."""
        base = rng.normal(size=(500, 8)).astype(np.float32)
        # 32 columns, groups of 4 strongly correlated
        x = np.concatenate(
            [base[:, i // 4: i // 4 + 1] + 0.1 * rng.normal(
                size=(500, 1)).astype(np.float32) for i in range(32)], axis=1)
        a = rng.normal(size=(32,)).astype(np.float32)
        y = x @ a
        rj = solvebakp(jnp.array(x), jnp.array(y), thr=8, max_iter=20,
                       mode="jacobi", omega=0.5)
        rg = solvebakp(jnp.array(x), jnp.array(y), thr=8, max_iter=20,
                       mode="gram")
        assert float(rg.sse) < float(rj.sse)

    def test_non_divisible_vars_padding(self, rng):
        x, y, a_true = make_system(rng, 300, 37)  # 37 % 16 != 0
        res = solvebakp(jnp.array(x), jnp.array(y), thr=16, max_iter=60,
                        mode="gram")
        np.testing.assert_allclose(np.array(res.coef), a_true, rtol=1e-3,
                                   atol=1e-3)

    def test_block_gram_cholesky_shapes(self, rng):
        x = rng.normal(size=(100, 32)).astype(np.float32)
        chol = block_gram_cholesky(jnp.array(x), 8, 1e-6)
        assert chol.shape == (4, 8, 8)
        g = np.einsum("obt,obs->bts", x.reshape(100, 4, 8),
                      x.reshape(100, 4, 8)) + 1e-6 * np.eye(8)
        np.testing.assert_allclose(np.array(chol @ chol.transpose(0, 2, 1)),
                                   g, rtol=1e-3, atol=1e-3)

    if HAS_HYPOTHESIS:
        @settings(max_examples=20, deadline=None)
        @given(obs=st.integers(24, 200), nvars=st.integers(2, 40),
               thr=st.sampled_from([2, 4, 8]), seed=st.integers(0, 2**30))
        def test_property_monotone_and_bounded(self, obs, nvars, thr, seed):
            """Property (Theorem 1): for any random system, SSE after any
            number of gram-mode sweeps is non-increasing and ≤ ||y||²."""
            r = np.random.default_rng(seed)
            x = r.normal(size=(obs, nvars)).astype(np.float32)
            y = r.normal(size=(obs,)).astype(np.float32)
            res = solvebakp(jnp.array(x), jnp.array(y), thr=thr, max_iter=10,
                            mode="gram")
            h = np.array(res.history)
            h = h[~np.isnan(h)]
            y2 = float(np.sum(y * y))
            assert h[0] <= y2 * (1 + 1e-4) + 1e-4
            assert np.all(np.diff(h) <= 1e-3 * h[:-1] + 1e-5)
    else:
        @pytest.mark.skip(reason="hypothesis not installed")
        def test_property_monotone_and_bounded(self):
            pass


@pytest.mark.parametrize("warm", [False, True])
@pytest.mark.parametrize("k", [1, 4])
@pytest.mark.parametrize("thr", [48, 128])
@pytest.mark.parametrize("mode", ["gram", "jacobi"])
def test_blocked_solve_matches_lstsq(mode, thr, k, warm):
    """Each column block is read straight out of x (the last one through
    the zero padding): the blocked solve reaches the float64 solution for
    every block width, RHS count and start."""
    x, y, ref, a0 = blocked_system(14, k)
    res = solvebakp(jnp.array(x), jnp.array(y), thr=thr, max_iter=60,
                    mode=mode, a0=jnp.array(a0) if warm else None)
    assert res.coef.shape == ref.shape
    np.testing.assert_allclose(np.array(res.coef), ref, rtol=0, atol=5e-6)
    np.testing.assert_allclose(np.array(res.residual),
                               y - x @ np.array(res.coef), rtol=0,
                               atol=2e-4)
    if warm:  # the start is honoured: the first sweep begins near ref
        cold = solvebakp(jnp.array(x), jnp.array(y), thr=thr, max_iter=1,
                         mode=mode)
        assert float(res.history[0]) < float(cold.history[0])


@pytest.mark.parametrize("warm", [False, True])
@pytest.mark.parametrize("mode", ["gram", "jacobi"])
def test_vmapped_batch_matches_lstsq(mode, warm):
    """The engine's same-shape batch path (``vmap`` over designs, so the
    block slice's axis moves under it) solves two designs of one shape."""
    thr, k = 48, 4
    systems = [blocked_system(seed, k) for seed in (21, 22)]
    xs = jnp.array(np.stack([s[0] for s in systems]))
    ys = jnp.array(np.stack([s[1] for s in systems]))
    a0s = jnp.array(np.stack([s[3] for s in systems]))
    nvars = xs.shape[2]
    pad = -nvars % thr
    x_pad = jnp.pad(xs, ((0, 0), (0, 0), (0, pad)))
    cns = jnp.sum(x_pad * x_pad, axis=1)
    method = "bakp_gram" if mode == "gram" else "bakp"
    one = _bakp_vmap_one(mode)(SolverSpec(method=method, thr=thr,
                                          max_iter=60))
    args = [xs, ys, cns, jnp.zeros((2,), jnp.float32)]
    if mode == "gram":
        args.append(jax.vmap(lambda x: block_gram_cholesky(x, thr, 1e-6))(
            x_pad))
    if warm:
        args.append(a0s)
    res = jax.jit(jax.vmap(one))(*args)
    for i, s in enumerate(systems):
        np.testing.assert_allclose(np.array(res.coef[i]), s[2], rtol=0,
                                   atol=5e-6)
