"""Mixed-precision X streaming (SolverSpec.precision) — ISSUE 7.

Parity gates: ``precision="bf16"`` (bf16 X storage, fp32 accumulators, no
polish) must land within 1e-2 of the fp32 solve; ``"bf16_fp32acc"`` (plus
the fp32 iterative-refinement polish) within 1e-5 — across single/multi-RHS
x warm/cold starts x every kernel path (fused, per-sweep bf16 stream, and
the engine's downgrade-to-fp32 route).

The VMEM-budget tests monkeypatch ``repro.kernels.cd_sweep.
VMEM_BUDGET_BYTES`` (reached via importlib — the package re-exports a
*function* named ``cd_sweep``) and pick a budget strictly between the bf16
and fp32 fused working sets: the acceptance criterion is that such a design
dispatches FUSED at bf16 (no XLA fallback) while the fp32 spec falls back.
"""
import importlib

import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import (PRECISIONS, SolverSpec, UnsupportedSpecError,
                        methods_for_precision, prepare)
from repro.core.types import column_norms_sq, column_norms_sq_t
from repro.kernels.cd_sweep import sweep_vmem_bytes
from repro.kernels.fused_solve import fused_vmem_bytes

_CD = importlib.import_module("repro.kernels.cd_sweep")


def _well_conditioned(rng, obs=512, nvars=64, k=None):
    """Design with singular values in [1, 2] (CD converges fast and the
    fp32/bf16 gap is representation error, not conditioning amplification),
    plus consistent right-hand side(s) and the true coefficients."""
    q1 = np.linalg.qr(rng.normal(size=(obs, nvars)))[0]
    q2 = np.linalg.qr(rng.normal(size=(nvars, nvars)))[0]
    x = (q1 * np.linspace(1.0, 2.0, nvars)) @ q2
    x = x.astype(np.float32)
    shape = (nvars,) if k is None else (nvars, k)
    a = rng.normal(size=shape).astype(np.float32)
    y = (x @ a).astype(np.float32)
    return x, a, y


def _max_err(got, want):
    return float(np.max(np.abs(np.asarray(got) - np.asarray(want))))


class TestSpecSurface:
    def test_precisions_tuple(self):
        assert PRECISIONS == ("fp32", "bf16", "bf16_fp32acc")
        assert set(methods_for_precision("bf16")) == {"bakp_fused",
                                                      "bak_fused",
                                                      "bakp_stream"}
        assert "bakp" in methods_for_precision("fp32")

    def test_malformed_precision_is_value_error(self):
        with pytest.raises(ValueError, match="precision"):
            SolverSpec(method="bakp_fused", precision="fp16")

    def test_unsupported_method_precision_raises_typed(self, rng):
        x, _, y = _well_conditioned(rng, obs=64, nvars=16)
        bad = SolverSpec(method="bakp", precision="bf16", thr=8)
        with pytest.raises(UnsupportedSpecError):
            prepare(x, bad)
        design = prepare(x, SolverSpec(method="bakp", thr=8))
        with pytest.raises(UnsupportedSpecError):
            design.solve(y, spec=bad)
        # The typed error is still a ValueError (pre-existing handlers).
        assert issubclass(UnsupportedSpecError, ValueError)


class TestParityFused:
    @pytest.mark.parametrize("variant", ["bakp_fused", "bak_fused"])
    @pytest.mark.parametrize("k", [None, 4])
    @pytest.mark.parametrize("warm", [False, True])
    def test_bf16_and_refined_vs_fp32(self, rng, variant, k, warm):
        x, a, y = _well_conditioned(rng, k=k)
        base = SolverSpec(method=variant, thr=16, max_iter=200, rtol=1e-12)
        design = prepare(x, base)
        a0 = None if not warm else (0.8 * a).astype(np.float32)
        r32 = design.solve(y, a0=a0)
        rbf = design.solve(y, a0=a0, spec=base.replace(precision="bf16"))
        racc = design.solve(y, a0=a0,
                            spec=base.replace(precision="bf16_fp32acc",
                                              refine_sweeps=8))
        assert _max_err(rbf.coef, r32.coef) <= 1e-2
        assert _max_err(racc.coef, r32.coef) <= 1e-5
        # The polish accounts for its sweeps and extends the history.
        assert racc.history.shape[0] == base.max_iter + 8

    def test_warm_cold_equivalence_of_quantized_tier(self, rng):
        """The bf16 tier is cast once and cached; warm (repeat) solves see
        the identical resident copy, so results are bit-stable."""
        x, _, y = _well_conditioned(rng, obs=256, nvars=32)
        spec = SolverSpec(method="bakp_fused", thr=16, max_iter=50,
                          precision="bf16")
        design = prepare(x, spec)
        cold = design.solve(y)
        warm = design.solve(y)
        np.testing.assert_array_equal(np.asarray(cold.coef),
                                      np.asarray(warm.coef))


class TestDispatchPaths:
    def test_bf16_only_fits_fused_dispatches_fused(self, rng, monkeypatch):
        """Acceptance: a design over the fp32 fused budget but inside it at
        bf16 runs FUSED under a bf16 precision (no XLA fallback), while the
        fp32 spec falls back."""
        x, a, y = _well_conditioned(rng, obs=512, nvars=64)
        spec32 = SolverSpec(method="bakp_fused", thr=16, max_iter=40,
                            rtol=1e-10)
        need32 = fused_vmem_bytes(64, 512, 1, 4, max_iter=40)
        need16 = fused_vmem_bytes(64, 512, 1, 2, max_iter=40)
        budget = (need32 + need16) // 2
        monkeypatch.setattr(_CD, "VMEM_BUDGET_BYTES", budget)
        design = prepare(x, spec32)
        obs.consume_dispatch()
        r32 = design.solve(y)
        assert obs.consume_dispatch() == "xla"
        racc = design.solve(y, spec=spec32.replace(precision="bf16_fp32acc",
                                                   refine_sweeps=8))
        assert obs.consume_dispatch() == "fused"
        assert _max_err(racc.coef, r32.coef) <= 1e-5
        np.testing.assert_allclose(np.asarray(racc.coef), a, rtol=1e-3,
                                   atol=1e-3)

    def test_bf16_over_budget_streams_persweep(self, rng, monkeypatch):
        """A bf16 solve too large even for the halved fused footprint keeps
        the bf16 per-sweep stream (halved HBM traffic), not the fp32 XLA
        path, and refinement still recovers fp32 accuracy."""
        x, _, y = _well_conditioned(rng, obs=512, nvars=64)
        spec = SolverSpec(method="bakp_fused", thr=16, max_iter=120,
                          rtol=1e-12)
        design = prepare(x, spec)
        r32 = design.solve(y)
        need16 = fused_vmem_bytes(64, 512, 1, 2, max_iter=120)
        sweep16 = sweep_vmem_bytes(512, 1, 2, block=16)
        assert sweep16 < need16
        monkeypatch.setattr(_CD, "VMEM_BUDGET_BYTES",
                            (sweep16 + need16) // 2)
        obs.consume_dispatch()
        rbf = design.solve(y, spec=spec.replace(precision="bf16"))
        assert obs.consume_dispatch() == "persweep"
        assert _max_err(rbf.coef, r32.coef) <= 1e-2
        racc = design.solve(y, spec=spec.replace(precision="bf16_fp32acc",
                                                 refine_sweeps=8))
        assert _max_err(racc.coef, r32.coef) <= 1e-5


class TestQuantizedCacheTier:
    def test_x_bf16_for_cached_and_layouted(self, rng):
        x, _, _ = _well_conditioned(rng, obs=128, nvars=24)
        design = prepare(x)
        xb = design.x_bf16_for(16)
        assert xb.dtype == jnp.bfloat16
        assert xb.shape == (32, 128)  # thr-padded transposed layout
        assert design.x_bf16_for(16) is xb  # memoised
        np.testing.assert_array_equal(
            np.asarray(xb, np.float32),
            np.asarray(design.x_t_for(16).astype(jnp.bfloat16), np.float32))

    def test_prepare_hook_warms_quantized_tier(self, rng):
        x, _, _ = _well_conditioned(rng, obs=128, nvars=24)
        d32 = prepare(x, SolverSpec(method="bakp_fused", thr=8))
        assert 8 in d32._x_t and 8 not in d32._x_bf16
        dbf = prepare(x, SolverSpec(method="bakp_fused", thr=8,
                                    precision="bf16"))
        assert 8 in dbf._x_bf16  # dispatcher pre-warm path hits this hook

    def test_norms_accumulate_fp32_on_bf16_input(self, rng):
        """Satellite bugfix: column_norms_sq(_t) must produce fp32 sums of
        the bf16 values — an in-dtype (bf16) accumulation loses ~2 decimal
        digits that inv_cn then amplifies in every sweep."""
        x = rng.normal(size=(2048, 8)).astype(np.float32)
        xb = jnp.asarray(x).astype(jnp.bfloat16)
        got_t = column_norms_sq_t(jnp.swapaxes(xb, 0, 1))
        got = column_norms_sq(xb)
        assert got.dtype == jnp.float32 and got_t.dtype == jnp.float32
        ref = np.sum(np.asarray(xb, np.float64) ** 2, axis=0)
        np.testing.assert_allclose(np.asarray(got), ref, rtol=1e-3)
        np.testing.assert_allclose(np.asarray(got_t), ref, rtol=1e-3)
        # fp32 inputs keep their exact pre-PR behaviour.
        np.testing.assert_allclose(
            np.asarray(column_norms_sq(jnp.asarray(x))),
            np.sum(x.astype(np.float64) ** 2, axis=0), rtol=1e-5)


class TestServingPrecision:
    def _engine(self, **cfg):
        from repro.serve import ServeConfig, SolverServeEngine

        return SolverServeEngine(ServeConfig(**cfg),
                                 registry=obs.MetricsRegistry())

    def test_engine_serves_bf16_and_labels_latency(self, rng):
        from repro.serve import SolveRequest

        x, _, _ = _well_conditioned(rng, obs=256, nvars=32)
        coefs = rng.normal(size=(32, 3)).astype(np.float32)
        spec = SolverSpec(method="bakp_fused", thr=16, max_iter=200,
                          rtol=1e-12, precision="bf16_fp32acc")
        eng = self._engine()
        served = eng.serve([
            SolveRequest(x=x, y=(x @ coefs[:, i]).astype(np.float32),
                         spec=spec, design_key="d0")
            for i in range(3)])
        assert all(s.ok for s in served)
        assert all(s.batch_kind == "multi_rhs" for s in served)
        for i, s in enumerate(served):
            np.testing.assert_allclose(s.coef, coefs[:, i], rtol=1e-4,
                                       atol=1e-4)
        lat = eng.registry.get("serve_solve_latency_seconds")
        assert lat.count(precision="bf16_fp32acc") == 1
        assert lat.count(precision="fp32") == 0

    def test_engine_downgrades_unsupported_precision(self, rng):
        """A precision its method can't run is served at fp32 (identical
        results to an fp32 request), never an error, and counts one
        solver_fallback_total{reason="precision"} per request."""
        from repro.serve import SolveRequest

        x, a, y = _well_conditioned(rng, obs=256, nvars=32)
        eng = self._engine()
        bad = SolverSpec(method="bakp_gram", thr=16, max_iter=60,
                         rtol=1e-10, precision="bf16")
        [served] = eng.serve([SolveRequest(x=x, y=y, spec=bad)])
        assert served.ok
        good = SolverSpec(method="bakp_gram", thr=16, max_iter=60,
                          rtol=1e-10)
        [ref] = self._engine().serve([SolveRequest(x=x, y=y, spec=good)])
        np.testing.assert_array_equal(served.coef, ref.coef)
        fb = eng.registry.get("solver_fallback_total")
        assert fb.value(method="bakp_gram", reason="precision") == 1.0
        # Counted once per request, not once per spec_for call.
        eng.serve([SolveRequest(x=x, y=y, spec=bad),
                   SolveRequest(x=x, y=y, spec=bad)])
        assert fb.value(method="bakp_gram", reason="precision") == 3.0

    def test_engine_precision_policy_on_legacy_requests(self, rng):
        """ServeConfig.precision applies to legacy per-field requests like
        omega/ridge; with prefer_fused the upgraded method carries it."""
        from repro.serve import SolveRequest

        x, a, y = _well_conditioned(rng, obs=256, nvars=32)
        eng = self._engine(precision="bf16_fp32acc", prefer_fused=True)
        req = SolveRequest(x=x, y=y, method="bakp", thr=16, max_iter=200,
                           rtol=1e-12)
        eff = eng.spec_for(req)
        assert eff.method == "bakp_fused"
        assert eff.precision == "bf16_fp32acc"
        [served] = eng.serve([req])
        assert served.ok and served.telemetry.kernel_path == "fused"
        np.testing.assert_allclose(served.coef, a, rtol=1e-4, atol=1e-4)
        # An explicit spec stays authoritative over the engine policy.
        explicit = SolveRequest(x=x, y=y, spec=SolverSpec(
            method="bakp_fused", thr=16, max_iter=50))
        assert eng.spec_for(explicit).precision == "fp32"

    def test_prefer_fused_upgrade_uses_bf16_headroom(self, rng,
                                                     monkeypatch):
        """A bucket over the fp32 fused budget still upgrades bakp ->
        bakp_fused when the bf16 footprint fits."""
        from repro.serve import SolveRequest

        x, _, y = _well_conditioned(rng, obs=512, nvars=64)
        # Bucket pads to (512, 64); thr=16 keeps vars_pb=64.
        need32 = fused_vmem_bytes(64, 512, 1, 4, max_iter=40)
        need16 = fused_vmem_bytes(64, 512, 1, 2, max_iter=40)
        monkeypatch.setattr(_CD, "VMEM_BUDGET_BYTES",
                            (need32 + need16) // 2)
        req = SolveRequest(x=x, y=y, method="bakp", thr=16, max_iter=40)
        assert self._engine(prefer_fused=True).spec_for(req).method == "bakp"
        eng = self._engine(prefer_fused=True, precision="bf16_fp32acc")
        assert eng.spec_for(req).method == "bakp_fused"
