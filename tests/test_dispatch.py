"""repro.serve.dispatch: flush policy, backpressure, deadlines, warm starts."""
import time

import numpy as np
import pytest

from conftest import make_system
from repro import obs
from repro.serve import (AsyncDispatcher, DispatchConfig, QueueFullError,
                         ServeConfig, SolveRequest, SolverServeEngine)


def _lstsq(x, y):
    return np.linalg.lstsq(np.asarray(x, np.float64),
                           np.asarray(y, np.float64), rcond=None)[0]


def _req(x, y, **kw):
    kw.setdefault("method", "bakp_gram")
    kw.setdefault("thr", 8)
    kw.setdefault("max_iter", 60)
    kw.setdefault("rtol", 1e-12)
    return SolveRequest(x=x, y=y, **kw)


# ------------------------------------------------------- flush policy (unit)
class TestFlushPolicy:
    """Drive _admit/_fire_ready directly — no threads, no timing races."""

    def _dispatcher(self, **kw):
        return AsyncDispatcher(SolverServeEngine(),
                               DispatchConfig(prewarm_cache=False, **kw))

    def _ticket(self, disp, req, deadline_s=None):
        from repro.serve.dispatch import SolveTicket
        t = SolveTicket(req, None if deadline_s is None
                        else obs.now() + deadline_s)
        disp._admit(t)
        return t

    def test_fires_when_full(self, rng):
        disp = self._dispatcher(max_batch=3, idle_timeout_s=1e9)
        x, y, _ = make_system(rng, 40, 4)
        for _ in range(2):
            self._ticket(disp, _req(x, y, design_key="d"))
        assert disp._fire_ready(obs.now()) == []
        self._ticket(disp, _req(x, y, design_key="d"))
        fired = disp._fire_ready(obs.now())
        assert len(fired) == 1 and len(fired[0][2]) == 3
        assert fired[0][0].label == "single:xla"
        assert disp.stats.fired_full == 1
        assert not disp._pending

    def test_deadline_ordered_flushing(self, rng):
        """The batch holding the most urgent deadline fires first, even when
        a looser-deadline batch was admitted earlier."""
        disp = self._dispatcher(max_batch=100, idle_timeout_s=1e9,
                                deadline_margin_s=0.5)
        x1, y1, _ = make_system(rng, 40, 4)
        x2, y2, _ = make_system(rng, 400, 40)  # different bucket
        loose = self._ticket(disp, _req(x1, y1, design_key="a"),
                             deadline_s=0.2)
        tight = self._ticket(disp, _req(x2, y2, design_key="b"),
                             deadline_s=0.1)
        fired = disp._fire_ready(obs.now())
        assert [b[2][0] for b in fired] == [tight, loose]
        assert [b[1] for b in fired] == sorted(b[1] for b in fired)
        assert disp.stats.fired_deadline == 2

    def test_burst_fires_in_max_batch_chunks(self, rng):
        """max_batch bounds each fired solve even when a burst lands in
        one dispatch iteration."""
        disp = self._dispatcher(max_batch=4, idle_timeout_s=1e9)
        x, y, _ = make_system(rng, 40, 4)
        for _ in range(10):
            self._ticket(disp, _req(x, y, design_key="d"))
        fired = disp._fire_ready(obs.now())
        assert [len(c) for _, _, c in fired] == [4, 4, 2]
        assert disp.stats.fired_full == 3

    def test_deadline_not_fired_outside_margin(self, rng):
        disp = self._dispatcher(max_batch=100, idle_timeout_s=1e9,
                                deadline_margin_s=0.01)
        x, y, _ = make_system(rng, 40, 4)
        self._ticket(disp, _req(x, y, design_key="d"), deadline_s=60.0)
        assert disp._fire_ready(obs.now()) == []

    def test_idle_timeout_fires(self, rng):
        disp = self._dispatcher(max_batch=100, idle_timeout_s=0.01)
        x, y, _ = make_system(rng, 40, 4)
        self._ticket(disp, _req(x, y, design_key="d"))
        assert disp._fire_ready(obs.now()) == []
        time.sleep(0.02)
        fired = disp._fire_ready(obs.now())
        assert len(fired) == 1
        assert disp.stats.fired_idle == 1

    def test_invalid_request_fails_ticket_at_admit(self, rng):
        disp = self._dispatcher()
        x, y, _ = make_system(rng, 40, 4)
        t = self._ticket(disp, SolveRequest(x=x, y=y[:-1]))
        assert t.done()
        with pytest.raises(ValueError, match="y must be"):
            t.result(timeout=0)


# ----------------------------------------------------------- backpressure
class TestBackpressure:
    def test_reject_policy_raises(self, rng):
        """With nothing firing, the (max_queue+1)-th submit is rejected."""
        x, y, _ = make_system(rng, 40, 4)
        cfg = DispatchConfig(max_queue=3, backpressure="reject",
                             max_batch=100, idle_timeout_s=1e9)
        with AsyncDispatcher(SolverServeEngine(), cfg) as disp:
            tickets = [disp.submit(_req(x, y, design_key="d"))
                       for _ in range(3)]
            with pytest.raises(QueueFullError):
                disp.submit(_req(x, y, design_key="d"))
            assert disp.stats.rejected == 1
            # Accepted requests still complete on drain.
            assert disp.drain(timeout=120)
            assert all(t.result(timeout=1).ok for t in tickets)

    def test_block_policy_completes_everything(self, rng):
        x, y, _ = make_system(rng, 40, 4)
        cfg = DispatchConfig(max_queue=2, backpressure="block",
                             max_batch=2, idle_timeout_s=0.005)
        with AsyncDispatcher(SolverServeEngine(), cfg) as disp:
            tickets = [disp.submit(_req(x, y, design_key="d"))
                       for _ in range(6)]  # blocks, never raises
            assert disp.drain(timeout=120)
        assert all(t.result(timeout=1).ok for t in tickets)
        assert disp.stats.rejected == 0
        assert disp.stats.submitted == 6

    def test_bad_backpressure_rejected(self):
        with pytest.raises(ValueError, match="backpressure"):
            AsyncDispatcher(config=DispatchConfig(backpressure="drop"))

    def test_stop_without_drain_fails_pending(self, rng):
        """stop(drain=False) abandons queued work instead of serving it."""
        from repro.serve import DispatcherStopped
        x, y, _ = make_system(rng, 40, 4)
        cfg = DispatchConfig(max_batch=100, idle_timeout_s=1e9)
        disp = AsyncDispatcher(SolverServeEngine(), cfg).start()
        tickets = [disp.submit(_req(x, y, design_key="d")) for _ in range(3)]
        disp.stop(drain=False)
        for t in tickets:
            assert t.done()
            with pytest.raises(DispatcherStopped):
                t.result(timeout=1)
        with pytest.raises(DispatcherStopped):
            disp.submit(_req(x, y))


# ------------------------------------------------------------- end to end
class TestAsyncEndToEnd:
    def test_matches_synchronous_engine(self, rng):
        """Same requests through the dispatcher and a plain engine flush
        produce identical coefficients (same batching, same programs)."""
        x_shared = rng.normal(size=(300, 24)).astype(np.float32)
        reqs = []
        for i in range(4):  # same design -> multi-RHS group
            a = rng.normal(size=(24,)).astype(np.float32)
            reqs.append((x_shared, x_shared @ a, "s"))
        for i in range(2):  # unique designs, same bucket -> vmap
            xu = rng.normal(size=(290, 20)).astype(np.float32)
            reqs.append((xu, xu @ np.ones(20, np.float32), f"u{i}"))

        sync = SolverServeEngine().serve(
            [_req(x, y, thr=16, design_key=k) for x, y, k in reqs])

        cfg = DispatchConfig(max_batch=len(reqs), idle_timeout_s=0.01)
        with AsyncDispatcher(SolverServeEngine(), cfg) as disp:
            tickets = [disp.submit(_req(x, y, thr=16, design_key=k))
                       for x, y, k in reqs]
            results = [t.result(timeout=120) for t in tickets]

        for s, r in zip(sync, results):
            assert r.ok
            assert r.batch_kind == s.batch_kind
            np.testing.assert_array_equal(r.coef, s.coef)

    def test_deadline_reporting(self, rng):
        x, y, _ = make_system(rng, 40, 4)
        cfg = DispatchConfig(max_batch=4, idle_timeout_s=0.005)
        with AsyncDispatcher(SolverServeEngine(), cfg) as disp:
            tickets = [disp.submit(_req(x, y, design_key="d"),
                                   deadline_s=120.0) for _ in range(4)]
            results = [t.result(timeout=120) for t in tickets]
        assert all(r.ok for r in results)
        assert all(t.deadline_met for t in tickets)
        assert all(t.latency_s is not None and t.latency_s >= 0
                   for t in tickets)
        assert disp.stats.deadline_misses == 0
        assert disp.stats.deadline_hit_rate == 1.0
        assert disp.stats.completed == 4


# -------------------------------------------------------------- warm starts
class TestWarmStart:
    def test_warm_matches_cold_within_rtol(self, rng):
        """A tenant's warm-started re-solve lands on the cold answer."""
        x = rng.normal(size=(300, 24)).astype(np.float32)
        a = rng.normal(size=(24,)).astype(np.float32)
        a2 = a + 0.01 * rng.normal(size=24).astype(np.float32)

        warm_eng = SolverServeEngine()
        warm_eng.serve([_req(x, x @ a, thr=16, design_key="d",
                             tenant_id="t")])
        warm, = warm_eng.serve([_req(x, x @ a2, thr=16, design_key="d",
                                     tenant_id="t")])
        cold, = SolverServeEngine().serve(
            [_req(x, x @ a2, thr=16, design_key="d")])

        assert warm.warm_start and not cold.warm_start
        np.testing.assert_allclose(warm.coef, cold.coef, rtol=1e-4,
                                   atol=1e-5)
        np.testing.assert_allclose(warm.coef, _lstsq(x, x @ a2), rtol=1e-3,
                                   atol=1e-3)

    def test_warm_and_cold_coalesce(self, rng):
        """Warm and cold tenants merge into ONE multi-RHS solve and each
        still gets the right answer (cold rides a zero a0 column)."""
        x = rng.normal(size=(300, 24)).astype(np.float32)
        eng = SolverServeEngine()
        a_warm = rng.normal(size=(24,)).astype(np.float32)
        eng.serve([_req(x, x @ a_warm, thr=16, design_key="d",
                        tenant_id="veteran")])

        a_new = rng.normal(size=(24,)).astype(np.float32)
        drifted = a_warm + 0.01 * rng.normal(size=24).astype(np.float32)
        out = eng.serve([
            _req(x, x @ drifted, thr=16, design_key="d",
                 tenant_id="veteran"),
            _req(x, x @ a_new, thr=16, design_key="d", tenant_id="rookie"),
        ])
        assert [r.batch_kind for r in out] == ["multi_rhs"] * 2
        assert out[0].warm_start and not out[1].warm_start
        np.testing.assert_allclose(out[0].coef, _lstsq(x, x @ drifted),
                                   rtol=1e-3, atol=1e-3)
        np.testing.assert_allclose(out[1].coef, _lstsq(x, x @ a_new),
                                   rtol=1e-3, atol=1e-3)
        assert eng.stats.warm_starts == 1

    def test_explicit_a0_beats_cached(self, rng):
        x = rng.normal(size=(64, 8)).astype(np.float32)
        a = rng.normal(size=(8,)).astype(np.float32)
        eng = SolverServeEngine()
        eng.serve([_req(x, x @ a, design_key="d", tenant_id="t")])
        # Explicit a0 equal to the exact answer: 0-sweep convergence via
        # rtol on the already-stalled residual would still take a sweep;
        # instead check it is used (warm flag) and exact.
        served, = eng.serve([_req(x, x @ a, design_key="d", tenant_id="t",
                                  a0=a)])
        assert served.warm_start
        np.testing.assert_allclose(served.coef, a, rtol=1e-4, atol=1e-5)

    def test_warm_reduces_sweeps(self, rng):
        x = rng.normal(size=(400, 32)).astype(np.float32)
        a = rng.normal(size=(32,)).astype(np.float32)
        drift = a + 0.001 * rng.normal(size=32).astype(np.float32)
        kw = dict(thr=16, rtol=1e-4, max_iter=100, design_key="d")
        eng = SolverServeEngine()
        eng.serve([_req(x, x @ a, tenant_id="t", **kw)])
        warm, = eng.serve([_req(x, x @ drift, tenant_id="t", **kw)])
        cold, = SolverServeEngine().serve([_req(x, x @ drift, **kw)])
        assert warm.warm_start
        assert warm.n_sweeps < cold.n_sweeps

    def test_warm_cache_off_stays_cold(self, rng):
        x = rng.normal(size=(64, 8)).astype(np.float32)
        eng = SolverServeEngine(ServeConfig(warm_cache=False))
        eng.serve([_req(x, x[:, 0], design_key="d", tenant_id="t")])
        served, = eng.serve([_req(x, x[:, 0], design_key="d",
                                  tenant_id="t")])
        assert not served.warm_start
        assert eng.stats.warm_starts == 0

    def test_vmap_path_warm_and_cold(self, rng):
        """Distinct-design (vmap) batches thread per-row a0 with zero rows
        for cold members."""
        x1 = rng.normal(size=(300, 24)).astype(np.float32)
        x2 = rng.normal(size=(300, 24)).astype(np.float32)
        a1 = rng.normal(size=(24,)).astype(np.float32)
        a2 = rng.normal(size=(24,)).astype(np.float32)
        out = SolverServeEngine().serve([
            _req(x1, x1 @ a1, thr=16, a0=a1 * 0.99),
            _req(x2, x2 @ a2, thr=16),
        ])
        assert [r.batch_kind for r in out] == ["vmap"] * 2
        assert out[0].warm_start and not out[1].warm_start
        np.testing.assert_allclose(out[0].coef, a1, rtol=1e-3, atol=1e-3)
        np.testing.assert_allclose(out[1].coef, a2, rtol=1e-3, atol=1e-3)

    def test_a0_broadcasts_across_rhs(self, rng):
        """A (vars,) a0 with multi-RHS y warm-starts every column."""
        import jax.numpy as jnp
        from repro.core import solvebak, solvebakp
        x = rng.normal(size=(100, 8)).astype(np.float32)
        a = rng.normal(size=(8,)).astype(np.float32)
        ys = np.stack([x @ a, x @ a], 1)
        r1 = solvebak(jnp.asarray(x), jnp.asarray(ys), max_iter=30,
                      a0=jnp.asarray(a))
        r2 = solvebakp(jnp.asarray(x), jnp.asarray(ys), thr=4, max_iter=30,
                       a0=jnp.asarray(a))
        for r in (r1, r2):
            np.testing.assert_allclose(np.asarray(r.coef),
                                       np.stack([a, a], 1), rtol=1e-4,
                                       atol=1e-5)

    def test_bad_a0_shape_rejected(self, rng):
        x, y, _ = make_system(rng, 50, 4)
        with pytest.raises(ValueError, match="a0 must be"):
            SolverServeEngine().submit(
                SolveRequest(x=x, y=y, a0=np.zeros(3, np.float32)))


# ---------------------------------------------- flush exception safety
class TestFlushExceptionSafety:
    """Regression: a solver raising mid-flush used to abort the whole flush,
    losing every already-dequeued request."""

    def test_poisoned_request_cannot_wedge_engine(self, rng):
        x, y, _ = make_system(rng, 64, 8)
        # retry_ladder=False: this test pins the raw isolation property —
        # with the ladder on the poisoned request is *recovered* instead
        # (covered in test_resilience.py).
        eng = SolverServeEngine(ServeConfig(retry_ladder=False))
        # thr=0 explodes inside solvebakp at trace time — after submit-time
        # validation, exactly the "poisoned request" class.
        poisoned = _req(x, y, method="bakp", thr=0, max_iter=5)
        healthy = [_req(x, y, design_key="d") for _ in range(2)]
        out = eng.serve([healthy[0], poisoned, healthy[1]])
        assert [r.ok for r in out] == [True, False, True]
        assert out[1].batch_kind == "error"
        assert "ZeroDivisionError" in out[1].error
        assert not out[1].converged
        np.testing.assert_allclose(out[0].coef, _lstsq(x, y), rtol=1e-3,
                                   atol=1e-3)
        assert eng.stats.failures == 1
        # The engine is not wedged: the next flush serves normally.
        again, = eng.serve([_req(x, y, design_key="d")])
        assert again.ok and again.cache_hit

    def test_poisoned_multi_rhs_group_isolated(self, rng, monkeypatch):
        """One group's failure doesn't take down sibling groups in the
        same flush."""
        x1 = rng.normal(size=(64, 8)).astype(np.float32)
        x2 = rng.normal(size=(64, 8)).astype(np.float32)
        eng = SolverServeEngine()
        real = eng._call_solver

        def boom(spec, entry, y_dev, atol, **kw):
            # The cached PreparedDesign's fingerprint is the design_key.
            if entry.fingerprint == "bad":
                raise RuntimeError("injected solver failure")
            return real(spec, entry, y_dev, atol, **kw)

        monkeypatch.setattr(eng, "_call_solver", boom)
        out = eng.serve([
            _req(x1, x1[:, 0], design_key="bad"),
            _req(x1, x1[:, 1], design_key="bad"),
            _req(x2, x2[:, 0], design_key="good"),
            _req(x2, x2[:, 1], design_key="good"),
        ])
        assert [r.ok for r in out] == [False, False, True, True]
        assert all("injected" in r.error for r in out[:2])
        assert eng.stats.failures == 2

    def test_failed_deadline_ticket_counts_as_miss(self, rng, monkeypatch):
        """A batch whose engine.serve raises marks deadline-carrying
        tickets as misses (hit rate must not be inflated by failures)."""
        x, y, _ = make_system(rng, 64, 8)
        eng = SolverServeEngine()
        monkeypatch.setattr(
            eng, "serve",
            lambda reqs: (_ for _ in ()).throw(RuntimeError("boom")))
        cfg = DispatchConfig(max_batch=1, idle_timeout_s=0.005)
        with AsyncDispatcher(eng, cfg) as disp:
            t = disp.submit(_req(x, y), deadline_s=120.0)
            with pytest.raises(RuntimeError, match="boom"):
                t.result(timeout=120)
        assert t.deadline_met is False
        assert disp.stats.deadline_misses == 1
        assert disp.stats.deadline_hit_rate == 0.0

    def test_dispatcher_surfaces_error_results(self, rng):
        x, y, _ = make_system(rng, 64, 8)
        cfg = DispatchConfig(max_batch=2, idle_timeout_s=0.005)
        eng = SolverServeEngine(ServeConfig(retry_ladder=False))
        with AsyncDispatcher(eng, cfg) as disp:
            bad = disp.submit(_req(x, y, method="bakp", thr=0, max_iter=5))
            good = disp.submit(_req(x, y, design_key="d"))
            bad_r = bad.result(timeout=120)
            good_r = good.result(timeout=120)
        assert not bad_r.ok and "ZeroDivisionError" in bad_r.error
        assert good_r.ok
