"""Program spans on the profiler's clock, and the per-request host counters.

``obs.span`` records the ring buffer always and, while a profiler trace is
open, a ``TraceAnnotation`` of the same name; a deferred span reaches the
ring and sink only when committed, which the dispatcher does for its waits
once it has released its lock; the dispatcher names its waits
(``dispatch.hold`` with a batch pending, ``dispatch.idle`` with no request
in the system); every served request carries ``lane_wait_s``, ``build_s``
and ``fetch_s`` on the one serving clock; and a real CPU profiler trace
holds the program's spans under their names.
"""
import json
import pathlib
import time

import pytest

from conftest import make_system
from repro import obs
from repro.serve import (AsyncDispatcher, DispatchConfig, ServeConfig,
                         SolveRequest, SolverServeEngine)


@pytest.fixture(autouse=True)
def _obs_enabled():
    prev = obs.set_enabled(True)
    yield
    obs.set_enabled(prev)


def _req(x, y, **kw):
    kw.setdefault("method", "bakp")
    kw.setdefault("max_iter", 15)
    return SolveRequest(x=x, y=y, **kw)


def _engine():
    return SolverServeEngine(ServeConfig(), registry=obs.MetricsRegistry())


def _spans(name):
    return obs.get_tracer().spans(name)


def _overlap(a, b) -> float:
    return min(a.t_end, b.t_end) - max(a.t_start, b.t_start)


# ------------------------------------------------------- one span API
@pytest.mark.parametrize("profiling", [False, True])
def test_span_annotates_only_while_profiling(monkeypatch, tmp_path,
                                             profiling):
    import jax

    entered = []

    class FakeAnnotation:
        def __init__(self, name, **kw):
            assert not kw  # tags would change the event's name
            self.name = name

        def __enter__(self):
            entered.append(("enter", self.name))

        def __exit__(self, *exc):
            entered.append(("exit", self.name))

    monkeypatch.setattr(jax.profiler, "start_trace", lambda d, **kw: None)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", FakeAnnotation)
    tr = obs.Tracer(capacity=8)
    if profiling:
        assert obs.start_profiling(str(tmp_path))
    try:
        with tr.span("engine.flush", requests=3):
            with tr.span("solve/bakp"):
                pass
    finally:
        obs.stop_profiling()
    with tr.span("after.stop"):
        pass
    assert [s.name for s in tr.spans()] == ["solve/bakp", "engine.flush",
                                            "after.stop"]
    assert tr.spans("engine.flush")[0].tags == {"requests": 3}
    expect = [("enter", "engine.flush"), ("enter", "solve/bakp"),
              ("exit", "solve/bakp"), ("exit", "engine.flush")]
    assert entered == (expect if profiling else [])
    assert not obs.profiling_active()


def test_span_disabled_neither_records_nor_annotates(monkeypatch, tmp_path):
    import jax

    entered = []
    monkeypatch.setattr(jax.profiler, "start_trace", lambda d, **kw: None)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation",
                        lambda name: entered.append(name))
    tr = obs.Tracer(capacity=8)
    assert obs.start_profiling(str(tmp_path))
    try:
        obs.set_enabled(False)
        with tr.span("engine.flush") as rec:
            assert rec is None
    finally:
        obs.set_enabled(True)
        obs.stop_profiling()
    assert tr.spans() == [] and entered == []


@pytest.mark.parametrize("sink", [False, True])
def test_deferred_span_is_written_on_commit(tmp_path, sink):
    path = tmp_path / "spans.jsonl"
    tr = obs.Tracer(capacity=8, jsonl_path=str(path) if sink else None)
    done = []
    with tr.span("dispatch.hold", defer=done, batches=2) as rec:
        pass
    assert done == [rec] and rec.t_end is not None
    assert tr.spans() == []
    if sink:
        assert path.read_text() == ""
    tr.commit(done)
    assert tr.spans() == [rec] and rec.tags == {"batches": 2}
    if sink:
        line, = path.read_text().splitlines()
        assert json.loads(line)["name"] == "dispatch.hold"
    tr.close()


# ----------------------------------------------------- dispatcher spans
def test_hold_spans_a_pending_batch(rng):
    x, y, _ = make_system(rng, 40, 8)
    idle_timeout = 0.05
    obs.get_tracer().clear()
    with AsyncDispatcher(_engine(), DispatchConfig(
            idle_timeout_s=idle_timeout)) as d:
        t = d.submit(_req(x, y))
        assert t.result(timeout=30.0).ok
    holds = _spans("dispatch.hold")
    assert holds and all(s.thread == "serve-dispatch" for s in holds)
    # A batch is pending only between its one request's submit and fire.
    assert all(t.submitted_at <= s.t_start and s.t_end <= t.fired_at
               for s in holds)
    assert sum(s.duration_s for s in holds) >= 0.5 * idle_timeout
    assert _spans("dispatch.admit")


def test_wait_spans_are_written_outside_the_lock(rng, monkeypatch):
    x, y, _ = make_system(rng, 40, 8)
    tracer = obs.get_tracer()
    real_commit = tracer.commit
    seen = []
    d = AsyncDispatcher(_engine(), DispatchConfig(idle_timeout_s=0.02))

    def commit(recs):
        seen.extend((r.name, d._cv._is_owned()) for r in recs)
        real_commit(recs)

    monkeypatch.setattr(tracer, "commit", commit)
    with d:
        for _ in range(2):
            assert d.submit(_req(x, y)).result(timeout=30.0).ok
            time.sleep(0.03)
    waits = [owned for name, owned in seen
             if name in ("dispatch.hold", "dispatch.idle")]
    assert waits and not any(waits)
    assert {"dispatch.hold", "dispatch.idle"} <= {n for n, _ in seen}


def test_idle_opens_only_with_nothing_in_flight(rng, monkeypatch):
    x, y, _ = make_system(rng, 40, 8)
    eng = _engine()
    real = eng._call_solver

    def slow(*args, **kw):
        time.sleep(0.1)  # the lane works while the dispatcher waits
        return real(*args, **kw)

    monkeypatch.setattr(eng, "_call_solver", slow)
    obs.get_tracer().clear()
    with AsyncDispatcher(eng, DispatchConfig(idle_timeout_s=0.005)) as d:
        for _ in range(2):
            assert d.submit(_req(x, y)).result(timeout=30.0).ok
            time.sleep(0.05)  # nothing in the system: the dispatcher idles
    batches = _spans("dispatch.solve_batch")
    idles = _spans("dispatch.idle")
    assert len(batches) == 2 and idles
    assert all(b.duration_s >= 0.1 for b in batches)
    for b in batches:
        assert all(_overlap(i, b) <= 0 for i in idles)
        # While only the lane works, the dispatch thread opens no span.
        for s in _spans("dispatch.hold") + idles:
            assert _overlap(s, b) < 0.05
    # One idle wait between the two requests, after the first answer.
    assert any(batches[0].t_end <= i.t_start and i.t_end <= batches[1].t_start
               for i in idles)


# ------------------------------------------------- per-request counters
def _path_requests(rng, kind):
    """Requests that one flush serves on ``kind``'s path."""
    if kind == "single":
        x, y, _ = make_system(rng, 40, 8)
        return [_req(x, y, design_key="s")]
    if kind == "multi_rhs":
        x, y, _ = make_system(rng, 40, 8)
        return [_req(x, y * (1.0 + c), design_key="m") for c in range(3)]
    systems = [make_system(rng, 40, 8) for _ in range(2)]
    return [_req(x, y, design_key=f"v{i}")
            for i, (x, y, _) in enumerate(systems)]


@pytest.mark.parametrize("kind", ["single", "multi_rhs", "vmap"])
def test_served_requests_carry_host_counters(rng, kind):
    reqs = _path_requests(rng, kind)
    with AsyncDispatcher(_engine(), DispatchConfig(idle_timeout_s=0.2)) as d:
        tickets = [d.submit(r) for r in reqs]
        results = [t.result(timeout=60.0) for t in tickets]
    for t, res in zip(tickets, results):
        assert res.ok and res.batch_kind == kind
        tel = res.telemetry
        parts = (tel.queue_wait_s, tel.lane_wait_s, tel.build_s,
                 tel.solve_s, tel.fetch_s)
        assert all(p is not None and p >= 0 for p in parts), parts
        assert tel.build_s > 0 and tel.fetch_s > 0
        # Disjoint stretches of one request's life, on one clock.
        assert sum(parts) <= t.latency_s
        assert tel.as_dict()["lane_wait_s"] == tel.lane_wait_s
    # A group shares its unit's build, solve and fetch.
    assert len({r.telemetry.fetch_s for r in results}) == 1


def test_sync_engine_fills_host_counters_but_no_lane_wait(rng):
    x, y, _ = make_system(rng, 40, 8)
    out, = _engine().serve([_req(x, y)])
    tel = out.telemetry
    assert tel.build_s > 0 and tel.fetch_s > 0
    assert tel.lane_wait_s is None and tel.queue_wait_s is None


# ------------------------------------------------- the device trace
def test_profiler_trace_holds_the_program_spans(rng, tmp_path):
    """A served window traced by the real profiler on the CPU: the host
    plane carries each program span as an event of exactly its name."""
    from jax.profiler import ProfileData

    x, y, _ = make_system(rng, 40, 8)
    eng = _engine()
    eng.serve([_req(x, y)])  # compile outside the trace
    assert obs.start_profiling(str(tmp_path))
    try:
        with AsyncDispatcher(eng, DispatchConfig(idle_timeout_s=0.02)) as d:
            for _ in range(2):
                assert d.submit(_req(x, y)).result(timeout=30.0).ok
    finally:
        obs.stop_profiling()
    path, = pathlib.Path(tmp_path).rglob("*.xplane.pb")
    names = {e.name for plane in ProfileData.from_file(str(path)).planes
             if plane.name == "/host:CPU"
             for line in plane.lines for e in line.events}
    assert {"dispatch.hold", "dispatch.admit", "dispatch.solve_batch",
            "engine.flush", "engine.build", "engine.fetch",
            "solve/bakp"} <= names
