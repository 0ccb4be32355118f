"""The readers of the program's per-request host counters and of the
dispatcher's hold, on synthetic runs: each reads what it is named for, and
returns nothing, without raising, where the program has no such counter.

    PYTHONPATH=src python -m pytest -q bench/tests
"""
import pathlib
import sys
import types

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402
from repro.obs import SolveTelemetry  # noqa: E402


def _run(telemetry, idle_gaps=None, window_s=50.0):
    recs = [types.SimpleNamespace(result=types.SimpleNamespace(telemetry=t))
            for t in telemetry]
    trace = (None if idle_gaps is None
             else {"idle_gaps": idle_gaps, "window_s": window_s})
    return types.SimpleNamespace(answered=recs, trace=trace)


READERS = {
    "lane_wait_p90_s.open": (lambda ts: np.percentile(
        [t.lane_wait_s for t in ts], 90)),
    "lane_wait_p95_s.closed": (lambda ts: np.percentile(
        [t.lane_wait_s for t in ts], 95)),
    "batch_build_p50_s.open": (lambda ts: np.median(
        [t.build_s for t in ts])),
    "result_fetch_p50_s.closed": (lambda ts: np.median(
        [t.fetch_s for t in ts])),
}


@pytest.mark.parametrize("name", sorted(READERS))
def test_counter_readers(name):
    read = harness.metric_reader(name)
    tels = [SolveTelemetry(lane_wait_s=0.1 * i, build_s=0.001 * i,
                           fetch_s=0.002 * i)
            for i in range(1, 12)]
    assert read(_run(tels)) == pytest.approx(float(READERS[name](tels)))
    # The program before these counters: nothing to read, and no raise.
    old = [types.SimpleNamespace(queue_wait_s=0.1, solve_s=0.2)] * 3
    assert read(_run(old)) is None
    assert read(_run([None])) is None


def test_held_idle_reader():
    read = harness.metric_reader("held_idle_pct.closed")
    gaps = [["dispatch.hold", 9.0], ["engine.flush", 0.3]]
    assert read(_run([], gaps, 50.0)) == pytest.approx(18.0)
    assert read(_run([], [["(no host span)", 12.0]], 50.0)) == 0.0
    assert read(_run([])) is None
