"""The reader of the collectives' share of the chips' busy time, on
synthetic trace summaries: what it reads with collectives in the window,
0 with none, and nothing, without raising, where the run was not traced.

    PYTHONPATH=src python -m pytest -q bench/tests
"""
import pathlib
import sys
import types

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness, trace  # noqa: E402


def _run(summary):
    return types.SimpleNamespace(trace=summary)


def test_share_of_busy_time_in_collectives():
    read = harness.metric_reader("collective_pct.closed8")
    assert read(_run({"busy_s": 40.0, "collective_s": 1.0,
                      "window_s": 50.0})) == pytest.approx(2.5)


def test_no_collectives_read_zero():
    read = harness.metric_reader("collective_pct.closed8")
    assert read(_run({"busy_s": 40.0, "collective_s": 0.0,
                      "window_s": 50.0})) == 0.0


def test_no_trace_reads_nothing():
    read = harness.metric_reader("collective_pct.closed8")
    assert read(_run(None)) is None
    assert read(_run({"busy_s": 0.0, "collective_s": 0.0,
                      "window_s": 50.0})) is None


def test_reads_the_reduction_of_a_traced_window():
    """From ``bench/trace.py``'s own reduction: an all-reduce that takes a
    quarter of the one device's busy time."""
    t = trace.Trace(
        devices={"/device:TPU:0": {"ops": [("fusion.1", 100, 150),
                                           ("all-reduce.2", 250, 50)],
                                   "modules": [("jit_solve", 100, 200)]}},
        spans=[("bench.window", 0, 400)])
    read = harness.metric_reader("collective_pct.closed8")
    assert read(_run(trace.summarize(t))) == pytest.approx(25.0)
