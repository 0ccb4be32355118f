"""Find the highest rate an open-loop cell sustains, by a sweep of rates.

    python3 bench/sweep.py --workload <name> --seed <n> --seconds 20 \
        --rates 4 8 12 16 20 24

Set-up once, then one window per offered rate (no reference check).  For
each rate a JSON line: requests offered and answered, latency p50/p95,
the median latency of the window's first and last thirds, and the most
requests in flight.  A rate is sustained when the last third's median
latency stays within twice the first third's and every request was
answered within the window's minute of grace.  The cell's traffic file
then takes 0.8 times the highest sustained rate, as a number.  Not run by
the benchmark itself.
"""
import argparse
import json
import pathlib
import sys
import time

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def backlog(records) -> int:
    """Most requests sent and not yet answered at any send time."""
    done = np.sort([r.done if r.done is not None else np.inf
                    for r in records])
    sent = np.array([r.sent for r in records])
    return int(max((np.arange(1, len(sent) + 1)
                    - np.searchsorted(done, sent, side="right")),
                   default=0))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)

    from bench import harness

    bench = harness.load_benchmark()
    c = harness.cell(bench, args.workload)
    try:
        harness.devices_for(c.chips)
    except harness.NoChip as exc:
        print(f"bench: {exc}; nothing was run", file=sys.stderr)
        return 2
    harness.use_compile_cache()
    harness.COUNTER = harness.CompileCounter()
    run = harness.CellRun(c, args.seed)
    t = time.perf_counter()
    run.setup()
    print(json.dumps({"setup_s": time.perf_counter() - t}), flush=True)
    for rate in args.rates:
        t0, recs, _ = run.window(args.seconds, rate_per_s=rate)
        harness.classify(recs, c.config)
        lat = np.array([r.latency_s for r in recs if r.latency_s is not None])
        third = len(recs) // 3
        first = [r.latency_s for r in recs[:third] if r.latency_s is not None]
        last = [r.latency_s for r in recs[-third:] if r.latency_s is not None]
        med_first = float(np.median(first)) if first else None
        med_last = float(np.median(last)) if last else None
        failed = sum(r.failure is not None for r in recs)
        print(json.dumps({
            "rate_per_s": rate, "offered": len(recs), "failed": failed,
            "p50_s": float(np.percentile(lat, 50)) if lat.size else None,
            "p95_s": float(np.percentile(lat, 95)) if lat.size else None,
            "first_third_p50_s": med_first, "last_third_p50_s": med_last,
            "max_in_flight": backlog(recs),
            "rhs_per_solve": (len(recs) / sum(1.0 / r.result.group_size
                                              for r in recs if r.result)
                              if any(r.result for r in recs) else None),
            "sustained": bool(failed == 0 and med_first is not None
                              and med_last <= 2 * med_first)}),
              flush=True)
    run.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
