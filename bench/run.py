"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout.  Set-up (data from the seed, the design built
into the serving engine's cache, every program warmed) counts as
``setup_s``; then the cell's traffic is served for ``--seconds`` and every
answer is checked against the float64 reference.  With ``--trace 0`` the
result carries the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics from a profiler trace of the window.

The last line of standard output is the result, one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics``, ``device``
(``breakdown`` when traced) and, last, ``checks``: each number compared,
with its limit.  The checks are also the last lines on standard error.
Without a TPU, or with fewer chips than the cell needs, it exits 2 and
prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="also copy the raw .xplane.pb of a traced run here")
    args = ap.parse_args(argv)

    from bench import harness

    try:
        result = harness.run(args.workload, args.seed, args.seconds,
                             bool(args.trace), t_start=T_START,
                             keep_trace=args.keep_trace)
    except harness.NoChip as exc:
        print(f"bench: {exc}; nothing was run", file=sys.stderr)
        return 2
    harness.print_checks(result["checks"])
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
