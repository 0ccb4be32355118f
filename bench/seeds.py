"""Readings of a cell's compared numbers over many seeds, in one process.

    python3 bench/seeds.py --workload <name> --seconds <s> --seeds 1 2 3 ...
    python3 bench/seeds.py ... --control high      # the control instead

Each seed is a whole run (new data, new engine, the window, the check), as
``bench/run.py`` makes it; the process compiles once.  One JSON line per
seed: the checks and the end-to-end metrics.  With ``--control`` the
reference's algorithm at that precision answers in the program's place
(``bench/inject.py``), which is how the upper reading of a limit is taken.
Not run by the benchmark itself.
"""
import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", default=None,
                    help="precision of the control: high or default")
    args = ap.parse_args(argv)

    from bench import harness, inject

    bench = harness.load_benchmark()
    patch = None
    if args.control:
        path = harness.cell(bench, args.workload).config["path"]
        patch = lambda: inject.control(path, args.control)  # noqa: E731
    for seed in args.seeds:
        try:
            r = harness.run(args.workload, seed, args.seconds, False,
                            t_start=time.perf_counter(), solve_patch=patch,
                            bench=bench)
        except harness.NoChip as exc:
            print(f"bench: {exc}; nothing was run", file=sys.stderr)
            return 2
        print(json.dumps({"seed": seed, "control": args.control,
                          "correct": r["correct"],
                          "attempted": r["attempted"],
                          "checks": r["checks"], "metrics": r["metrics"]}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
