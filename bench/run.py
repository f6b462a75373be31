"""Run one cell of the benchmark once, on the chip it is started on.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

Set-up (tracks made from the seed, the server built, every launch shape
of the cell compiled and run once) is timed as ``setup_s``; then the
cell's driver serves its traffic for ``--seconds``; then the answers are
checked against the plain reference. The last line of standard output is
one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
cell's end-to-end metrics, or with ``--trace 1`` its per-layer ones),
``device``, with ``--trace 1`` a ``breakdown``, and last ``checks``, each
number compared beside its limit; the same numbers close standard error.

It exits non-zero, printing no result, where JAX finds no TPU or fewer
chips than the cell asks for.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    if args.seed < 0:
        p.error("--seed is a non-negative whole number")

    from bench import harness

    run = harness.load_run(args.workload, args.seed, args.seconds,
                           bool(args.trace))
    try:
        out = harness.run_cell(run, T_START)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    harness.print_result(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
