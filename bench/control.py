"""The readings that a cell's correctness limits are set from.

Not a cell. For one cell, in one process on the chip, over many seeds:

* ``program``: the numbers `bench.check` compares, for answers of the
  program's own timed path (one fleet job, or one served stream of
  ``--seconds``), as a run of the cell computes them;
* ``control``: the same numbers for the plain reference put in the
  program's place, in float32 with every matrix product at
  ``precision="high"`` (three bfloat16 passes; the configurations state
  float32 at ``highest``), on the chip, over the same tracks;
* ``reference_f32``: the same for the reference in float32 at
  ``highest`` on the chip, which shows what float32 alone costs.

    python3 bench/control.py --workload ct-ieks.fleet-pow2 --seeds 11-22 \\
        --control-seeds 11-13 --seconds 10

One JSON line per seed and kind, with ``correct`` as `bench.check.judge`
finds it against the cell's limits (the control has to come out not
correct) and the widest gap on the tracks not compared. The server is
built and warmed once; each seed then has tracks of its own.
"""
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def _seeds(text: str) -> list:
    out = []
    for part in text.split(","):
        lo, _, hi = part.partition("-")
        out.extend(range(int(lo), int(hi or lo) + 1))
    return out


def main(argv=None) -> int:
    import argparse
    import json

    import numpy as np

    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", default="")
    p.add_argument("--control-seeds", default="")
    p.add_argument("--seconds", type=float, default=10.0)
    args = p.parse_args(argv)

    import jax

    from bench import check, harness
    from bench.reference.problem import load_problem
    from repro.launch.compile_cache import enable_compile_cache

    devices = harness.require_chips(1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    enable_compile_cache()
    seeds, ctrl_seeds = _seeds(args.seeds), _seeds(args.control_seeds)
    first = (seeds or ctrl_seeds)[0]
    run = harness.load_run(args.workload, first, args.seconds, False)
    run.problem = load_problem(run.config["problem"],
                               np.dtype(run.config["dtype"]))
    state = run.driver.setup(run)
    cfg, limits = run.config, check.load_limits(args.workload)

    def emit(kind, seed, answers, ref, conv, lens, missing, failed_over,
             extra=None):
        values = check.numbers(answers, ref, conv, lens, missing,
                               failed_over)
        correct, _ = check.judge(values, limits)
        g = check.gaps(answers, ref, lens)[~conv]
        print(json.dumps({
            "cell": args.workload, "kind": kind, "seed": seed,
            "correct": correct, "tracks": len(lens),
            "compared": int(conv.sum()), **values,
            "unconverged_gap_max": float(g.max()) if len(g) else None,
            **(extra or {})}), flush=True)

    for seed in sorted(set(seeds) | set(ctrl_seeds)):
        t0 = time.perf_counter()
        fresh = harness.load_run(args.workload, seed, args.seconds, False)
        fresh.problem, fresh.driver = run.problem, run.driver
        run.driver.reseed(fresh, state)
        fresh.outcome = run.driver.window(fresh, state)
        idx = run.driver.sample(fresh, state)
        ys = [state["ys"][i] for i in idx]
        lens = [len(y) for y in ys]
        t1 = time.perf_counter()
        ref, conv, gave_up = check.reference_answers(cfg["problem"],
                                                     cfg["spec"], ys)
        t_ref = time.perf_counter() - t1
        if seed in seeds:
            results = fresh.outcome.results
            missing = sum(r is None or not np.all(np.isfinite(r))
                          for r in results)
            emit("program", seed, [results[i] for i in idx], ref, conv,
                 lens, missing, max(0, fresh.outcome.results_failed
                                    - int(gave_up.sum())),
                 {"seconds": t1 - t0, "reference_s": t_ref})
        if seed in ctrl_seeds:
            for kind, matmul in (("control", "high"),
                                 ("reference_f32", "highest")):
                got, _, _ = check.reference_answers(
                    cfg["problem"], cfg["spec"], ys, dtype="float32",
                    matmul=matmul, device=devices[0])
                emit(kind, seed, got, ref, conv, lens, 0, 0)
    return 0


if __name__ == "__main__":
    sys.exit(main())
