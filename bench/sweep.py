"""The knee sweep of a served mix: its traffic at rising rates.

Not a cell. One process on the chip sets a configuration and an
open-loop traffic mix up once, then serves the mix for ``--seconds`` at
each rate in turn, and prints one
JSON line per rate: p50 and p95 latency over every request due, the
drain (seconds from the last send to the last answer) and the ratio of
the median latency of the last quarter of requests to that of the first
quarter. The knee is the highest rate at which p95 stays under the
traffic's ``deadline_s`` and the backlog does not grow (drain under
``max_wait_s`` plus one flush, ratio near 1).

    python3 bench/sweep.py --config ct-ieks --traffic served-poisson \\
        --seed 7 --seconds 10 --rates 1,2,4,8
"""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    import argparse
    import json

    import jax
    import numpy as np

    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--config", required=True)
    p.add_argument("--traffic", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, default=10.0)
    p.add_argument("--rates", required=True)
    args = p.parse_args(argv)

    from bench import harness
    from bench.reference.problem import load_problem
    from repro.launch.compile_cache import enable_compile_cache

    harness.require_chips(1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    enable_compile_cache()
    run = harness.make_run(
        {"name": "sweep", "config": args.config, "traffic": args.traffic},
        args.seed, args.seconds, False)
    run.problem = load_problem(run.config["problem"],
                               np.dtype(run.config["dtype"]))
    state = run.driver.setup(run)
    counter = harness.CompileCounter()
    for rate in (float(r) for r in args.rates.split(",")):
        before = counter.count
        run.traffic = dict(run.traffic, rate=rate)
        run.driver.reseed(run, state)
        out = run.driver.serve(state["server"], state["ys"], state["due"],
                               state["policy"], state["estimator"])
        lat = np.nan_to_num(out["latency_s"], nan=np.inf)
        q = max(1, len(lat) // 4)
        print(json.dumps({
            "rate": rate, "requests": len(lat),
            "launches": len(out["launches"]),
            "mean_width": float(np.mean([len(l) for l in out["launches"]])),
            "compiles": counter.count - before,
            "p50_ms": float(np.percentile(lat, 50)) * 1e3,
            "p95_ms": float(np.percentile(lat, 95)) * 1e3,
            "drain_s": out["elapsed_s"] - float(state["due"][-1]),
            "late_over_early": float(np.median(lat[-q:])
                                     / np.median(lat[:q])),
            "lateness_p95_ms": float(np.percentile(out["lateness_s"], 95))
            * 1e3,
            "flush_ms_p50": float(np.median(out["flush_s"])) * 1e3,
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
