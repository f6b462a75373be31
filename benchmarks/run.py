"""Benchmark harness — one module per paper table/figure + framework
benches. Prints ``name,us_per_call,derived`` CSV (task spec deliverable
(d)) and optionally writes the same rows as machine-readable JSON
(``--json PATH``) so the perf trajectory is tracked across PRs.

  paper_fig1         — paper Fig. 1a/1b: parallel vs sequential IEKS/IPLS
  paper_convergence  — IEKS/IPLS M=10 convergence + par==seq gap +
                       early-stop parity
  kernels_bench      — Pallas kernel paths vs references
  models_bench       — reduced-config train steps for the arch zoo
  smoothers_bench    — batched multi-trajectory throughput (traj/sec for
                       B in {1, 8, 64, 256}; batched vs loop vs sequential)
  backend_bench      — combine-backend crossover across T (compiled
                       kernel vs fused-jnp vs jnp vs sequential; the
                       arXiv 2511.10363 span-vs-work regime);
                       ``--smoke`` is the CI backend="auto" gate
  serve_bench        — autobatching service latency: static vs
                       deadline-aware flush under poisson/bursty arrivals,
                       plus the multi-tenant mixed-scenario rows
                       (p50/p95, traj/s; snapshot BENCH_serve.json)
  scenarios_bench    — scenario-zoo smoke bench: warm smooth per
                       registered scenario x linearization method

Roofline/dry-run numbers (full configs, production mesh) come from
``python -m repro.launch.dryrun --all`` — see EXPERIMENTS.md.
"""
from __future__ import annotations

import argparse
import json
import re
import sys


def _parse_derived(derived: str) -> dict:
    """Split 'k1=v1;k2=v2' into a dict, coercing numeric values."""
    out = {}
    for part in str(derived).split(";"):
        if "=" not in part:
            if part:
                out["note"] = part
            continue
        k, v = part.split("=", 1)
        m = re.fullmatch(r"[-+0-9.eE]+x?", v)
        if m:
            try:
                out[k] = float(v.rstrip("x"))
                continue
            except ValueError:
                pass
        out[k] = v
    return out


def write_json(rows, path: str) -> None:
    payload = {name: {"us_per_call": float(us), **_parse_derived(derived)}
               for name, us, derived in rows}
    with open(path, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
        f.write("\n")


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--only", type=str, default=None,
                   help="comma-separated subset: fig1,convergence,kernels,"
                        "models,smoothers,backend,serve,scenarios")
    p.add_argument("--quick", action="store_true",
                   help="smaller sizes for CI")
    p.add_argument("--json", type=str, default=None, metavar="PATH",
                   help="also write collected rows as JSON "
                        "(e.g. BENCH_smoothers.json)")
    args = p.parse_args()
    only = set(args.only.split(",")) if args.only else None

    # Convergence validation runs in float64 (covariance-form parallel
    # smoothers are f32-fragile on long horizons — see the sqrt_parallel
    # extension); runtime benches pin float32 explicitly like the paper.
    import jax
    jax.config.update("jax_enable_x64", True)
    from repro.launch.compile_cache import enable_compile_cache
    print(f"# compile cache: {enable_compile_cache()}", file=sys.stderr)

    rows = []
    print("name,us_per_call,derived")
    if only is None or "fig1" in only:
        from benchmarks import paper_fig1
        sizes = (128, 512, 2048) if args.quick else paper_fig1.SIZES
        rows += paper_fig1.run(sizes=sizes)
    if only is None or "convergence" in only:
        from benchmarks import paper_convergence
        rows += paper_convergence.run(n=200 if args.quick else 500)
    if only is None or "kernels" in only:
        from benchmarks import kernels_bench
        rows += kernels_bench.run()
    if only is None or "models" in only:
        from benchmarks import models_bench
        rows += models_bench.run()
    if only is None or "smoothers" in only:
        from benchmarks import smoothers_bench
        if args.quick:
            rows += smoothers_bench.run(n=128, batches=(1, 8, 64))
        else:
            rows += smoothers_bench.run()
    if only is None or "backend" in only:
        from benchmarks import backend_bench
        rows += backend_bench.run(
            sizes=backend_bench.SIZES if args.quick
            else backend_bench.SIZES_FULL)
    if only is None or "serve" in only:
        from benchmarks import serve_bench
        rows += serve_bench.run(quick=args.quick)
    if only is None or "scenarios" in only:
        from benchmarks import scenarios_bench
        rows += scenarios_bench.run(quick=args.quick)
    if args.json:
        write_json(rows, args.json)
        print(f"# wrote {len(rows)} rows to {args.json}")


if __name__ == "__main__":
    main()
