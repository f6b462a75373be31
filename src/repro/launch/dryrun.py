import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS", ""))
# NOTE: the two lines above MUST run before any jax import (jax locks the
# device count at first backend init). Everything else follows.

_DOC = """Multi-pod dry-run (task spec deliverable (e)).

For every (architecture x input-shape) cell, build the production mesh
(single-pod 16x16 = 256 chips, and multi-pod 2x16x16 = 512 chips), lower
the step with ShapeDtypeStruct inputs (no allocation), compile, and record
``memory_analysis()`` + ``cost_analysis()`` + the collective-bytes parse.
Failures (sharding mismatch, OOM at compile, unsupported collective) are
bugs in the framework — the run exits non-zero.

Usage:
  python -m repro.launch.dryrun --arch internlm2-1.8b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod-only|--single-pod-only]
  python -m repro.launch.dryrun --all --out results/dryrun.json
"""
__doc__ = _DOC

import argparse
import json
import sys
import time
import traceback

import jax

from repro.configs import ALL_SHAPES, SHAPES, get_config, list_configs
from repro.launch.mesh import make_production_mesh
from repro.launch.hlo_analysis import analyze_hlo
from repro.launch.roofline import roofline_report
from repro.launch.steps import make_cell_plan

#: The chip the production meshes are made of (a `CHIP_PEAKS` key): the
#: dry-run compiles on host devices, which have no peaks of their own.
TARGET_DEVICE_KIND = "TPU v5 lite"


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             verbose: bool = True) -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, reason = cfg.supports_shape(shape)
    if not ok:
        return {"arch": arch, "shape": shape_name,
                "mesh": "2x16x16" if multi_pod else "16x16",
                "status": "skipped", "reason": reason}
    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    with mesh:
        plan = make_cell_plan(cfg, mesh, shape)
        lowered = plan.step_fn.lower(*plan.args, **plan.kwargs)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

        mem = compiled.memory_analysis()
        raw_cost = compiled.cost_analysis()
        hlo = compiled.as_text()
    t_analyze = time.time() - t0 - t_lower - t_compile
    cost = analyze_hlo(hlo)   # per-chip, trip-count-exact (hlo_analysis)
    n_chips = mesh.devices.size
    arg_bytes = plan.per_chip_argument_bytes()
    result = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "status": "ok",
        "kind": shape.kind,
        "chips": int(n_chips),
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "flops": cost["flops"],
        "hbm_bytes": cost["hbm_bytes"],
        "collective_bytes": cost["collective_bytes"],
        "memory": {
            "per_chip_argument_bytes": arg_bytes,
            # XLA's own numbers for reference (CPU backend reports the
            # unpartitioned view for some fields — see DESIGN.md §8):
            "xla_argument_bytes": int(getattr(mem,
                                              "argument_size_in_bytes", 0)),
            "xla_temp_bytes": int(getattr(mem, "temp_size_in_bytes", 0)),
            "xla_output_bytes": int(getattr(mem, "output_size_in_bytes",
                                            0)),
        },
        "xla_cost_raw": {k: float(raw_cost.get(k, 0.0))
                         for k in ("flops", "bytes accessed")},
    }
    result["roofline"] = roofline_report(cfg, shape, result,
                                         TARGET_DEVICE_KIND)
    fits = arg_bytes < 16 * 2 ** 30
    result["fits_hbm16"] = bool(fits)
    if verbose:
        print(f"[dryrun] {arch} x {shape_name} x {result['mesh']}: OK "
              f"(lower {t_lower:.0f}s, compile {t_compile:.0f}s, "
              f"analyze {t_analyze:.0f}s)")
        print(f"  per-chip argument bytes: {arg_bytes / 2**30:.2f} GiB "
              f"({'fits' if fits else 'DOES NOT FIT'} 16 GiB HBM)")
        print("  memory_analysis:", result["memory"])
        print("  per-chip: flops=%.3e hbm_bytes=%.3e"
              % (cost["flops"], cost["hbm_bytes"]))
        print("  collective_bytes:",
              {k: "%.3e" % v for k, v in cost["collective_bytes"].items()})
        print("  roofline:", json.dumps(result["roofline"], indent=2))
    return result


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--arch", type=str, default=None)
    p.add_argument("--shape", type=str, default=None,
                   choices=[s.name for s in ALL_SHAPES] + [None])
    p.add_argument("--all", action="store_true")
    p.add_argument("--multi-pod", action="store_true",
                   help="use the 2x16x16 mesh (default: 16x16)")
    p.add_argument("--both-meshes", action="store_true")
    p.add_argument("--out", type=str, default=None)
    args = p.parse_args(argv)

    assert len(jax.devices()) == 512, (
        "dryrun requires 512 host devices; do not import jax before this "
        f"module (got {len(jax.devices())})")

    cells = []
    if args.all:
        archs = sorted(list_configs())
        shapes = [s.name for s in ALL_SHAPES]
    else:
        archs = [args.arch]
        shapes = [args.shape] if args.shape else [s.name for s in
                                                  ALL_SHAPES]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    results, failures = [], []
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                try:
                    results.append(run_cell(arch, shape, mp))
                except Exception as e:  # noqa: BLE001
                    traceback.print_exc()
                    failures.append((arch, shape, mp, repr(e)))
                    results.append({"arch": arch, "shape": shape,
                                    "mesh": "2x16x16" if mp else "16x16",
                                    "status": "failed", "error": repr(e)})
    if args.out:
        with open(args.out, "w") as f:
            json.dump(results, f, indent=2)
        print(f"[dryrun] wrote {len(results)} cells to {args.out}")
    n_ok = sum(r["status"] == "ok" for r in results)
    n_skip = sum(r["status"] == "skipped" for r in results)
    print(f"[dryrun] {n_ok} ok, {n_skip} skipped (documented), "
          f"{len(failures)} failed")
    if failures:
        for f in failures:
            print("  FAILED:", f)
        sys.exit(1)


if __name__ == "__main__":
    main()
