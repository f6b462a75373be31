"""Where the per-element algebra should stop unrolling (`core.types`).

For each state dimension ``d`` and each lowering of the three primitives
the smoother's element algebra is made of (the inverse, the Cholesky
quadratic form, the matrix product), on ``B x T = 8,192`` elements (the
Lorenz-96 fleet cell's launch) in float32:

* ``compile_s``: host seconds to lower and compile it;
* ``ms``: device milliseconds per 8,192 elements (host clock around
  ``block_until_ready``, median of 10 calls, each on ``R`` batches of
  8,192 at once so that one call takes a millisecond or more);
* ``err``: the largest relative error against float64 numpy over the
  first 64 elements (the inverse and the product: over all entries,
  scaled by the largest entry of the truth; the quadratic form: of each
  value).

Lowerings: ``unrolled`` (`types._inverse_unrolled`,
`types._chol_half_quad_unrolled`, `types._bmm_unrolled`), ``wide``
(`types.inverse_blocked`, `types.chol_half_quad_blocked`,
`types.matmul_wide`) and ``lax`` (``jnp.linalg.inv``;
``jnp.linalg.cholesky`` and ``solve_triangular``). The unrolled Cholesky
is not compiled above ``--chol-unrolled-max`` (its program grows with
``d^3 / 6``: 154 s to compile at 24 on a TPU v5e host), nor the unrolled
inverse above ``--inverse-unrolled-max`` (at 80 it had not compiled after
290 s). A lowering that fails (out of memory, say) gets a line with its
``error`` and the sweep goes on.

    python3 scripts/algebra_sweep.py --out algebra_sweep.jsonl

One JSON line per (primitive, lowering, d) to ``--out`` and to standard
output. Run it on the chip: a CPU run times XLA:CPU, not the device.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))

ELEMS = 8192
BYTES_PER_CALL = 512 * 2 ** 20


def _inputs(rng, n, d):
    import numpy as np

    A = rng.standard_normal((n, d, d))
    S = A @ np.swapaxes(A, -1, -2) / d + 0.5 * np.eye(d)
    return A, S, rng.standard_normal((n, d))


def _lowerings():
    import jax.numpy as jnp
    from jax.scipy.linalg import solve_triangular

    from repro.core import types

    def chol_lax(x, S):
        L = jnp.linalg.cholesky(S)
        z = solve_triangular(L, x[..., None], lower=True)[..., 0]
        return 0.5 * jnp.sum(z * z, axis=-1)

    return {
        "inverse": {"unrolled": lambda A, S, x: types._inverse_unrolled(S),
                    "wide": lambda A, S, x: types.inverse_blocked(S),
                    "lax": lambda A, S, x: jnp.linalg.inv(S)},
        "chol_half_quad": {
            "unrolled": lambda A, S, x: types._chol_half_quad_unrolled(
                x, S)[0],
            "wide": lambda A, S, x: types.chol_half_quad_blocked(x, S)[0],
            "lax": lambda A, S, x: chol_lax(x, S)},
        "product": {"unrolled": lambda A, S, x: types._bmm_unrolled(A, S),
                    "wide": lambda A, S, x: types.matmul_wide(A, S)},
    }


def _truth(prim, A, S, x):
    import numpy as np

    if prim == "inverse":
        return np.linalg.inv(S)
    if prim == "product":
        return A @ S
    return 0.5 * np.sum(x * np.linalg.solve(S, x[..., None])[..., 0], -1)


def _err(prim, got, want):
    import numpy as np

    got = np.asarray(got, np.float64)
    if prim == "chol_half_quad":
        return float(np.max(np.abs(got - want) / np.abs(want)))
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def measure(prim, name, fn, d, rng):
    import jax
    import jax.numpy as jnp

    reps = max(1, BYTES_PER_CALL // (ELEMS * d * d * 4 * 4))
    A, S, x = _inputs(rng, ELEMS * reps, d)
    args = [jnp.asarray(a, jnp.float32) for a in (A, S, x)]
    jitted = jax.jit(fn)
    t0 = time.perf_counter()
    compiled = jitted.lower(*args).compile()
    compile_s = time.perf_counter() - t0
    out = jax.block_until_ready(compiled(*args))
    times = []
    for _ in range(10):
        t0 = time.perf_counter()
        jax.block_until_ready(compiled(*args))
        times.append(time.perf_counter() - t0)
    k = 64
    err = _err(prim, out[:k], _truth(prim, A[:k], S[:k], x[:k]))
    return {"primitive": prim, "lowering": name, "d": d,
            "compile_s": compile_s,
            "ms": 1e3 * statistics.median(times) / reps,
            "reps": reps, "err": err}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--dims", default="8,16,24,40,80")
    p.add_argument("--chol-unrolled-max", type=int, default=24)
    p.add_argument("--inverse-unrolled-max", type=int, default=40)
    p.add_argument("--out", default=None)
    args = p.parse_args(argv)

    import jax
    import numpy as np

    dev = jax.devices()[0]
    print(f"[sweep] device {dev.platform} {dev.device_kind}", flush=True)
    rng = np.random.default_rng(0)
    sink = open(args.out, "w") if args.out else None
    for d in (int(v) for v in args.dims.split(",")):
        for prim, ways in _lowerings().items():
            for name, fn in ways.items():
                if name == "unrolled" and d > {
                        "chol_half_quad": args.chol_unrolled_max,
                        "inverse": args.inverse_unrolled_max}.get(prim, d):
                    continue
                try:
                    rec = measure(prim, name, fn, d, rng)
                except Exception as e:  # noqa: BLE001 — recorded, not hidden
                    rec = {"primitive": prim, "lowering": name, "d": d,
                           "error": f"{type(e).__name__}: "
                                    f"{str(e).splitlines()[0][:300]}"}
                rec["device"] = dev.device_kind
                line = json.dumps(rec)
                print(line, flush=True)
                if sink:
                    sink.write(line + "\n")
                    sink.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
