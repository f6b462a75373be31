"""Combine-backend crossover bench: compiled kernel vs fused-jnp vs
textbook-jnp vs sequential across T.

Reproduces the span-vs-work regime of "On The Performance of Prefix-Sum
Parallel Kalman Filters and Smoothers on GPUs" (PAPERS.md, arXiv
2511.10363) on this host: the parallel-in-time smoother does O(T log T)
*work* for O(log T) *span*, so against the O(T)-work sequential baseline
there is a crossover T below which sequential wins (too little work to
fill the machine) and above which the parallel path pulls ahead — and
*within* the parallel path, a second crossover where the compiled combine
kernel overtakes the XLA-fused twin (per-level launch overhead amortizes;
the kernel's fused Gauss-Jordan + matmuls stop paying XLA's materialized
intermediates). Rows land in ``BENCH_smoothers.json`` as
``backend/T=<T>/<variant>``.

``--smoke`` is the CI gate for the backend="auto" contract (ISSUE 8
acceptance): the autotuner must never record a choice slower than the
fused twin on the build host, and off-accelerator a
``combine_impl="pallas"`` spec must run within 2x of ``"fused"`` wall
clock with bit-identical outputs (it *is* the fused path after the
dispatch fix, not an interpret-mode kernel).
"""
from __future__ import annotations

import argparse
import time
import warnings

import jax
import jax.numpy as jnp

from repro.core import SmootherSpec, build_smoother
from repro.data import CoordinatedTurnConfig, make_coordinated_turn_model, \
    simulate_trajectory

B = 8          # fixed fleet width; T is the swept axis
N_ITER = 3
SIZES = (64, 256, 1024)
SIZES_FULL = (64, 256, 1024, 4096)
REPS = 3


def _time_fn(fn, *args, reps=REPS):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / reps


def _batched_ys(model, n, batch=B):
    ys = [simulate_trajectory(model, n, jax.random.PRNGKey(i))[1]
          for i in range(batch)]
    return jnp.stack(ys)


def _variants():
    """(label, spec) per combine strategy. "pallas" takes the compiled
    kernel where one exists and the fused fallback elsewhere (measuring
    the dispatch bugfix on CPU hosts); "auto" is the measured chooser."""
    mk = lambda **kw: SmootherSpec(n_iter=N_ITER, lm_lambda=1.0, **kw)
    return [
        ("auto", mk()),                                   # backend="auto"
        ("fused", mk(combine_impl="fused")),
        ("jnp", mk(combine_impl="jnp")),
        ("pallas", mk(combine_impl="pallas")),
        ("sequential", mk(mode="sequential")),
    ]


def run(sizes=SIZES, emit=print):
    model = make_coordinated_turn_model(CoordinatedTurnConfig(),
                                        dtype=jnp.float32)
    rows = []
    with warnings.catch_warnings():
        # The off-accelerator "pallas" variant warns once by design.
        warnings.simplefilter("ignore", RuntimeWarning)
        for n in sizes:
            ys = _batched_ys(model, n)
            timings = {}
            for label, spec in _variants():
                sm = build_smoother(spec,
                                    autotune_for=(B, n, model.nx, jnp.float32)
                                    if spec.backend == "auto" else None)
                fn = jax.jit(lambda ys, sm=sm: sm.iterate(model, ys).mean)
                timings[label] = _time_fn(fn, ys)
            seq = timings["sequential"]
            for label, dt in timings.items():
                us = dt * 1e6
                derived = (f"B={B};vs_seq={seq / dt:.2f}x"
                           if label != "sequential" else f"B={B}")
                rows.append((f"backend/T={n}/{label}", f"{us:.1f}",
                             derived))
                emit(f"backend/T={n}/{label},{us:.1f},{derived}")
    return rows


def run_smoke(emit=print):
    """CI gate (fast shapes): the two acceptance assertions."""
    from repro.kernels.kalman_combine import autotune as kc_autotune
    from repro.kernels.kalman_combine import ops as kc_ops

    model = make_coordinated_turn_model(CoordinatedTurnConfig(),
                                        dtype=jnp.float32)
    n = 64
    ys = _batched_ys(model, n)

    # 1. backend="auto" never records a choice slower than fused-jnp.
    sm_auto = build_smoother(SmootherSpec(n_iter=N_ITER, lm_lambda=1.0),
                             autotune_for=(B, n, model.nx, jnp.float32))
    entry = kc_autotune.lookup(sm_auto.spec_id, B, n, model.nx,
                               jnp.float32)
    assert entry is not None, "autotune_for did not populate the cache"
    if entry["choice"] == kc_autotune.CHOICE_KERNEL:
        assert entry["kernel_us"] <= entry["fused_us"], entry
    emit(f"# auto choice for (B={B}, T={n}, nx={model.nx}): "
         f"{entry['choice']} ({entry})")

    sm_fused = build_smoother(SmootherSpec(n_iter=N_ITER, lm_lambda=1.0,
                                           combine_impl="fused"))
    fn_auto = jax.jit(lambda ys: sm_auto.iterate(model, ys).mean)
    fn_fused = jax.jit(lambda ys: sm_fused.iterate(model, ys).mean)
    t_auto = _time_fn(fn_auto, ys)
    t_fused = _time_fn(fn_fused, ys)
    assert t_auto <= 1.5 * t_fused, (
        f"auto ({t_auto * 1e6:.0f}us) slower than fused "
        f"({t_fused * 1e6:.0f}us)")
    emit(f"# auto {t_auto * 1e6:.0f}us vs fused {t_fused * 1e6:.0f}us")

    # 2. Off-accelerator: a "pallas" spec is the fused path — within 2x
    #    wall clock, bit-identical outputs (the dispatch bugfix).
    if kc_ops.kernel_backend() is None:
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)
            sm_pallas = build_smoother(
                SmootherSpec(n_iter=N_ITER, lm_lambda=1.0,
                             combine_impl="pallas"))
            fn_pallas = jax.jit(lambda ys: sm_pallas.iterate(model, ys).mean)
            t_pallas = _time_fn(fn_pallas, ys)
        assert t_pallas <= 2.0 * t_fused, (
            f"pallas-spec'd smoother {t_pallas * 1e6:.0f}us vs fused "
            f"{t_fused * 1e6:.0f}us: off-accelerator fallback is slow")
        same = bool(jnp.all(fn_pallas(ys) == fn_fused(ys)))
        assert same, "pallas fallback output differs from fused"
        emit(f"# cpu pallas fallback {t_pallas * 1e6:.0f}us "
             f"(fused {t_fused * 1e6:.0f}us), bit-identical: {same}")
    emit("# backend smoke OK")
    return True


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--smoke", action="store_true",
                   help="fast CI assertions instead of the full sweep")
    p.add_argument("--full", action="store_true",
                   help="sweep the large-T sizes too")
    args = p.parse_args(argv)
    if args.smoke:
        run_smoke()
        return 0
    print("name,us_per_call,derived")
    run(sizes=SIZES_FULL if args.full else SIZES)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
