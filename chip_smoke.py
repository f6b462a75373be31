"""Smoke test of the smoother service on one TPU chip.

Drives the served path once through the entry points of
`repro.launch.serve`, in float32 (the chip has no float64), and checks
what comes out. Five phases:

  one-shot      `serve_smoother` at the CLI's default fleet: 64
                coordinated-turn requests of lengths {256, 384, 512},
                launch width 64, ekf, 10 iterations, tol 1e-6; four
                results are checked against the float64 sequential
                smoother, run on the host's CPU;
  streaming     the same fleet as a poisson stream under the deadline
                policy, ``backend="auto"``: warmup times the compiled
                combine kernel against the fused combine for every
                bucket; four results are checked as in one-shot;
  multi-tenant  a stream over the six small registry scenarios (nx 1,
                2, 4, 5 and 8) through `MultiTenantServer`;
  forced-kernel a batched `Smoother.iterate` with ``backend="tpu"``
                (B=64, n=512), whose executable must hold the Mosaic
                kernel, and the kernels against their fused twins;
  fleet         one batched iterate at B=1024, n=512, with its memory;
                four lanes are checked against the float64 sequential
                smoother.

Every input is made from a seed. Run it from the repository root:

    python chip_smoke.py

It exits non-zero where JAX finds no TPU, and when any check fails. The
last line of its output is one JSON object naming the device.
"""
from __future__ import annotations

import dataclasses
import json
import os
import sys
import time
import traceback

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

#: Position RMSE against the simulated truth under which a coordinated-
#: turn request counts as tracked: the verify runbook's bound ("well
#: under 0.1"). At the CLI's default fleet (lengths 256-512, ten passes
#: at fixed damping 1.0) the float64 sequential smoother itself leaves
#: about a third of the tracks of length 384 and 512 unfound (position
#: RMSE 1 to 8; fleet mean 0.87 on a CPU), so the fleet *mean* measures
#: those tracks and not the chip. The served phases hold the *median*
#: request to the bound: most requests must be tracked. At n=512 alone
#: (forced-kernel, fleet) only about half the lanes are tracked, so
#: those phases are held to a reference run instead.
RMSE_BOUND = 0.1

#: Gap of a served float32 trajectory to the float64 sequential smoother
#: run on the host CPU on the same padded bucket (the same measurements,
#: the same R-inflated padding steps, the same ten damped passes): the
#: largest position difference over the request's steps. On a request
#: the reference tracks, what separates the two is float32 rounding (at
#: most 5e-4 on a CPU at these lengths); a tenth of the tracking bound
#: cannot change whether a request is tracked. On a request the
#: reference does not track, the unconverged iterate is sensitive to any
#: rounding (gaps of 6 were seen on a CPU), so the N_REF requests checked
#: are the first ones the reference tracks.
REF_GAP_TOL = 0.1 * RMSE_BOUND
N_REF = 4

#: Kernel against fused twin, one combine on random well-conditioned
#: elements: the float32 tolerance of the interpret-mode parity suite
#: (tests/kernels/test_kalman_combine.py).
KERNEL_RTOL, KERNEL_ATOL = 2e-4, 2e-5

#: Multi-tenant stream: short tracks and narrow launches, so that six
#: tenants' bucket executables compile within the run's time budget.
MT_REQUESTS, MT_N, MT_MAX_BATCH = 24, 64, 4
#: Its tenants: every registry scenario but the 40-site Lorenz-96, whose
#: wide algebra has a benchmark cell of its own (`l96-ieks.fleet-w64`).
MT_TENANTS = ("bearings_only", "coordinated_turn", "lorenz96", "pendulum",
              "population", "stochastic_volatility")

FORCED_B, FLEET_B, PHASE_N = 64, 1024, 512


def require_tpu():
    """The device check. Runs before anything else is built."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU chip: JAX's devices are {devices} "
                 f"(platform {devices[0].platform!r}); this smoke test "
                 "runs only on a TPU")
    return devices


class CompileCounter:
    """Counts XLA backend compiles, the seconds they take, and
    persistent-cache hits through `jax.monitoring`."""

    def __init__(self):
        import jax

        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, name, secs, **_kw):
        if name == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += secs

    def _event(self, name, **_kw):
        if name == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self):
        return self.compiles, self.compile_s, self.cache_hits


class Smoke:
    def __init__(self):
        self.failures = []
        self.counter = CompileCounter()

    def check(self, ok: bool, what: str) -> None:
        print(f"  [{'ok' if ok else 'FAIL'}] {what}", flush=True)
        if not ok:
            self.failures.append(what)

    def phase(self, name: str, fn) -> None:
        c0, s0, h0 = self.counter.snapshot()
        t0 = time.perf_counter()
        print(f"[phase {name}]", flush=True)
        try:
            fn()
        except Exception:  # a failed phase fails the run; go on to the next
            traceback.print_exc()
            self.failures.append(f"phase {name} raised")
        wall = time.perf_counter() - t0
        c1, s1, h1 = self.counter.snapshot()
        print(f"[phase {name}] wall {wall:.3f} s, {c1 - c0} compiles "
              f"taking {s1 - s0:.3f} s, {h1 - h0} compile-cache hits",
              flush=True)

    # -- checks shared by the served phases ------------------------------

    def check_served(self, stats: dict, n_requests: int) -> None:
        verdicts = stats["verdicts"]
        self.check(verdicts == {"ok": n_requests},
                   f"all {n_requests} verdicts ok: {verdicts}")
        errors = [l["error"] for l in stats.get("launch_log", [])
                  if "error" in l]
        self.check(not errors, f"no launch raised: {errors[:3]}")
        if "backend_choices" in stats:
            print(f"  backend choices: {stats['backend_choices']}")


def serve_config(**overrides):
    from repro.launch.serve import SmootherServeConfig

    return dataclasses.replace(SmootherServeConfig(f64=False), **overrides)


def position_rmse(means, truths) -> np.ndarray:
    """Per-request position RMSE over the real steps (state 0 is the
    prior)."""
    return np.asarray([
        np.sqrt(np.mean((np.asarray(m)[1:, :2] - np.asarray(t)[1:, :2]) ** 2))
        for m, t in zip(means, truths)])


def check_tracking(smoke: Smoke, rmse: np.ndarray, what: str) -> None:
    med = float(np.median(rmse))
    smoke.check(med < RMSE_BOUND,
                f"{what}: median position RMSE {med!r} < {RMSE_BOUND} "
                f"(mean {float(np.mean(rmse))!r}; "
                f"{int(np.sum(rmse < RMSE_BOUND))}/{len(rmse)} tracked)")


def served_fleet(cfg):
    """The requests and truths `serve_smoother` generates for ``cfg``
    (regenerated from the seed on the same device)."""
    import jax.numpy as jnp

    from repro.launch.serve import make_fleet
    from repro.scenarios import get_scenario

    sc = get_scenario("coordinated_turn")
    return make_fleet(sc, sc.make_model(jnp.float32), cfg)


def check_reference(smoke: Smoke, cfg, means, requests, truths,
                    what: str) -> None:
    """Compare served trajectories with the float64 sequential smoother,
    run on the host CPU on the bucket the server launched for each
    request (padded to its time bucket), for the first N_REF requests
    that the reference tracks (see REF_GAP_TOL)."""
    import jax
    import jax.numpy as jnp

    from repro.core import build_smoother
    from repro.launch.autobatch import next_pow2
    from repro.launch.serve import pad_requests
    from repro.scenarios import get_scenario

    sc = get_scenario("coordinated_turn")
    cpu = jax.devices("cpu")[0]
    checked = 0
    with jax.enable_x64(True), jax.default_device(cpu):
        model = sc.make_model(jnp.float64)
        ref = build_smoother(sc.default_spec(
            mode="sequential", n_iter=cfg.n_iter, tol=cfg.tol,
            lm_lambda=cfg.lm_lambda))
        run = jax.jit(lambda ys, rs: ref.iterate(
            dataclasses.replace(model, R=rs), ys).mean)
        for i, ys in enumerate(requests):
            if checked == N_REF:
                break
            ys_p, rs = pad_requests([np.asarray(ys, np.float64)],
                                    next_pow2(len(ys)), 1,
                                    np.asarray(model.R))
            want = np.asarray(run(ys_p, rs))[0, :len(ys) + 1]
            if position_rmse([want], [truths[i]])[0] >= RMSE_BOUND:
                continue
            checked += 1
            gap = float(np.max(np.abs(np.asarray(means[i])[:, :2]
                                      - want[:, :2])))
            smoke.check(gap < REF_GAP_TOL,
                        f"{what} request {i} (n={len(ys)}): position gap "
                        f"to the float64 sequential smoother {gap!r} < "
                        f"{REF_GAP_TOL}")
    smoke.check(checked == N_REF,
                f"{checked} {what} requests compared with the reference")


def one_shot(smoke: Smoke) -> None:
    from repro.launch.serve import serve_smoother

    cfg = serve_config()
    stats = serve_smoother(cfg)
    smoke.check_served(stats, cfg.requests)
    requests, truths = served_fleet(cfg)
    check_tracking(smoke, position_rmse(stats["results"], truths),
                   "one-shot fleet")
    check_reference(smoke, cfg, stats["results"], requests, truths,
                    "one-shot")


def streaming(smoke: Smoke) -> None:
    from repro.launch.serve import serve_smoother

    cfg = serve_config(arrival="poisson", policy="deadline")
    stats = serve_smoother(cfg)
    smoke.check_served(stats, cfg.requests)
    requests, truths = served_fleet(cfg)
    check_tracking(smoke, position_rmse(stats["results"], truths),
                   "streamed fleet")
    check_reference(smoke, cfg, stats["results"], requests, truths,
                    "streamed")
    print(f"  {stats['compiles']} bucket signatures")


def multi_tenant(smoke: Smoke) -> None:
    from repro.kernels.kalman_combine import autotune as kc_autotune
    from repro.launch.serve import TenantSpec, serve_smoother_multitenant

    cfg = serve_config(requests=MT_REQUESTS, n=MT_N, max_batch=MT_MAX_BATCH,
                       vary_lengths=False, arrival="poisson",
                       policy="deadline")
    tenants = [TenantSpec.parse(name) for name in MT_TENANTS]
    stats = serve_smoother_multitenant(cfg, tenants)
    smoke.check_served(stats, cfg.requests)
    served = sorted(stats["per_tenant"])
    smoke.check(served == sorted(t.tenant for t in tenants),
                f"every tenant served: {served}")
    for key, entry in kc_autotune.cache_entries().items():
        print(f"  autotune {key}: {entry}")
    rmse = stats["mean_rmse_per_tenant"]
    smoke.check(all(np.isfinite(v) for v in rmse.values()),
                f"finite state RMSE per tenant: {rmse}")


def _fleet(sc, model, B: int, n: int, seed: int):
    """B trajectories of length n, simulated on the device in one call."""
    import functools

    import jax

    keys = jax.random.split(jax.random.PRNGKey(seed), B)
    sim = jax.jit(jax.vmap(functools.partial(sc.simulate, model, n)))
    return sim(keys)


def forced_kernel(smoke: Smoke) -> None:
    import jax
    import jax.numpy as jnp

    from repro.core import LANE_DIVERGED, build_smoother
    from repro.core.types import FilteringElement, SmoothingElement
    from repro.kernels.kalman_combine import kalman_combine as kc, ops
    from repro.scenarios import get_scenario

    cfg = serve_config()
    sc = get_scenario("coordinated_turn")
    model = sc.make_model(jnp.float32)
    spec = sc.default_spec(n_iter=cfg.n_iter, tol=cfg.tol,
                           lm_lambda=cfg.lm_lambda, backend="tpu")
    xs, ys = _fleet(sc, model, FORCED_B, PHASE_N, seed=1)
    kernel = jax.jit(lambda ys: build_smoother(spec).iterate(
        model, ys, return_info=True))
    compiled = kernel.lower(ys).compile()
    smoke.check("tpu_custom_call" in compiled.as_text(),
                "the backend=\"tpu\" executable holds tpu_custom_call")
    traj, info = compiled(ys)
    fused = jax.jit(lambda ys: build_smoother(
        dataclasses.replace(spec, backend="jnp")).iterate(model, ys))
    mean, mean_f = np.asarray(traj.mean), np.asarray(fused(ys).mean)
    smoke.check(bool(np.all(np.asarray(info.code) != LANE_DIVERGED)),
                "no kernel lane diverged")
    # At n=512 about half the lanes are not tracked by any run (see
    # RMSE_BOUND); where the fused run tracks, the kernel run differs
    # from it by float32 rounding only (see REF_GAP_TOL).
    rmse, rmse_f = position_rmse(mean, xs), position_rmse(mean_f, xs)
    lanes = rmse_f < RMSE_BOUND
    print(f"  tracked: kernel {int(np.sum(rmse < RMSE_BOUND))}, fused "
          f"{int(lanes.sum())} of {FORCED_B} lanes")
    smoke.check(lanes.sum() >= N_REF,
                f"the fused run tracks {int(lanes.sum())} >= {N_REF} lanes")
    gap = float(np.max(np.abs(mean[lanes, :, :2] - mean_f[lanes, :, :2]),
                       initial=0.0))
    smoke.check(gap < REF_GAP_TOL,
                f"kernel-to-fused position gap {gap!r} < {REF_GAP_TOL} "
                f"on the lanes the fused run tracks")

    # One combine at every scenario nx, kernel against its fused twin
    # (the interpret-mode parity suite's check and tolerance, compiled on
    # the chip), over a batch that leaves the last block ragged.
    rng = np.random.default_rng(0)
    B = 4096 + 3
    for nx in (1, 2, 4, 5, 8):
        def mat():
            return jnp.asarray(rng.standard_normal((B, nx, nx))
                               / np.sqrt(nx), jnp.float32)

        def psd():
            a = rng.standard_normal((B, nx, nx))
            return jnp.asarray(a @ np.swapaxes(a, -1, -2) / nx
                               + 0.1 * np.eye(nx), jnp.float32)

        def vec():
            return jnp.asarray(rng.standard_normal((B, nx)), jnp.float32)

        cases = (
            ("filtering", ops.filtering_combine_op,
             kc.filtering_combine_batched_jnp,
             lambda: FilteringElement(mat(), vec(), psd(), vec(), psd())),
            ("smoothing", ops.smoothing_combine_op,
             kc.smoothing_combine_batched_jnp,
             lambda: SmoothingElement(mat(), vec(), psd())))
        for name, op, twin, make in cases:
            ei, ej = make(), make()
            got = jax.jit(lambda a, b, op=op: op(
                a, b, impl="kernel", backend="tpu"))(ei, ej)
            want = jax.jit(twin)(ei, ej)
            close = all(np.allclose(np.asarray(g), np.asarray(w),
                                    rtol=KERNEL_RTOL, atol=KERNEL_ATOL)
                        for g, w in zip(got, want))
            err = max(float(np.max(np.abs(np.asarray(g) - np.asarray(w))))
                      for g, w in zip(got, want))
            smoke.check(close, f"{name} kernel matches its fused twin at "
                               f"B={B}, nx={nx} (max abs diff {err!r})")


def fleet(smoke: Smoke) -> None:
    import jax
    import jax.numpy as jnp

    from repro.core import LANE_DIVERGED, build_smoother
    from repro.scenarios import get_scenario

    cfg = serve_config()
    sc = get_scenario("coordinated_turn")
    model = sc.make_model(jnp.float32)
    spec = sc.default_spec(n_iter=cfg.n_iter, tol=cfg.tol,
                           lm_lambda=cfg.lm_lambda)
    xs, ys = _fleet(sc, model, FLEET_B, PHASE_N, seed=2)
    run = jax.jit(lambda ys: build_smoother(spec).iterate(
        model, ys, return_info=True))
    compiled = run.lower(ys).compile()
    print(f"  memory_analysis: {compiled.memory_analysis()}")
    t0 = time.perf_counter()
    traj, info = compiled(ys)
    jax.block_until_ready(traj.mean)
    print(f"  B={FLEET_B}, n={PHASE_N}: {time.perf_counter() - t0:.3f} s "
          "(one call, compiled beforehand)")
    stats = jax.devices()[0].memory_stats() or {}
    print(f"  peak_bytes_in_use: {stats.get('peak_bytes_in_use')!r}")
    smoke.check(bool(np.all(np.asarray(info.code) != LANE_DIVERGED)),
                "no fleet lane diverged")
    mean, xs = np.asarray(traj.mean), np.asarray(xs)
    rmse = position_rmse(mean, xs)
    print(f"  position RMSE: median {float(np.median(rmse))!r}, mean "
          f"{float(np.mean(rmse))!r}; {int(np.sum(rmse < RMSE_BOUND))}/"
          f"{FLEET_B} tracked")
    check_reference(smoke, cfg, mean, np.asarray(ys), xs, "fleet")


def main() -> int:
    devices = require_tpu()
    from repro.launch.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}", flush=True)
    smoke = Smoke()
    for name, fn in (("one-shot", one_shot), ("streaming", streaming),
                     ("multi-tenant", multi_tenant),
                     ("forced-kernel", forced_kernel), ("fleet", fleet)):
        smoke.phase(name, lambda fn=fn: fn(smoke))
    if smoke.failures:
        print(f"chip_smoke: {len(smoke.failures)} failed: {smoke.failures}",
              file=sys.stderr)
        return 1
    dev = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
