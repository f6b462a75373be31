"""The Lorenz-96 configuration (``l96-ieks``): the program's scenario and
the benchmark's own statement of the problem agree, and the program's
batched parallel IEKS agrees with the plain float64 reference on it."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from bench import check, harness, program, traffic
from bench.reference.problem import load_problem


def _config(dtype="float64"):
    with open(os.path.join(harness.BENCH_DIR, "configs", "l96-ieks.json")) \
            as fh:
        cfg = json.load(fh)
    cfg["dtype"] = dtype
    return cfg


def _spin_up(d=40, forcing=8.0, dt=0.05, steps=2000):
    """The prior mean's recipe (`repro.scenarios.lorenz96`): float64 numpy,
    x = F at every site plus 0.01 at site 0, 2,000 RK4 steps, no noise."""
    def rhs(x):
        return (np.roll(x, -1) - np.roll(x, 2)) * np.roll(x, 1) - x + forcing

    x = np.full(d, forcing)
    x[0] += 0.01
    for _ in range(steps):
        k1 = rhs(x)
        k2 = rhs(x + 0.5 * dt * k1)
        k3 = rhs(x + 0.5 * dt * k2)
        k4 = rhs(x + dt * k3)
        x = x + dt / 6.0 * (k1 + 2 * k2 + 2 * k3 + k4)
    return x


def test_prior_mean_is_its_recipe():
    from repro.scenarios.lorenz96 import M0_40

    want = _spin_up()
    np.testing.assert_array_equal(np.asarray(M0_40), want)
    np.testing.assert_array_equal(
        np.asarray(_config()["problem"]["params"]["m0"]), want)


def test_program_and_reference_state_the_same_problem():
    """f, h, Q, R, m0 and P0 of the registry's ``lorenz96_40`` and of
    `bench/reference/models/lorenz96.py` at seeded states on and off the
    attractor (float64; the same operations in the same order, so equal
    to rounding)."""
    from repro.scenarios import get_scenario

    cfg = _config()
    with jax.enable_x64(True):
        ref = load_problem(cfg["problem"], jnp.float64)
        prog = get_scenario(cfg["program"]["scenario"]).make_model(
            jnp.float64)
        assert (prog.nx, prog.ny) == (cfg["problem"]["nx"],
                                      cfg["problem"]["ny"]) == (40, 40)
        for name in ("Q", "R", "m0", "P0"):
            np.testing.assert_array_equal(np.asarray(getattr(prog, name)),
                                          np.asarray(getattr(ref, name)))
        rng = np.random.default_rng(2 ** 31 + 5)
        for x in rng.normal(2.0, 3.6, size=(8, 40)):
            x = jnp.asarray(x)
            for fn in ("f", "h"):
                np.testing.assert_allclose(getattr(prog, fn)(x),
                                           getattr(ref, fn)(x), rtol=1e-14,
                                           atol=1e-14)


def test_program_matches_the_reference_at_every_site():
    """4 tracks of 16 steps, one width-4 launch, float64 on both sides,
    every one of the 40 sites compared (the benchmark's check compares
    sites 0 and 1 only). The program's parallel scans and blocked
    factorizations and the reference's sequential filter and LAPACK
    solves round differently; on converged tracks that leaves about
    1e-14 (CPU), so 1e-10 is far below any float32 effect (1e-6 and
    up) and far above float64's."""
    cfg = _config()
    lens = np.asarray([16, 16, 16, 16])
    with jax.enable_x64(True):
        problem = load_problem(cfg["problem"], jnp.float64)
        ys = traffic.tracks(problem, lens, seed=2 ** 31 + 7)
        server = program.build_server(cfg, {"max_batch": 4})
        stats = server.serve_requests(ys, emit=lambda *_: None)
        ref, conv, gave_up = check.reference_answers(cfg["problem"],
                                                     cfg["spec"], ys)
    assert stats["launches"] == 1 and stats["wide_launches"] == 1
    assert stats["verdicts"] == {"ok": 4} and conv.all() and not gave_up.any()
    for got, want in zip(stats["results"], ref):
        assert got.shape == want.shape == (17, 40)
        np.testing.assert_allclose(got, want, rtol=0, atol=1e-10)
