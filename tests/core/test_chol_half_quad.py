"""The unrolled Cholesky quadratic form (`types.chol_half_quad`) that the
Gauss-Newton cost and `mvn_logpdf` evaluate per element.

Contract under test:
  * ``(1/2 d^T S^-1 d, log det S)`` agrees with a Cholesky factor plus a
    triangular solve (``jnp.linalg`` / ``jax.scipy``) in float64 and
    float32: at every size from 1 to 17 (unrolled up to
    ``types.UNROLL_MAX``, blocked at 17),
    per element, over ``[B, n]`` stacks, with a shared
    covariance broadcast to the batch, with serving's 1e8-inflated padded
    steps, and on ill-conditioned covariances like the coordinated-turn
    ``Q``; in the working precision it is no less accurate than the path
    it replaced;
  * a covariance that is not positive definite gives a non-finite result,
    and the adaptive damping loop gives up that lane alone;
  * `mvn_logpdf` agrees with the formula it replaced;
  * the batched cost and log-likelihood lower to plain arithmetic (no
    factorization op, no custom call) at the coordinated-turn sizes and
    at ``d = 17``.
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.scipy.linalg import solve_triangular

from repro.core import (LANE_DIVERGED, IteratedConfig, LinearizedSSM,
                        SmootherSpec, build_smoother, gn_cost,
                        initial_trajectory_batched, mvn_logpdf,
                        smoothed_log_likelihood, smoothing_cost)
from repro.core.types import chol_half_quad
from repro.data import CoordinatedTurnConfig, make_coordinated_turn_model, \
    simulate_trajectory

DIMS = (1, 2, 4, 5, 8, 16, 17)
CASES = ("n", "Bn", "shared", "inflated", "ill")
B, N = 3, 6
R_PAD_SCALE = 1e8     # `launch.serve.pad_requests`' inflation of padded R


def _spd(rng, shape, d):
    A = rng.normal(size=shape + (d, d))
    return A @ np.swapaxes(A, -1, -2) / d + 0.5 * np.eye(d)


def _ill(d):
    """The coordinated-turn ``Q`` at ``d = 5`` (eigenvalues 8.3e-9 to
    1e-3); at other sizes its position-velocity coupling block repeated
    along the diagonal (a trailing odd dimension gets the turn-rate
    variance)."""
    if d == 5:
        model = make_coordinated_turn_model(CoordinatedTurnConfig())
        return np.asarray(model.Q, np.float64)
    S = np.zeros((d, d))
    for k in range(0, d - 1, 2):
        S[k:k + 2, k:k + 2] = [[1e-3 / 3e4, 5e-6], [5e-6, 1e-3]]
    if d % 2:
        S[-1, -1] = 1e-3
    return S


def _inputs(case, d, rng):
    """``(diff, cov)`` in float64 for one case."""
    batch = (N,) if case == "n" else (B, N)
    diff = rng.normal(size=batch + (d,))
    if case == "shared":
        cov = _spd(rng, (), d)
    elif case == "ill":
        cov = _ill(d) * rng.uniform(0.5, 2.0, size=batch)[..., None, None]
        diff = diff * 1e-3
    else:
        cov = _spd(rng, batch, d)
    if case == "inflated":                      # padded tail steps
        cov[:, N // 2:] *= R_PAD_SCALE
    return diff, cov


def _linalg(diff, cov):
    """Cholesky factor plus triangular solve, the comparison path."""
    batch = jnp.broadcast_shapes(diff.shape[:-1], cov.shape[:-2])
    d = diff.shape[-1]
    chol = jnp.linalg.cholesky(jnp.broadcast_to(cov, batch + (d, d)))
    z = solve_triangular(chol, jnp.broadcast_to(diff, batch + (d,))[..., None],
                         lower=True)[..., 0]
    logdet = 2.0 * jnp.sum(jnp.log(jnp.diagonal(chol, axis1=-2, axis2=-1)),
                           axis=-1)
    return 0.5 * jnp.sum(z * z, axis=-1), logdet


def _exact(diff, cov):
    """float64 numpy truth for the (already rounded) inputs."""
    diff = np.asarray(diff, np.float64)
    cov = np.broadcast_to(np.asarray(cov, np.float64),
                          diff.shape[:-1] + (diff.shape[-1],) * 2)
    sol = np.linalg.solve(cov, diff[..., None])[..., 0]
    return 0.5 * np.sum(diff * sol, axis=-1), np.linalg.slogdet(cov)[1]


@pytest.mark.parametrize("dtype", [np.float64, np.float32])
@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("d", DIMS)
def test_chol_half_quad_matches_linalg(d, case, dtype):
    rng = np.random.default_rng(1000 * d + CASES.index(case))
    diff, cov = _inputs(case, d, rng)
    diff, cov = jnp.asarray(diff, dtype), jnp.asarray(cov, dtype)
    quad, logdet = chol_half_quad(diff, cov)
    ref_quad, ref_logdet = _linalg(diff, cov)
    shape = jnp.broadcast_shapes(diff.shape[:-1], cov.shape[:-2])
    assert quad.shape == logdet.shape == shape
    assert quad.dtype == logdet.dtype == dtype
    eps = np.finfo(dtype).eps
    # Two roundings of one formula: close to each other, ...
    np.testing.assert_allclose(quad, ref_quad, rtol=16 * d * eps)
    np.testing.assert_allclose(logdet, ref_logdet, rtol=16 * d * eps,
                               atol=16 * d * eps)
    # ... and no further from the exact value than the path replaced.
    true_quad, true_logdet = _exact(diff, cov)
    err = np.max(np.abs(np.asarray(quad, np.float64) - true_quad) / true_quad)
    ref_err = np.max(np.abs(np.asarray(ref_quad, np.float64) - true_quad)
                     / true_quad)
    assert err <= 2.0 * ref_err + 16 * eps
    err = np.max(np.abs(np.asarray(logdet, np.float64) - true_logdet))
    ref_err = np.max(np.abs(np.asarray(ref_logdet, np.float64) - true_logdet))
    assert err <= 2.0 * ref_err + 16 * eps * np.max(np.abs(true_logdet))


def _not_pd(kind, d):
    S = np.eye(d)
    if kind == "indefinite":
        S[d - 1, d - 2] = S[d - 2, d - 1] = 2.0
    elif kind == "singular":
        S[d - 1, d - 1] = 0.0
    elif kind == "negative":
        S[0, 0] = -1.0
    else:
        S[d - 1, 0] = S[0, d - 1] = np.nan
    return S


@pytest.mark.parametrize("dtype", [np.float64, np.float32])
@pytest.mark.parametrize("kind", ["indefinite", "singular", "negative", "nan"])
@pytest.mark.parametrize("d", [2, 5, 17])
def test_non_pd_covariance_gives_non_finite(d, kind, dtype):
    """Only the offending element goes non-finite, as with
    ``jnp.linalg.cholesky``: the adaptive loop's ``isfinite`` check on a
    candidate's cost relies on it."""
    rng = np.random.default_rng(d)
    cov = np.stack([np.eye(d), _not_pd(kind, d), 2.0 * np.eye(d)])
    diff = jnp.asarray(rng.normal(size=(3, d)), dtype)
    quad, logdet = chol_half_quad(diff, jnp.asarray(cov, dtype))
    np.testing.assert_array_equal(np.isfinite(quad), [True, False, True])
    np.testing.assert_array_equal(np.isfinite(logdet), [True, False, True])


def test_adaptive_rejects_lane_whose_cost_is_not_finite():
    """A lane whose per-step measurement covariance is indefinite at one
    step has a non-finite cost; the adaptive loop marks that lane
    diverged and returns it finite, while its co-lane iterates."""
    model = make_coordinated_turn_model(CoordinatedTurnConfig())
    n = 16
    ys = jnp.stack([simulate_trajectory(model, n, jax.random.PRNGKey(k))[1]
                    for k in (3, 4)])
    R = np.broadcast_to(np.asarray(model.R), (2, n, 2, 2)).copy()
    R[1, n // 2] = [[1.0, 2.0], [2.0, 1.0]]
    model = dataclasses.replace(model, R=jnp.asarray(R))
    traj0 = initial_trajectory_batched(model, 2, n)
    cost = np.asarray(gn_cost(model, ys, traj0))
    np.testing.assert_array_equal(np.isfinite(cost), [True, False])
    cfg = IteratedConfig(method="ekf", n_iter=3, parallel=True,
                         damping="adaptive", lm_lambda=1.0)
    traj, info = build_smoother(SmootherSpec.from_iterated_config(
        cfg)).iterate(model, ys, return_info=True)
    np.testing.assert_array_equal(np.asarray(info.code)[1], LANE_DIVERGED)
    np.testing.assert_array_equal(np.asarray(info.iterations), [3, 0])
    assert np.isfinite(np.asarray(traj.mean)).all()
    np.testing.assert_array_equal(np.asarray(traj.mean[1]),
                                  np.asarray(traj0.mean[1]))
    assert float(info.final_cost[0]) < float(cost[0])


def _old_mvn_logpdf(x, mean, cov):
    """The formula `mvn_logpdf` evaluated before the unrolled primitive."""
    d = x.shape[-1]
    chol = jnp.linalg.cholesky(cov)
    z = jnp.linalg.solve(chol, (x - mean)[..., None])[..., 0]
    logdet = 2.0 * jnp.sum(jnp.log(jnp.diagonal(chol, axis1=-2, axis2=-1)),
                           axis=-1)
    return -0.5 * (jnp.sum(z * z, axis=-1) + logdet
                   + d * jnp.log(2.0 * jnp.pi))


@pytest.mark.parametrize("dtype", [np.float64, np.float32])
@pytest.mark.parametrize("batch", [(), (N,), (B, N)])
@pytest.mark.parametrize("d", [2, 5, 17])
def test_mvn_logpdf_matches_old_formula(d, batch, dtype):
    rng = np.random.default_rng(d + len(batch))
    x = jnp.asarray(rng.normal(size=batch + (d,)), dtype)
    mean = jnp.asarray(rng.normal(size=batch + (d,)), dtype)
    cov = jnp.asarray(_spd(rng, batch, d), dtype)
    got = mvn_logpdf(x, mean, cov)
    assert got.shape == batch and got.dtype == dtype
    np.testing.assert_allclose(got, _old_mvn_logpdf(x, mean, cov),
                               rtol=16 * d * np.finfo(dtype).eps)


# Factorization ops, as XLA or LAPACK name them, and any custom call.
_DECOMPOSITION = re.compile(
    r"custom_call|cholesky|triangular_solve|\blu\b|getrf|potrf|trsm", re.I)


def _lin(rng, b, n, nx, ny, dtype=np.float32):
    r = lambda *s: jnp.asarray(rng.normal(size=s), dtype)  # noqa: E731
    spd = lambda d: jnp.asarray(_spd(rng, (b, n), d), dtype)  # noqa: E731
    return LinearizedSSM(F=r(b, n, nx, nx), c=r(b, n, nx), Qp=spd(nx),
                         H=r(b, n, ny, nx), d=r(b, n, ny), Rp=spd(ny))


@pytest.mark.parametrize("what,dim", [("cost", 5), ("loglik", 2),
                                      ("cost", 17), ("loglik", 17)])
def test_batched_cost_and_loglik_lower_without_decompositions(what, dim):
    """The mechanism has no runtime counter, so read it off the lowered
    program: nothing to factor, at the coordinated-turn sizes and above
    them."""
    rng = np.random.default_rng(dim)
    b, n = 4, 8
    if what == "cost":
        lin = _lin(rng, b, n, dim, 2 if dim == 5 else dim)
        means = jnp.asarray(rng.normal(size=(b, n + 1, dim)), jnp.float32)
        ys = jnp.asarray(rng.normal(size=(b, n, lin.d.shape[-1])),
                         jnp.float32)
        m0, P0 = means[0, 0], jnp.asarray(_spd(rng, (), dim), jnp.float32)
        fn, args = smoothing_cost, (lin, ys, means, m0, P0)
    elif dim == 2:
        model = make_coordinated_turn_model(CoordinatedTurnConfig(),
                                            jnp.float32)
        ys = jnp.asarray(rng.normal(size=(b, n, 2)), jnp.float32)
        traj = initial_trajectory_batched(model, b, n)
        fn = lambda ys, traj: smoothed_log_likelihood(  # noqa: E731
            model, ys, traj, IteratedConfig(method="ekf"))
        args = (ys, traj)
    else:
        x = jnp.asarray(rng.normal(size=(b, n, dim)), jnp.float32)
        cov = jnp.asarray(_spd(rng, (b, n), dim), jnp.float32)
        fn, args = mvn_logpdf, (x, jnp.zeros_like(x), cov)
    text = jax.jit(fn).lower(*args).as_text()
    found = sorted(set(m.lower() for m in _DECOMPOSITION.findall(text)))
    assert found == [], found
