"""The wide lowering of the per-element algebra (`core.types`), and where it
is chosen.

Contract under test:
  * the blocked inverse, the blocked Cholesky quadratic form and the
    `dot_general` product agree with ``jnp.linalg`` / ``jax.scipy`` and
    with float64 numpy, in float64 and float32, at d in {8, 17, 40, 80}
    (17: a last block that is not whole) over ``[n]`` and ``[B, n]``
    stacks; a covariance that is not positive definite still gives a
    non-finite result, element by element;
  * the lowering is chosen from the static shape: at the coordinated
    turn's sizes (nx = 5, ny = 2, 7 rows a step with damping) the served
    program is the one the unrolled forms alone give, with no
    ``smoother.factor`` scope; at Lorenz-96's 40 sites the factorizations
    of every stage run under ``smoother.factor``.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.scipy.linalg import solve_triangular

from repro.core import types
from repro.launch.serve import SmootherServeConfig, SmootherServer
from repro.scenarios import get_scenario

B, N = 3, 6


def _spd(rng, shape, d):
    A = rng.normal(size=shape + (d, d))
    return A @ np.swapaxes(A, -1, -2) / d + 0.5 * np.eye(d)


@jax.jit
def _wide(A, S, x):
    return (types.inverse_blocked(S), types.chol_half_quad_blocked(x, S),
            types.matmul_wide(A, S))


@jax.jit
def _linalg(A, S, x):
    chol = jnp.linalg.cholesky(S)
    z = solve_triangular(chol, x[..., None], lower=True)[..., 0]
    logdet = 2.0 * jnp.sum(jnp.log(jnp.diagonal(chol, axis1=-2, axis2=-1)),
                           axis=-1)
    return (jnp.linalg.inv(S), (0.5 * jnp.sum(z * z, axis=-1), logdet),
            jnp.matmul(A, S, precision=jax.lax.Precision.HIGHEST))


@pytest.mark.parametrize("batch", [(N,), (B, N)], ids=["n", "Bn"])
@pytest.mark.parametrize("dtype", [np.float64, np.float32])
@pytest.mark.parametrize("d", [8, 17, 40, 80])
def test_wide_lowering_matches_linalg(d, dtype, batch):
    rng = np.random.default_rng(d + len(batch))
    A64, S64 = rng.normal(size=batch + (d, d)), _spd(rng, batch, d)
    x64 = rng.normal(size=batch + (d,))
    A, S, x = (jnp.asarray(v, dtype) for v in (A64, S64, x64))
    inv, (quad, logdet), prod = _wide(A, S, x)
    ref_inv, (ref_quad, ref_logdet), ref_prod = _linalg(A, S, x)
    assert inv.shape == S.shape and quad.shape == logdet.shape == batch
    assert inv.dtype == quad.dtype == prod.dtype == dtype
    eps = np.finfo(dtype).eps
    # Two roundings of one formula, each entry a sum of about d terms of
    # a well-conditioned matrix (eigenvalues 0.5 to about 4.5): within a
    # few d roundings of each other, scaled by the largest entry.
    tol = 16 * d * eps
    scale = lambda v: np.max(np.abs(np.asarray(v)))  # noqa: E731
    assert scale(inv - ref_inv) <= tol * scale(ref_inv)
    assert scale(prod - ref_prod) <= tol * scale(ref_prod)
    np.testing.assert_allclose(quad, ref_quad, rtol=tol)
    np.testing.assert_allclose(logdet, ref_logdet, rtol=tol, atol=tol)
    # ... and no further from the exact value than the library's path.
    exact_inv = np.linalg.inv(np.asarray(S, np.float64))
    exact_quad = 0.5 * np.sum(
        x64 * np.linalg.solve(np.asarray(S, np.float64),
                              np.asarray(x, np.float64)[..., None])[..., 0],
        axis=-1)
    err = lambda v, w: scale(np.asarray(v, np.float64) - w)  # noqa: E731
    assert err(inv, exact_inv) <= 2 * err(ref_inv, exact_inv) \
        + 16 * eps * scale(exact_inv)
    assert err(quad, exact_quad) <= 2 * err(ref_quad, exact_quad) \
        + 16 * eps * scale(exact_quad)


@pytest.mark.parametrize("dtype", [np.float64, np.float32])
def test_wide_non_pd_covariance_gives_non_finite(dtype):
    """Only the element whose covariance is indefinite (past the first
    block, so the trailing update carries it) goes non-finite."""
    d = 40
    bad = np.eye(d)
    bad[d - 1, d - 2] = bad[d - 2, d - 1] = 2.0
    cov = jnp.asarray(np.stack([np.eye(d), bad, 2.0 * np.eye(d)]), dtype)
    diff = jnp.asarray(np.random.default_rng(0).normal(size=(3, d)), dtype)
    quad, logdet = types.chol_half_quad(diff, cov)
    np.testing.assert_array_equal(np.isfinite(quad), [True, False, True])
    np.testing.assert_array_equal(np.isfinite(logdet), [True, False, True])


def test_lowering_follows_the_static_shape():
    assert types.lowering(5, 7) == types.lowering(types.UNROLL_MAX) \
        == "unrolled"
    assert types.lowering(40, 80) == types.lowering(
        types.UNROLL_MAX + 1) == "wide"


def _served_text(name, b, n, monkeypatch=None, unroll_max=None,
                 debug_info=False):
    """The lowered text of a scenario's served launch under the
    benchmark's spec (adaptive damping from lambda 1)."""
    if unroll_max is not None:
        monkeypatch.setattr(types, "UNROLL_MAX", unroll_max)
    sc = get_scenario(name)
    model = sc.make_model(jnp.float32)
    server = SmootherServer(
        model, SmootherServeConfig(max_batch=b, f64=False),
        spec=sc.default_spec(n_iter=3, tol=1e-6, damping="adaptive",
                             lm_lambda=1.0))
    ys, rs = server._pad_bucket([np.zeros((n, model.ny), np.float32)], n, b)
    return server._run.lower(ys, rs).as_text(debug_info=debug_info)


def test_coordinated_turn_lowers_to_the_unrolled_forms(monkeypatch):
    assert "smoother.factor" not in _served_text("coordinated_turn", 4, 16,
                                                 debug_info=True)
    text = _served_text("coordinated_turn", 4, 16)
    forced = _served_text("coordinated_turn", 4, 16, monkeypatch, 10 ** 9)
    same = forced == text         # (a bare == on the texts would diff them)
    assert same
    # The check can see a change: with nothing unrolled the text differs.
    wide = _served_text("coordinated_turn", 4, 16, monkeypatch, 1)
    differs = wide != text
    assert differs and "dot_general" in wide


def test_wide_factorizations_are_scoped_inside_their_stages():
    text = _served_text("lorenz96_40", 2, 4, debug_info=True)
    stages = set()
    for name in re.findall(r'"([^"]*smoother\.factor[^"]*)"', text):
        scopes = re.findall(r"smoother\.[a-z]+", name)
        stages.add(scopes[scopes.index("smoother.factor") - 1])
    assert {"smoother.filter", "smoother.smooth", "smoother.combine",
            "smoother.cost", "smoother.loglik"} <= stages, stages
