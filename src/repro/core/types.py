"""Core pytree types for the parallel iterated Kalman smoothers.

Conventions (see DESIGN.md §11):
  * ``n`` measurements ``y_{1:n}``; states ``x_{0:n}``.
  * Transition params ``F_k, c_k, Lambda_k`` map ``x_k -> x_{k+1}`` and are
    stored for ``k = 0..n-1`` (leading dim ``n``).
  * Measurement params ``H_k, d_k, Omega_k`` are for ``y_k`` at ``x_k``,
    ``k = 1..n``, stored 0-based (leading dim ``n``).
  * Filtering outputs have leading dim ``n`` (posteriors of ``x_1..x_n``).
  * Smoothing outputs have leading dim ``n+1`` (``x_0..x_n``).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp


class Gaussian(NamedTuple):
    """A (batched) Gaussian ``N(mean, cov)``."""

    mean: jnp.ndarray  # [..., nx]
    cov: jnp.ndarray   # [..., nx, nx]


class LinearizedSSM(NamedTuple):
    """Affine-Gaussian approximation of the model over a full trajectory.

    ``p(x_{k+1}|x_k) ~= N(F[k] x_k + c[k], Qp[k])`` for ``k = 0..n-1`` and
    ``p(y_k|x_k) ~= N(H[k-1] x_k + d[k-1], Rp[k-1])`` for ``k = 1..n``,
    where ``Qp = Q + Lambda`` and ``Rp = R + Omega`` (paper Eq. 11).
    """

    F: jnp.ndarray   # [n, nx, nx]
    c: jnp.ndarray   # [n, nx]
    Qp: jnp.ndarray  # [n, nx, nx]
    H: jnp.ndarray   # [n, ny, nx]
    d: jnp.ndarray   # [n, ny]
    Rp: jnp.ndarray  # [n, ny, ny]


class FilteringElement(NamedTuple):
    """Parallel filtering element ``a_k = (A, b, C, eta, J)`` (paper Eq. 13-14)."""

    A: jnp.ndarray    # [..., nx, nx]
    b: jnp.ndarray    # [..., nx]
    C: jnp.ndarray    # [..., nx, nx]
    eta: jnp.ndarray  # [..., nx]
    J: jnp.ndarray    # [..., nx, nx]


class SmoothingElement(NamedTuple):
    """Parallel smoothing element ``a_k = (E, g, L)`` (paper Eq. 17-18)."""

    E: jnp.ndarray  # [..., nx, nx]
    g: jnp.ndarray  # [..., nx]
    L: jnp.ndarray  # [..., nx, nx]


@dataclasses.dataclass(frozen=True)
class StateSpaceModel:
    """Nonlinear additive-Gaussian state-space model (paper Eq. 4).

    ``x_k = f(x_{k-1}) + q``, ``q ~ N(0, Q)``;
    ``y_k = h(x_k) + r``,     ``r ~ N(0, R)``;
    ``x_0 ~ N(m0, P0)``.

    ``f``/``h`` act on a single (unbatched) state vector; time-varying
    models can close over ``k`` by passing stacked ``Q``/``R`` with leading
    dim ``n`` (otherwise they are broadcast).
    """

    f: Callable[[jnp.ndarray], jnp.ndarray]
    h: Callable[[jnp.ndarray], jnp.ndarray]
    Q: jnp.ndarray
    R: jnp.ndarray
    m0: jnp.ndarray
    P0: jnp.ndarray

    @property
    def nx(self) -> int:
        return self.m0.shape[-1]

    @property
    def ny(self) -> int:
        return self.R.shape[-1]


def broadcast_noise(M: jnp.ndarray, n: int) -> jnp.ndarray:
    """Broadcast a single covariance to a stacked ``[n, d, d]`` array."""
    M = jnp.asarray(M)
    if M.ndim == 2:
        return jnp.broadcast_to(M, (n,) + M.shape)
    if M.shape[0] != n:
        raise ValueError(f"noise stack has length {M.shape[0]}, expected {n}")
    return M


def symmetrize(M: jnp.ndarray) -> jnp.ndarray:
    return 0.5 * (M + jnp.swapaxes(M, -1, -2))


def eye32(n: int, dtype) -> jnp.ndarray:
    """``jnp.eye(n, dtype=dtype)`` built from int32 iotas. `jnp.eye`'s
    iotas are int64 when float64 is enabled, and Mosaic has no 64-bit
    vector layout: inside a TPU kernel that aborts the compiler."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    return (rows == cols).astype(dtype)


def gauss_jordan_inverse(W: jnp.ndarray) -> jnp.ndarray:
    """Batched inverse of ``[..., n, n]`` via Gauss-Jordan, unrolled over n.

    No pivoting — callers must pass matrices that are safe without it
    (positive definite, or ``I + PSD @ PSD`` whose spectrum lies right of
    1). The point is throughput: ``jnp.linalg.solve``/``inv`` dispatch one
    LAPACK call *per matrix*, which dominates wall-clock when a batched
    scan level carries tens of thousands of tiny (nx <= 16) systems; this
    form is pure vectorized arithmetic over the whole batch. It is also
    the in-register elimination used inside the `kalman_combine` Pallas
    kernel (the 2D iota keeps Mosaic happy).
    """
    n = W.shape[-1]
    eye = eye32(n, W.dtype)
    aug = jnp.concatenate(
        [W, jnp.broadcast_to(eye, W.shape[:-2] + (n, n))], axis=-1)
    row_ids = jax.lax.broadcasted_iota(jnp.int32, (n, 1), 0)
    for k in range(n):
        pivot_row = aug[..., k:k + 1, :] / aug[..., k:k + 1, k:k + 1]
        factors = aug[..., :, k:k + 1]
        eliminated = aug - factors * pivot_row
        aug = jnp.where(row_ids == k, pivot_row, eliminated)
    return aug[..., :, n:]


def chol_half_quad(diff: jnp.ndarray, cov: jnp.ndarray
                   ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """``(1/2 diff^T cov^-1 diff, log det cov)`` for SPD ``cov [..., d, d]``
    and ``diff [..., d]``, batched (and broadcast) over the leading axes.

    Cholesky-Banachiewicz then forward substitution ``L z = diff``,
    unrolled over the static ``d``: every factor entry ``L[i][j]`` and
    every ``z[i]`` is an array of the batch shape, so the work is
    elementwise arithmetic with the batch (time) axes on the vector lanes
    and no per-matrix LAPACK-style call, as in `gauss_jordan_inverse`.
    Like ``jnp.linalg.cholesky``: no pivoting, the input is symmetrized,
    and a pivot that is not positive gives NaN (a non-PD covariance makes
    both results NaN).
    """
    d = diff.shape[-1]
    L = [[None] * d for _ in range(d)]
    inv_diag, z = [], []
    logdet = quad = 0.0
    for i in range(d):
        for j in range(i + 1):
            s = (cov[..., i, i] if i == j
                 else 0.5 * (cov[..., i, j] + cov[..., j, i]))
            for k in range(j):
                s = s - L[i][k] * L[j][k]
            if i == j:
                pivot = jnp.sqrt(jnp.where(s > 0, s, jnp.nan))
                inv_diag.append(1.0 / pivot)
                logdet = logdet + jnp.log(pivot)
            else:
                L[i][j] = s * inv_diag[j]
        r = diff[..., i]
        for k in range(i):
            r = r - L[i][k] * z[k]
        z.append(r * inv_diag[i])
        quad = quad + z[i] * z[i]
    return 0.5 * quad, jnp.broadcast_to(2.0 * logdet, quad.shape)


def bmm(A: jnp.ndarray, B: jnp.ndarray) -> jnp.ndarray:
    """Batched tiny matmul ``[..., n, m] @ [..., m, p]`` as broadcast-mul-
    reduce over the *last* (contiguous/lane) axis: C[i,k] = sum_j A[i,j] *
    B^T[k,j]. Both the TPU VPU and XLA:CPU vectorize this far better than
    a strided middle-axis reduction (~2x on CPU) and it avoids
    dot_general's per-matrix batched-gemm overhead (~4x)."""
    return jnp.sum(A[..., :, None, :] * jnp.swapaxes(B, -1, -2)[..., None, :, :],
                   axis=-1)


def bmv(A: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    """Batched matvec ``[..., n, m] @ [..., m] -> [..., n]``."""
    return jnp.sum(A * x[..., None, :], axis=-1)


def bcast_prior(x: jnp.ndarray, B: int, ndim: int) -> jnp.ndarray:
    """Broadcast a shared prior (``[nx]``/``[nx, nx]``, i.e. ``ndim``
    axes) to ``B`` lanes; per-lane priors pass through unchanged."""
    x = jnp.asarray(x)
    if x.ndim == ndim:
        return jnp.broadcast_to(x, (B,) + x.shape)
    return x


def mvn_logpdf(x: jnp.ndarray, mean: jnp.ndarray, cov: jnp.ndarray) -> jnp.ndarray:
    """Log-density of ``N(x; mean, cov)`` (used for data log-likelihood)."""
    d = x.shape[-1]
    half_quad, logdet = chol_half_quad(x - mean, cov)
    return -0.5 * (2.0 * half_quad + logdet + d * jnp.log(2.0 * jnp.pi))
