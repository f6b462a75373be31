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

from .scopes import FACTOR


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


#: The widest static dimension the per-element algebra unrolls. At or
#: below it, products are broadcast-multiply-reduce on the vector unit and
#: the inverse and the Cholesky factor are unrolled over the dimension, as
#: the coordinated turn (5 x 5 and 7 x 7) has always run. Above it,
#: products are `dot_general` at precision highest (the MXU) and the
#: inverse and the factor are `fori_loop`s over blocks of `BLOCK`, whose
#: program does not grow with the dimension (the unrolled Cholesky grows
#: with its cube: 5.7 s to compile at 16 on a TPU v5e host, 154 s at 24).
#: Chosen from the shapes, never from a knob; DESIGN.md §3 has the
#: measurements behind it.
UNROLL_MAX = 16
#: Rows per step of the blocked inverse and Cholesky factor; each block's
#: own pivot is the unrolled form at this size.
BLOCK = 8


def lowering(*dims: int) -> str:
    """The algebra that runs at these static dimensions: ``"unrolled"``
    where every one is at most `UNROLL_MAX`, else ``"wide"``."""
    return "unrolled" if max(dims) <= UNROLL_MAX else "wide"


def gauss_jordan_inverse(W: jnp.ndarray) -> jnp.ndarray:
    """Batched inverse of ``[..., n, n]`` by Gauss-Jordan elimination.

    No pivoting — callers must pass matrices that are safe without it
    (positive definite, or ``I + PSD @ PSD`` whose spectrum lies right of
    1). The point is throughput: ``jnp.linalg.solve``/``inv`` dispatch one
    LAPACK call *per matrix*, which dominates wall-clock when a batched
    scan level carries tens of thousands of tiny systems; this form is
    pure vectorized arithmetic over the whole batch. Unrolled over ``n``
    up to `UNROLL_MAX` (the in-register elimination inside the
    `kalman_combine` Pallas kernel; the 2D iota keeps Mosaic happy), in
    blocks of `BLOCK` rows above it (`inverse_blocked`).
    """
    n = W.shape[-1]
    if lowering(n) == "wide":
        with jax.named_scope(FACTOR):
            return inverse_blocked(W)
    return _inverse_unrolled(W)


def _inverse_unrolled(W: jnp.ndarray) -> jnp.ndarray:
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


def _pad_to_blocks(W: jnp.ndarray) -> jnp.ndarray:
    """``[[W, 0], [0, I]]`` up to a whole number of `BLOCK` rows: the
    identity adds nothing to a product, inverse or factor of ``W``."""
    n = W.shape[-1]
    extra = -n % BLOCK
    if not extra:
        return W
    pad = [(0, 0)] * (W.ndim - 2)
    eye = jnp.pad(eye32(extra, W.dtype), [(n, 0), (n, 0)])
    return jnp.pad(W, pad + [(0, extra), (0, extra)]) + eye


def inverse_blocked(W: jnp.ndarray) -> jnp.ndarray:
    """The same Gauss-Jordan elimination, `BLOCK` pivots at a time, as a
    `fori_loop` whose program does not grow with ``n``: each step inverts
    its diagonal block (unrolled), scales the block's rows by it and
    eliminates the block's columns from every other row with one matrix
    product."""
    n = W.shape[-1]
    Wp = _pad_to_blocks(W)
    m = Wp.shape[-1]
    aug = jnp.concatenate(
        [Wp, jnp.broadcast_to(eye32(m, W.dtype), Wp.shape)], axis=-1)

    def step(k, aug):
        at = k * BLOCK
        rows = jax.lax.dynamic_slice_in_dim(aug, at, BLOCK, axis=-2)
        pivot = jax.lax.dynamic_slice_in_dim(rows, at, BLOCK, axis=-1)
        pivot_rows = matmul_wide(_inverse_unrolled(pivot), rows)
        cols = jax.lax.dynamic_slice_in_dim(aug, at, BLOCK, axis=-1)
        aug = aug - matmul_wide(cols, pivot_rows)
        return jax.lax.dynamic_update_slice_in_dim(aug, pivot_rows, at,
                                                   axis=-2)

    aug = jax.lax.fori_loop(0, m // BLOCK, step, aug)
    return aug[..., :n, m:m + n]


def chol_half_quad(diff: jnp.ndarray, cov: jnp.ndarray
                   ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """``(1/2 diff^T cov^-1 diff, log det cov)`` for SPD ``cov [..., d, d]``
    and ``diff [..., d]``, batched (and broadcast) over the leading axes.

    Cholesky-Banachiewicz then forward substitution ``L z = diff``,
    unrolled over the static ``d`` up to `UNROLL_MAX`: every factor entry
    ``L[i][j]`` and every ``z[i]`` is an array of the batch shape, so the
    work is elementwise arithmetic with the batch (time) axes on the
    vector lanes and no per-matrix LAPACK-style call, as in
    `gauss_jordan_inverse`. Above it, the same factorization runs in
    blocks (`chol_half_quad_blocked`). Like ``jnp.linalg.cholesky``: no
    pivoting, the input is symmetrized, and a pivot that is not positive
    gives NaN (a non-PD covariance makes both results NaN).
    """
    if lowering(diff.shape[-1]) == "wide":
        with jax.named_scope(FACTOR):
            return chol_half_quad_blocked(diff, cov)
    return _chol_half_quad_unrolled(diff, cov)


def _chol_half_quad_unrolled(diff: jnp.ndarray, cov: jnp.ndarray
                             ) -> tuple[jnp.ndarray, jnp.ndarray]:
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


def chol_half_quad_blocked(diff: jnp.ndarray, cov: jnp.ndarray
                           ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """`chol_half_quad` by a right-looking blocked Cholesky, as a
    `fori_loop` whose program does not grow with ``d``: each step factors
    its `BLOCK` x `BLOCK` diagonal block (unrolled), solves the columns
    below it and the block of ``z`` together by forward substitution, and
    takes their outer product off the trailing rows and columns with one
    matrix product."""
    batch = jnp.broadcast_shapes(diff.shape[:-1], cov.shape[:-2])
    d = diff.shape[-1]
    A = _pad_to_blocks(symmetrize(jnp.broadcast_to(cov, batch + (d, d))))
    m = A.shape[-1]
    r = jnp.pad(jnp.broadcast_to(diff, batch + (d,)),
                [(0, 0)] * len(batch) + [(0, m - d)])
    row_ids = jax.lax.broadcasted_iota(jnp.int32, (m, 1), 0)

    def step(k, carry):
        A, r, quad, logdet = carry
        at = k * BLOCK
        cols = jax.lax.dynamic_slice_in_dim(A, at, BLOCK, axis=-1)
        pivot = jax.lax.dynamic_slice_in_dim(cols, at, BLOCK, axis=-2)
        # Every row of the block's columns, and the residual's block as
        # one more row: solved against the block's factor together.
        X = jnp.concatenate(
            [cols, jax.lax.dynamic_slice_in_dim(r, at, BLOCK,
                                                axis=-1)[..., None, :]],
            axis=-2)
        L = [[None] * BLOCK for _ in range(BLOCK)]
        inv_diag, Y = [], []
        for i in range(BLOCK):
            for j in range(i + 1):
                s = pivot[..., i, j]
                for q in range(j):
                    s = s - L[i][q] * L[j][q]
                if i == j:
                    p = jnp.sqrt(jnp.where(s > 0, s, jnp.nan))
                    inv_diag.append(1.0 / p)
                    logdet = logdet + jnp.log(p)
                else:
                    L[i][j] = s * inv_diag[j]
            y = X[..., i]
            for q in range(i):
                y = y - L[i][q][..., None] * Y[q]
            Y.append(y * inv_diag[i][..., None])
        Y = jnp.stack(Y, axis=-1)                     # [..., m + 1, BLOCK]
        z = Y[..., -1, :]
        P = jnp.where(row_ids >= at + BLOCK, Y[..., :-1, :], 0.0)
        A = A - matmul_wide(P, jnp.swapaxes(P, -1, -2))
        r = r - bmv(P, z)
        return A, r, quad + jnp.sum(z * z, axis=-1), logdet

    zero = jnp.zeros(batch, A.dtype)
    _, _, quad, logdet = jax.lax.fori_loop(0, m // BLOCK, step,
                                           (A, r, zero, zero))
    return 0.5 * quad, 2.0 * logdet


def matmul_wide(A: jnp.ndarray, B: jnp.ndarray) -> jnp.ndarray:
    """``[..., n, m] @ [..., m, p]`` as one `dot_general` at precision
    highest: MXU work on the TPU, float32 kept float32."""
    return jnp.matmul(A, B, precision=jax.lax.Precision.HIGHEST)


def bmm(A: jnp.ndarray, B: jnp.ndarray) -> jnp.ndarray:
    """Batched matmul ``[..., n, m] @ [..., m, p]``. Up to `UNROLL_MAX`
    as broadcast-mul-reduce over the *last* (contiguous/lane) axis:
    C[i,k] = sum_j A[i,j] * B^T[k,j]. Both the TPU VPU and XLA:CPU
    vectorize this far better than a strided middle-axis reduction (~2x
    on CPU) and it avoids dot_general's per-matrix batched-gemm overhead
    (~4x). Wider, one `dot_general` (`matmul_wide`)."""
    if lowering(*A.shape[-2:], B.shape[-1]) == "wide":
        return matmul_wide(A, B)
    return _bmm_unrolled(A, B)


def _bmm_unrolled(A: jnp.ndarray, B: jnp.ndarray) -> jnp.ndarray:
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
