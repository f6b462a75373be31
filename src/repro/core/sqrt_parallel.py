"""Square-root (Cholesky-factor) parallel filtering and smoothing —
beyond-paper extension for single-precision robustness.

The 2021 paper's combines propagate covariances ``C`` and information
matrices ``J`` directly; long products of Eq. 15 lose positive
definiteness in float32 (observed here and acknowledged by the authors'
follow-up work on square-root parallel smoothers). This module propagates
*factors* ``U`` (``C = U Uᵀ``), ``Z`` (``J = Z Zᵀ``) and ``D``
(``L = D Dᵀ``) instead, with all updates via QR triangularization — the
standard square-root-filter construction lifted to the parallel combine:

  filtering element  a_k = (A, b, U, eta, Z)
  smoothing element  a_k = (E, g, D)

Combine identities (Woodbury on ``(I + C_i J_j)^{-1}`` with
``G = U_iᵀ Z_j``):
  (I + C_i J_j)^{-1}      = I - U_i (I + GGᵀ)^{-1} G Z_jᵀ
  (I + C_i J_j)^{-1} C_i  = U_i (I + GGᵀ)^{-1} U_iᵀ
  (I + J_j C_i)^{-1} J_j  = Z_j (I + GᵀG)^{-1} Z_jᵀ
so each combine costs two [nx, 2nx] QRs + triangular solves and never
forms C or J. Outputs match `repro.core.parallel` exactly in float64 and
stay stable in float32 where the covariance form diverges (see
tests/core/test_sqrt_parallel.py and EXPERIMENTS.md §Beyond-paper).
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax.scipy.linalg import solve_triangular

from . import scan as scan_lib
from .scopes import COMBINE, FILTER, SMOOTH, scoped
from .types import (Gaussian, LinearizedSSM, bcast_prior as _bcast_prior,
                    symmetrize)


class SqrtFilteringElement(NamedTuple):
    A: jnp.ndarray    # [..., nx, nx]
    b: jnp.ndarray    # [..., nx]
    U: jnp.ndarray    # [..., nx, nx]  lower-tri factor of C
    eta: jnp.ndarray  # [..., nx]
    Z: jnp.ndarray    # [..., nx, nx]  factor of J


class SqrtSmoothingElement(NamedTuple):
    E: jnp.ndarray  # [..., nx, nx]
    g: jnp.ndarray  # [..., nx]
    D: jnp.ndarray  # [..., nx, nx]  lower-tri factor of L


def tria(M: jnp.ndarray) -> jnp.ndarray:
    """Lower-triangular T with T Tᵀ = M Mᵀ, via QR of Mᵀ. M is [n, m]."""
    r = jnp.linalg.qr(jnp.swapaxes(M, -1, -2), mode="r")
    return jnp.swapaxes(r, -1, -2)


def _chol_inv_apply(L: jnp.ndarray, X: jnp.ndarray) -> jnp.ndarray:
    """(L Lᵀ)^{-1} X given lower-triangular L."""
    y = solve_triangular(L, X, lower=True)
    return solve_triangular(jnp.swapaxes(L, -1, -2), y, lower=False)


# ---------------------------------------------------------------------------
# Element construction
# ---------------------------------------------------------------------------

def _sqrt_predict_update(F, c, LQ, H, d, LR, y, m, LP):
    """One square-root KF step from (m, chol P). Returns (m', LP')."""
    nx = m.shape[-1]
    ny = y.shape[-1]
    LP_pred = tria(jnp.concatenate([F @ LP, LQ], axis=-1))
    m_pred = F @ m + c
    # Joint triangularization gives chol(S), the gain factor and chol(P').
    top = jnp.concatenate([H @ LP_pred, LR], axis=-1)            # [ny, .]
    bot = jnp.concatenate([LP_pred,
                           jnp.zeros((nx, ny), LP.dtype)], axis=-1)
    Psi = tria(jnp.concatenate([top, bot], axis=0))
    Psi11 = Psi[:ny, :ny]
    Psi21 = Psi[ny:, :ny]
    Psi22 = Psi[ny:, ny:]
    innov = y - (H @ m_pred + d)
    m_new = m_pred + Psi21 @ solve_triangular(Psi11, innov, lower=True)
    return m_new, Psi22


def _first_sqrt_element(lin0, y1, m0, LP0) -> SqrtFilteringElement:
    F, c, LQ, H, d, LR = lin0
    nx = m0.shape[-1]
    b, U = _sqrt_predict_update(F, c, LQ, H, d, LR, y1, m0, LP0)
    z = jnp.zeros((nx,), m0.dtype)
    Zm = jnp.zeros((nx, nx), m0.dtype)
    return SqrtFilteringElement(A=Zm, b=b, U=U, eta=z, Z=Zm)


def _generic_sqrt_element(F, c, LQ, H, d, LR, y) -> SqrtFilteringElement:
    nx = F.shape[-1]
    ny = y.shape[-1]
    I = jnp.eye(nx, dtype=F.dtype)
    top = jnp.concatenate([H @ LQ, LR], axis=-1)
    bot = jnp.concatenate([LQ, jnp.zeros((nx, ny), F.dtype)], axis=-1)
    Psi = tria(jnp.concatenate([top, bot], axis=0))
    Psi11 = Psi[:ny, :ny]          # chol(S)
    Psi21 = Psi[ny:, :ny]          # Q' Hᵀ chol(S)^{-T}
    U = Psi[ny:, ny:]              # chol((I - K H) Q')
    K = Psi21 @ jnp.linalg.inv(Psi11)  # small ny; triangular inverse
    innov = y - (H @ c + d)
    A = (I - K @ H) @ F
    b = c + K @ innov
    # Z Zᵀ = (H F)ᵀ S^{-1} (H F):  Z = Fᵀ Hᵀ chol(S)^{-T}  — naturally
    # [nx, ny]; normalized to a square [nx, nx] factor (zero-padded or
    # re-triangularized) so scan elements are shape-uniform.
    Z = solve_triangular(Psi11, H @ F, lower=True)
    Z = jnp.swapaxes(Z, -1, -2)
    eta = Z @ solve_triangular(Psi11, innov, lower=True)
    if ny < nx:
        Z = jnp.concatenate(
            [Z, jnp.zeros((nx, nx - ny), F.dtype)], axis=-1)
    elif ny > nx:
        Z = tria(Z)
    return SqrtFilteringElement(A=A, b=b, U=U, eta=eta, Z=Z)


def sqrt_filtering_elements(lin: LinearizedSSM, ys, m0, P0
                            ) -> SqrtFilteringElement:
    LQ = jnp.linalg.cholesky(symmetrize(lin.Qp))
    LR = jnp.linalg.cholesky(symmetrize(lin.Rp))
    LP0 = jnp.linalg.cholesky(symmetrize(P0))
    generic = jax.vmap(_generic_sqrt_element)(lin.F, lin.c, LQ, lin.H,
                                              lin.d, LR, ys)
    first = _first_sqrt_element(
        (lin.F[0], lin.c[0], LQ[0], lin.H[0], lin.d[0], LR[0]),
        ys[0], m0, LP0)
    return jax.tree_util.tree_map(
        lambda f, g: jnp.concatenate([f[None], g[1:]], axis=0), first,
        generic)


# ---------------------------------------------------------------------------
# Combines
# ---------------------------------------------------------------------------

@scoped(COMBINE)
def sqrt_filtering_combine(ei: SqrtFilteringElement,
                           ej: SqrtFilteringElement
                           ) -> SqrtFilteringElement:
    nx = ei.b.shape[-1]
    I = jnp.eye(nx, dtype=ei.b.dtype)
    G = jnp.swapaxes(ei.U, -1, -2) @ ej.Z               # U_iᵀ Z_j
    L1 = tria(jnp.concatenate([G, I], axis=-1))          # chol(I + GGᵀ)
    L2 = tria(jnp.concatenate([jnp.swapaxes(G, -1, -2), I], axis=-1))

    # T1 = (I + C_i J_j)^{-1}
    T1 = I - ei.U @ _chol_inv_apply(L1, G @ jnp.swapaxes(ej.Z, -1, -2))
    AjT1 = ej.A @ T1
    A = AjT1 @ ei.A
    b = AjT1 @ (ei.b + ei.U @ (jnp.swapaxes(ei.U, -1, -2) @ ej.eta)) + ej.b
    # C part: A_j U_i (I + GGᵀ)^{-1} U_iᵀ A_jᵀ + C_j
    U1 = ej.A @ ei.U @ jnp.swapaxes(
        jnp.linalg.inv(L1), -1, -2)                      # A_j U_i L1^{-T}
    U = tria(jnp.concatenate([U1, ej.U], axis=-1))
    # eta / J part
    T1t = jnp.swapaxes(T1, -1, -2)                       # (I + J_j C_i)^{-1}
    eta = jnp.swapaxes(ei.A, -1, -2) @ (
        T1t @ (ej.eta - ej.Z @ (jnp.swapaxes(ej.Z, -1, -2) @ ei.b))) \
        + ei.eta
    Z1 = jnp.swapaxes(ei.A, -1, -2) @ ej.Z @ jnp.swapaxes(
        jnp.linalg.inv(L2), -1, -2)                      # A_iᵀ Z_j L2^{-T}
    Z = tria(jnp.concatenate([Z1, ei.Z], axis=-1))
    return SqrtFilteringElement(A=A, b=b, U=U, eta=eta, Z=Z)


@scoped(COMBINE)
def sqrt_smoothing_combine(ei: SqrtSmoothingElement,
                           ej: SqrtSmoothingElement) -> SqrtSmoothingElement:
    E = ei.E @ ej.E
    g = ei.E @ ej.g + ei.g
    D = tria(jnp.concatenate([ei.E @ ej.D, ei.D], axis=-1))
    return SqrtSmoothingElement(E=E, g=g, D=D)


def sqrt_filtering_identity(nx: int, dtype=jnp.float32):
    return SqrtFilteringElement(
        A=jnp.eye(nx, dtype=dtype), b=jnp.zeros((nx,), dtype),
        U=jnp.zeros((nx, nx), dtype), eta=jnp.zeros((nx,), dtype),
        Z=jnp.zeros((nx, nx), dtype))


def sqrt_smoothing_identity(nx: int, dtype=jnp.float32):
    return SqrtSmoothingElement(E=jnp.eye(nx, dtype=dtype),
                                g=jnp.zeros((nx,), dtype),
                                D=jnp.zeros((nx, nx), dtype))


# ---------------------------------------------------------------------------
# Drivers (mirror repro.core.parallel)
# ---------------------------------------------------------------------------

@scoped(FILTER)
def sqrt_parallel_filter(lin: LinearizedSSM, ys, m0, P0, *,
                         axis_name=None) -> Gaussian:
    elems = sqrt_filtering_elements(lin, ys, m0, P0)
    scanned = scan_lib.associative_scan(
        sqrt_filtering_combine, elems, reverse=False,
        axis_name=axis_name,
        identity=lambda: sqrt_filtering_identity(m0.shape[-1], m0.dtype))
    cov = scanned.U @ jnp.swapaxes(scanned.U, -1, -2)
    return Gaussian(mean=scanned.b, cov=cov)


def _generic_sqrt_smoothing_element(mf, Pf, F, c, LQk
                                    ) -> SqrtSmoothingElement:
    nx = mf.shape[-1]
    Uf = jnp.linalg.cholesky(symmetrize(Pf))
    top = jnp.concatenate([F @ Uf, LQk], axis=-1)
    bot = jnp.concatenate([Uf, jnp.zeros((nx, nx), mf.dtype)], axis=-1)
    Phi = tria(jnp.concatenate([top, bot], axis=0))
    Phi11 = Phi[:nx, :nx]
    Phi21 = Phi[nx:, :nx]
    D = Phi[nx:, nx:]
    E = Phi21 @ jnp.linalg.inv(Phi11)
    g = mf - E @ (F @ mf + c)
    return SqrtSmoothingElement(E=E, g=g, D=D)


def sqrt_smoothing_elements(lin: LinearizedSSM, filtered: Gaussian
                            ) -> SqrtSmoothingElement:
    LQ = jnp.linalg.cholesky(symmetrize(lin.Qp))
    body = jax.vmap(_generic_sqrt_smoothing_element)(
        filtered.mean[:-1], filtered.cov[:-1],
        lin.F[1:], lin.c[1:], LQ[1:])
    nx = filtered.mean.shape[-1]
    last = SqrtSmoothingElement(
        E=jnp.zeros((nx, nx), filtered.mean.dtype),
        g=filtered.mean[-1],
        D=jnp.linalg.cholesky(symmetrize(filtered.cov[-1])))
    return jax.tree_util.tree_map(
        lambda b, l: jnp.concatenate([b, l[None]], axis=0), body, last)


@scoped(SMOOTH)
def sqrt_parallel_smoother(lin: LinearizedSSM, filtered: Gaussian, m0, P0,
                           *, axis_name=None) -> Gaussian:
    elems = sqrt_smoothing_elements(lin, filtered)
    scanned = scan_lib.associative_scan(
        sqrt_smoothing_combine, elems, reverse=True, axis_name=axis_name,
        identity=lambda: sqrt_smoothing_identity(m0.shape[-1], m0.dtype))
    means = scanned.g
    covs = scanned.D @ jnp.swapaxes(scanned.D, -1, -2)

    F, c, Qp = lin.F[0], lin.c[0], lin.Qp[0]
    P_pred = symmetrize(F @ P0 @ F.T + Qp)
    G = jnp.linalg.solve(P_pred, F @ P0).T
    m0_s = m0 + G @ (means[0] - (F @ m0 + c))
    P0_s = symmetrize(P0 + G @ (covs[0] - P_pred) @ G.T)
    return Gaussian(mean=jnp.concatenate([m0_s[None], means], axis=0),
                    cov=jnp.concatenate([P0_s[None], covs], axis=0))


def sqrt_parallel_filter_smoother(lin: LinearizedSSM, ys, m0, P0
                                  ) -> Tuple[Gaussian, Gaussian]:
    filtered = sqrt_parallel_filter(lin, ys, m0, P0)
    smoothed = sqrt_parallel_smoother(lin, filtered, m0, P0)
    return filtered, smoothed


# ---------------------------------------------------------------------------
# Batched drivers (batch axis before time; one fused scan per level)
# ---------------------------------------------------------------------------

def sqrt_filtering_elements_batched(lin: LinearizedSSM, ys, m0, P0
                                    ) -> SqrtFilteringElement:
    """All ``B x n`` square-root filtering elements: one flattened vmap for
    the generic rows, the k=1 case written in-batch into row 0."""
    B, n = ys.shape[:2]
    LQ = jnp.linalg.cholesky(symmetrize(lin.Qp))
    LR = jnp.linalg.cholesky(symmetrize(lin.Rp))
    LP0 = jnp.linalg.cholesky(symmetrize(_bcast_prior(P0, B, 2)))
    flat = lambda x: x.reshape((B * n,) + x.shape[2:])
    generic = jax.vmap(_generic_sqrt_element)(
        flat(lin.F), flat(lin.c), flat(LQ), flat(lin.H), flat(lin.d),
        flat(LR), flat(ys))
    generic = jax.tree_util.tree_map(
        lambda x: x.reshape((B, n) + x.shape[1:]), generic)
    first = jax.vmap(_first_sqrt_element)(
        (lin.F[:, 0], lin.c[:, 0], LQ[:, 0], lin.H[:, 0], lin.d[:, 0],
         LR[:, 0]), ys[:, 0], _bcast_prior(m0, B, 1), LP0)
    return jax.tree_util.tree_map(
        lambda g, f: g.at[:, 0].set(f), generic, first)


@scoped(FILTER)
def sqrt_parallel_filter_batched(lin: LinearizedSSM, ys, m0, P0, *,
                                 axis_name=None) -> Gaussian:
    elems = sqrt_filtering_elements_batched(lin, ys, m0, P0)
    scanned = scan_lib.associative_scan(
        sqrt_filtering_combine, elems, reverse=False, axis_name=axis_name,
        batch_dims=1,
        identity=lambda: sqrt_filtering_identity(lin.F.shape[-1],
                                                 lin.F.dtype))
    cov = scanned.U @ jnp.swapaxes(scanned.U, -1, -2)
    return Gaussian(mean=scanned.b, cov=cov)


def sqrt_smoothing_elements_batched(lin: LinearizedSSM, filtered: Gaussian
                                    ) -> SqrtSmoothingElement:
    B, n = filtered.mean.shape[:2]
    nx = filtered.mean.shape[-1]
    LQ = jnp.linalg.cholesky(symmetrize(lin.Qp))
    flat = lambda x: x.reshape((B * (n - 1),) + x.shape[2:])
    body = jax.vmap(_generic_sqrt_smoothing_element)(
        flat(filtered.mean[:, :-1]), flat(filtered.cov[:, :-1]),
        flat(lin.F[:, 1:]), flat(lin.c[:, 1:]), flat(LQ[:, 1:]))
    body = jax.tree_util.tree_map(
        lambda x: x.reshape((B, n - 1) + x.shape[1:]), body)
    last = SqrtSmoothingElement(
        E=jnp.zeros((B, nx, nx), filtered.mean.dtype),
        g=filtered.mean[:, -1],
        D=jnp.linalg.cholesky(symmetrize(filtered.cov[:, -1])))
    return jax.tree_util.tree_map(
        lambda b, l: jnp.concatenate([b, l[:, None]], axis=1), body, last)


@scoped(SMOOTH)
def sqrt_parallel_smoother_batched(lin: LinearizedSSM, filtered: Gaussian,
                                   m0, P0, *, axis_name=None) -> Gaussian:
    B = filtered.mean.shape[0]
    elems = sqrt_smoothing_elements_batched(lin, filtered)
    scanned = scan_lib.associative_scan(
        sqrt_smoothing_combine, elems, reverse=True, axis_name=axis_name,
        batch_dims=1,
        identity=lambda: sqrt_smoothing_identity(lin.F.shape[-1],
                                                 lin.F.dtype))
    means = scanned.g
    covs = scanned.D @ jnp.swapaxes(scanned.D, -1, -2)

    def x0_step(F, c, Qp, m0k, P0k, m1_s, P1_s):
        P_pred = symmetrize(F @ P0k @ F.T + Qp)
        G = jnp.linalg.solve(P_pred, F @ P0k).T
        m0_s = m0k + G @ (m1_s - (F @ m0k + c))
        P0_s = symmetrize(P0k + G @ (P1_s - P_pred) @ G.T)
        return m0_s, P0_s

    m0_s, P0_s = jax.vmap(x0_step)(
        lin.F[:, 0], lin.c[:, 0], lin.Qp[:, 0],
        _bcast_prior(m0, B, 1), _bcast_prior(P0, B, 2),
        means[:, 0], covs[:, 0])
    return Gaussian(mean=jnp.concatenate([m0_s[:, None], means], axis=1),
                    cov=jnp.concatenate([P0_s[:, None], covs], axis=1))


def _sqrt_parallel_filter_smoother_batched(lin: LinearizedSSM, ys, m0, P0
                                           ) -> Tuple[Gaussian, Gaussian]:
    filtered = sqrt_parallel_filter_batched(lin, ys, m0, P0)
    smoothed = sqrt_parallel_smoother_batched(lin, filtered, m0, P0)
    return filtered, smoothed


def sqrt_parallel_filter_smoother_batched(lin: LinearizedSSM, ys, m0, P0
                                          ) -> Tuple[Gaussian, Gaussian]:
    """Deprecated: `build_smoother(spec).smooth` dispatches single vs
    batched from ``ys.ndim``."""
    from ._deprecation import warn_deprecated
    from .api import build_smoother
    warn_deprecated(
        "sqrt_parallel_filter_smoother_batched",
        'build_smoother(form="sqrt").smooth(lin, ys, m0, P0)')
    return build_smoother(form="sqrt").smooth(lin, ys, m0, P0)
