"""Parallel-in-time filtering and smoothing (the paper's contribution).

Filtering: elements ``a_k = (A, b, C, eta, J)`` (Eq. 13-14), associative
combine (Eq. 15). The k-th *prefix* under the combine is the filtering
posterior ``N(x_k; b, C)``.

Smoothing: elements ``a_k = (E, g, L)`` (Eq. 17-18), associative combine
(Eq. 19) applied as a *reverse* (suffix) scan; the k-th suffix is the
smoothing marginal ``N(x_k; g, L)``.

Both scans run through :func:`repro.core.scan.associative_scan`, which is
``jax.lax.associative_scan`` (Blelloch, span O(log n)) with an optional
Pallas-kernel combine and an optional cross-device (sharded) schedule.

Two paper typos are corrected here (verified against ref [12], Lemmas 8-10,
and by the parallel==sequential oracle tests):
  * Eq. 13 ``b_k`` uses ``d_k`` (not ``d_{k-1}``);
  * Eq. 14 ``eta_k = (H F)^T S^{-1} (y - H c - d)`` (no extra ``H``).
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from . import scan as scan_lib
from .scopes import COMBINE, FILTER, SMOOTH, scoped
from .types import (FilteringElement, Gaussian, LinearizedSSM,
                    SmoothingElement, bcast_prior as _bcast_prior,
                    bmm as _mm, bmv as _mv, gauss_jordan_inverse,
                    symmetrize)


def _T(A: jnp.ndarray) -> jnp.ndarray:
    return jnp.swapaxes(A, -1, -2)


# ---------------------------------------------------------------------------
# Associative combines (single pair; vmapped/tiled by the scan driver)
# ---------------------------------------------------------------------------

@scoped(COMBINE)
def filtering_combine(ei: FilteringElement, ej: FilteringElement
                      ) -> FilteringElement:
    """Paper Eq. 15: ``a_i (x) a_j`` with ``i`` earlier in time than ``j``.

    All four solves share the single matrix ``W = (I + C_i J_j)^T``
    (symmetry of C, J gives ``(I + J_j C_i) = W``), so one LU factorization
    serves the whole combine.
    """
    nx = ei.b.shape[-1]
    I = jnp.eye(nx, dtype=ei.b.dtype)
    W = I + ej.J @ ei.C  # == (I + C_i J_j)^T
    # X = A_j (I + C_i J_j)^{-1}  via  X^T = W^{-1} A_j^T
    # Z = (I + J_j C_i)^{-1} [eta_j - J_j b_i | J_j A_i]
    rhs = jnp.concatenate(
        [ej.A.T,
         (ej.eta - ej.J @ ei.b)[:, None],
         ej.J @ ei.A],
        axis=1)
    sol = jnp.linalg.solve(W, rhs)
    Xt = sol[:, :nx]                 # == X^T
    z_eta = sol[:, nx]
    Z_J = sol[:, nx + 1:]
    X = Xt.T

    A = X @ ei.A
    b = X @ (ei.b + ei.C @ ej.eta) + ej.b
    C = symmetrize(X @ ei.C @ ej.A.T + ej.C)
    eta = ei.A.T @ z_eta + ei.eta
    J = symmetrize(ei.A.T @ Z_J + ei.J)
    return FilteringElement(A=A, b=b, C=C, eta=eta, J=J)


@scoped(COMBINE)
def smoothing_combine(ei: SmoothingElement, ej: SmoothingElement
                      ) -> SmoothingElement:
    """Paper Eq. 19: ``a_i (x) a_j`` with ``i`` earlier in time than ``j``."""
    E = ei.E @ ej.E
    g = ei.E @ ej.g + ei.g
    L = symmetrize(ei.E @ ej.L @ ei.E.T + ei.L)
    return SmoothingElement(E=E, g=g, L=L)


def filtering_identity(nx: int, dtype=jnp.float32) -> FilteringElement:
    """Identity element of the filtering combine (used by sharded scans)."""
    return FilteringElement(
        A=jnp.eye(nx, dtype=dtype), b=jnp.zeros((nx,), dtype),
        C=jnp.zeros((nx, nx), dtype), eta=jnp.zeros((nx,), dtype),
        J=jnp.zeros((nx, nx), dtype))


def smoothing_identity(nx: int, dtype=jnp.float32) -> SmoothingElement:
    return SmoothingElement(E=jnp.eye(nx, dtype=dtype),
                            g=jnp.zeros((nx,), dtype),
                            L=jnp.zeros((nx, nx), dtype))


# ---------------------------------------------------------------------------
# Element construction
# ---------------------------------------------------------------------------

def _first_filtering_element(lin0, y1, m0, P0) -> FilteringElement:
    """k = 1: a standard predict+update collapsed into (A=0, b=m1|1, C=P1|1).

    eta/J only influence elements to the left of k=1, of which there are
    none, so they are zero (paper: ``p(y_1|x_0) = p(y_1)`` is constant).
    """
    F, c, Qp, H, d, Rp = lin0
    nx = m0.shape[-1]
    m_pred = F @ m0 + c
    P_pred = symmetrize(F @ P0 @ F.T + Qp)
    S = symmetrize(H @ P_pred @ H.T + Rp)
    K = jnp.linalg.solve(S, H @ P_pred).T
    b = m_pred + K @ (y1 - (H @ m_pred + d))
    C = symmetrize(P_pred - K @ S @ K.T)
    z = jnp.zeros((nx,), dtype=m0.dtype)
    Z = jnp.zeros((nx, nx), dtype=m0.dtype)
    return FilteringElement(A=Z, b=b, C=C, eta=z, J=Z)


def _generic_filtering_element(F, c, Qp, H, d, Rp, y) -> FilteringElement:
    """k >= 2: paper Eq. 13-14 (with the typo fixes noted above)."""
    nx = F.shape[-1]
    I = jnp.eye(nx, dtype=F.dtype)
    S = symmetrize(H @ Qp @ H.T + Rp)
    K = jnp.linalg.solve(S, H @ Qp).T          # Q' H^T S^{-1}
    innov = y - (H @ c + d)
    A = (I - K @ H) @ F
    b = c + K @ innov
    C = symmetrize((I - K @ H) @ Qp)
    HF = H @ F
    SinvHF = jnp.linalg.solve(S, HF)           # S^{-1} H F
    eta = HF.T @ jnp.linalg.solve(S, innov)
    J = symmetrize(HF.T @ SinvHF)
    return FilteringElement(A=A, b=b, C=C, eta=eta, J=J)


def _generic_smoothing_element(mf, Pf, F, c, Qp) -> SmoothingElement:
    """Paper Eq. 17-18 for one interior time step."""
    P_pred = symmetrize(F @ Pf @ F.T + Qp)
    E = jnp.linalg.solve(P_pred, F @ Pf).T       # P F^T (F P F^T + Q')^{-1}
    g = mf - E @ (F @ mf + c)
    L = symmetrize(Pf - E @ F @ Pf)
    return SmoothingElement(E=E, g=g, L=L)


def filtering_elements(lin: LinearizedSSM, ys: jnp.ndarray, m0: jnp.ndarray,
                       P0: jnp.ndarray) -> FilteringElement:
    """Build all n filtering elements (vmapped; leading dim n)."""
    generic = jax.vmap(_generic_filtering_element)(
        lin.F, lin.c, lin.Qp, lin.H, lin.d, lin.Rp, ys)
    first = _first_filtering_element(
        (lin.F[0], lin.c[0], lin.Qp[0], lin.H[0], lin.d[0], lin.Rp[0]),
        ys[0], m0, P0)
    return jax.tree_util.tree_map(
        lambda f, g: jnp.concatenate([f[None], g[1:]], axis=0), first, generic)


def filtering_elements_batched(lin: LinearizedSSM, ys: jnp.ndarray,
                               m0: jnp.ndarray, P0: jnp.ndarray
                               ) -> FilteringElement:
    """Build all ``B x n`` filtering elements as one contiguous block.

    ``lin`` leaves and ``ys`` carry a leading batch axis (``[B, n, ...]``);
    ``m0``/``P0`` may be shared (``[nx]``) or per-lane (``[B, nx]``). The
    generic rows are computed with directly batched Eq. 13-14 algebra over
    all ``B*n`` rows at once — batched matmuls plus one Gauss-Jordan
    inverse of S, instead of a vmapped per-element LAPACK solve (which
    costs one library call per row and dominates batched CPU/GPU runs).
    The k=1 special case is written in-batch into row 0 of every lane.
    """
    B, n = ys.shape[:2]
    F, c, Qp, H, d, Rp = lin
    nx = F.shape[-1]
    I = jnp.eye(nx, dtype=F.dtype)
    S = symmetrize(_mm(_mm(H, Qp), _T(H)) + Rp)
    Sinv = gauss_jordan_inverse(S)               # S is PD: no-pivot safe
    K = _mm(_mm(Qp, _T(H)), Sinv)                # Q' H^T S^{-1}
    innov = ys - (_mv(H, c) + d)
    IKH = I - _mm(K, H)
    HF = _mm(H, F)
    generic = FilteringElement(
        A=_mm(IKH, F),
        b=c + _mv(K, innov),
        C=symmetrize(_mm(IKH, Qp)),
        eta=_mv(_T(HF), _mv(Sinv, innov)),
        J=symmetrize(_mm(_T(HF), _mm(Sinv, HF))))
    m0b = _bcast_prior(m0, B, 1)
    P0b = _bcast_prior(P0, B, 2)
    first = jax.vmap(_first_filtering_element)(
        (lin.F[:, 0], lin.c[:, 0], lin.Qp[:, 0], lin.H[:, 0], lin.d[:, 0],
         lin.Rp[:, 0]), ys[:, 0], m0b, P0b)
    return jax.tree_util.tree_map(
        lambda g, f: g.at[:, 0].set(f), generic, first)


def smoothing_elements(lin: LinearizedSSM, filtered: Gaussian
                       ) -> SmoothingElement:
    """Build all n smoothing elements from filtering results (Eq. 17-18).

    Element k (row k-1) uses the transition k -> k+1, i.e. ``F[k]`` —
    paper Eq. 17's ``Q'_{k-1}`` is read as ``Q'_k`` (consistent with its
    own Eq. 6 indexing; verified against the sequential RTS oracle).
    """
    # Rows 0..n-2 use transitions 1..n-1 (lin.F rows 1..n-1).
    body = jax.vmap(_generic_smoothing_element)(
        filtered.mean[:-1], filtered.cov[:-1],
        lin.F[1:], lin.c[1:], lin.Qp[1:])
    nx = filtered.mean.shape[-1]
    last = SmoothingElement(
        E=jnp.zeros((nx, nx), dtype=filtered.mean.dtype),
        g=filtered.mean[-1], L=filtered.cov[-1])
    return jax.tree_util.tree_map(
        lambda b, l: jnp.concatenate([b, l[None]], axis=0), body, last)


def smoothing_elements_batched(lin: LinearizedSSM, filtered: Gaussian
                               ) -> SmoothingElement:
    """Batched Eq. 17-18 elements: directly batched algebra over all
    ``B*(n-1)`` rows (one Gauss-Jordan inverse of the PD ``P_pred`` instead
    of per-row LAPACK solves), with the k=n boundary element written
    in-batch into the last row."""
    B, n = filtered.mean.shape[:2]
    nx = filtered.mean.shape[-1]
    mf, Pf = filtered.mean[:, :-1], filtered.cov[:, :-1]
    F, c, Qp = lin.F[:, 1:], lin.c[:, 1:], lin.Qp[:, 1:]
    FPf = _mm(F, Pf)
    P_pred = symmetrize(_mm(FPf, _T(F)) + Qp)
    E = _mm(_T(FPf), gauss_jordan_inverse(P_pred))  # P F^T P_pred^{-1}
    body = SmoothingElement(
        E=E,
        g=mf - _mv(E, _mv(F, mf) + c),
        L=symmetrize(Pf - _mm(E, FPf)))
    last = SmoothingElement(
        E=jnp.zeros((B, nx, nx), dtype=filtered.mean.dtype),
        g=filtered.mean[:, -1], L=filtered.cov[:, -1])
    return jax.tree_util.tree_map(
        lambda b, l: jnp.concatenate([b, l[:, None]], axis=1), body, last)


# ---------------------------------------------------------------------------
# Drivers
# ---------------------------------------------------------------------------

@scoped(FILTER)
def parallel_filter(lin: LinearizedSSM, ys: jnp.ndarray, m0: jnp.ndarray,
                    P0: jnp.ndarray, *, combine_impl: str = "jnp",
                    axis_name: str = None) -> Gaussian:
    """Parallel Kalman filter: prefix-scan of filtering elements.

    ``axis_name`` switches to the cross-device sharded scan (the elements'
    leading/time axis must be sharded over that mesh axis).
    """
    elems = filtering_elements(lin, ys, m0, P0)
    scanned = scan_lib.associative_scan(
        filtering_combine, elems, reverse=False, combine_impl=combine_impl,
        axis_name=axis_name,
        identity=lambda: filtering_identity(m0.shape[-1], m0.dtype))
    return Gaussian(mean=scanned.b, cov=scanned.C)


@scoped(SMOOTH)
def parallel_smoother(lin: LinearizedSSM, filtered: Gaussian, m0: jnp.ndarray,
                      P0: jnp.ndarray, *, combine_impl: str = "jnp",
                      axis_name: str = None) -> Gaussian:
    """Parallel RTS smoother: suffix-scan of smoothing elements.

    Returns smoothed marginals for ``x_0..x_n`` (leading dim n+1); the x_0
    row is one extra (non-scan) backward step through the first transition.
    """
    elems = smoothing_elements(lin, filtered)
    scanned = scan_lib.associative_scan(
        smoothing_combine, elems, reverse=True, combine_impl=combine_impl,
        axis_name=axis_name,
        identity=lambda: smoothing_identity(m0.shape[-1], m0.dtype))
    means, covs = scanned.g, scanned.L

    # x_0: one backward step from the smoothed x_1 through transition 0.
    F, c, Qp = lin.F[0], lin.c[0], lin.Qp[0]
    P_pred = symmetrize(F @ P0 @ F.T + Qp)
    G = jnp.linalg.solve(P_pred, F @ P0).T
    m0_s = m0 + G @ (means[0] - (F @ m0 + c))
    P0_s = symmetrize(P0 + G @ (covs[0] - P_pred) @ G.T)
    return Gaussian(mean=jnp.concatenate([m0_s[None], means], axis=0),
                    cov=jnp.concatenate([P0_s[None], covs], axis=0))


def parallel_filter_smoother(lin: LinearizedSSM, ys: jnp.ndarray,
                             m0: jnp.ndarray, P0: jnp.ndarray,
                             *, combine_impl: str = "jnp",
                             axis_name: str = None
                             ) -> Tuple[Gaussian, Gaussian]:
    filtered = parallel_filter(lin, ys, m0, P0, combine_impl=combine_impl,
                               axis_name=axis_name)
    smoothed = parallel_smoother(lin, filtered, m0, P0,
                                 combine_impl=combine_impl,
                                 axis_name=axis_name)
    return filtered, smoothed


# ---------------------------------------------------------------------------
# Batched drivers: B trajectories, one fused scan per Blelloch level
# ---------------------------------------------------------------------------

@scoped(FILTER)
def parallel_filter_batched(lin: LinearizedSSM, ys: jnp.ndarray,
                            m0: jnp.ndarray, P0: jnp.ndarray, *,
                            combine_impl: str = "fused",
                            axis_name: str = None) -> Gaussian:
    """Batched parallel Kalman filter over ``[B, n]`` trajectories.

    Unlike an outer ``vmap`` of :func:`parallel_filter`, the scan runs with
    ``batch_dims=1``: each Blelloch level issues one combine call over all
    ``B x P`` contiguous element pairs (B-fold more parallelism per launch).
    """
    elems = filtering_elements_batched(lin, ys, m0, P0)
    scanned = scan_lib.associative_scan(
        filtering_combine, elems, reverse=False, combine_impl=combine_impl,
        axis_name=axis_name, batch_dims=1,
        identity=lambda: filtering_identity(lin.F.shape[-1], lin.F.dtype))
    return Gaussian(mean=scanned.b, cov=scanned.C)


@scoped(SMOOTH)
def parallel_smoother_batched(lin: LinearizedSSM, filtered: Gaussian,
                              m0: jnp.ndarray, P0: jnp.ndarray, *,
                              combine_impl: str = "fused",
                              axis_name: str = None) -> Gaussian:
    """Batched parallel RTS smoother (suffix scan with ``batch_dims=1``).

    Returns smoothed marginals ``[B, n+1, nx]``; the x_0 row is one extra
    vmapped backward step per lane, as in :func:`parallel_smoother`.
    """
    B = filtered.mean.shape[0]
    elems = smoothing_elements_batched(lin, filtered)
    scanned = scan_lib.associative_scan(
        smoothing_combine, elems, reverse=True, combine_impl=combine_impl,
        axis_name=axis_name, batch_dims=1,
        identity=lambda: smoothing_identity(lin.F.shape[-1], lin.F.dtype))
    means, covs = scanned.g, scanned.L

    def x0_step(F, c, Qp, m0k, P0k, m1_s, P1_s):
        P_pred = symmetrize(F @ P0k @ F.T + Qp)
        G = jnp.linalg.solve(P_pred, F @ P0k).T
        m0_s = m0k + G @ (m1_s - (F @ m0k + c))
        P0_s = symmetrize(P0k + G @ (P1_s - P_pred) @ G.T)
        return m0_s, P0_s

    m0b = _bcast_prior(m0, B, 1)
    P0b = _bcast_prior(P0, B, 2)
    m0_s, P0_s = jax.vmap(x0_step)(lin.F[:, 0], lin.c[:, 0], lin.Qp[:, 0],
                                   m0b, P0b, means[:, 0], covs[:, 0])
    return Gaussian(mean=jnp.concatenate([m0_s[:, None], means], axis=1),
                    cov=jnp.concatenate([P0_s[:, None], covs], axis=1))


def _parallel_filter_smoother_batched(lin: LinearizedSSM, ys: jnp.ndarray,
                                      m0: jnp.ndarray, P0: jnp.ndarray,
                                      *, combine_impl: str = "fused",
                                      axis_name: str = None
                                      ) -> Tuple[Gaussian, Gaussian]:
    filtered = parallel_filter_batched(lin, ys, m0, P0,
                                       combine_impl=combine_impl,
                                       axis_name=axis_name)
    smoothed = parallel_smoother_batched(lin, filtered, m0, P0,
                                         combine_impl=combine_impl,
                                         axis_name=axis_name)
    return filtered, smoothed


def parallel_filter_smoother_batched(lin: LinearizedSSM, ys: jnp.ndarray,
                                     m0: jnp.ndarray, P0: jnp.ndarray,
                                     *, combine_impl: str = "fused",
                                     axis_name: str = None
                                     ) -> Tuple[Gaussian, Gaussian]:
    """Deprecated: `build_smoother(spec).smooth` dispatches single vs
    batched from ``ys.ndim``."""
    from ._deprecation import warn_deprecated
    from .api import build_smoother
    warn_deprecated(
        "parallel_filter_smoother_batched",
        'build_smoother(mode="parallel").smooth(lin, ys, m0, P0)')
    if axis_name is not None:
        # The sharded path is not representable on the spec axes yet.
        return _parallel_filter_smoother_batched(
            lin, ys, m0, P0, combine_impl=combine_impl,
            axis_name=axis_name)
    return build_smoother(combine_impl=combine_impl).smooth(lin, ys, m0,
                                                            P0)
