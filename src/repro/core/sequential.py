"""Sequential Kalman filter and RTS smoother over a linearized SSM.

These are the paper's *sequential baselines* (span O(n), one `lax.scan`).
They double as the oracle for the parallel formulations: for the same
`LinearizedSSM` both must produce identical posteriors.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from .scopes import FILTER, SMOOTH, scoped
from .types import (Gaussian, LinearizedSSM, bcast_prior as _bcast_prior,
                    mvn_logpdf, symmetrize)


@scoped(FILTER)
def kalman_filter(lin: LinearizedSSM, ys: jnp.ndarray, m0: jnp.ndarray,
                  P0: jnp.ndarray, return_loglik: bool = False):
    """Sequential (extended/SLR) Kalman filter.

    Args:
      lin: linearized model (leading dim n).
      ys: measurements ``[n, ny]`` (row k-1 is ``y_k``).
      m0, P0: prior on ``x_0``.

    Returns:
      Gaussian of filtered posteriors ``x_1..x_n`` (leading dim n);
      optionally the total data log-likelihood under the linearized model.
    """

    def step(carry, inp):
        m, P = carry
        F, c, Qp, H, d, Rp, y = inp
        # Predict.
        m_pred = F @ m + c
        P_pred = symmetrize(F @ P @ F.T + Qp)
        # Update.
        S = symmetrize(H @ P_pred @ H.T + Rp)
        innov = y - (H @ m_pred + d)
        K = jnp.linalg.solve(S, H @ P_pred).T
        m_new = m_pred + K @ innov
        P_new = symmetrize(P_pred - K @ S @ K.T)
        ll = mvn_logpdf(y, H @ m_pred + d, S)
        return (m_new, P_new), (m_new, P_new, ll)

    (_, _), (ms, Ps, lls) = jax.lax.scan(
        step, (m0, P0), (lin.F, lin.c, lin.Qp, lin.H, lin.d, lin.Rp, ys))
    out = Gaussian(mean=ms, cov=Ps)
    if return_loglik:
        return out, jnp.sum(lls)
    return out


@scoped(SMOOTH)
def rts_smoother(lin: LinearizedSSM, filtered: Gaussian, m0: jnp.ndarray,
                 P0: jnp.ndarray) -> Gaussian:
    """Sequential Rauch-Tung-Striebel smoother.

    Returns smoothed posteriors for ``x_0..x_n`` (leading dim n+1); the
    row-0 entry smooths the prior through the first transition.
    """
    n = filtered.mean.shape[0]
    # Append the prior as the "time 0 filtered" state so one reverse scan
    # covers x_0..x_{n-1}; transitions F[k] connect row k -> row k+1.
    ms_f = jnp.concatenate([m0[None], filtered.mean[:-1]], axis=0)   # [n, nx] rows 0..n-1
    Ps_f = jnp.concatenate([P0[None], filtered.cov[:-1]], axis=0)

    def step(carry, inp):
        m_next_s, P_next_s = carry
        m_f, P_f, F, c, Qp = inp
        m_pred = F @ m_f + c
        P_pred = symmetrize(F @ P_f @ F.T + Qp)
        G = jnp.linalg.solve(P_pred, F @ P_f).T  # P_f F^T P_pred^{-1}
        m_s = m_f + G @ (m_next_s - m_pred)
        P_s = symmetrize(P_f + G @ (P_next_s - P_pred) @ G.T)
        return (m_s, P_s), (m_s, P_s)

    init = (filtered.mean[-1], filtered.cov[-1])
    (_, _), (ms_s, Ps_s) = jax.lax.scan(
        step, init, (ms_f, Ps_f, lin.F, lin.c, lin.Qp), reverse=True)
    mean = jnp.concatenate([ms_s, filtered.mean[-1:]], axis=0)
    cov = jnp.concatenate([Ps_s, filtered.cov[-1:]], axis=0)
    return Gaussian(mean=mean, cov=cov)


def filter_smoother(lin: LinearizedSSM, ys: jnp.ndarray, m0: jnp.ndarray,
                    P0: jnp.ndarray) -> Tuple[Gaussian, Gaussian]:
    """One sequential filtering+smoothing pass. Smoothed has leading n+1."""
    filtered = kalman_filter(lin, ys, m0, P0)
    smoothed = rts_smoother(lin, filtered, m0, P0)
    return filtered, smoothed


# ---------------------------------------------------------------------------
# Batched baselines: one time scan carrying B lanes (not an outer vmap, so
# a batch of trajectories costs one lax.scan dispatch, n steps of [B, ...]
# vectorized work — the sequential counterpart of the batched fused scan)
# ---------------------------------------------------------------------------

def _time_major(tree):
    return jax.tree_util.tree_map(lambda x: jnp.swapaxes(x, 0, 1), tree)


@scoped(FILTER)
def kalman_filter_batched(lin: LinearizedSSM, ys: jnp.ndarray,
                          m0: jnp.ndarray, P0: jnp.ndarray,
                          return_loglik: bool = False):
    """Sequential Kalman filter over ``[B, n]`` trajectories in one scan.

    ``lin`` leaves and ``ys`` carry a leading batch axis; ``m0``/``P0``
    may be shared or per-lane. Returns filtered ``[B, n, ...]`` (and the
    per-lane log-likelihood ``[B]`` when requested).
    """
    B = ys.shape[0]

    def step(carry, inp):
        m, P = carry
        F, c, Qp, H, d, Rp, y = inp
        m_pred = jnp.einsum("bij,bj->bi", F, m) + c
        P_pred = symmetrize(
            jnp.einsum("bij,bjk,blk->bil", F, P, F) + Qp)
        S = symmetrize(jnp.einsum("bij,bjk,blk->bil", H, P_pred, H) + Rp)
        innov = y - (jnp.einsum("bij,bj->bi", H, m_pred) + d)
        K = jnp.swapaxes(
            jnp.linalg.solve(S, jnp.einsum("bij,bjk->bik", H, P_pred)),
            -1, -2)
        m_new = m_pred + jnp.einsum("bij,bj->bi", K, innov)
        P_new = symmetrize(
            P_pred - jnp.einsum("bij,bjk,blk->bil", K, S, K))
        ll = mvn_logpdf(y, jnp.einsum("bij,bj->bi", H, m_pred) + d, S)
        return (m_new, P_new), (m_new, P_new, ll)

    inputs = _time_major((lin.F, lin.c, lin.Qp, lin.H, lin.d, lin.Rp, ys))
    init = (_bcast_prior(m0, B, 1), _bcast_prior(P0, B, 2))
    (_, _), (ms, Ps, lls) = jax.lax.scan(step, init, inputs)
    out = Gaussian(mean=jnp.swapaxes(ms, 0, 1), cov=jnp.swapaxes(Ps, 0, 1))
    if return_loglik:
        return out, jnp.sum(lls, axis=0)
    return out


@scoped(SMOOTH)
def rts_smoother_batched(lin: LinearizedSSM, filtered: Gaussian,
                         m0: jnp.ndarray, P0: jnp.ndarray) -> Gaussian:
    """Sequential RTS smoother over ``[B, n]`` lanes in one reverse scan."""
    B = filtered.mean.shape[0]
    m0b = _bcast_prior(m0, B, 1)
    P0b = _bcast_prior(P0, B, 2)
    ms_f = jnp.concatenate([m0b[:, None], filtered.mean[:, :-1]], axis=1)
    Ps_f = jnp.concatenate([P0b[:, None], filtered.cov[:, :-1]], axis=1)

    def step(carry, inp):
        m_next_s, P_next_s = carry
        m_f, P_f, F, c, Qp = inp
        m_pred = jnp.einsum("bij,bj->bi", F, m_f) + c
        P_pred = symmetrize(
            jnp.einsum("bij,bjk,blk->bil", F, P_f, F) + Qp)
        G = jnp.swapaxes(
            jnp.linalg.solve(P_pred, jnp.einsum("bij,bjk->bik", F, P_f)),
            -1, -2)
        m_s = m_f + jnp.einsum("bij,bj->bi", G, m_next_s - m_pred)
        P_s = symmetrize(
            P_f + jnp.einsum("bij,bjk,blk->bil", G, P_next_s - P_pred, G))
        return (m_s, P_s), (m_s, P_s)

    init = (filtered.mean[:, -1], filtered.cov[:, -1])
    inputs = _time_major((ms_f, Ps_f, lin.F, lin.c, lin.Qp))
    (_, _), (ms_s, Ps_s) = jax.lax.scan(step, init, inputs, reverse=True)
    mean = jnp.concatenate([jnp.swapaxes(ms_s, 0, 1),
                            filtered.mean[:, -1:]], axis=1)
    cov = jnp.concatenate([jnp.swapaxes(Ps_s, 0, 1),
                           filtered.cov[:, -1:]], axis=1)
    return Gaussian(mean=mean, cov=cov)


def _filter_smoother_batched(lin: LinearizedSSM, ys: jnp.ndarray,
                             m0: jnp.ndarray, P0: jnp.ndarray
                             ) -> Tuple[Gaussian, Gaussian]:
    """One batched sequential pass. Smoothed has shape ``[B, n+1, ...]``."""
    filtered = kalman_filter_batched(lin, ys, m0, P0)
    smoothed = rts_smoother_batched(lin, filtered, m0, P0)
    return filtered, smoothed


def filter_smoother_batched(lin: LinearizedSSM, ys: jnp.ndarray,
                            m0: jnp.ndarray, P0: jnp.ndarray
                            ) -> Tuple[Gaussian, Gaussian]:
    """Deprecated: `build_smoother(spec).smooth` dispatches single vs
    batched from ``ys.ndim``."""
    from ._deprecation import warn_deprecated
    from .api import build_smoother
    warn_deprecated(
        "filter_smoother_batched",
        'build_smoother(mode="sequential").smooth(lin, ys, m0, P0)')
    return build_smoother(mode="sequential").smooth(lin, ys, m0, P0)
