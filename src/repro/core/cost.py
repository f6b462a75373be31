"""Gauss-Newton smoothing cost: the objective the iterated smoothers descend.

IEKS/IPLS iterations are Gauss-Newton steps on the MAP objective (Bell
1994); under the linearization ``(F, c, Qp, H, d, Rp)`` at the current
trajectory the objective is the quadratic

    J(m) = 1/2 |m_0 - m0|^2_{P0^-1}
         + 1/2 sum_k |m_{k+1} - F_k m_k - c_k|^2_{Qp_k^-1}
         + 1/2 sum_k |y_k - H_k m_{k+1} - d_k|^2_{Rp_k^-1}

(for Taylor linearization at the means this equals the exact nonlinear
MAP cost, since ``F_k m_k + c_k = f(m_k)``; for SLR it is the
statistically-linearized cost the sigma-point iteration minimizes).
The adaptive Levenberg-Marquardt driver in `core/iterated.py` evaluates
this after every candidate pass to decide per-lane accept/reject — the
cost-monitored iteration the ROADMAP's "Robust iteration at scale" item
calls for (DESIGN.md §13).

Shape-polymorphic over one leading lane axis: ``means [n+1, nx]`` gives a
scalar, ``[B, n+1, nx]`` gives ``[B]`` (per-lane costs, never reduced
across lanes — a diverging trajectory must not poison its bucket mates).
"""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from .linearization import (linearize_model_slr, linearize_model_slr_batched,
                            linearize_model_taylor,
                            linearize_model_taylor_batched)
from .scopes import COST, scoped
from .sigma_points import SigmaScheme, get_scheme
from .types import (Gaussian, LinearizedSSM, StateSpaceModel, bmv,
                    chol_half_quad)


@scoped(COST)
def smoothing_cost(lin: LinearizedSSM, ys: jnp.ndarray, means: jnp.ndarray,
                   m0: jnp.ndarray, P0: jnp.ndarray) -> jnp.ndarray:
    """GN/MAP cost of a mean trajectory under a linearized model.

    ``lin`` leaves carry leading ``[n, ...]`` (or ``[B, n, ...]``) axes,
    ``means`` is ``[n+1, nx]`` (or ``[B, n+1, nx]``), ``ys`` is
    ``[n, ny]`` (or ``[B, n, ny]``); ``m0/P0`` may be shared or per-lane.
    Returns a scalar (or ``[B]`` per-lane costs).
    """
    prev = means[..., :-1, :]
    nxt = means[..., 1:, :]
    prior_res = means[..., 0, :] - m0
    trans_res = nxt - bmv(lin.F, prev) - lin.c
    meas_res = ys - bmv(lin.H, nxt) - lin.d
    return (chol_half_quad(prior_res, P0)[0]
            + jnp.sum(chol_half_quad(trans_res, lin.Qp)[0], axis=-1)
            + jnp.sum(chol_half_quad(meas_res, lin.Rp)[0], axis=-1))


def gn_cost(model: StateSpaceModel, ys: jnp.ndarray, traj: Gaussian,
            method: str = "ekf", scheme: Optional[SigmaScheme] = None,
            jitter: float = 0.0) -> jnp.ndarray:
    """Linearize ``model`` at ``traj`` (Taylor for ``method="ekf"``, SLR
    for ``"slr"``) and evaluate :func:`smoothing_cost` at its means —
    the linearized sibling of `smoothed_log_likelihood`. ``scheme`` may
    be a `SigmaScheme` or a scheme name (resolved against ``model.nx``);
    it defaults to cubature for SLR. Scalar for ``ys [n, ny]``, ``[B]``
    for ``ys [B, n, ny]``.
    """
    batched = ys.ndim == 3
    if method == "ekf":
        lin = (linearize_model_taylor_batched(model, traj.mean) if batched
               else linearize_model_taylor(model, traj.mean))
    elif method == "slr":
        if scheme is None or isinstance(scheme, str):
            scheme = get_scheme(scheme or "cubature", model.nx)
        lin = (linearize_model_slr_batched(model, traj, scheme, jitter)
               if batched
               else linearize_model_slr(model, traj, scheme, jitter))
    else:
        raise ValueError(f"unknown method {method!r}")
    return smoothing_cost(lin, ys, traj.mean, model.m0, model.P0)
