"""Linearization strategies: first-order Taylor (IEKS) and sigma-point SLR (IPLS).

Both produce, for a nonlinear map ``phi`` and a linearization Gaussian
``N(m, P)``, an affine-Gaussian approximation

    phi(x) ~= F x + c + e,   e ~ N(0, Lambda)

Taylor (paper Eq. 10): ``F = d phi/dx (m)``, ``c = phi(m) - F m``,
``Lambda = 0``. Sigma-point SLR (paper Eq. 7-9): moment-matched regression
through transformed sigma points; ``Lambda`` is the SLR residual covariance.

`linearize_model` applies a strategy across the whole trajectory (vmap) to
build the :class:`LinearizedSSM` consumed by both the sequential and the
parallel filters/smoothers — the linearization is *offline* w.r.t. the
current pass (paper §3), which is exactly what makes the iterated smoothers
scan-parallelizable.
"""
from __future__ import annotations

from typing import Callable, Tuple

import jax
import jax.numpy as jnp

from .scopes import LINEARIZE, scoped
from .sigma_points import SigmaScheme
from .types import Gaussian, LinearizedSSM, StateSpaceModel, broadcast_noise, symmetrize

AffineParams = Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]  # (F, c, Lambda)


def linearize_taylor(phi: Callable, m: jnp.ndarray, P: jnp.ndarray = None
                     ) -> AffineParams:
    """First-order Taylor linearization at ``m`` (covariance unused)."""
    del P
    F = jax.jacfwd(phi)(m)
    z = phi(m)
    c = z - F @ m
    Lam = jnp.zeros((z.shape[-1], z.shape[-1]), dtype=m.dtype)
    return F, c, Lam


def linearize_slr(phi: Callable, m: jnp.ndarray, P: jnp.ndarray,
                  scheme: SigmaScheme, jitter: float = 0.0) -> AffineParams:
    """Sigma-point statistical linear regression (paper Eq. 7-9)."""
    pts, wm, wc = scheme.points(m, P, jitter)        # [s, nx]
    Z = jax.vmap(phi)(pts)                           # [s, nz]
    zbar = jnp.einsum("s,sz->z", wm, Z)
    dx = pts - m[None, :]
    dz = Z - zbar[None, :]
    Psi = jnp.einsum("s,sx,sz->xz", wc, dx, dz)      # cov(x, z)
    Phi = jnp.einsum("s,sz,sw->zw", wc, dz, dz)      # cov(z, z)
    # F = Psi^T P^{-1}  (solve with the *sampled* P for consistency)
    F = jnp.linalg.solve(symmetrize(P) + jitter * jnp.eye(P.shape[-1], dtype=P.dtype),
                         Psi).T
    c = zbar - F @ m
    Lam = symmetrize(Phi - F @ symmetrize(P) @ F.T)
    return F, c, Lam


@scoped(LINEARIZE)
def linearize_model_taylor(model: StateSpaceModel, traj_means: jnp.ndarray
                           ) -> LinearizedSSM:
    """Build the linearized SSM by Taylor expansion around a nominal
    trajectory ``traj_means [n+1, nx]`` (rows 0..n; see DESIGN.md §11)."""
    n = traj_means.shape[0] - 1
    Fs, cs, _ = jax.vmap(lambda m: linearize_taylor(model.f, m))(traj_means[:-1])
    Hs, ds, _ = jax.vmap(lambda m: linearize_taylor(model.h, m))(traj_means[1:])
    Q = broadcast_noise(model.Q, n)
    R = broadcast_noise(model.R, n)
    return LinearizedSSM(F=Fs, c=cs, Qp=Q, H=Hs, d=ds, Rp=R)


@scoped(LINEARIZE)
def linearize_model_slr(model: StateSpaceModel, traj: Gaussian,
                        scheme: SigmaScheme, jitter: float = 0.0
                        ) -> LinearizedSSM:
    """Build the linearized SSM by SLR around smoothed moments
    ``traj = Gaussian(means [n+1, nx], covs [n+1, nx, nx])``."""
    n = traj.mean.shape[0] - 1

    def lin_f(m, P):
        return linearize_slr(model.f, m, P, scheme, jitter)

    def lin_h(m, P):
        return linearize_slr(model.h, m, P, scheme, jitter)

    Fs, cs, Lams = jax.vmap(lin_f)(traj.mean[:-1], traj.cov[:-1])
    Hs, ds, Oms = jax.vmap(lin_h)(traj.mean[1:], traj.cov[1:])
    Q = broadcast_noise(model.Q, n) + Lams
    R = broadcast_noise(model.R, n) + Oms
    return LinearizedSSM(F=Fs, c=cs, Qp=symmetrize(Q), H=Hs, d=ds, Rp=symmetrize(R))


# ---------------------------------------------------------------------------
# Batched linearization: B trajectories, one flattened vmap per map
# ---------------------------------------------------------------------------

def broadcast_noise_batched(M: jnp.ndarray, B: int, n: int) -> jnp.ndarray:
    """Broadcast process/measurement noise to a ``[B, n, d, d]`` stack.

    Accepts shared ``[d, d]``, per-step ``[n, d, d]``, or per-lane
    ``[B, n, d, d]`` (the latter is what serving's time-padding uses to
    inflate R on padded steps).
    """
    M = jnp.asarray(M)
    if M.ndim == 2:
        return jnp.broadcast_to(M, (B, n) + M.shape)
    if M.ndim == 3:
        if M.shape[0] != n:
            raise ValueError(f"noise stack has length {M.shape[0]}, "
                             f"expected {n}")
        return jnp.broadcast_to(M, (B,) + M.shape)
    if M.shape[:2] != (B, n):
        raise ValueError(f"batched noise stack is {M.shape[:2]}, "
                         f"expected {(B, n)}")
    return M


def _flat_rows(x: jnp.ndarray) -> jnp.ndarray:
    return x.reshape((-1,) + x.shape[2:])


def _unflat_rows(x: jnp.ndarray, B: int, n: int) -> jnp.ndarray:
    return x.reshape((B, n) + x.shape[1:])


@scoped(LINEARIZE)
def linearize_model_taylor_batched(model: StateSpaceModel,
                                   traj_means: jnp.ndarray) -> LinearizedSSM:
    """Taylor-linearize around ``B`` nominal trajectories ``[B, n+1, nx]``.

    All ``B*n`` Jacobians per map come from a single flattened vmap call,
    so the resulting ``[B, n, ...]`` stacks are contiguous for the batched
    scan. Returns a `LinearizedSSM` whose leaves carry a leading batch axis.
    """
    B, np1 = traj_means.shape[:2]
    n = np1 - 1
    lin_f = jax.vmap(lambda m: linearize_taylor(model.f, m))
    lin_h = jax.vmap(lambda m: linearize_taylor(model.h, m))
    Fs, cs, _ = lin_f(_flat_rows(traj_means[:, :-1]))
    Hs, ds, _ = lin_h(_flat_rows(traj_means[:, 1:]))
    return LinearizedSSM(
        F=_unflat_rows(Fs, B, n), c=_unflat_rows(cs, B, n),
        Qp=broadcast_noise_batched(model.Q, B, n),
        H=_unflat_rows(Hs, B, n), d=_unflat_rows(ds, B, n),
        Rp=broadcast_noise_batched(model.R, B, n))


@scoped(LINEARIZE)
def linearize_model_slr_batched(model: StateSpaceModel, traj: Gaussian,
                                scheme: SigmaScheme, jitter: float = 0.0
                                ) -> LinearizedSSM:
    """SLR-linearize around ``B`` smoothed trajectories
    ``traj = Gaussian(means [B, n+1, nx], covs [B, n+1, nx, nx])``."""
    B, np1 = traj.mean.shape[:2]
    n = np1 - 1
    lin_f = jax.vmap(lambda m, P: linearize_slr(model.f, m, P, scheme,
                                                jitter))
    lin_h = jax.vmap(lambda m, P: linearize_slr(model.h, m, P, scheme,
                                                jitter))
    Fs, cs, Lams = lin_f(_flat_rows(traj.mean[:, :-1]),
                         _flat_rows(traj.cov[:, :-1]))
    Hs, ds, Oms = lin_h(_flat_rows(traj.mean[:, 1:]),
                        _flat_rows(traj.cov[:, 1:]))
    Q = broadcast_noise_batched(model.Q, B, n) + _unflat_rows(Lams, B, n)
    R = broadcast_noise_batched(model.R, B, n) + _unflat_rows(Oms, B, n)
    return LinearizedSSM(
        F=_unflat_rows(Fs, B, n), c=_unflat_rows(cs, B, n),
        Qp=symmetrize(Q),
        H=_unflat_rows(Hs, B, n), d=_unflat_rows(ds, B, n),
        Rp=symmetrize(R))
