"""Lorenz-96 chaotic dynamics on a ring of ``d`` sites.

``dx_i/dt = (x_{i+1} - x_{i-2}) x_{i-1} - x_i + F``, integrated with one
RK4 step per transition. Two deployments:

* ``lorenz96``: d = 8, every other site observed — the smoother must
  reconstruct the unobserved half through the coupling. It exercises the
  batched combine math at a state dim the tracking scenarios do not,
  which the multi-tenant bucket signature ``(model_id, method, n_pad,
  nx)`` must keep separate.
* ``lorenz96_40``: the field's standard data-assimilation setup (Sakov &
  Oke 2008, Tellus A 60(2); DAPPER's ``mods/Lorenz96/sakov2008.py``):
  40 sites, F = 8, dt = 0.05, every site observed with R = I. With
  Q = 0.01 I, P0 = I, and the prior mean on the attractor: `M0_40` is
  the state after 2,000 RK4 steps of dt = 0.05 in float64 (numpy, the
  right-hand side as `_l96_rhs` writes it, no noise) from x = F at every
  site with 0.01 added to site 0. The widest tenant in the catalogue:
  nx = ny = 40, and with adaptive damping 80 rows per step, so the
  per-element algebra runs its wide lowering (`core.types.UNROLL_MAX`).
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.core.types import StateSpaceModel

from .base import Scenario, register

D = 8
FORCING = 8.0
DT = 0.02
Q_STD = 0.05     # per-step additive process noise
R_STD = 0.5      # observation noise on observed sites

D_40 = 40
DT_40 = 0.05
Q_STD_40 = 0.1
R_STD_40 = 1.0
M0_40 = (
    3.7513255912968955, 0.7751104262398721, 1.3625295796388568,
    -2.4326547769636755, 1.3361521526588327, 5.185639316754416,
    6.335737609944491, -4.130851837208958, -4.261436912452498,
    0.021137435128551596, 1.4322817071806353, 7.069563626841151,
    -0.9911940467508993, -0.44379241439118566, 1.1639013049670788,
    6.201516883319899, 0.36674431941609265, -3.832628548660381,
    -0.3475869230435147, 1.4978070284349376, 6.448644378275662,
    2.916801246542982, -2.9573069823429665, 0.28338507834189763,
    1.1232271546205217, 4.874163812951744, 8.442498913347343,
    2.6132845705719845, 2.7428846306984584, 2.3799674802357296,
    -2.974328138278225, 3.918438181547031, 5.901247787939213,
    -0.46694789383088453, 1.410231318982511, 6.3908337881194965,
    -0.6205799548067219, -1.5364623968779307, 0.5070465932305525,
    8.97625240162892)


def _l96_rhs(x):
    return ((jnp.roll(x, -1) - jnp.roll(x, 2)) * jnp.roll(x, 1)
            - x + FORCING)


def _rk4(dt):
    def f(x):
        k1 = _l96_rhs(x)
        k2 = _l96_rhs(x + 0.5 * dt * k1)
        k3 = _l96_rhs(x + 0.5 * dt * k2)
        k4 = _l96_rhs(x + dt * k3)
        return x + dt / 6.0 * (k1 + 2 * k2 + 2 * k3 + k4)
    return f


def make_lorenz96_model(dtype=jnp.float64) -> StateSpaceModel:
    Q = (Q_STD ** 2) * jnp.eye(D, dtype=dtype)
    R = (R_STD ** 2) * jnp.eye(D // 2, dtype=dtype)
    # Start near the attractor: the forcing fixed point plus a bump that
    # seeds the chaotic transient.
    m0 = jnp.full((D,), FORCING, dtype=dtype).at[0].add(1.0)
    P0 = 0.5 * jnp.eye(D, dtype=dtype)
    return StateSpaceModel(f=_rk4(DT), h=lambda x: x[::2], Q=Q, R=R, m0=m0,
                           P0=P0)


def make_lorenz96_40_model(dtype=jnp.float64) -> StateSpaceModel:
    eye = jnp.eye(D_40, dtype=dtype)
    return StateSpaceModel(f=_rk4(DT_40), h=lambda x: x,
                           Q=(Q_STD_40 ** 2) * eye, R=(R_STD_40 ** 2) * eye,
                           m0=jnp.asarray(M0_40, dtype), P0=eye)


register(Scenario(
    name="lorenz96",
    build=make_lorenz96_model,
    nx=D, ny=D // 2,
    default_method="ekf",
    lm_lambda=1.0,   # chaotic dynamics: keep Gauss-Newton damped
    description="Lorenz-96 ring (d=8, F=8, RK4), every other site "
                "observed.",
    params=(("d", D), ("forcing", FORCING), ("dt", DT),
            ("q_std", Q_STD), ("r_std", R_STD)),
))

register(Scenario(
    name="lorenz96_40",
    build=make_lorenz96_40_model,
    nx=D_40, ny=D_40,
    default_method="ekf",
    lm_lambda=1.0,
    description="Lorenz-96 ring at the data-assimilation standard (d=40, "
                "F=8, dt=0.05, RK4), every site observed, R=I.",
    params=(("d", D_40), ("forcing", FORCING), ("dt", DT_40),
            ("q_std", Q_STD_40), ("r_std", R_STD_40), ("m0", M0_40),
            ("p0", 1.0)),
))
