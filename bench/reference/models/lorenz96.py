"""The Lorenz-96 problem: ``d`` sites on a ring, every site observed.

``dx_i/dt = (x_{i+1} - x_{i-2}) x_{i-1} - x_i + F`` (Lorenz 1996), one
classical Runge-Kutta step of ``dt`` per transition, ``y = x + r``, as
in the data-assimilation setup of Sakov & Oke 2008 (Tellus A 60(2);
DAPPER ``mods/Lorenz96/sakov2008.py``). Every number comes from the
configuration file's ``problem`` group: ``d``, ``forcing``, ``dt``,
``q_std`` (``Q = q_std^2 I``), ``r_std`` (``R = r_std^2 I``), ``m0`` and
``p0_diag``.

This file is the benchmark's own statement of the problem: the traffic
simulates tracks from it, and the plain reference smooths with it. It
imports nothing of the program.
"""
from __future__ import annotations

import jax.numpy as jnp

from bench.reference.problem import Problem


def build(params: dict, dtype) -> Problem:
    d, forcing, dt = params["d"], params["forcing"], params["dt"]

    def rhs(x):
        return (jnp.roll(x, -1) - jnp.roll(x, 2)) * jnp.roll(x, 1) - x + forcing

    def f(x):
        k1 = rhs(x)
        k2 = rhs(x + 0.5 * dt * k1)
        k3 = rhs(x + 0.5 * dt * k2)
        k4 = rhs(x + dt * k3)
        return x + dt / 6.0 * (k1 + 2 * k2 + 2 * k3 + k4)

    def h(x):
        return x

    eye = jnp.eye(d, dtype=dtype)
    return Problem(f=f, h=h, Q=params["q_std"] ** 2 * eye,
                   R=params["r_std"] ** 2 * eye,
                   m0=jnp.asarray(params["m0"], dtype),
                   P0=jnp.diag(jnp.asarray(params["p0_diag"], dtype)))
