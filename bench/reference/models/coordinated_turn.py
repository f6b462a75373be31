"""The coordinated-turn problem with bearings from two sensors.

The state is ``x = [p_x, p_y, v_x, v_y, omega]``: position, velocity
and turn rate. The transition is the exact coordinated turn over one
step of ``dt`` (Bar-Shalom & Li), the measurement the bearings of the
target from two fixed sensors, as in Särkkä & Svensson 2020 and the
experiment of Yaghoobi, Corenflos, Hassan & Särkkä 2021 (§5). Every
number comes from the configuration file's ``problem`` group.

This file is the benchmark's own statement of the problem: the traffic
simulates tracks from it, and the plain reference smooths with it. It
imports nothing of the program.
"""
from __future__ import annotations

import jax.numpy as jnp

from bench.reference.problem import Problem


def build(params: dict, dtype) -> Problem:
    dt, q1, q2 = params["dt"], params["q1"], params["q2"]
    s1 = jnp.asarray(params["sensor1"], dtype)
    s2 = jnp.asarray(params["sensor2"], dtype)

    def f(x):
        px, py, vx, vy, w = x
        wd = w * dt
        # sin(w dt)/w and (1 - cos(w dt))/w, with their series near
        # w = 0 (both branches are evaluated under forward-mode AD, so
        # the division is guarded).
        small = jnp.abs(wd) < 1e-6
        safe = jnp.where(small, 1.0, wd)
        sw = jnp.where(small, dt * (1.0 - wd * wd / 6.0),
                       jnp.sin(safe) / safe * dt)
        cw = jnp.where(small, dt * (wd / 2.0 - wd ** 3 / 24.0),
                       (1.0 - jnp.cos(safe)) / safe * dt)
        c, s = jnp.cos(wd), jnp.sin(wd)
        return jnp.stack([px + sw * vx - cw * vy,
                          py + cw * vx + sw * vy,
                          c * vx - s * vy,
                          s * vx + c * vy,
                          w])

    def h(x):
        return jnp.stack([jnp.arctan2(x[1] - s1[1], x[0] - s1[0]),
                          jnp.arctan2(x[1] - s2[1], x[0] - s2[0])])

    Q = jnp.asarray([
        [q1 * dt ** 3 / 3, 0, q1 * dt ** 2 / 2, 0, 0],
        [0, q1 * dt ** 3 / 3, 0, q1 * dt ** 2 / 2, 0],
        [q1 * dt ** 2 / 2, 0, q1 * dt, 0, 0],
        [0, q1 * dt ** 2 / 2, 0, q1 * dt, 0],
        [0, 0, 0, 0, q2 * dt]], dtype)
    R = params["r_std"] ** 2 * jnp.eye(2, dtype=dtype)
    return Problem(f=f, h=h, Q=Q, R=R,
                   m0=jnp.asarray(params["m0"], dtype),
                   P0=jnp.diag(jnp.asarray(params["p0_diag"], dtype)))
