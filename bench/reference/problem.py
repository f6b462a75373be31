"""A state-space problem with additive Gaussian noise, and its simulation.

    x_0 ~ N(m0, P0),  x_k = f(x_{k-1}) + q_k,  y_k = h(x_k) + r_k,
    q_k ~ N(0, Q),  r_k ~ N(0, R).

A configuration names its problem (``problem.model``); the module of that
name under ``bench/reference/models/`` builds it from the configuration's
numbers.
"""
from __future__ import annotations

import importlib
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp


class Problem(NamedTuple):
    f: Callable
    h: Callable
    Q: jnp.ndarray
    R: jnp.ndarray
    m0: jnp.ndarray
    P0: jnp.ndarray

    @property
    def nx(self) -> int:
        return self.Q.shape[-1]

    @property
    def ny(self) -> int:
        return self.R.shape[-1]


def load_problem(problem_cfg: dict, dtype) -> Problem:
    """The problem a configuration's ``problem`` group states."""
    module = importlib.import_module(
        f"bench.reference.models.{problem_cfg['model']}")
    return module.build(problem_cfg["params"], dtype)


def simulate(problem: Problem, n: int, key) -> tuple:
    """One track: ``x_{0:n}`` ``[n + 1, nx]`` and ``y_{1:n}`` ``[n, ny]``."""
    kx, kq, kr = jax.random.split(key, 3)
    dtype = problem.m0.dtype
    x0 = problem.m0 + jnp.linalg.cholesky(problem.P0) @ jax.random.normal(
        kx, (problem.nx,), dtype)
    qs = jax.random.normal(kq, (n, problem.nx), dtype) @ \
        jnp.linalg.cholesky(problem.Q).T
    rs = jax.random.normal(kr, (n, problem.ny), dtype) @ \
        jnp.linalg.cholesky(problem.R).T

    def step(x, noise):
        x = problem.f(x) + noise[0]
        return x, (x, problem.h(x) + noise[1])

    _, (xs, ys) = jax.lax.scan(step, x0, (qs, rs))
    return jnp.concatenate([x0[None], xs]), ys
