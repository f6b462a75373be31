"""The plain reference: a sequential iterated Kalman smoother.

It computes what a configuration's spec asks of the program, one track at
a time and in the plainest form: a covariance-form Kalman filter and a
Rauch-Tung-Striebel smoother over the *unpadded* track, iterated as
Gauss-Newton passes (Bell 1994) with Levenberg-Marquardt damping
(Särkkä & Svensson 2020). Linearization is a first-order Taylor
expansion at the previous means (IEKS) or a statistical linear
regression through cubature points at the previous smoothed marginals
(IPLS; Yaghoobi, Corenflos, Hassan & Särkkä 2021).

The damping schedule is the spec's ``damping="adaptive"`` (DESIGN.md
§13): each pass adds the pseudo-measurement ``x_k ~ N(m_k, I / lam)`` of
the previous iterate at every step, the pass is accepted if the
Gauss-Newton cost of the candidate, under its own linearization, is
finite and not larger, and then ``lam`` is divided by ``NU``, else
multiplied by it and the previous iterate kept. A track stops once an
accepted step moves no mean by more than ``tol``, and is given up
(frozen at its last accepted iterate) after ``MAX_BAD`` non-finite
candidates in a row, or when ``lam`` reaches its cap while rejecting.

Tracks come padded to one length ``T`` with their real length ``n``:
steps past ``n`` are skipped (the filter leaves its state unchanged,
the smoother starts at step ``n``, the cost and the convergence test
read steps ``0..n`` only), so the answer is the unpadded track's. It
imports nothing of the program.

``matmul="high"`` computes every matrix product as three bfloat16
products (the hi*hi, hi*lo and lo*hi parts of each operand), which is
what ``precision="high"`` does on a TPU, on any platform: the control
that a comparison against this reference has to fail.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from bench.reference.problem import Problem

NU = 10.0
LAM_MIN = 1e-9
LAM_MAX = 1e8
LAM_INIT = 1.0
MAX_BAD = 2

HIGHEST = lax.Precision.HIGHEST


class Result(NamedTuple):
    mean: jnp.ndarray        # [T + 1, nx]; rows past n are not part of it
    cov: jnp.ndarray         # [T + 1, nx, nx]
    converged: jnp.ndarray   # the last accepted step moved no mean past tol
    diverged: jnp.ndarray
    iterations: jnp.ndarray  # passes made


def _split_bf16(a):
    hi = a.astype(jnp.bfloat16).astype(a.dtype)
    return hi, (a - hi).astype(jnp.bfloat16).astype(a.dtype)


def make_mm(matmul: str):
    """The matrix product: ``"highest"`` (exact to the dtype) or
    ``"high"`` (three bfloat16 passes)."""
    if matmul == "highest":
        return functools.partial(jnp.matmul, precision=HIGHEST)
    if matmul != "high":
        raise ValueError(f"unknown matmul precision {matmul!r}")

    def mm(a, b):
        ah, al = _split_bf16(a)
        bh, bl = _split_bf16(b)
        dot = functools.partial(jnp.matmul, precision=HIGHEST)
        return dot(ah, bh) + (dot(ah, bl) + dot(al, bh))

    return mm


class _Lin(NamedTuple):
    F: jnp.ndarray
    c: jnp.ndarray
    Qp: jnp.ndarray
    H: jnp.ndarray
    d: jnp.ndarray
    Rp: jnp.ndarray


def _sym(M):
    return 0.5 * (M + M.T)


def _taylor(phi, m, P, mm):
    del P
    F = jax.jacfwd(phi)(m)
    return F, phi(m) - mm(F, m), jnp.zeros((F.shape[0],) * 2, m.dtype)


def _cubature(phi, m, P, mm):
    """Statistical linear regression of ``phi`` at ``N(m, P)`` through
    the 2 nx cubature points ``m +- sqrt(nx) chol(P) e_i``."""
    nx = m.shape[0]
    xi = jnp.sqrt(jnp.asarray(nx, m.dtype)) * jnp.concatenate(
        [jnp.eye(nx, dtype=m.dtype), -jnp.eye(nx, dtype=m.dtype)])
    pts = m + mm(xi, jnp.linalg.cholesky(_sym(P)).T)       # [2 nx, nx]
    Z = jax.vmap(phi)(pts)
    w = 1.0 / (2 * nx)
    zbar = w * jnp.sum(Z, axis=0)
    dx, dz = pts - m, Z - zbar
    Psi = w * mm(dx.T, dz)                                 # cov(x, z)
    Phi = w * mm(dz.T, dz)                                 # cov(z, z)
    F = jnp.linalg.solve(_sym(P), Psi).T
    return F, zbar - mm(F, m), _sym(Phi - mm(mm(F, _sym(P)), F.T))


def _linearize(problem: Problem, mean, cov, linearization, mm) -> _Lin:
    lin = {"taylor": _taylor, "slr": _cubature}[linearization]
    one = functools.partial(lin, mm=mm)
    F, c, Lq = jax.vmap(lambda m, P: one(problem.f, m, P))(mean[:-1],
                                                          cov[:-1])
    H, d, Lr = jax.vmap(lambda m, P: one(problem.h, m, P))(mean[1:],
                                                          cov[1:])
    return _Lin(F, c, problem.Q + Lq, H, d, problem.R + Lr)


def _half_quad(r, S):
    """``r^T S^-1 r / 2`` by a Cholesky factor."""
    z = jax.scipy.linalg.solve_triangular(jnp.linalg.cholesky(_sym(S)), r,
                                          lower=True)
    return 0.5 * jnp.sum(z * z)


def _cost(problem, lin: _Lin, ys, mean, real, mm):
    """Gauss-Newton cost of ``mean`` under ``lin`` over steps ``0..n``."""
    mv = lambda A, x: mm(A, x[:, None])[:, 0]
    trans = jax.vmap(lambda F, c, Qp, m0, m1: _half_quad(
        m1 - mv(F, m0) - c, Qp))(lin.F, lin.c, lin.Qp, mean[:-1], mean[1:])
    meas = jax.vmap(lambda H, d, Rp, y, m1: _half_quad(
        y - mv(H, m1) - d, Rp))(lin.H, lin.d, lin.Rp, ys, mean[1:])
    return (_half_quad(mean[0] - problem.m0, problem.P0)
            + jnp.sum(jnp.where(real, trans + meas, 0.0)))


def _pass(problem, lin: _Lin, ys, prev_mean, lam, real, mm):
    """One damped filter + smoother pass; returns smoothed ``[T + 1]``."""
    nx = problem.nx
    eye = jnp.eye(nx, dtype=ys.dtype)
    mv = lambda A, x: mm(A, x[:, None])[:, 0]

    def filt(carry, inp):
        m, P = carry
        F, c, Qp, H, d, Rp, y, pseudo, is_real = inp
        m_pred = mv(F, m) + c
        P_pred = _sym(mm(mm(F, P), F.T) + Qp)
        # The damping term: a pseudo-measurement of the previous iterate.
        Ha = jnp.concatenate([H, eye])
        da = jnp.concatenate([d, jnp.zeros((nx,), ys.dtype)])
        ya = jnp.concatenate([y, pseudo])
        ny = H.shape[0]
        Ra = jnp.zeros((ny + nx, ny + nx), ys.dtype)
        Ra = Ra.at[:ny, :ny].set(Rp).at[ny:, ny:].set(eye / lam)
        S = _sym(mm(mm(Ha, P_pred), Ha.T) + Ra)
        K = jnp.linalg.solve(S, mm(Ha, P_pred)).T
        m_new = m_pred + mv(K, ya - mv(Ha, m_pred) - da)
        P_new = _sym(P_pred - mm(mm(K, S), K.T))
        m_new = jnp.where(is_real, m_new, m)
        P_new = jnp.where(is_real, P_new, P)
        return (m_new, P_new), (m_new, P_new)

    _, (mf, Pf) = lax.scan(
        filt, (problem.m0, problem.P0),
        (lin.F, lin.c, lin.Qp, lin.H, lin.d, lin.Rp, ys, prev_mean[1:],
         real))
    mf = jnp.concatenate([problem.m0[None], mf])             # rows 0..T
    Pf = jnp.concatenate([problem.P0[None], Pf])

    def smooth(carry, inp):
        ms, Ps = carry
        m, P, F, c, Qp, next_real = inp
        m_pred = mv(F, m) + c
        P_pred = _sym(mm(mm(F, P), F.T) + Qp)
        G = jnp.linalg.solve(P_pred, mm(F, P)).T
        m_s = m + mv(G, ms - m_pred)
        P_s = _sym(P + mm(mm(G, Ps - P_pred), G.T))
        # Rows past the last real step keep their filtered values, so the
        # recursion starts from the filtered state at step n.
        m_s = jnp.where(next_real, m_s, m)
        P_s = jnp.where(next_real, P_s, P)
        return (m_s, P_s), (m_s, P_s)

    _, (ms, Ps) = lax.scan(
        smooth, (mf[-1], Pf[-1]),
        (mf[:-1], Pf[:-1], lin.F, lin.c, lin.Qp, real), reverse=True)
    return (jnp.concatenate([ms, mf[-1:]]),
            jnp.concatenate([Ps, Pf[-1:]]))


def iterated_smoother(problem: Problem, ys, n, *, linearization: str,
                      n_iter: int, tol: float, lm_lambda: float,
                      matmul: str = "highest") -> Result:
    """Smooth one track ``ys [T, ny]`` of real length ``n`` (the steps
    ``1..n``; rows past ``n`` are ignored). Trace under ``jax.vmap`` to
    smooth many tracks."""
    mm = make_mm(matmul)
    T, nx = ys.shape[0], problem.nx
    dtype = ys.dtype
    real = jnp.arange(1, T + 1) <= n                       # step k real
    rows = (jnp.arange(T + 1) <= n)[:, None]                # rows 0..n

    def lin_at(mean, cov):
        return _linearize(problem, mean, cov, linearization, mm)

    def cost_of(mean, cov):
        return _cost(problem, lin_at(mean, cov), ys, mean, real, mm)

    def finite(mean, cov, cost):
        return (jnp.all(jnp.where(rows, jnp.isfinite(mean), True))
                & jnp.all(jnp.where(rows[:, :, None], jnp.isfinite(cov),
                                    True))
                & jnp.isfinite(cost))

    mean0 = jnp.broadcast_to(problem.m0, (T + 1, nx))
    cov0 = jnp.broadcast_to(problem.P0, (T + 1, nx, nx))
    cost0 = cost_of(mean0, cov0)
    lam0 = jnp.asarray(lm_lambda if lm_lambda > 0 else LAM_INIT, dtype)

    def body(_, carry):
        mean, cov, cost, lam, active, conv, div, bad, iters = carry
        cm, cc = _pass(problem, lin_at(mean, cov), ys, mean, lam, real, mm)
        c_cost = cost_of(cm, cc)
        ok = finite(cm, cc, c_cost)
        accept = active & ok & (c_cost <= cost)
        delta = jnp.max(jnp.where(rows, jnp.abs(cm - mean), 0.0))
        mean = jnp.where(accept, cm, mean)
        cov = jnp.where(accept, cc, cov)
        cost = jnp.where(accept, c_cost, cost)
        lam = jnp.where(accept, jnp.maximum(lam / NU, LAM_MIN),
                        jnp.where(active, jnp.minimum(lam * NU, LAM_MAX),
                                  lam))
        bad = jnp.where(accept, 0, jnp.where(active, bad + 1, bad))
        iters = iters + active.astype(jnp.int32)
        now_conv = accept & (delta <= tol) if tol > 0 else accept & False
        hopeless = active & ~accept & ((~ok & (bad >= MAX_BAD))
                                       | (lam >= LAM_MAX))
        return (mean, cov, cost, lam, active & ~now_conv & ~hopeless,
                conv | now_conv, div | hopeless, bad, iters)

    carry = (mean0, cov0, cost0, lam0, ~jnp.isnan(cost0),
             jnp.asarray(False), jnp.isnan(cost0), jnp.asarray(0),
             jnp.asarray(0))
    mean, cov, _, _, _, conv, div, _, iters = lax.fori_loop(
        0, n_iter, body, carry)
    return Result(mean=mean, cov=cov, converged=conv, diverged=div,
                  iterations=iters)


def smooth_tracks(problem: Problem, ys, ns, spec: dict,
                  matmul: str = "highest") -> Result:
    """:func:`iterated_smoother` over tracks ``ys [K, T, ny]`` of real
    lengths ``ns [K]``, for the spec group of a configuration file."""
    run = functools.partial(
        iterated_smoother, problem, linearization=spec["linearization"],
        n_iter=spec["n_iter"], tol=spec["tol"],
        lm_lambda=spec["lm_lambda"], matmul=matmul)
    return jax.vmap(run)(ys, ns)
