"""Iterated smoothers: IEKS (Taylor) and IPLS (sigma-point SLR).

The outer loop (paper §3) repeats up to M times:
  1. linearize the model around the previous *smoothed* trajectory
     (offline w.r.t. the current pass — this is what admits the scan);
  2. run a filter + smoother pass, either sequential (baseline) or
     parallel-in-time (the paper's method).

IEKS iterations are Gauss-Newton steps on the MAP objective (Bell 1994);
optional Levenberg-Marquardt damping (Särkkä & Svensson 2020, ref [15])
augments each measurement with a pseudo-observation of the previous iterate
with covariance ``(1/lambda) I``.

Iteration count is adaptive (DESIGN.md §Iteration): with ``tol > 0`` the
fixed-``M`` `lax.scan` is replaced by a `lax.while_loop` that stops once
the mean update ``max|m_new - m_old|`` falls below ``tol`` (Gauss-Newton
passes past convergence are pure waste). The batched driver keeps a
per-trajectory active mask and freezes converged lanes, stopping globally
when every lane is done. ``tol = 0`` (the default) preserves the exact
fixed-``M`` path.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from . import parallel, sequential, sqrt_parallel
from ._deprecation import warn_deprecated
from .cost import gn_cost
from .linearization import (linearize_model_slr, linearize_model_slr_batched,
                            linearize_model_taylor,
                            linearize_model_taylor_batched)
from .scopes import ITERATE, LOGLIK, scoped
from .sigma_points import SCHEMES, SigmaScheme, get_scheme
from .types import (Gaussian, LinearizedSSM, StateSpaceModel, bmm, bmv,
                    mvn_logpdf)

jtm = jax.tree_util.tree_map

#: Axis vocabularies shared with `repro.core.api.SmootherSpec` — defined
#: here (the leaf module) so the two validators can never drift.
FORMS = ("standard", "sqrt")
COMBINE_IMPLS = ("auto", "jnp", "fused", "pallas")
DAMPINGS = ("fixed", "adaptive")
#: Compiled-kernel dispatch axis: "auto" (measured autotuner — kernel vs
#: fused-jnp per (B, T, nx), cached per spec_id), "jnp" (never lower a
#: kernel: fused twins only), "tpu" / "gpu" (force that lowering; falls
#: back to fused with a warning off-platform).
BACKENDS = ("auto", "jnp", "tpu", "gpu")

#: `LaneStatus.code` vocabulary (DESIGN.md §13): the per-lane verdict of
#: the outer Gauss-Newton loop.
LANE_CONVERGED = 0   # mean delta fell below tol (requires tol > 0)
LANE_MAX_ITERS = 1   # iteration budget exhausted while still finite
LANE_DIVERGED = 2    # non-finite iterate / cost, or damping cap exhausted

#: Adaptive Levenberg-Marquardt schedule (classic nu = 10): accepted
#: steps decay the damping, rejected steps raise it; a lane whose
#: candidates stay non-finite for LM_MAX_BAD consecutive attempts — or
#: whose damping hits the cap while still rejecting — is declared
#: diverged and frozen at its last accepted iterate.
LM_NU = 10.0
LM_LAMBDA_INIT = 1.0
LM_LAMBDA_MIN = 1e-9
LM_LAMBDA_MAX = 1e8
LM_MAX_BAD = 2


def validate_iteration_knobs(n_iter: int, tol: float, lm_lambda: float,
                             jitter: float) -> None:
    """Shared numeric-knob validation for IteratedConfig/SmootherSpec."""
    if n_iter < 1:
        raise ValueError(f"n_iter must be >= 1, got {n_iter}")
    if tol < 0.0:
        raise ValueError(f"tol must be >= 0, got {tol}")
    if lm_lambda < 0.0:
        raise ValueError(f"lm_lambda must be >= 0, got {lm_lambda}")
    if jitter < 0.0:
        raise ValueError(f"jitter must be >= 0, got {jitter}")


@dataclasses.dataclass(frozen=True)
class IteratedConfig:
    method: str = "ekf"             # "ekf" (IEKS) | "slr" (IPLS)
    n_iter: int = 10                # paper uses M = 10 (max iters if tol>0)
    parallel: bool = True           # paper's contribution vs. baseline
    sigma_scheme: str = "cubature"  # for method="slr"
    lm_lambda: float = 0.0          # Levenberg-Marquardt damping (0 = off)
    combine_impl: str = "auto"      # "auto" | "jnp" | "fused" | "pallas"
    jitter: float = 0.0
    tol: float = 0.0                # early-stop mean-delta tol (0 = fixed M)
    model_id: str = ""              # scenario content hash (registry tenants)
    form: str = "standard"          # "standard" | "sqrt" (parallel only)
    damping: str = "fixed"          # "fixed" | "adaptive" (per-lane LM)
    backend: str = "auto"           # "auto" | "jnp" | "tpu" | "gpu"

    def __post_init__(self):
        """Eager validation: a bad axis name or iteration knob must fail
        here with a readable message, not deep inside a traced scan."""
        if self.method not in ("ekf", "slr"):
            raise ValueError(f"unknown method {self.method!r}; "
                             f"available: ['ekf', 'slr']")
        if self.form not in FORMS:
            raise ValueError(f"unknown form {self.form!r}; "
                             f"available: {sorted(FORMS)}")
        if self.form == "sqrt" and not self.parallel:
            raise ValueError(
                'form="sqrt" requires parallel=True: no sequential '
                "square-root pass is implemented (DESIGN.md §9)")
        if self.sigma_scheme not in SCHEMES:
            raise ValueError(
                f"unknown sigma-point scheme {self.sigma_scheme!r}; "
                f"available: {sorted(SCHEMES)}")
        if self.combine_impl not in COMBINE_IMPLS:
            raise ValueError(
                f"unknown combine_impl {self.combine_impl!r}; "
                f"available: {sorted(COMBINE_IMPLS)}")
        if self.damping not in DAMPINGS:
            raise ValueError(f"unknown damping {self.damping!r}; "
                             f"available: {sorted(DAMPINGS)}")
        if self.backend not in BACKENDS:
            raise ValueError(f"unknown backend {self.backend!r}; "
                             f"available: {sorted(BACKENDS)}")
        if self.combine_impl == "pallas" and self.backend == "jnp":
            raise ValueError(
                'combine_impl="pallas" contradicts backend="jnp" '
                "(a compiled kernel with kernels disabled) — drop one")
        validate_iteration_knobs(self.n_iter, self.tol, self.lm_lambda,
                                 self.jitter)

    def resolved_combine_impl(self, batched: bool,
                              shape: Optional[tuple] = None) -> str:
        """The scan-driver ``combine_impl`` string for one call site.

        ``shape`` is the static launch shape ``(B, T, nx, dtype)`` when
        the caller knows it (the batched pass drivers do) — it keys the
        ``backend="auto"`` autotune-cache lookup. Resolution:

          * explicit ``combine_impl`` wins; "pallas" is qualified to
            "pallas:tpu"/"pallas:gpu" when the backend forces a lowering
            (off-platform the scan driver raises: no silent fused run);
          * "auto" + single trajectory -> "jnp" (textbook vmap);
          * "auto" + batched: ``backend="jnp"`` -> "fused";
            ``backend="tpu"/"gpu"`` -> that compiled kernel;
            ``backend="auto"`` -> the measured winner recorded by
            `repro.kernels.kalman_combine.autotune` for
            ``(model_id, B, T, nx, dtype)`` — ``model_id`` carries the spec_id
            on API-built smoothers — else the fused twin (the safe
            default: an unmeasured site is never slower than fused).

        Pure host-side lookup, trace-stable for a fixed cache state
        (warmup/build populates the cache before tracing).
        """
        if self.combine_impl == "auto":
            if not batched:
                return "jnp"
            if self.backend in ("tpu", "gpu"):
                return f"pallas:{self.backend}"
            if self.backend == "auto" and shape is not None:
                # Late import: kernels depend on core.
                from repro.kernels.kalman_combine import autotune as kc_at
                if kc_at.decide(self.model_id, *shape) == kc_at.CHOICE_KERNEL:
                    return "pallas"
            return "fused"
        if self.combine_impl == "pallas" and self.backend in ("tpu", "gpu"):
            return f"pallas:{self.backend}"
        return self.combine_impl

    def cache_key(self, n_pad: int, b_pad: int, nx: int) -> tuple:
        """Hashable executable signature of one padded bucket launch.

        The serving queue (launch/autobatch.py) jit-caches one batched
        smoother executable per (config, time bucket, batch width,
        state dim); this is the key its warmup and compile-count
        bookkeeping use. Frozen config => the tuple is hashable, and
        ``model_id`` (the scenario content hash) rides inside the
        config, so multi-tenant serving cannot collide two models'
        executables — this is the single bucketing contract shared by
        `launch/serve.py` and `launch/autobatch.py` (DESIGN.md §7).
        """
        return (self, int(n_pad), int(b_pad), int(nx))


class LaneStatus(NamedTuple):
    """Per-lane verdict of the outer loop (scalar fields for the single-
    trajectory driver, ``[B]`` for the batched one).

    ``code`` is one of `LANE_CONVERGED` / `LANE_MAX_ITERS` /
    `LANE_DIVERGED`; ``iterations`` counts the passes the lane executed;
    ``final_delta`` is the last accepted mean update; ``final_cost`` the
    GN cost of the returned trajectory (`core.cost.smoothing_cost`;
    zeros on fixed-damping paths unless ``return_info`` requested it).
    The first two fields keep the legacy `IterationInfo` positions, so
    ``info.iterations`` / ``info.final_delta`` consumers are unchanged.
    """

    iterations: jnp.ndarray
    final_delta: jnp.ndarray
    code: jnp.ndarray
    final_cost: jnp.ndarray


#: Legacy alias: `IterationInfo` grew lane-health fields and became
#: `LaneStatus` — same leading fields, same pytree structure.
IterationInfo = LaneStatus


def _augment_lm(lin: LinearizedSSM, prev_means: jnp.ndarray, lam
                ) -> Tuple[LinearizedSSM, jnp.ndarray]:
    """LM damping: pseudo-measurement ``x_k ~ N(prev_mean_k, (1/lam) I)``.

    Shape-polymorphic over leading axes (``[n, ...]`` or ``[B, n, ...]``):
    returns the augmented model and the pseudo measurements (the caller
    concatenates the real ys with them along the last axis). ``lam`` is a
    scalar (fixed damping) or a per-lane ``[B]`` array (the adaptive
    driver's independently-damped lanes).
    """
    ny, nx = lin.H.shape[-2:]
    lead = lin.H.shape[:-2]
    I = jnp.eye(nx, dtype=lin.H.dtype)
    inv = 1.0 / jnp.asarray(lam, lin.Rp.dtype)
    inv = inv.reshape(inv.shape + (1,) * (len(lead) + 2 - inv.ndim))
    H_aug = jnp.concatenate(
        [lin.H, jnp.broadcast_to(I, lead + (nx, nx))], axis=-2)
    d_aug = jnp.concatenate(
        [lin.d, jnp.zeros(lead + (nx,), lin.d.dtype)], axis=-1)
    R_pad = jnp.zeros(lead + (ny, nx), lin.Rp.dtype)
    R_top = jnp.concatenate([lin.Rp, R_pad], axis=-1)
    R_bot = jnp.concatenate(
        [jnp.swapaxes(R_pad, -1, -2),
         jnp.broadcast_to(I, lead + (nx, nx)) * inv], axis=-1)
    Rp_aug = jnp.concatenate([R_top, R_bot], axis=-2)
    return LinearizedSSM(F=lin.F, c=lin.c, Qp=lin.Qp,
                         H=H_aug, d=d_aug, Rp=Rp_aug), prev_means


def _one_pass(model: StateSpaceModel, ys: jnp.ndarray, traj: Gaussian,
              cfg: IteratedConfig, scheme: Optional[SigmaScheme],
              lam=None) -> Gaussian:
    if cfg.method == "ekf":
        lin = linearize_model_taylor(model, traj.mean)
    elif cfg.method == "slr":
        lin = linearize_model_slr(model, traj, scheme, cfg.jitter)
    else:
        raise ValueError(f"unknown method {cfg.method!r}")

    ys_eff = ys
    if lam is not None:
        lin, pseudo = _augment_lm(lin, traj.mean[1:], lam)
        ys_eff = jnp.concatenate([ys, pseudo], axis=-1)
    elif cfg.lm_lambda > 0.0:
        lin, pseudo = _augment_lm(lin, traj.mean[1:], cfg.lm_lambda)
        ys_eff = jnp.concatenate([ys, pseudo], axis=-1)

    if cfg.parallel:
        if cfg.form == "sqrt":
            _, smoothed = sqrt_parallel.sqrt_parallel_filter_smoother(
                lin, ys_eff, model.m0, model.P0)
        else:
            _, smoothed = parallel.parallel_filter_smoother(
                lin, ys_eff, model.m0, model.P0,
                combine_impl=cfg.resolved_combine_impl(batched=False))
    else:
        _, smoothed = sequential.filter_smoother(lin, ys_eff, model.m0,
                                                 model.P0)
    return smoothed


def _one_pass_batched(model: StateSpaceModel, ys: jnp.ndarray,
                      traj: Gaussian, cfg: IteratedConfig,
                      scheme: Optional[SigmaScheme], lam=None) -> Gaussian:
    """One linearize->filter->smooth pass over ``[B, n]`` trajectories.

    ``lam`` (per-lane ``[B]``) overrides ``cfg.lm_lambda`` — the adaptive
    driver damps each lane independently."""
    if cfg.method == "ekf":
        lin = linearize_model_taylor_batched(model, traj.mean)
    elif cfg.method == "slr":
        lin = linearize_model_slr_batched(model, traj, scheme, cfg.jitter)
    else:
        raise ValueError(f"unknown method {cfg.method!r}")

    ys_eff = ys
    if lam is not None:
        lin, pseudo = _augment_lm(lin, traj.mean[:, 1:], lam)
        ys_eff = jnp.concatenate([ys, pseudo], axis=-1)
    elif cfg.lm_lambda > 0.0:
        lin, pseudo = _augment_lm(lin, traj.mean[:, 1:], cfg.lm_lambda)
        ys_eff = jnp.concatenate([ys, pseudo], axis=-1)

    if cfg.parallel:
        if cfg.form == "sqrt":
            _, smoothed = \
                sqrt_parallel._sqrt_parallel_filter_smoother_batched(
                    lin, ys_eff, model.m0, model.P0)
        else:
            _, smoothed = parallel._parallel_filter_smoother_batched(
                lin, ys_eff, model.m0, model.P0,
                combine_impl=cfg.resolved_combine_impl(
                    batched=True,
                    shape=(ys.shape[0], ys.shape[1],
                           traj.mean.shape[-1], ys.dtype)))
    else:
        _, smoothed = sequential._filter_smoother_batched(
            lin, ys_eff, model.m0, model.P0)
    return smoothed


def initial_trajectory(model: StateSpaceModel, n: int) -> Gaussian:
    """Nominal initialization: the prior tiled along the trajectory."""
    mean = jnp.broadcast_to(model.m0, (n + 1,) + model.m0.shape)
    cov = jnp.broadcast_to(model.P0, (n + 1,) + model.P0.shape)
    return Gaussian(mean=mean, cov=cov)


def initial_trajectory_batched(model: StateSpaceModel, B: int, n: int
                               ) -> Gaussian:
    mean = jnp.broadcast_to(model.m0, (B, n + 1) + model.m0.shape)
    cov = jnp.broadcast_to(model.P0, (B, n + 1) + model.P0.shape)
    return Gaussian(mean=mean, cov=cov)


def _pack_result(traj, hist, info, return_history, return_info):
    out = (traj,)
    if return_history:
        out = out + (hist,)
    if return_info:
        out = out + (info,)
    return out[0] if len(out) == 1 else out


def _mean_delta(new: Gaussian, old: Gaussian, lane_axes) -> jnp.ndarray:
    return jnp.max(jnp.abs(new.mean - old.mean), axis=lane_axes)


def _lane_axes(mean_ndim: int) -> tuple:
    """Reduction axes collapsing one trajectory to its lane: ``(0, 1)``
    for single ``[n+1, nx]`` means, ``(1, 2)`` for batched."""
    return (0, 1) if mean_ndim == 2 else (1, 2)


def _finite_lanes(traj: Gaussian) -> jnp.ndarray:
    """Per-lane all-finite check over means and covariances (scalar bool
    for single trajectories, ``[B]`` batched)."""
    ma = _lane_axes(traj.mean.ndim)
    return (jnp.all(jnp.isfinite(traj.mean), axis=ma)
            & jnp.all(jnp.isfinite(traj.cov), axis=ma + (ma[-1] + 1,)))


def _make_info(model, ys, traj, cfg, scheme, iterations, delta, converged,
               want_cost: bool) -> LaneStatus:
    """Final `LaneStatus` for the fixed-damping drivers: classify each
    lane from its finiteness + convergence flag, and (only when the
    caller asked for info) evaluate the GN cost of the returned
    trajectory."""
    finite = _finite_lanes(traj)
    if want_cost:
        cost = gn_cost(model, ys, traj, cfg.method, scheme, cfg.jitter)
    else:
        cost = jnp.zeros(finite.shape, traj.mean.dtype)
    code = jnp.where(
        finite,
        jnp.where(converged, LANE_CONVERGED, LANE_MAX_ITERS),
        LANE_DIVERGED).astype(jnp.int32)
    return LaneStatus(iterations=iterations, final_delta=delta,
                      code=code, final_cost=cost)


def _adaptive_iterated(model: StateSpaceModel, ys: jnp.ndarray,
                       cfg: IteratedConfig, scheme: Optional[SigmaScheme],
                       traj0: Gaussian, return_history: bool,
                       return_info: bool, batched: bool):
    """Per-lane adaptive Levenberg-Marquardt outer loop (DESIGN.md §13).

    Every iteration runs one damped pass for all lanes, evaluates the GN
    cost of each candidate under its own linearization, and then — per
    lane, independently — accepts the step (cost decreased: damping
    decays by `LM_NU`), rejects it (cost rose: the lane keeps its
    previous iterate and raises its damping), or declares divergence
    (`LM_MAX_BAD` consecutive non-finite candidates, or the damping cap
    reached while still rejecting) and freezes the lane at its last
    accepted — hence finite — iterate. NaNs therefore never reach the
    returned means/covariances: a lane that never accepts returns the
    initial trajectory. ``cfg.lm_lambda > 0`` seeds the damping,
    otherwise `LM_LAMBDA_INIT`.
    """
    M = cfg.n_iter
    dtype = traj0.mean.dtype
    lane_shape = traj0.mean.shape[:-2]
    one_pass = _one_pass_batched if batched else _one_pass
    axes = _lane_axes(traj0.mean.ndim)

    lam0 = jnp.full(lane_shape,
                    cfg.lm_lambda if cfg.lm_lambda > 0.0 else LM_LAMBDA_INIT,
                    dtype)
    cost0 = gn_cost(model, ys, traj0, cfg.method, scheme, cfg.jitter)
    # A NaN initial cost (NaN observations) can never win a comparison:
    # mark the lane diverged up front instead of burning its budget.
    active0 = ~jnp.isnan(cost0)
    code0 = jnp.where(active0, LANE_MAX_ITERS, LANE_DIVERGED
                      ).astype(jnp.int32)
    hist0 = (jnp.zeros((M,) + traj0.mean.shape, dtype)
             if return_history else jnp.zeros((0,), dtype))

    def cond(carry):
        return (carry[-1] < M) & jnp.any(carry[3])

    def body(carry):
        traj, cost, lam, active, iters, code, bad, delta, hist, it = carry
        cand = one_pass(model, ys, traj, cfg, scheme, lam=lam)
        cand_cost = gn_cost(model, ys, cand, cfg.method, scheme, cfg.jitter)
        cand_finite = _finite_lanes(cand) & jnp.isfinite(cand_cost)
        accept = active & cand_finite & (cand_cost <= cost)
        step_delta = _mean_delta(cand, traj, axes)
        traj = _freeze_lanes(accept, cand, traj)
        cost = jnp.where(accept, cand_cost, cost)
        delta = jnp.where(accept, step_delta, delta)
        lam = jnp.where(
            accept, jnp.maximum(lam / LM_NU, LM_LAMBDA_MIN),
            jnp.where(active, jnp.minimum(lam * LM_NU, LM_LAMBDA_MAX), lam))
        bad = jnp.where(accept, 0, jnp.where(active, bad + 1, bad))
        iters = iters + active.astype(jnp.int32)
        if cfg.tol > 0.0:
            conv = accept & (step_delta <= cfg.tol)
        else:
            conv = jnp.zeros_like(accept)
        hopeless = active & ~accept & (
            (~cand_finite & (bad >= LM_MAX_BAD)) | (lam >= LM_LAMBDA_MAX))
        code = jnp.where(conv, LANE_CONVERGED,
                         jnp.where(hopeless, LANE_DIVERGED, code)
                         ).astype(jnp.int32)
        active = active & ~conv & ~hopeless
        if return_history:
            hist = lax.dynamic_update_index_in_dim(hist, traj.mean, it, 0)
        return traj, cost, lam, active, iters, code, bad, delta, hist, it + 1

    carry0 = (traj0, cost0, lam0, active0,
              jnp.zeros(lane_shape, jnp.int32), code0,
              jnp.zeros(lane_shape, jnp.int32),
              jnp.full(lane_shape, jnp.inf, dtype), hist0,
              jnp.asarray(0, jnp.int32))
    traj, cost, _, _, iters, code, _, delta, hist, it = lax.while_loop(
        cond, body, carry0)
    if return_history:
        done = jnp.arange(M) < it
        done = done.reshape((M,) + (1,) * traj.mean.ndim)
        hist = jnp.where(done, hist, traj.mean[None])
    info = LaneStatus(iterations=iters, final_delta=delta, code=code,
                      final_cost=cost)
    return _pack_result(traj, hist, info, return_history, return_info)


@scoped(ITERATE)
def iterated_smoother(model: StateSpaceModel, ys: jnp.ndarray,
                      cfg: IteratedConfig = IteratedConfig(),
                      init: Optional[Gaussian] = None,
                      return_history: bool = False,
                      return_info: bool = False):
    """Run up to M linearize->filter->smooth passes.

    Returns the final smoothed trajectory (leading dim n+1); optionally the
    mean history ``[M, n+1, nx]`` and/or an `IterationInfo`. With
    ``cfg.tol > 0`` iteration stops once the mean update falls below the
    tolerance; history rows past the executed passes repeat the final mean.
    """
    n = ys.shape[0]
    traj0 = init if init is not None else initial_trajectory(model, n)
    scheme = (get_scheme(cfg.sigma_scheme, model.nx)
              if cfg.method == "slr" else None)
    M = cfg.n_iter

    if cfg.damping == "adaptive":
        return _adaptive_iterated(model, ys, cfg, scheme, traj0,
                                  return_history, return_info, batched=False)

    if cfg.tol <= 0.0:
        # Fixed-M path: identical to the paper's M=10 loop.
        def step(carry, _):
            smoothed = _one_pass(model, ys, carry, cfg, scheme)
            delta = _mean_delta(smoothed, carry, None)
            out = smoothed.mean if return_history else None
            return smoothed, (out, delta)

        traj, (hist, deltas) = lax.scan(step, traj0, None, length=M)
        info = _make_info(model, ys, traj, cfg, scheme,
                          iterations=jnp.asarray(M), delta=deltas[-1],
                          converged=False, want_cost=return_info)
        return _pack_result(traj, hist, info, return_history, return_info)

    hist0 = (jnp.zeros((M,) + traj0.mean.shape, traj0.mean.dtype)
             if return_history else jnp.zeros((0,), traj0.mean.dtype))
    big = jnp.asarray(jnp.inf, traj0.mean.dtype)

    def cond(carry):
        _, it, delta, _ = carry
        return (it < M) & (delta > cfg.tol)

    def body(carry):
        traj, it, _, hist = carry
        new = _one_pass(model, ys, traj, cfg, scheme)
        delta = _mean_delta(new, traj, None)
        if return_history:
            hist = lax.dynamic_update_index_in_dim(hist, new.mean, it, 0)
        return new, it + 1, delta, hist

    traj, it, delta, hist = lax.while_loop(
        cond, body, (traj0, jnp.asarray(0, jnp.int32), big, hist0))
    if return_history:
        done = jnp.arange(M) < it
        hist = jnp.where(done[:, None, None], hist, traj.mean[None])
    info = _make_info(model, ys, traj, cfg, scheme, iterations=it,
                      delta=delta, converged=delta <= cfg.tol,
                      want_cost=return_info)
    return _pack_result(traj, hist, info, return_history, return_info)


def _freeze_lanes(active: jnp.ndarray, new: Gaussian, old: Gaussian
                  ) -> Gaussian:
    """Keep the old trajectory on lanes whose mask is False."""
    def sel(n, o):
        mask = active.reshape(active.shape + (1,) * (n.ndim - 1))
        return jnp.where(mask, n, o)
    return jtm(sel, new, old)


@scoped(ITERATE)
def _iterated_smoother_batched(model: StateSpaceModel, ys: jnp.ndarray,
                               cfg: IteratedConfig = IteratedConfig(),
                               init: Optional[Gaussian] = None,
                               return_history: bool = False,
                               return_info: bool = False):
    """Batched iterated smoother over ``ys [B, n, ny]``.

    Every pass runs all B trajectories through one fused batched
    filter+smoother; with ``cfg.tol > 0`` a per-lane active mask freezes
    converged trajectories (their output stops changing, and
    ``info.iterations`` records per-lane pass counts) and the loop exits
    as soon as every lane has converged. Returns ``[B, n+1, ...]``
    marginals; history is ``[M, B, n+1, nx]``.
    """
    B, n = ys.shape[:2]
    traj0 = init if init is not None else initial_trajectory_batched(
        model, B, n)
    scheme = (get_scheme(cfg.sigma_scheme, model.nx)
              if cfg.method == "slr" else None)
    M = cfg.n_iter

    if cfg.damping == "adaptive":
        return _adaptive_iterated(model, ys, cfg, scheme, traj0,
                                  return_history, return_info, batched=True)

    if cfg.tol <= 0.0:
        def step(carry, _):
            smoothed = _one_pass_batched(model, ys, carry, cfg, scheme)
            delta = _mean_delta(smoothed, carry, (1, 2))
            out = smoothed.mean if return_history else None
            return smoothed, (out, delta)

        traj, (hist, deltas) = lax.scan(step, traj0, None, length=M)
        info = _make_info(model, ys, traj, cfg, scheme,
                          iterations=jnp.full((B,), M, jnp.int32),
                          delta=deltas[-1], converged=False,
                          want_cost=return_info)
        return _pack_result(traj, hist, info, return_history, return_info)

    hist0 = (jnp.zeros((M,) + traj0.mean.shape, traj0.mean.dtype)
             if return_history else jnp.zeros((0,), traj0.mean.dtype))

    def cond(carry):
        _, it, active, _, _, _ = carry
        return (it < M) & jnp.any(active)

    def body(carry):
        traj, it, active, iters, delta, hist = carry
        new = _one_pass_batched(model, ys, traj, cfg, scheme)
        new = _freeze_lanes(active, new, traj)
        step_delta = _mean_delta(new, traj, (1, 2))
        delta = jnp.where(active, step_delta, delta)
        iters = iters + active.astype(jnp.int32)
        active = active & (step_delta > cfg.tol)
        if return_history:
            hist = lax.dynamic_update_index_in_dim(hist, new.mean, it, 0)
        return new, it + 1, active, iters, delta, hist

    carry0 = (traj0, jnp.asarray(0, jnp.int32), jnp.ones((B,), bool),
              jnp.zeros((B,), jnp.int32),
              jnp.full((B,), jnp.inf, traj0.mean.dtype), hist0)
    traj, it, _, iters, delta, hist = lax.while_loop(cond, body, carry0)
    if return_history:
        done = jnp.arange(M) < it
        hist = jnp.where(done[:, None, None, None], hist, traj.mean[None])
    info = _make_info(model, ys, traj, cfg, scheme, iterations=iters,
                      delta=delta, converged=delta <= cfg.tol,
                      want_cost=return_info)
    return _pack_result(traj, hist, info, return_history, return_info)


@scoped(LOGLIK)
def smoothed_log_likelihood(model: StateSpaceModel, ys: jnp.ndarray,
                            traj: Gaussian,
                            cfg: IteratedConfig = IteratedConfig(),
                            per_step: bool = False) -> jnp.ndarray:
    """Measurement log-likelihood under the smoothed posterior.

    For each step the observation is scored against its posterior
    predictive under the linearized model at ``traj`` (the same
    linearization family the smoother iterated with —
    ``cfg.method``/``cfg.sigma_scheme``):

        y_k ~ N(H_k m_k + d_k,  H_k P_k H_k^T + Rp_k)

    summed over time (``per_step=True`` returns the per-step terms
    instead — serving uses this to mask padded steps before summing).
    Shape-polymorphic: ``ys [n, ny]`` with ``traj [n+1, ...]`` gives a
    scalar; ``ys [B, n, ny]`` with ``traj [B, n+1, ...]`` gives ``[B]``
    (per-trajectory fit scores). This is the "fit score" the scenario
    registry asserts statistical sanity with and the smoother service
    returns per request.
    """
    batched = ys.ndim == 3
    scheme = (get_scheme(cfg.sigma_scheme, model.nx)
              if cfg.method == "slr" else None)
    if cfg.method == "ekf":
        lin = (linearize_model_taylor_batched(model, traj.mean) if batched
               else linearize_model_taylor(model, traj.mean))
    elif cfg.method == "slr":
        lin = (linearize_model_slr_batched(model, traj, scheme, cfg.jitter)
               if batched
               else linearize_model_slr(model, traj, scheme, cfg.jitter))
    else:
        raise ValueError(f"unknown method {cfg.method!r}")
    mean_post = traj.mean[..., 1:, :]
    cov_post = traj.cov[..., 1:, :, :]
    y_mean = bmv(lin.H, mean_post) + lin.d
    y_cov = bmm(bmm(lin.H, cov_post), jnp.swapaxes(lin.H, -1, -2)) + lin.Rp
    lls = mvn_logpdf(ys, y_mean, y_cov)
    return lls if per_step else jnp.sum(lls, axis=-1)


# ---------------------------------------------------------------------------
# Legacy entry points (delegating shims; warn once per process)
# ---------------------------------------------------------------------------

def iterated_smoother_batched(model, ys,
                              cfg: IteratedConfig = IteratedConfig(),
                              init=None, return_history: bool = False,
                              return_info: bool = False):
    """Deprecated: `build_smoother(spec).iterate` dispatches single vs
    batched from ``ys.ndim`` — there is no separate batched driver on
    the public surface any more."""
    from .api import SmootherSpec, build_smoother
    warn_deprecated("iterated_smoother_batched",
                    "build_smoother(SmootherSpec(...)).iterate(model, ys)")
    return build_smoother(SmootherSpec.from_iterated_config(cfg)).iterate(
        model, ys, init=init, return_history=return_history,
        return_info=return_info)


def ieks(model, ys, n_iter: int = 10, parallel_mode: bool = True, **kw):
    """Deprecated alias for the paper's IEKS: Taylor linearization
    through `build_smoother`."""
    from .api import SmootherSpec, build_smoother
    warn_deprecated(
        "ieks", 'build_smoother(SmootherSpec(linearization="taylor", '
        '...)).iterate(model, ys)')
    cfg = IteratedConfig(method="ekf", n_iter=n_iter, parallel=parallel_mode,
                         **kw)
    return build_smoother(SmootherSpec.from_iterated_config(cfg)).iterate(
        model, ys)


def ipls(model, ys, n_iter: int = 10, parallel_mode: bool = True,
         sigma_scheme: str = "cubature", **kw):
    """Deprecated alias for the paper's IPLS: sigma-point SLR
    linearization through `build_smoother`."""
    from .api import SmootherSpec, build_smoother
    warn_deprecated(
        "ipls", 'build_smoother(SmootherSpec(linearization="slr", '
        '...)).iterate(model, ys)')
    cfg = IteratedConfig(method="slr", n_iter=n_iter, parallel=parallel_mode,
                         sigma_scheme=sigma_scheme, **kw)
    return build_smoother(SmootherSpec.from_iterated_config(cfg)).iterate(
        model, ys)
