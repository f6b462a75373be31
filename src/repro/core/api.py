"""Unified estimator API: one declarative `SmootherSpec` + `build_smoother`.

The paper's method family is ONE algorithm varied along a few orthogonal
axes — sequential vs parallel-in-time span, covariance vs square-root
form, Taylor (IEKS) vs sigma-point SLR (IPLS) linearization — but the
repo historically exposed every axis combination as its own entry point
(``parallel_filter_smoother_batched``, ``sqrt_parallel_smoother``,
``iterated_smoother_batched``, ...), and the serving/scenario layers
re-encoded the axes ad hoc (``IteratedConfig.cache_key``/``model_id``
strings, bucket signatures). This module is the single declarative
surface all layers key off (DESIGN.md §Public API):

  * :class:`SmootherSpec` — a frozen dataclass capturing every axis in
    one place, validated eagerly (bad values fail at construction, not
    deep inside a traced scan), with a stable content-hash
    :attr:`SmootherSpec.spec_id` that subsumes the legacy
    ``cache_key``/``model_id`` identities;
  * :func:`build_smoother` — ``spec -> Smoother``, a callable object
    with ``.filter/.smooth/.iterate/.log_likelihood`` that dispatches to
    the existing kernels and handles single vs batched inputs uniformly
    by inspecting leading dims (no ``*_batched`` twins in user code).

Quickstart::

    from repro.core import SmootherSpec, build_smoother
    spec = SmootherSpec(linearization="slr", sigma_scheme="cubature",
                        n_iter=10, tol=1e-6)
    smoother = build_smoother(spec)
    traj = smoother.iterate(model, ys)          # ys [n, ny] or [B, n, ny]
    ll = smoother.log_likelihood(model, ys, traj)

The legacy entry points survive as delegating shims that warn once per
process (`repro.core._deprecation`). ``python -m repro.core.api
--dump-surface`` prints the public `repro.core` surface for the CI
snapshot check (``tests/api_surface.txt``).
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import hashlib
import sys
from typing import Optional

import jax

from . import cost as _cost
from . import iterated as _iterated
from . import parallel as _parallel
from . import sequential as _sequential
from . import sqrt_parallel as _sqrt
from .iterated import (BACKENDS, COMBINE_IMPLS, DAMPINGS, FORMS,
                       IteratedConfig, validate_iteration_knobs)
from .sigma_points import SCHEMES

MODES = ("parallel", "sequential")
LINEARIZATIONS = ("taylor", "slr")

_SPEC_ID_VERSION = "v1"


def _check_choice(field: str, value: str, allowed) -> None:
    if value not in allowed:
        raise ValueError(f"unknown {field} {value!r}; "
                         f"available: {sorted(allowed)}")


@dataclasses.dataclass(frozen=True)
class SmootherSpec:
    """Every axis of the smoother family, in one frozen declarative spec.

    Axes (DESIGN.md §Public API):
      * ``mode``          — "parallel" (O(log n) span scans, the paper's
                            contribution) | "sequential" (O(n) baseline);
      * ``form``          — "standard" (covariance) | "sqrt"
                            (Cholesky-factor combines; float32-robust;
                            parallel mode only);
      * ``linearization`` — "taylor" (IEKS) | "slr" (sigma-point IPLS);
      * ``sigma_scheme``  — sigma-point rule for SLR;
      * iteration control — ``n_iter`` (Gauss-Newton pass cap), ``tol``
                            (early-stop mean-delta; 0 = fixed passes),
                            ``lm_lambda`` (Levenberg-Marquardt damping);
      * ``combine_impl``  — scan combine kernel ("auto" picks the fused
                            twin for batched runs);
      * ``jitter``        — SLR covariance jitter;
      * ``model_id``      — scenario content hash (registry tenants);
      * ``backend``       — compiled-kernel dispatch: "auto" (measured
                            kernel-vs-fused autotuner, cached per
                            ``spec_id``; see :meth:`Smoother.autotune`),
                            "jnp" (fused twins only, never a kernel),
                            "tpu" / "gpu" (force that Pallas lowering;
                            raises off-platform).

    Validation happens at construction: bad axis names or nonsensical
    iteration knobs raise ``ValueError`` immediately instead of failing
    deep inside a traced scan.
    """

    mode: str = "parallel"
    form: str = "standard"
    linearization: str = "taylor"
    sigma_scheme: str = "cubature"
    n_iter: int = 10
    tol: float = 0.0
    lm_lambda: float = 0.0
    combine_impl: str = "auto"
    jitter: float = 0.0
    model_id: str = ""
    backend: str = "auto"
    damping: str = "fixed"

    def __post_init__(self):
        _check_choice("mode", self.mode, MODES)
        _check_choice("form", self.form, FORMS)
        _check_choice("linearization", self.linearization, LINEARIZATIONS)
        _check_choice("sigma_scheme", self.sigma_scheme, tuple(SCHEMES))
        _check_choice("combine_impl", self.combine_impl, COMBINE_IMPLS)
        _check_choice("backend", self.backend, BACKENDS)
        _check_choice("damping", self.damping, DAMPINGS)
        if self.combine_impl == "pallas" and self.backend == "jnp":
            raise ValueError(
                'combine_impl="pallas" contradicts backend="jnp" '
                "(a compiled kernel with kernels disabled) — drop one")
        if self.form == "sqrt" and self.mode == "sequential":
            raise ValueError(
                'form="sqrt" requires mode="parallel": no sequential '
                "square-root pass is implemented (DESIGN.md §9)")
        validate_iteration_knobs(self.n_iter, self.tol, self.lm_lambda,
                                 self.jitter)
        # The hash is immutable (frozen dataclass) and the serving path
        # derives a bucket key from it per request — compute it once.
        object.__setattr__(self, "_spec_id", self._compute_spec_id())

    @property
    def method(self) -> str:
        """Legacy linearization name ("ekf" | "slr") — the bucket
        signature's method slot and `IteratedConfig.method`."""
        return "ekf" if self.linearization == "taylor" else "slr"

    @property
    def spec_id(self) -> str:
        """Stable content hash of the full spec (cached at construction).

        Subsumes the legacy ``cache_key``/``model_id`` identities: two
        specs share a ``spec_id`` iff every field matches, so jit caches
        and autobatch bucket signatures keyed by it can never collide
        across semantically different configurations, and the hash is
        reproducible across processes (no object identity, no dict
        order). Every field is hashed — including ``combine_impl`` and
        ``backend`` on paths that do not consume them — matching the
        legacy ``cache_key`` (which hashed the whole config):
        conservative over-keying can cost a duplicate compile, silent
        under-keying would reuse a wrong executable. The
        ``<scenario>/`` prefix keeps serving logs readable.
        """
        return self._spec_id

    def _compute_spec_id(self) -> str:
        # ``damping`` joined the spec after v1 ids were already baked
        # into caches and bench baselines: the default ("fixed", the
        # exact pre-existing behavior) is excluded from the payload so
        # every previously-constructible spec keeps its id, while any
        # non-default damping re-keys (pinned in tests/core/test_api.py).
        payload = ";".join(
            f"{f.name}={getattr(self, f.name)!r}"
            for f in dataclasses.fields(self)
            if not (f.name == "damping" and self.damping == "fixed"))
        digest = hashlib.sha1(
            f"{_SPEC_ID_VERSION};{payload}".encode()).hexdigest()[:12]
        prefix = self.model_id.split(":")[0] if self.model_id else "anon"
        return f"{prefix}/{digest}"

    @classmethod
    def from_iterated_config(cls, cfg: IteratedConfig,
                             **overrides) -> "SmootherSpec":
        """Lift a legacy `IteratedConfig` onto the spec axes (the bridge
        the deprecated shims and the serving layer use)."""
        kw = dict(
            mode="parallel" if cfg.parallel else "sequential",
            form=cfg.form,
            linearization="taylor" if cfg.method == "ekf" else "slr",
            sigma_scheme=cfg.sigma_scheme,
            n_iter=cfg.n_iter, tol=cfg.tol, lm_lambda=cfg.lm_lambda,
            combine_impl=cfg.combine_impl, jitter=cfg.jitter,
            model_id=cfg.model_id, damping=cfg.damping,
            backend=cfg.backend)
        kw.update(overrides)
        return cls(**kw)

    def iterated_config(self) -> IteratedConfig:
        """The execution `IteratedConfig` for this spec.

        ``model_id`` is set to :attr:`spec_id` — so the legacy
        ``IteratedConfig.cache_key`` tuples and the autobatch bucket
        signature both carry the *full* spec identity through the one
        string slot the serving stack already routes on.
        """
        return IteratedConfig(
            method=self.method, n_iter=self.n_iter,
            parallel=self.mode == "parallel",
            sigma_scheme=self.sigma_scheme, lm_lambda=self.lm_lambda,
            combine_impl=self.combine_impl, jitter=self.jitter,
            tol=self.tol, model_id=self.spec_id, form=self.form,
            damping=self.damping, backend=self.backend)


def _full_precision(method):
    """Trace ``method`` with float32 matmuls at full precision. On the
    TPU a float32 matmul at the default precision rounds its operands to
    bfloat16, which the covariance updates (``P - K S K^T``) cannot
    afford; elsewhere the setting changes nothing."""
    @functools.wraps(method)
    def traced(*args, **kwargs):
        with jax.default_matmul_precision("highest"):
            return method(*args, **kwargs)
    return traced


class Smoother:
    """Configured estimator built by :func:`build_smoother`.

    Methods dispatch on the spec axes to the underlying kernels in
    ``core/{sequential,parallel,sqrt_parallel,iterated}.py`` and accept
    single-trajectory or batched inputs uniformly: ``ys [n, ny]`` runs
    the single-trajectory path, ``ys [B, n, ny]`` the fused batched
    path. Instances are stateless and cheap; calling the object is
    :meth:`iterate`.
    """

    __slots__ = ("spec", "config")

    def __init__(self, spec: SmootherSpec):
        self.spec = spec
        #: Execution `IteratedConfig`; its ``model_id`` is ``spec_id``
        #: (see `SmootherSpec.iterated_config`).
        self.config = spec.iterated_config()

    @property
    def spec_id(self) -> str:
        return self.spec.spec_id

    def __repr__(self) -> str:
        return f"Smoother({self.spec!r})"

    @staticmethod
    def _launch_shape(ys, m0):
        """Static ``(B, T, nx, dtype)`` of a batched call site (None for
        single trajectories) — the ``backend="auto"`` autotune-cache
        key."""
        if ys.ndim != 3:
            return None
        return (int(ys.shape[0]), int(ys.shape[1]), int(m0.shape[-1]),
                ys.dtype)

    # -- backend autotuning -------------------------------------------------

    def autotune(self, B: int, n: int, nx: int, dtype) -> dict:
        """Measure compiled-kernel vs fused-jnp combine for ``(B, n, nx)``
        launches in ``dtype`` (the dtype the launches run in; the
        measurement is made in it and it keys the verdict) and cache the
        winner under this smoother's ``spec_id``.

        Host-side and idempotent per shape: `build_smoother` (via
        ``autotune_for``) and server warmup call this once per bucket
        signature; subsequent builds/warmups hit the in-process cache.
        After it runs, ``backend="auto"`` call sites of this shape
        dispatch to the measured winner — never a path slower than the
        fused twin (on hosts with no compiled lowering nothing is
        measured and the choice is always "fused"). Returns the cache
        entry ``{choice, backend, kernel_us, fused_us}``.
        """
        from repro.kernels.kalman_combine import autotune as _at
        return _at.autotune(self.spec_id, B, n, nx, dtype)

    # -- one linearized pass ------------------------------------------------

    @_full_precision
    def filter(self, lin, ys, m0, P0):
        """One filtering pass over an already-linearized SSM.

        ``ys [n, ny]`` -> filtered ``[n, ...]``; ``ys [B, n, ny]`` (with
        ``lin`` leaves carrying the matching batch axis) -> ``[B, n, ...]``.
        """
        batched = ys.ndim == 3
        if self.spec.mode == "sequential":
            fn = (_sequential.kalman_filter_batched if batched
                  else _sequential.kalman_filter)
            return fn(lin, ys, m0, P0)
        if self.spec.form == "sqrt":
            fn = (_sqrt.sqrt_parallel_filter_batched if batched
                  else _sqrt.sqrt_parallel_filter)
            return fn(lin, ys, m0, P0)
        fn = (_parallel.parallel_filter_batched if batched
              else _parallel.parallel_filter)
        return fn(lin, ys, m0, P0,
                  combine_impl=self.config.resolved_combine_impl(
                      batched, shape=self._launch_shape(ys, m0)))

    @_full_precision
    def smooth(self, lin, ys, m0, P0):
        """One filtering + smoothing pass over a linearized SSM.

        Returns ``(filtered, smoothed)``; smoothed has leading ``n + 1``
        (``[B, n + 1, ...]`` batched).
        """
        batched = ys.ndim == 3
        if self.spec.mode == "sequential":
            fn = (_sequential._filter_smoother_batched if batched
                  else _sequential.filter_smoother)
            return fn(lin, ys, m0, P0)
        if self.spec.form == "sqrt":
            fn = (_sqrt._sqrt_parallel_filter_smoother_batched if batched
                  else _sqrt.sqrt_parallel_filter_smoother)
            return fn(lin, ys, m0, P0)
        fn = (_parallel._parallel_filter_smoother_batched if batched
              else _parallel.parallel_filter_smoother)
        return fn(lin, ys, m0, P0,
                  combine_impl=self.config.resolved_combine_impl(
                      batched, shape=self._launch_shape(ys, m0)))

    # -- the full iterated smoother ----------------------------------------

    @_full_precision
    def iterate(self, model, ys, init=None, return_history: bool = False,
                return_info: bool = False):
        """Run the iterated smoother (IEKS/IPLS per the spec) on a
        nonlinear model: up to ``n_iter`` linearize->filter->smooth
        passes (early-stopped under ``tol``). ``ys [n, ny]`` returns
        ``[n + 1, ...]`` marginals; ``ys [B, n, ny]`` the fused batched
        driver's ``[B, n + 1, ...]``."""
        fn = (_iterated._iterated_smoother_batched if ys.ndim == 3
              else _iterated.iterated_smoother)
        return fn(model, ys, self.config, init=init,
                  return_history=return_history, return_info=return_info)

    __call__ = iterate

    @_full_precision
    def log_likelihood(self, model, ys, traj, per_step: bool = False):
        """Measurement log-likelihood of ``ys`` under the smoothed
        posterior ``traj`` (the spec's linearization family); scalar for
        single trajectories, ``[B]`` batched, per-step terms with
        ``per_step=True``."""
        return _iterated.smoothed_log_likelihood(
            model, ys, traj, self.config, per_step=per_step)

    @_full_precision
    def cost(self, model, ys, traj):
        """Gauss-Newton smoothing cost of ``traj`` under the spec's
        linearization family (`core.cost.gn_cost`) — the objective
        :meth:`iterate` descends and the adaptive-damping driver
        monitors; scalar for single trajectories, ``[B]`` batched."""
        return _cost.gn_cost(model, ys, traj, method=self.spec.method,
                             scheme=self.spec.sigma_scheme,
                             jitter=self.spec.jitter)


def build_smoother(spec: Optional[SmootherSpec] = None, *,
                   autotune_for: Optional[tuple] = None,
                   **axes) -> Smoother:
    """Build the configured estimator for ``spec``.

    Field overrides may be passed directly instead of a spec
    (``build_smoother(linearization="slr", n_iter=5)``).

    ``autotune_for=(B, n, nx, dtype)`` runs :meth:`Smoother.autotune` for
    that launch shape before returning, so ``backend="auto"`` call sites of
    the shape dispatch to the measured winner from the first trace.
    Cached per ``(spec_id, shape)`` — repeated builds don't re-measure.
    """
    if spec is None:
        spec = SmootherSpec(**axes)
    elif axes:
        spec = dataclasses.replace(spec, **axes)
    smoother = Smoother(spec)
    if autotune_for is not None:
        smoother.autotune(*autotune_for)
    return smoother


# ---------------------------------------------------------------------------
# Public-API surface dump (CI snapshot: tests/api_surface.txt)
# ---------------------------------------------------------------------------

def _describe(name: str, obj) -> list:
    """One deterministic line per exported name (methods get their own
    lines) — the text the surface snapshot diffs."""
    import inspect

    if dataclasses.is_dataclass(obj) and isinstance(obj, type):
        fields = ", ".join(
            (f.name if f.default is dataclasses.MISSING
             else f"{f.name}={f.default!r}")
            for f in dataclasses.fields(obj))
        return [f"{name} = dataclass({fields})"]
    if isinstance(obj, type) and issubclass(obj, tuple) \
            and hasattr(obj, "_fields"):
        return [f"{name} = namedtuple({', '.join(obj._fields)})"]
    if isinstance(obj, type):
        lines = [f"{name} = class"]
        for m in sorted(vars(obj)):
            if m.startswith("_") and m != "__call__":
                continue
            member = inspect.getattr_static(obj, m)
            if isinstance(member, property):
                lines.append(f"{name}.{m} = property")
            elif callable(member):
                lines.append(f"{name}.{m}{inspect.signature(member)}")
        return lines
    if callable(obj):
        return [f"{name}{inspect.signature(obj)}"]
    return [f"{name} = constant"]


def dump_surface() -> str:
    """The public `repro.core` surface as stable text, one line per name
    (dataclass fields + defaults, function signatures, class methods).
    CI diffs this against the committed ``tests/api_surface.txt`` so the
    surface cannot grow or break silently."""
    import repro.core as core

    lines = [f"# repro.core public API surface ({len(core.__all__)} names)"]
    for name in sorted(core.__all__):
        lines.extend(_describe(name, getattr(core, name)))
    return "\n".join(lines) + "\n"


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        description="repro.core public-API tooling")
    p.add_argument("--dump-surface", action="store_true",
                   help="print the API surface snapshot text")
    args = p.parse_args(argv)
    if args.dump_surface:
        sys.stdout.write(dump_surface())
        return 0
    p.error("nothing to do (pass --dump-surface)")
    return 2


if __name__ == "__main__":
    sys.exit(main())
