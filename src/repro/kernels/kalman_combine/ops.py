"""Public jit'd wrappers for the fused Kalman combine kernels.

Dispatch policy (DESIGN.md §2/§12):
  * TPU backend -> compiled Pallas (Mosaic) kernel;
  * GPU backend -> compiled Pallas (Triton) kernel (`triton.py`);
  * CPU / no compiled lowering -> the fused jnp twins. Interpret-mode
    pallas is *never* a dispatch target: it is orders of magnitude
    slower than the fused twins, so ``combine_impl="pallas"`` (the
    platform's lowering, whatever it is) where only interpret mode exists
    falls back to the fused path and warns once per process. A forced
    lowering that the host lacks (``backend="tpu"`` off a TPU) raises:
    the caller asked for that device, and a fused run would hide that it
    is not there.

The kernel-vs-reference choice is **trace-stable**: it is made once per
call site from the *total* element count of the scan (`select_impl`), not
from the per-level batch size. Inside a Blelloch scan the pair count halves
every level, so a per-level policy would flip implementations mid-scan and
retrace the Pallas kernel for every level that crosses the threshold; a
static per-call-site decision keeps one implementation (and one trace) for
the whole scan.

`batched_combine_for` adapts a *scalar* core combine (as passed to
`repro.core.scan.associative_scan`) to its fused batched kernel — this is
the hook `combine_impl="pallas"` uses; the scan driver passes the static
total element count and the resolved kernel backend down.
"""
from __future__ import annotations

import functools
import warnings
from typing import Optional

import jax

from repro.core.parallel import filtering_combine, smoothing_combine

from . import kalman_combine as _k
from . import ref as _ref

_MIN_KERNEL_BATCH = 8

#: Kernel lowerings a caller may force. "interpret" is a debug/test
#: escape hatch (the parity suites use it on CPU); dispatch never picks
#: it on its own.
KERNEL_BACKENDS = ("tpu", "gpu", "interpret")

_warned: set = set()


def _warn_once(key: str, msg: str) -> None:
    if key not in _warned:
        _warned.add(key)
        warnings.warn(msg, RuntimeWarning, stacklevel=3)


def kernel_backend() -> Optional[str]:
    """The platform's *compiled* kernel lowering: "tpu" (Mosaic), "gpu"
    (Triton), or ``None`` where only interpret mode exists (CPU)."""
    plat = jax.default_backend()
    if plat == "tpu":
        return "tpu"
    if plat == "gpu":
        return "gpu"
    return None


def resolve_backend(requested: Optional[str] = None) -> Optional[str]:
    """Resolve a requested kernel backend against the host platform.

    ``None`` (auto) takes the platform lowering; ``None`` comes back on
    hosts with no compiled lowering — the caller must fall back to the
    fused/ref path (the off-accelerator dispatch bugfix: interpret-mode
    pallas is pathologically slower than the fused twins and must never
    be the silent default). An explicit "tpu"/"gpu" that does not match
    the host raises ``RuntimeError``: it names a device, and running the
    fused twin instead would pass off a CPU run as that device's.
    "interpret" is honored as requested (tests opt in deliberately).
    """
    have = kernel_backend()
    if requested is None:
        if have is None:
            _warn_once(
                "pallas-no-lowering",
                'combine_impl="pallas" has no compiled lowering on '
                f'backend "{jax.default_backend()}" — falling back to the '
                "fused jnp combine (interpret-mode pallas would be "
                "orders of magnitude slower). Use combine_impl=\"fused\" "
                "to silence this warning.")
        return have
    if requested == "interpret":
        return "interpret"
    if requested not in KERNEL_BACKENDS:
        raise ValueError(f"unknown kernel backend {requested!r}; "
                         f"available: {sorted(KERNEL_BACKENDS)}")
    if requested != have:
        raise RuntimeError(
            f'backend="{requested}" forces the compiled {requested} '
            f'kernel, but the host platform is "{jax.default_backend()}" '
            f"(devices: {jax.devices()}); no {requested} device is "
            'attached. Use backend="auto" to let the platform decide.')
    return requested


def select_impl(total_elems: Optional[int],
                backend: Optional[str] = None) -> str:
    """Static policy: "kernel", "fused", or "ref" from the call site's
    element count and resolved kernel backend.

    ``total_elems`` is the number of elements entering the scan (B * T for
    a batched scan), a Python int known at trace time — never a per-level
    pair count. ``None`` (unknown) defaults to the kernel path *on hosts
    with a compiled lowering*; off-accelerator the default is the fused
    jnp twin (never interpret mode — the dispatch bugfix this policy
    encodes).
    """
    if backend is None:
        backend = kernel_backend()
    if backend is None:
        return "fused"
    if total_elems is not None and total_elems < _MIN_KERNEL_BATCH:
        return "ref"
    return "kernel"


def _kernel_call(combine_kind: str, ei, ej, backend: str):
    if backend == "gpu":
        from . import triton as _t
        fn = (_t.filtering_combine_batched_triton if combine_kind == "f"
              else _t.smoothing_combine_batched_triton)
        return fn(ei, ej)
    # "tpu" -> compiled Mosaic; "interpret" -> the same kernel in
    # interpret mode (explicit test/debug opt-in only).
    fn = (_k.filtering_combine_batched if combine_kind == "f"
          else _k.smoothing_combine_batched)
    return fn(ei, ej, interpret=backend == "interpret")


def filtering_combine_op(ei, ej, *, impl: str = "auto",
                         backend: Optional[str] = None):
    B = ei.b.shape[0]
    if impl == "auto":
        impl = select_impl(B, backend)
    # B == 0 happens on degenerate scan levels (lax.associative_scan slices
    # can be empty); pallas_call rejects a zero grid, the vmap ref is a
    # no-op there. Static shape, so this never flips within a trace.
    if impl == "ref" or B == 0:
        return _ref.filtering_combine_batched_ref(ei, ej)
    if impl == "fused":
        return _k.filtering_combine_batched_jnp(ei, ej)
    kb = backend if backend is not None else kernel_backend()
    if kb is None:
        return _k.filtering_combine_batched_jnp(ei, ej)
    return _kernel_call("f", ei, ej, kb)


def smoothing_combine_op(ei, ej, *, impl: str = "auto",
                         backend: Optional[str] = None):
    B = ei.g.shape[0]
    if impl == "auto":
        impl = select_impl(B, backend)
    if impl == "ref" or B == 0:
        return _ref.smoothing_combine_batched_ref(ei, ej)
    if impl == "fused":
        return _k.smoothing_combine_batched_jnp(ei, ej)
    kb = backend if backend is not None else kernel_backend()
    if kb is None:
        return _k.smoothing_combine_batched_jnp(ei, ej)
    return _kernel_call("s", ei, ej, kb)


def batched_combine_for(combine, total_elems: Optional[int] = None,
                        backend: Optional[str] = None):
    """Map a core combine fn to its fused batched kernel.

    The returned operator is pinned to one implementation chosen from
    ``total_elems`` and the resolved ``backend`` (see `select_impl`), so
    every level of the enclosing scan dispatches identically. ``backend``
    must already be resolved (`resolve_backend`) — ``None`` here means
    "platform default", which off-accelerator routes every level to the
    fused twin.
    """
    impl = select_impl(total_elems, backend)
    if combine is filtering_combine:
        return functools.partial(filtering_combine_op, impl=impl,
                                 backend=backend)
    if combine is smoothing_combine:
        return functools.partial(smoothing_combine_op, impl=impl,
                                 backend=backend)
    # Unknown combine: fall back to vmap (e.g. user-supplied operators).
    return jax.vmap(combine)


def fused_batched_combine_for(combine):
    """Map a core combine fn to its plain-jnp fused twin (no Pallas, no
    per-matrix LAPACK) — the off-TPU fast path for batched scans.

    Returns ``None`` for unknown combines: fused twins broadcast over
    arbitrary leading axes, which a per-element user combine cannot be
    assumed to do, so the scan driver must fall back to its vmap path
    (with flattening) instead.
    """
    if combine is filtering_combine:
        return _k.filtering_combine_batched_jnp
    if combine is smoothing_combine:
        return _k.smoothing_combine_batched_jnp
    return None
