"""Measured backend chooser for ``SmootherSpec.backend="auto"``.

The compiled combine kernel wins when one Blelloch level carries enough
element pairs to amortize the launch; below that, XLA's fused jnp twin
wins. The crossover depends on the host (arXiv 2511.10363 measures
exactly this span-vs-work regime on GPUs), so "auto" does not guess: it
*times* both paths for the call site's ``(B, T, nx, dtype)`` once and
caches the winner in a ``spec_id``-keyed in-process table.

Contract (DESIGN.md §12):
  * `decide` is consulted at trace time and therefore NEVER measures —
    it is a pure dict lookup with a safe default ("fused": the chosen
    path can never be slower than the fused twin, because an unmeasured
    site simply *is* the fused twin);
  * `autotune` performs the measurement host-side (build time / server
    warmup — `SmootherServer.warmup` calls it per bucket signature, so
    streaming traffic never pays for it) and populates the cache;
  * on hosts with no compiled lowering (CPU) there is nothing to
    measure: the choice is "fused" without timing anything — interpret
    mode is never a candidate;
  * repeated builds and warmups for the same ``(spec_id, B, T, nx,
    dtype)`` hit the cache and do not re-measure;
  * the measurement runs in the call site's dtype, and the dtype is in
    the key: a float32 verdict never routes a float64 trace to a kernel
    that has only a float32 lowering;
  * a kernel that the compiler refuses at the shape (at nx = 40 Mosaic
    has no lowering for the blocked inverse's dynamic slices) is not a
    candidate: the entry says "fused", keeps the refusal under
    ``kernel_error``, and a warning says so.
"""
from __future__ import annotations

import time
import warnings
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import kalman_combine as _k
from . import ops as _ops

#: Timing repetitions per candidate (one extra warm call precedes them).
_REPS = 3

#: choice -> the combine_impl the scan driver should run.
CHOICE_KERNEL = "pallas"
CHOICE_FUSED = "fused"

Key = Tuple[str, str, int, int, int, str]

_cache: Dict[Key, dict] = {}


def cache_key(spec_id: str, B: int, T: int, nx: int, dtype) -> Key:
    """One entry per (spec identity, launch shape, dtype, host platform).
    The platform rides in the key so a cache serialized across processes
    (not done today — the table is in-process) could never leak a GPU
    verdict onto a CPU host."""
    return (str(spec_id), jax.default_backend(), int(B), int(T), int(nx),
            jnp.dtype(dtype).name)


def lookup(spec_id: str, B: int, T: int, nx: int, dtype) -> Optional[dict]:
    return _cache.get(cache_key(spec_id, B, T, nx, dtype))


def decide(spec_id: str, B: Optional[int], T: Optional[int],
           nx: Optional[int], dtype=None) -> str:
    """Trace-time choice for ``backend="auto"``: the cached measured
    winner, else the fused twin. Pure lookup — never measures, so it is
    safe to call while tracing and is trace-stable for a given cache
    state (warmup populates the cache *before* the executable traces)."""
    if B is None or T is None or nx is None or dtype is None:
        return CHOICE_FUSED
    entry = lookup(spec_id, B, T, nx, dtype)
    if entry is None:
        return CHOICE_FUSED
    return entry["choice"]


def clear_cache() -> None:
    _cache.clear()


def cache_entries() -> Dict[str, dict]:
    """Readable snapshot (serving surfaces this in service stats):
    ``"spec_id@platform/B=../T=../nx=../dtype" -> {choice, kernel_us,
    fused_us}``."""
    return {
        f"{sid}@{plat}/B={B}/T={T}/nx={nx}/{dt}": dict(entry)
        for (sid, plat, B, T, nx, dt), entry in sorted(_cache.items())
    }


def _time_op(fn, ei, ej) -> float:
    out = fn(ei, ej)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(_REPS):
        jax.block_until_ready(fn(ei, ej))
    return (time.perf_counter() - t0) / _REPS * 1e6


def _level_elements(n_pairs: int, nx: int, dtype):
    """A representative top-Blelloch-level operand: ``n_pairs`` random
    filtering element pairs (well-conditioned PSD C/J)."""
    from repro.core.types import FilteringElement

    rng = np.random.default_rng(0)
    def psd():
        a = rng.standard_normal((n_pairs, nx, nx))
        return jnp.asarray(a @ np.swapaxes(a, -1, -2) / nx
                           + 0.1 * np.eye(nx), dtype)
    e = FilteringElement(
        A=jnp.asarray(rng.standard_normal((n_pairs, nx, nx))
                      / np.sqrt(nx), dtype),
        b=jnp.asarray(rng.standard_normal((n_pairs, nx)), dtype),
        C=psd(),
        eta=jnp.asarray(rng.standard_normal((n_pairs, nx)), dtype),
        J=psd())
    return e


def autotune(spec_id: str, B: int, T: int, nx: int, dtype) -> dict:
    """Measure kernel vs fused-jnp for one launch shape in ``dtype`` (the
    dtype the call site runs) and cache the winner. Idempotent per key;
    returns the cache entry.

    The probe is the filtering combine at the scan's *top level*
    (``B * T / 2`` pairs — the widest, most kernel-favorable level; if
    the kernel loses there it loses everywhere, and lower levels only
    shrink, so picking by the top level can flip a win to "fused" on a
    borderline site but never selects a slower-than-fused path).
    """
    key = cache_key(spec_id, B, T, nx, dtype)
    if key in _cache:
        return _cache[key]
    backend = _ops.kernel_backend()
    if backend is None:
        entry = {"choice": CHOICE_FUSED, "backend": "none",
                 "kernel_us": None, "fused_us": None}
        _cache[key] = entry
        return entry
    n_pairs = max((int(B) * int(T)) // 2, 1)
    ei = _level_elements(n_pairs, nx, dtype)
    ej = _level_elements(n_pairs, nx, dtype)
    kernel_op = _ops.batched_combine_for(
        # the real dispatch target at this element count
        __import__("repro.core.parallel", fromlist=["filtering_combine"])
        .filtering_combine, total_elems=int(B) * int(T), backend=backend)
    fused = jax.jit(_k.filtering_combine_batched_jnp)
    try:
        kernel = jax.jit(kernel_op).lower(ei, ej).compile()
    except Exception as e:  # noqa: BLE001 — whatever the compiler refuses
        # Not a candidate at this shape (Mosaic has no lowering for an
        # op of the kernel body, or the tile does not fit VMEM): the
        # fused twin runs, and the refusal is kept and said.
        error = f"{type(e).__name__}: {str(e).splitlines()[0][:300]}"
        warnings.warn(
            f"the {backend} combine kernel does not compile at B={B}, "
            f"T={T}, nx={nx}, {jnp.dtype(dtype).name}; the fused combine "
            f"runs there ({error})", RuntimeWarning, stacklevel=2)
        entry = {"choice": CHOICE_FUSED, "backend": backend,
                 "kernel_us": None, "fused_us": _time_op(fused, ei, ej),
                 "kernel_error": error}
        _cache[key] = entry
        return entry
    kernel_us = _time_op(kernel, ei, ej)
    fused_us = _time_op(fused, ei, ej)
    choice = CHOICE_KERNEL if kernel_us < fused_us else CHOICE_FUSED
    entry = {"choice": choice, "backend": backend,
             "kernel_us": kernel_us, "fused_us": fused_us}
    _cache[key] = entry
    return entry
