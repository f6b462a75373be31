"""Kernel micro-benchmarks: each Pallas kernel against its reference.

On a TPU the kernels run compiled (Mosaic). Anywhere else they run in
the Pallas interpreter, and the rows say so ("interpret"): such a number
tracks the kernel's code path, it is not a kernel time."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np


def _t(fn, *args, reps=3):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / reps * 1e6


def run(emit=print):
    rows = []
    rng = np.random.default_rng(0)
    interpret = jax.default_backend() != "tpu"
    mode = "interpret" if interpret else "mosaic"

    # kalman_combine: one Blelloch level over B element pairs.
    from repro.core.types import FilteringElement
    from repro.kernels.kalman_combine.kalman_combine import \
        filtering_combine_batched
    from repro.kernels.kalman_combine.ref import \
        filtering_combine_batched_ref
    B, nx = 4096, 5
    psd = lambda: jnp.asarray(
        (lambda a: a @ np.swapaxes(a, -1, -2) / nx + 0.1 * np.eye(nx))(
            rng.standard_normal((B, nx, nx))), jnp.float32)
    fe = FilteringElement(
        A=jnp.asarray(rng.standard_normal((B, nx, nx)), jnp.float32),
        b=jnp.asarray(rng.standard_normal((B, nx)), jnp.float32),
        C=psd(), eta=jnp.asarray(rng.standard_normal((B, nx)), jnp.float32),
        J=psd())
    us = _t(lambda a, b: filtering_combine_batched(a, b, interpret=interpret),
            fe, fe)
    rows.append((f"kernel/kalman_combine/B={B},nx={nx}", us, mode))
    us_ref = _t(jax.jit(filtering_combine_batched_ref), fe, fe)
    rows.append((f"kernel/kalman_combine_ref/B={B},nx={nx}", us_ref, "jnp"))

    # ssm_scan
    from repro.kernels.ssm_scan.ssm_scan import ssm_scan_batched
    from repro.kernels.ssm_scan.ref import ssm_scan_ref
    a = jnp.asarray(rng.uniform(0.5, 1.0, (4, 2048, 256)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((4, 2048, 256)), jnp.float32)
    us = _t(lambda x, y: ssm_scan_batched(x, y, interpret=interpret),
            a, b)
    rows.append(("kernel/ssm_scan/B=4,T=2048,D=256", us, mode))
    us_ref = _t(jax.jit(ssm_scan_ref), a, b)
    rows.append(("kernel/ssm_scan_ref/B=4,T=2048,D=256", us_ref,
                 "lax.scan"))

    # flash_attention
    from repro.kernels.flash_attention.flash_attention import \
        flash_attention_batched
    from repro.kernels.flash_attention.ref import attention_ref
    q = jnp.asarray(rng.standard_normal((1, 4, 512, 64)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 2, 512, 64)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 2, 512, 64)), jnp.float32)
    us = _t(lambda *x: flash_attention_batched(*x, interpret=interpret),
            q, k, v)
    rows.append(("kernel/flash_attention/T=512", us, mode))
    us_ref = _t(jax.jit(attention_ref), q, k, v)
    rows.append(("kernel/flash_attention_ref/T=512", us_ref, "naive"))

    for name, us, derived in rows:
        emit(f"{name},{us:.1f},{derived}")
    return rows


if __name__ == "__main__":
    run()
