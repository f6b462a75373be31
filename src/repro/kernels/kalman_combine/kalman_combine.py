"""Fused batched Kalman combine kernels (paper Eq. 15 and Eq. 19).

Why a kernel: one Blelloch level of the parallel smoother applies the
combine to O(n) element pairs. Expressed in jnp, the filtering combine is
~15 separate batched ops — each reading/writing ``[B, nx, nx]`` arrays from
HBM, so the op is HBM-bound at ~30x the minimum traffic. The fused kernel
reads the two input element tiles into VMEM once, performs all the small
matrix algebra on-core, and writes one output tile: traffic drops to the
roofline minimum (2 reads + 1 write per element).

TPU adaptation (DESIGN.md §3): state dims are tiny (nx <= 16), so an
MXU-shaped matmul would waste >99% of the systolic array. Instead the batch
axis is tiled across VMEM blocks (``TB`` elements per grid step, sized per
nx by `block_rows`) and the nx-side algebra is expressed as
broadcast-multiply-reduce (VPU work), unrolled over the static nx. The
``(I + C_i J_j)^{-1}`` solve becomes an in-register Gauss-Jordan
elimination (no pivoting: the matrix is
``I + PSD @ PSD``, whose spectrum lies right of 1), sharing one inverse
across all four solve sites of Eq. 15.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

# Shared batched-tiny-linalg primitives (also used by the plain-jnp fast
# paths in repro.core): last-axis-reduce matmuls and the no-pivot
# Gauss-Jordan elimination, both Mosaic-compatible.
from repro.core.types import bmm as _bmm, bmv as _bmv, eye32 as _eye32, \
    gauss_jordan_inverse as _gauss_jordan_inverse


def _bt(A: jnp.ndarray) -> jnp.ndarray:
    return jnp.swapaxes(A, -1, -2)


# ---------------------------------------------------------------------------
# Filtering combine (Eq. 15)
# ---------------------------------------------------------------------------

def filtering_combine_math(ai, bi, ci, ei, ji, aj, bj, cj, ej, jj):
    """Eq. 15 on batched arrays ``[..., nx(, nx)]``: the kernel body, also
    usable as a plain-jnp fused combine (no per-matrix LAPACK calls)."""
    # W = (I + C_i J_j)^T = I + J_j C_i ; one inverse serves all solves.
    n = ai.shape[-1]
    W = _eye32(n, ai.dtype) + _bmm(jj, ci)
    Winv = _gauss_jordan_inverse(W)
    # (I + C_i J_j)^{-1} = Winv^T
    X = _bmm(aj, _bt(Winv))                      # A_j (I + C_i J_j)^{-1}

    A = _bmm(X, ai)
    b = _bmv(X, bi + _bmv(ci, ej)) + bj
    Cnew = _bmm(_bmm(X, ci), _bt(aj)) + cj
    C = 0.5 * (Cnew + _bt(Cnew))
    z = _bmv(Winv, ej - _bmv(jj, bi))            # (I + J_j C_i)^{-1} (...)
    eta = _bmv(_bt(ai), z) + ei
    ZJ = _bmm(Winv, _bmm(jj, ai))
    Jnew = _bmm(_bt(ai), ZJ) + ji
    J = 0.5 * (Jnew + _bt(Jnew))
    return A, b, C, eta, J


def _filtering_kernel(Ai, bi, Ci, etai, Ji, Aj, bj, Cj, etaj, Jj,
                      Ao, bo, Co, etao, Jo):
    outs = filtering_combine_math(
        Ai[...], bi[...], Ci[...], etai[...], Ji[...],
        Aj[...], bj[...], Cj[...], etaj[...], Jj[...])
    Ao[...], bo[...], Co[...], etao[...], Jo[...] = outs


def filtering_combine_batched_jnp(ei, ej):
    """Fused batched Eq. 15 combine in plain jnp — the CPU/GPU fast path.

    Same algebra as the Pallas kernel (one shared Gauss-Jordan inverse for
    all four solve sites) over any leading batch shape. This is what the
    batched multi-trajectory scan uses off-TPU: a vmapped textbook combine
    would issue one LAPACK solve per element pair, which dominates at
    B*T-sized levels.
    """
    return type(ei)(*filtering_combine_math(*ei, *ej))


# ---------------------------------------------------------------------------
# Smoothing combine (Eq. 19)
# ---------------------------------------------------------------------------

def smoothing_combine_math(ei, gi, li, ej, gj, lj):
    """Eq. 19 on batched arrays (kernel body / plain-jnp fused combine)."""
    E = _bmm(ei, ej)
    g = _bmv(ei, gj) + gi
    Lnew = _bmm(_bmm(ei, lj), _bt(ei)) + li
    L = 0.5 * (Lnew + _bt(Lnew))
    return E, g, L


def _smoothing_kernel(Ei, gi, Li, Ej, gj, Lj, Eo, go, Lo):
    Eo[...], go[...], Lo[...] = smoothing_combine_math(
        Ei[...], gi[...], Li[...], Ej[...], gj[...], Lj[...])


def smoothing_combine_batched_jnp(ei, ej):
    """Fused batched Eq. 19 combine in plain jnp (see filtering twin)."""
    return type(ei)(*smoothing_combine_math(*ei, *ej))


#: VMEM budget of one grid step, in (8, 128) 32-bit tiles, for the
#: widest intermediate of the kernel bodies: the ``[tb, nx, nx, nx]``
#: broadcast product inside `bmm`. Mosaic pads the minor two dims of every
#: block and intermediate to whole tiles, so one element of that product
#: takes ``nx`` tiles for any nx <= 8, and the VMEM a step needs grows
#: with ``tb * nx``. A fixed ``tb=512`` ran out of VMEM from nx=2
#: (filtering) and nx=5 (smoothing) on a v5e; 64 tiles compiles both
#: kernels at nx in {1, 2, 4, 5, 8}, and keeps each compile near a second
#: (Mosaic unrolls the body over tiles, so compile time grows with it).
_STEP_TILES = 64


def block_rows(nx: int) -> int:
    """Elements per grid step for state dim ``nx``: the largest power of
    two (8 at least, for the sublane tiling of the ``[tb, nx]`` vector
    blocks) whose ``[tb, nx, nx, nx]`` intermediate fits `_STEP_TILES`."""
    tiles_per_elem = nx * -(-nx // 8) * -(-nx // 128)
    tb = 8
    while 2 * tb * tiles_per_elem <= _STEP_TILES:
        tb *= 2
    return tb


def _check_compiled_dtype(x, interpret: bool) -> None:
    if not interpret and x.dtype != jnp.float32:
        raise ValueError(
            f"the compiled combine kernels run in float32 only (got "
            f"{x.dtype}); Mosaic has no {x.dtype} lowering — use the "
            "fused combine (backend=\"jnp\") or float32 inputs")


def _block_specs(num_fields, nx, tb):
    # int32 block indices: a Python 0 would trace as int64 when float64
    # is enabled, which Mosaic cannot lower.
    zero = np.int32(0)
    mat = pl.BlockSpec((tb, nx, nx), lambda i: (i, zero, zero))
    vec = pl.BlockSpec((tb, nx), lambda i: (i, zero))
    # Field layout: alternating (mat, vec, mat, vec, mat) per element.
    layout = {5: [mat, vec, mat, vec, mat], 3: [mat, vec, mat]}
    return layout[num_fields]


@functools.partial(jax.jit, static_argnames=("interpret", "tile"))
def filtering_combine_batched(ei, ej, *, interpret: bool,
                              tile: Optional[int] = None):
    """Fused Eq. 15 combine over batched elements (leading dim B).

    ``interpret`` has no default: the Pallas interpreter is a test path,
    and a caller says so. ``tile`` overrides `block_rows` (tests)."""
    B, nx = ei.b.shape
    _check_compiled_dtype(ei.b, interpret)
    tb = min(tile or block_rows(nx), max(B, 1))
    pad = (-B) % tb
    def padded(x):
        return jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1))
    args = [padded(x) for x in (ei + ej)]
    nblocks = (B + pad) // tb
    spec5 = _block_specs(5, nx, tb)
    out_shapes = [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in args[:5]]
    outs = pl.pallas_call(
        _filtering_kernel,
        grid=(nblocks,),
        in_specs=spec5 + spec5,
        out_specs=spec5,
        out_shape=out_shapes,
        interpret=interpret,
    )(*args)
    return type(ei)(*(o[:B] for o in outs))


@functools.partial(jax.jit, static_argnames=("interpret", "tile"))
def smoothing_combine_batched(ei, ej, *, interpret: bool,
                              tile: Optional[int] = None):
    """Fused Eq. 19 combine over batched elements (leading dim B).

    ``interpret`` has no default: the Pallas interpreter is a test path,
    and a caller says so. ``tile`` overrides `block_rows` (tests)."""
    B, nx = ei.g.shape
    _check_compiled_dtype(ei.g, interpret)
    tb = min(tile or block_rows(nx), max(B, 1))
    pad = (-B) % tb
    def padded(x):
        return jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1))
    args = [padded(x) for x in (ei + ej)]
    nblocks = (B + pad) // tb
    spec3 = _block_specs(3, nx, tb)
    out_shapes = [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in args[:3]]
    outs = pl.pallas_call(
        _smoothing_kernel,
        grid=(nblocks,),
        in_specs=spec3 + spec3,
        out_specs=spec3,
        out_shape=out_shapes,
        interpret=interpret,
    )(*args)
    return type(ei)(*(o[:B] for o in outs))
