"""Triton (GPU) lowering of the fused batched Kalman combines.

Same kernel bodies as the Mosaic TPU path (`kalman_combine.py`): one
Blelloch level reads the two input element tiles once, runs the whole
Eq. 15 / Eq. 19 algebra — including the shared no-pivot Gauss-Jordan
inverse — on registers/SMEM, and writes one output tile, so HBM traffic
stays at the roofline minimum (2 reads + 1 write per element) instead of
the ~15 separate batched jnp ops XLA materializes.

GPU adaptation vs the TPU variant (DESIGN.md §3): the batch axis is
tiled across *programs* (one CTA per ``TB``-element block) rather than
VMEM blocks, and the tile is sized for register pressure, not VMEM
capacity — the unrolled nx-side algebra holds ~10 live ``[TB, nx, nx]``
intermediates, so the default ``TB`` is one small constant here, where
the TPU kernel sizes its tile per nx (`kalman_combine.block_rows`).
``num_warps=4`` matches one 128-lane block per tile row; the nx loops are fully unrolled at trace time exactly as on TPU (state
dims are tiny, nx <= 16).

Off-GPU these wrappers run in interpret mode — that is a *test* path
(the parity suite runs it on CPU in CI), never a dispatch target:
`ops.resolve_backend` routes CPU callers to the fused jnp twins instead.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import triton as plgpu

from . import ref as _ref
from .kalman_combine import (_block_specs, _filtering_kernel,
                             _smoothing_kernel)

#: Default per-program batch tile. The filtering combine keeps ~10 live
#: [TB, nx, nx] f32 intermediates; at nx=8, TB=128 that is ~320 KB of
#: tile-resident data per CTA — beyond this register spills dominate.
_TILE = 128


def _compiler_params(num_warps: int, num_stages: int):
    return plgpu.CompilerParams(num_warps=num_warps, num_stages=num_stages)


def _combine_call(kernel, num_fields, ei, ej, B, nx, tile, interpret,
                  num_warps, num_stages):
    tb = min(tile, max(B, 1))
    pad = (-B) % tb
    def padded(x):
        return jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1))
    args = [padded(x) for x in (ei + ej)]
    nblocks = (B + pad) // tb
    spec = _block_specs(num_fields, nx, tb)
    out_shapes = [jax.ShapeDtypeStruct(a.shape, a.dtype)
                  for a in args[:num_fields]]
    outs = pl.pallas_call(
        kernel,
        grid=(nblocks,),
        in_specs=spec + spec,
        out_specs=spec,
        out_shape=out_shapes,
        compiler_params=_compiler_params(num_warps, num_stages),
        interpret=interpret,
    )(*args)
    return type(ei)(*(o[:B] for o in outs))


@functools.partial(jax.jit, static_argnames=("tile", "interpret",
                                             "num_warps", "num_stages"))
def filtering_combine_batched_triton(ei, ej, *, tile: int = _TILE,
                                     interpret: bool = False,
                                     num_warps: int = 4,
                                     num_stages: int = 2):
    """Fused Eq. 15 combine over batched elements — Triton lowering."""
    B, nx = ei.b.shape
    if B == 0:
        # Degenerate scan level: a zero grid is rejected by pallas_call,
        # the vmapped reference is a shape-correct no-op.
        return _ref.filtering_combine_batched_ref(ei, ej)
    return _combine_call(_filtering_kernel, 5, ei, ej, B, nx, tile,
                         interpret, num_warps, num_stages)


@functools.partial(jax.jit, static_argnames=("tile", "interpret",
                                             "num_warps", "num_stages"))
def smoothing_combine_batched_triton(ei, ej, *, tile: int = _TILE,
                                     interpret: bool = False,
                                     num_warps: int = 4,
                                     num_stages: int = 2):
    """Fused Eq. 19 combine over batched elements — Triton lowering."""
    B, nx = ei.g.shape
    if B == 0:
        return _ref.smoothing_combine_batched_ref(ei, ej)
    return _combine_call(_smoothing_kernel, 3, ei, ej, B, nx, tile,
                         interpret, num_warps, num_stages)
