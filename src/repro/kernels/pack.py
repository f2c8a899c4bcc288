"""Pallas TPU microkernels: tensor.pack / tensor.unpack.

IREE lowers tensor.pack/unpack to generic microkernels; on TPU these are pure
relayout (memory-bound) kernels.  Each grid step copies a slab of whole tiles
through VMEM, doing the 2-D -> 4-D (or inverse) relayout on-chip, so HBM sees
only contiguous reads and contiguous writes.

Both kernels require tile-aligned 2-D operands; `ops.pack` pads with XLA first
(pad is fused into the producer by XLA, so the kernel never sees ragged edges).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu


def _pack_kernel(x_ref, out_ref):
    br1, bc1, t0, t1 = out_ref.shape
    x = x_ref[...]  # (br1*t0, bc1*t1)
    out_ref[...] = x.reshape(br1, t0, bc1, t1).transpose(0, 2, 1, 3)


def _unpack_kernel(x_ref, out_ref):
    br1, bc1, t0, t1 = x_ref.shape
    x = x_ref[...]
    out_ref[...] = x.transpose(0, 2, 1, 3).reshape(br1 * t0, bc1 * t1)


@functools.partial(jax.jit, static_argnames=("tile", "blocks", "interpret"))
def pack_pallas(
    x: jnp.ndarray,
    *,
    tile: tuple[int, int],
    blocks: tuple[int, int] = (1, 1),
    interpret: bool = False,
) -> jnp.ndarray:
    """(R, C) -> (R1, C1, T0, T1). R, C must be multiples of the tile."""
    t0, t1 = tile
    r, c = x.shape
    assert r % t0 == 0 and c % t1 == 0, (x.shape, tile)
    r1, c1 = r // t0, c // t1
    br1, bc1 = blocks
    assert r1 % br1 == 0 and c1 % bc1 == 0, ((r1, c1), blocks)
    grid = (r1 // br1, c1 // bc1)
    return pl.pallas_call(
        _pack_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((br1 * t0, bc1 * t1), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((br1, bc1, t0, t1), lambda i, j: (i, j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((r1, c1, t0, t1), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
        interpret=interpret,
        name="tensor_pack",
    )(x)


@functools.partial(jax.jit, static_argnames=("blocks", "interpret"))
def unpack_pallas(
    x4: jnp.ndarray,
    *,
    blocks: tuple[int, int] = (1, 1),
    interpret: bool = False,
) -> jnp.ndarray:
    """(R1, C1, T0, T1) -> (R1*T0, C1*T1). Crop (if any) is done by the caller."""
    r1, c1, t0, t1 = x4.shape
    br1, bc1 = blocks
    assert r1 % br1 == 0 and c1 % bc1 == 0, (x4.shape, blocks)
    grid = (r1 // br1, c1 // bc1)
    return pl.pallas_call(
        _unpack_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((br1, bc1, t0, t1), lambda i, j: (i, j, 0, 0))],
        out_specs=pl.BlockSpec((br1 * t0, bc1 * t1), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((r1 * t0, c1 * t1), x4.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
        interpret=interpret,
        name="tensor_unpack",
    )(x4)
