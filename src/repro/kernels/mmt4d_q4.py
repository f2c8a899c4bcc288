"""Pallas TPU microkernels: w4a8 group-quantized mmt4d (prefill) and fused
GEMV (decode) — the paper's Llama.cpp-Q4-class weight format, data-tiled.

The 4-bit path exists for one reason: decode is weight-streaming-bound
(§Roofline), and int4 halves the dominant HBM term again over w8a8.  Weights
are stored in the mmt4d packed layout with two's-complement nibbles packed two
per byte across the halves of N0 (byte [r, k] of a tile holds rows r and
r + N0/2 of column k) plus one scale per `group` (default 16) consecutive K
elements:

    rhs4_p (N1, K1, N0/2, K0) uint8      s_w4 (N1, K1, N0, K0/group) bf16

Packing across N0 keeps every unpack a sublane operation: the two nibble
planes of a tile are stacked along rows, never interleaved along lanes.

Unlike w8a8, the per-K-group scale cannot factor out of the contraction into
the epilogue — each group's partial sum carries its own scale — so both
kernels fuse the dequant *into* the contraction: nibbles unpack and scale to
f32 VMEM-locally (per streamed weight tile, never materialized in HBM) and the
MXU contracts f32.  The group scales widen to one per K lane through a 0/1
expansion matmul, exact for bf16 scales.  Products |a_q * w_q| <= 127*7 are
exact in f32; the activation's per-row scale s_a still factors into the
epilogue.

    fused_gemv_q4_pallas : decode — plain int8 activation rows in, N-streaming
                           grid, plain f32 rows out (pack/unpack-free, the
                           fused_gemv.py contract)
    mmt4d_q4_pallas      : prefill — blocked (M1, N1, K1) grid over packed
                           operands, f32 accumulator scratch
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.fused_gemv import stream_k_tiles


def _dequant_tile(rhs_tile: jnp.ndarray, sw_tile: jnp.ndarray, group: int):
    """(N0/2, K0) packed nibbles + (N0, K0/group) scales -> (N0, K0) f32,
    VMEM-local."""
    bi = rhs_tile.astype(jnp.int32)
    lo = ((bi & 0xF) ^ 8) - 8
    hi = ((bi >> 4) ^ 8) - 8
    w = jnp.concatenate([lo, hi], axis=0).astype(jnp.float32)
    n_groups, k0 = sw_tile.shape[-1], w.shape[-1]
    # expand[g, k] = 1 where K lane k belongs to group g.
    expand = (
        jax.lax.broadcasted_iota(jnp.int32, (n_groups, k0), 1) // group
        == jax.lax.broadcasted_iota(jnp.int32, (n_groups, k0), 0)
    ).astype(jnp.float32)
    s = jnp.dot(
        sw_tile.astype(jnp.float32), expand, preferred_element_type=jnp.float32
    )
    return w * s


def _fused_gemv_q4_kernel(lhs_ref, rhs_ref, sa_ref, sw_ref, out_ref, *, group):
    bn1, k1, _, k0 = rhs_ref.shape
    m, n0 = lhs_ref.shape[0], sw_ref.shape[2]
    sa = sa_ref[...]  # (M, 1) f32
    for b in range(bn1):
        acc = stream_k_tiles(
            lhs_ref, k1, k0,
            lambda x, c, b=b: (
                x.astype(jnp.float32),
                _dequant_tile(rhs_ref[b, c], sw_ref[b, c], group),
            ),
            (m, n0), jnp.float32,
        )
        out_ref[:, b * n0:(b + 1) * n0] = (acc * sa).astype(out_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("bn1", "group", "out_dtype", "interpret")
)
def fused_gemv_q4_pallas(
    lhs_q: jnp.ndarray,   # (M, K) int8 activation rows
    rhs4_p: jnp.ndarray,  # (N1, K1, N0/2, K0) uint8 nibble-packed weights
    s_a: jnp.ndarray,     # (M, 1) f32 per-row activation scales
    s_w4: jnp.ndarray,    # (N1, K1, N0, K0/group) per-group weight scales
    *,
    bn1: int = 1,
    group: int = 32,
    out_dtype=jnp.float32,
    interpret: bool = False,
) -> jnp.ndarray:
    """w4a8 fused decode GEMV: out (M, N1*N0) = (a_q @ deq(w4)^T) * s_a."""
    m, k = lhs_q.shape
    n1, k1, n0h, k0 = rhs4_p.shape
    n0 = 2 * n0h
    assert k == k1 * k0, (lhs_q.shape, rhs4_p.shape)
    assert k0 % group == 0, (k0, group)
    assert s_a.shape == (m, 1), (s_a.shape, m)
    assert s_w4.shape == (n1, k1, n0, k0 // group), (s_w4.shape, rhs4_p.shape)
    assert n1 % bn1 == 0, (n1, bn1)
    grid = (n1 // bn1,)

    return pl.pallas_call(
        functools.partial(_fused_gemv_q4_kernel, group=group),
        grid=grid,
        in_specs=[
            pl.BlockSpec((m, k), lambda j: (0, 0)),
            pl.BlockSpec((bn1, k1, n0h, k0), lambda j: (j, 0, 0, 0)),
            pl.BlockSpec((m, 1), lambda j: (0, 0)),
            pl.BlockSpec((bn1, k1, n0, k0 // group), lambda j: (j, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((m, bn1 * n0), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((m, n1 * n0), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
        name="fused_gemv_q4",
    )(lhs_q, rhs4_p, s_a, s_w4)


def _mmt4d_q4_kernel(
    lhs_ref, rhs_ref, sa_ref, sw_ref, out_ref, acc_ref, *, k_steps, group
):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros(acc_ref.shape, acc_ref.dtype)

    bm1, bk1 = lhs_ref.shape[0], lhs_ref.shape[1]
    bn1 = rhs_ref.shape[0]
    for a in range(bm1):
        for b in range(bn1):
            acc = acc_ref[a, b]
            for c in range(bk1):
                w = _dequant_tile(rhs_ref[b, c], sw_ref[b, c], group)
                acc = acc + jax.lax.dot_general(
                    lhs_ref[a, c].astype(jnp.float32),
                    w,
                    dimension_numbers=(((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
            acc_ref[a, b] = acc

    @pl.when(k == k_steps - 1)
    def _epilogue():
        for a in range(bm1):
            for b in range(bn1):
                # s_a arrives as (M0, 1) columns: a per-row scale.
                out_ref[a, b] = (acc_ref[a, b] * sa_ref[a]).astype(out_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("blocks", "group", "out_dtype", "interpret")
)
def mmt4d_q4_pallas(
    lhs4_q: jnp.ndarray,  # (M1, K1, M0, K0) int8
    rhs4_p: jnp.ndarray,  # (N1, K1, N0/2, K0) uint8
    s_a: jnp.ndarray,     # (M1, M0) f32
    s_w4: jnp.ndarray,    # (N1, K1, N0, K0/group)
    *,
    blocks: tuple[int, int, int] = (1, 1, 1),
    group: int = 32,
    out_dtype=jnp.float32,
    interpret: bool = False,
) -> jnp.ndarray:
    m1, k1, m0, k0 = lhs4_q.shape
    n1, k1r, n0h, k0r = rhs4_p.shape
    n0 = 2 * n0h
    assert (k1, k0) == (k1r, k0r), (lhs4_q.shape, rhs4_p.shape)
    assert k0 % group == 0, (k0, group)
    assert s_w4.shape == (n1, k1, n0, k0 // group), (s_w4.shape, rhs4_p.shape)
    bm1, bn1, bk1 = blocks
    assert m1 % bm1 == 0 and n1 % bn1 == 0 and k1 % bk1 == 0
    grid = (m1 // bm1, n1 // bn1, k1 // bk1)

    return pl.pallas_call(
        functools.partial(_mmt4d_q4_kernel, k_steps=grid[2], group=group),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm1, bk1, m0, k0), lambda i, j, k: (i, k, 0, 0)),
            pl.BlockSpec((bn1, bk1, n0h, k0), lambda i, j, k: (j, k, 0, 0)),
            # Row scales as (M1, M0, 1) columns: legal for any bm1.
            pl.BlockSpec((bm1, m0, 1), lambda i, j, k: (i, 0, 0)),
            pl.BlockSpec(
                (bn1, bk1, n0, k0 // group), lambda i, j, k: (j, k, 0, 0)
            ),
        ],
        out_specs=pl.BlockSpec((bm1, bn1, m0, n0), lambda i, j, k: (i, j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((m1, n1, m0, n0), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm1, bn1, m0, n0), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="mmt4d_q4",
    )(lhs4_q, rhs4_p, s_a.reshape(m1, m0, 1), s_w4)
