"""Pallas TPU microkernel: fused pack + mmt4d-GEMV + unpack (decode fast path).

The decode analogue of `fused_pack_mmt4d.py`.  The unfused decode projection
(`encoded_matmul` backend="pallas", Phase.DECODE) pays two activation HBM
round-trips per projection that the weight-streaming GEMV itself never needed:

    ref.pack(x)    : write (M1,K1,M0,K0) + read it back          (2*M*K*s bytes)
    ref.unpack(out): write (M1,N1,M0,N0) f32 + read it back      (2*M*N*4 bytes)

At decode those transfers are the same order as the activation row itself, and
the paper's whole decode story (V-Seek; §Roofline here) is that this regime is
bandwidth-bound — so the pack and unpack move *into* the kernel:

    lhs  : (M, K)   plain 2-D activation rows (M = live decode slots, tiny)
    rhs4 : (N1, K1, N0, K0)  packed weights, streamed HBM->VMEM exactly once
           (or one layer of an (L, N1, K1, N0, K0) stack, read in place)
    out  : (M, N)   plain 2-D, written in (M, BN1*N0) slabs

The grid walks N only (weight streaming); the full activation row block stays
resident in VMEM for the whole kernel, exactly like `mmt4d_gemv.py`.  Packed
weight tiles feed the MXU as stored, so the kernel holds no relayouted copy
of the weight block: the bf16/f32 kernel contracts each K slab of the rows
against all the block's N tiles in one dot (a (bn1*N0, K0) view of the
block's K slab, a merge of leading dims), the quantized kernels one
(N0, K0) tile per dot (`stream_k_tiles`).

`fused_gemv_q8_pallas` is the w8a8 variant: int8 activation rows + int8 packed
weights, s32 accumulation, with the factorized-scale epilogue
(out = acc * s_a[m] * s_w[n]) fused into the same single dispatch — the int8
path previously paid the identical pack/unpack round-trips plus a separate
scale multiply over the (M1,N1,M0,N0) tensor.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# Contract both operands' last dim: x (M, K0) @ w (N0, K0)^T — the MXU's
# native "NT" form, so a packed weight tile feeds the dot as stored.
_NT = (((1,), (1,)), ((), ()))


def stream_k_tiles(lhs_ref, k1, k0, tile, acc_shape, acc_dtype):
    """Accumulate one packed N tile's output over the K tiles:
    sum_c lhs[:, c-th K0 slab] @ tile(c)^T.  `tile(x, c)` returns the
    (x, w) operand pair of K tile c (a dequantizing format converts both
    here).  The loop stays rolled, so the kernel's code size does not grow
    with K, and every operand is a 2-D (rows, 128) slab: no in-kernel 4-D
    relayout, and no VMEM copy of the streamed weight block."""

    def body(c, acc):
        x = lhs_ref[:, pl.ds(pl.multiple_of(c * k0, k0), k0)]
        x, w = tile(x, c)
        return acc + jax.lax.dot_general(
            x, w, _NT, preferred_element_type=acc_dtype
        )

    return jax.lax.fori_loop(0, k1, body, jnp.zeros(acc_shape, acc_dtype))


# K slabs per iteration of the bf16/f32 kernel's rolled K loop.  With two,
# a block of two N tiles already gives the loop body four independent
# weight tiles, one per v5e MXU, so none is latched into two MXUs: on a
# v5e at M = 64 it takes the (1536, 8960) projection at bn1 = 2 from 76 to
# 50 us a call (PERF.md §6).  Mosaic lowers a loop either rolled or whole,
# so the kernel unrolls by hand.
_K_UNROLL = 2


def _fused_gemv_kernel(layer_ref, lhs_ref, rhs_ref, out_ref):
    """One grid step: out[:, j-block] = lhs @ W[j-block]^T.

    Each K slab of the rows meets all bn1 packed N tiles of the block in
    one dot, (M, K0) x (bn1*N0, K0)^T, and its f32 result adds into the
    output block, resident in VMEM for the whole grid step.  The dots of
    consecutive slabs depend on each other only through that add, and
    each gives Mosaic bn1*N0 output lanes to spread over the MXUs.  Every
    output element still sums its K slabs in order, from zero."""
    bn1, k1, n0, k0 = rhs_ref.shape
    out_ref[...] = jnp.zeros(out_ref.shape, out_ref.dtype)

    def slab(c):
        x = lhs_ref[:, pl.ds(pl.multiple_of(c * k0, k0), k0)]
        w = rhs_ref[:, c].reshape(bn1 * n0, k0)
        out_ref[...] += jax.lax.dot_general(
            x, w, _NT, preferred_element_type=jnp.float32
        )

    def body(i, carry):
        for u in range(_K_UNROLL):
            slab(i * _K_UNROLL + u)
        return carry

    jax.lax.fori_loop(0, k1 // _K_UNROLL, body, 0)
    for c in range(k1 - k1 % _K_UNROLL, k1):
        slab(c)


@functools.partial(jax.jit, static_argnames=("bn1", "interpret"))
def fused_gemv_pallas(
    lhs: jnp.ndarray,
    rhs4: jnp.ndarray,
    *,
    bn1: int = 1,
    layer: jnp.ndarray | int | None = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """lhs (M, K) x packed rhs (N1, K1, N0, K0) -> out (M, N1*N0) f32, the
    accumulator itself.

    With `layer`, rhs4 is a stack of packed weights (L, N1, K1, N0, K0) and
    the kernel streams layer `layer`'s blocks from it in place (a scalar
    prefetch picks the block), so a layer scan needs no copy of the layer's
    weight: sliced out first, the copy is a separate pass over the same
    bytes, and XLA may place it in on-chip memory.

    M is the live decode row count (padded by ops.py to a sublane multiple);
    K must equal K1*K0 (ops.py mirrors the packed K padding).  bn1 = packed N
    tiles streamed per grid step; must divide N1.
    """
    if layer is None:
        rhs4, layer = rhs4[None], 0
    m, k = lhs.shape
    _, n1, k1, n0, k0 = rhs4.shape
    assert k == k1 * k0, (lhs.shape, rhs4.shape)
    assert n1 % bn1 == 0, (n1, bn1)

    return pl.pallas_call(
        _fused_gemv_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n1 // bn1,),
            in_specs=[
                # Full activation row block, resident across the whole grid.
                pl.BlockSpec((m, k), lambda j, l: (0, 0)),
                # Weight stream: each packed block of the layer visited once.
                pl.BlockSpec(
                    (pl.Squeezed(), bn1, k1, n0, k0), lambda j, l: (l[0], j, 0, 0, 0)
                ),
            ],
            out_specs=pl.BlockSpec((m, bn1 * n0), lambda j, l: (0, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((m, n1 * n0), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
        name="fused_gemv",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), lhs, rhs4)


def _fused_gemv_q8_kernel(lhs_ref, rhs_ref, sa_ref, sw_ref, out_ref):
    bn1, k1, n0, k0 = rhs_ref.shape
    m = lhs_ref.shape[0]
    sa = sa_ref[...]  # (M, 1) f32
    for b in range(bn1):
        acc = stream_k_tiles(
            lhs_ref, k1, k0, lambda x, c, b=b: (x, rhs_ref[b, c]),
            (m, n0), jnp.int32,
        )
        # Fused factorized-scale epilogue: out = acc * s_a[m] * s_w[n].
        out_ref[:, b * n0:(b + 1) * n0] = (
            acc.astype(jnp.float32) * sa * sw_ref[b]
        ).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bn1", "out_dtype", "interpret"))
def fused_gemv_q8_pallas(
    lhs_q: jnp.ndarray,   # (M, K) int8 activation rows
    rhs4_q: jnp.ndarray,  # (N1, K1, N0, K0) int8 packed weights
    s_a: jnp.ndarray,     # (M, 1) f32 per-row activation scales
    s_w: jnp.ndarray,     # (N1, N0) f32 per-channel weight scales
    *,
    bn1: int = 1,
    out_dtype=jnp.float32,
    interpret: bool = False,
) -> jnp.ndarray:
    """w8a8 fused decode GEMV: out (M, N1*N0) = (lhs_q @ rhs_q^T) * s_a * s_w."""
    m, k = lhs_q.shape
    n1, k1, n0, k0 = rhs4_q.shape
    assert k == k1 * k0, (lhs_q.shape, rhs4_q.shape)
    assert s_a.shape == (m, 1), (s_a.shape, m)
    assert s_w.shape == (n1, n0), (s_w.shape, rhs4_q.shape)
    assert n1 % bn1 == 0, (n1, bn1)
    grid = (n1 // bn1,)

    return pl.pallas_call(
        _fused_gemv_q8_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((m, k), lambda j: (0, 0)),
            pl.BlockSpec((bn1, k1, n0, k0), lambda j: (j, 0, 0, 0)),
            pl.BlockSpec((m, 1), lambda j: (0, 0)),
            # Scales as (N1, 1, N0): a block of bn1 rows of one lane row
            # each, legal for any bn1 (a (bn1, N0) block needs 8 | bn1).
            pl.BlockSpec((bn1, 1, n0), lambda j: (j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((m, bn1 * n0), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((m, n1 * n0), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
        name="fused_gemv_q8",
    )(lhs_q, rhs4_q, s_a, s_w.reshape(n1, 1, n0))
