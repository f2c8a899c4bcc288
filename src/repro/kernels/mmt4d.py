"""Pallas TPU microkernel: linalg.mmt4d, prefill/train (GEMM) variant.

The paper's prefill microkernel holds an M0 x (N0 lanes) accumulator block in
vector registers and streams K.  The TPU adaptation holds a
(BM1*M0) x (BN1*N0) f32 accumulator block in VMEM scratch, feeds the MXU with
(M0, K0) x (N0, K0)^T native 128x128 tiles, and streams BK1 pack-tiles of K per
grid step.  Grid is (M-blocks, N-blocks, K-blocks) with K innermost so the
accumulator revisits are adjacent.

Operands are in mmt4d packed layout (see kernels/ref.py):
    lhs4: (M1, K1, M0, K0)
    rhs4: (N1, K1, N0, K0)   # transposed operand
    out4: (M1, N1, M0, N0)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _mmt4d_kernel(lhs_ref, rhs_ref, out_ref, acc_ref, *, k_steps: int):
    """One grid step: acc[bm1, bn1] += sum_bk lhs[bm1, bk] @ rhs[bn1, bk]^T."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros(acc_ref.shape, acc_ref.dtype)

    bm1, bk1 = lhs_ref.shape[0], lhs_ref.shape[1]
    bn1 = rhs_ref.shape[0]
    # Statically unrolled tile loop: every dot is a native (M0,K0)x(N0,K0)^T
    # MXU contraction — no in-kernel 4-D transposes (Mosaic-friendly).
    for a in range(bm1):
        for b in range(bn1):
            acc = acc_ref[a, b]
            for c in range(bk1):
                acc = acc + jax.lax.dot_general(
                    lhs_ref[a, c],
                    rhs_ref[b, c],
                    dimension_numbers=(((1,), (1,)), ((), ())),
                    preferred_element_type=acc_ref.dtype,
                )
            acc_ref[a, b] = acc

    @pl.when(k == k_steps - 1)
    def _epilogue():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("blocks", "out_dtype", "acc_dtype", "interpret"),
)
def mmt4d_pallas(
    lhs4: jnp.ndarray,
    rhs4: jnp.ndarray,
    *,
    blocks: tuple[int, int, int] = (1, 1, 1),
    out_dtype=jnp.float32,
    acc_dtype=jnp.float32,
    interpret: bool = False,
) -> jnp.ndarray:
    """Packed-layout GEMM. blocks = (BM1, BN1, BK1) pack-tiles per grid step.

    Block factors must divide (M1, N1, K1); `ops.mmt4d` computes legal ones
    from `encoding.select_kernel_blocks`.
    """
    m1, k1, m0, k0 = lhs4.shape
    n1, k1r, n0, k0r = rhs4.shape
    assert (k1, k0) == (k1r, k0r), (lhs4.shape, rhs4.shape)
    bm1, bn1, bk1 = blocks
    assert m1 % bm1 == 0 and n1 % bn1 == 0 and k1 % bk1 == 0, (
        (m1, n1, k1),
        blocks,
    )
    grid = (m1 // bm1, n1 // bn1, k1 // bk1)
    k_steps = grid[2]

    return pl.pallas_call(
        functools.partial(_mmt4d_kernel, k_steps=k_steps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm1, bk1, m0, k0), lambda i, j, k: (i, k, 0, 0)),
            pl.BlockSpec((bn1, bk1, n0, k0), lambda i, j, k: (j, k, 0, 0)),
        ],
        out_specs=pl.BlockSpec((bm1, bn1, m0, n0), lambda i, j, k: (i, j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((m1, n1, m0, n0), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm1, bn1, m0, n0), acc_dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="mmt4d_gemm",
    )(lhs4, rhs4)
