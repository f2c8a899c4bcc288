"""BEYOND-PAPER Pallas kernel: fused pack + mmt4d + unpack.

IREE materializes `tensor.pack(lhs)` and `tensor.unpack(out)` as separate ops,
paying two extra HBM round-trips per matmul (packed-lhs write+read, packed-out
write+read).  Weights are packed once so their round-trip amortizes to zero —
but activations don't.  On TPU the HBM->VMEM copy machinery can read *strided
slabs* of the 2-D activation directly, so the pack of the LHS and the unpack of
the output can live entirely inside the matmul kernel:

    lhs  : (M, K)   plain 2-D          (read in (BM, BK) slabs)
    rhs4 : (N1, K1, N0, K0)  packed    (weights: packed once at load)
    out  : (M, N)   plain 2-D          (written in (BM, BN) slabs)

Saved HBM traffic per matmul ≈ 2*M*K*s + 2*M*N*4 bytes — measured in
EXPERIMENTS.md §Perf.  The in-kernel relayout of the rhs tile
((BK1, N0, K0) -> (BK1*K0, N0)) happens in VMEM/registers.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _fused_kernel(lhs_ref, rhs_ref, out_ref, acc_ref, *, k_steps: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros(acc_ref.shape, acc_ref.dtype)

    bn1, bk1, n0, k0 = rhs_ref.shape
    lhs = lhs_ref[...]  # (BM, BK1*K0)
    # Implicit "pack": the MXU contraction consumes the 2-D slab directly.
    # rhs tile relayout (VMEM-local): (BN1, BK1, N0, K0) -> (BK1*K0, BN1*N0).
    rhs = rhs_ref[...].transpose(1, 3, 0, 2).reshape(bk1 * k0, bn1 * n0)
    acc_ref[...] += jax.lax.dot_general(
        lhs,
        rhs,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=acc_ref.dtype,
    )

    @pl.when(k == k_steps - 1)
    def _epilogue():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("blocks", "out_dtype", "acc_dtype", "interpret"),
)
def fused_pack_mmt4d_pallas(
    lhs: jnp.ndarray,
    rhs4: jnp.ndarray,
    *,
    blocks: tuple[int, int, int] = (1, 1, 1),
    out_dtype=jnp.float32,
    acc_dtype=jnp.float32,
    interpret: bool = False,
) -> jnp.ndarray:
    """lhs (M, K) x packed rhs (N1, K1, N0, K0) -> out (M, N1*N0).

    blocks = (BM1, BN1, BK1) in units of (M0=rhs K0-matched rows, N0, K0) tiles;
    BM rows per step = BM1 * 128 (MXU-aligned slab).  M and K must be
    tile-aligned (ops.py pads).
    """
    m, k = lhs.shape
    n1, k1, n0, k0 = rhs4.shape
    assert k == k1 * k0, (lhs.shape, rhs4.shape)
    bm1, bn1, bk1 = blocks
    bm = bm1 * 128
    assert m % bm == 0 and n1 % bn1 == 0 and k1 % bk1 == 0, (
        (m, n1, k1),
        blocks,
    )
    grid = (m // bm, n1 // bn1, k1 // bk1)

    return pl.pallas_call(
        functools.partial(_fused_kernel, k_steps=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk1 * k0), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bn1, bk1, n0, k0), lambda i, j, kk: (j, kk, 0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn1 * n0), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n1 * n0), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn1 * n0), acc_dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="fused_pack_mmt4d",
    )(lhs, rhs4)
