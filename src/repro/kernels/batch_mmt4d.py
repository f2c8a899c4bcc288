"""Pallas TPU microkernel: linalg.batch_mmt4d.

IREE's encoding pipeline also lowers *batched* contractions (attention
score/context matmuls at short sequence lengths) to `linalg.batch_mmt4d`
microkernels; the paper implemented only the unbatched mmt4d for RISC-V.
This is the TPU batch variant for layout-parity with IREE's op set:

    lhs: (B, M1, K1, M0, K0)   rhs: (B, N1, K1, N0, K0)
    out: (B, M1, N1, M0, N0)   f32 accumulation

The model's long-context attention path intentionally does NOT use it — the
flash-chunked attention (models/layers.py) has strictly better memory
behaviour at 32k+; batch_mmt4d covers the short-S regime and completes the
microkernel library.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _batch_mmt4d_kernel(lhs_ref, rhs_ref, out_ref, acc_ref, *, k_steps: int):
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros(acc_ref.shape, acc_ref.dtype)

    bm1, bk1 = lhs_ref.shape[1], lhs_ref.shape[2]
    bn1 = rhs_ref.shape[1]
    for a in range(bm1):
        for b in range(bn1):
            acc = acc_ref[0, a, b]
            for c in range(bk1):
                acc = acc + jax.lax.dot_general(
                    lhs_ref[0, a, c],
                    rhs_ref[0, b, c],
                    dimension_numbers=(((1,), (1,)), ((), ())),
                    preferred_element_type=acc_ref.dtype,
                )
            acc_ref[0, a, b] = acc

    @pl.when(k == k_steps - 1)
    def _epilogue():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("blocks", "out_dtype", "acc_dtype", "interpret")
)
def batch_mmt4d_pallas(
    lhs5: jnp.ndarray,
    rhs5: jnp.ndarray,
    *,
    blocks: tuple[int, int, int] = (1, 1, 1),
    out_dtype=jnp.float32,
    acc_dtype=jnp.float32,
    interpret: bool = False,
) -> jnp.ndarray:
    bsz, m1, k1, m0, k0 = lhs5.shape
    bsz2, n1, k1r, n0, k0r = rhs5.shape
    assert bsz == bsz2 and (k1, k0) == (k1r, k0r), (lhs5.shape, rhs5.shape)
    bm1, bn1, bk1 = blocks
    assert m1 % bm1 == 0 and n1 % bn1 == 0 and k1 % bk1 == 0
    grid = (bsz, m1 // bm1, n1 // bn1, k1 // bk1)

    return pl.pallas_call(
        functools.partial(_batch_mmt4d_kernel, k_steps=grid[3]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bm1, bk1, m0, k0), lambda b, i, j, k: (b, i, k, 0, 0)),
            pl.BlockSpec((1, bn1, bk1, n0, k0), lambda b, i, j, k: (b, j, k, 0, 0)),
        ],
        out_specs=pl.BlockSpec(
            (1, bm1, bn1, m0, n0), lambda b, i, j, k: (b, i, j, 0, 0)
        ),
        out_shape=jax.ShapeDtypeStruct((bsz, m1, n1, m0, n0), out_dtype),
        scratch_shapes=[pltpu.VMEM((1, bm1, bn1, m0, n0), acc_dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="batch_mmt4d",
    )(lhs5, rhs5)


def batch_mmt4d_ref(lhs5: jnp.ndarray, rhs5: jnp.ndarray, acc_dtype=jnp.float32):
    return jnp.einsum(
        "zmkac,znkbc->zmnab", lhs5, rhs5, preferred_element_type=acc_dtype
    )
