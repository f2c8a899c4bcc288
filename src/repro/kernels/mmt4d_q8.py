"""Pallas TPU microkernel: int8 mmt4d (weights-and-activations quantized).

Beyond-paper serving extension: the paper ships f16xf16->f32 microkernels and
motivates custom kernels via mixed precision; TPU v5e's MXU runs int8 at 2x
bf16 throughput and int8 weights halve the decode weight-streaming bound (the
§Roofline decode bottleneck).  Factorized symmetric quantization keeps the
matmul exact w.r.t. the quantized operands:

    out[m, n] = s_a[m] * s_w[n] * sum_k a_q[m,k] * w_q[n,k]      (s32 accum)

  * weights: per-output-channel scale (s_w), packed once (serving format)
  * activations: per-row dynamic scale (s_a), quantized on the fly
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _mmt4d_q8_kernel(lhs_ref, rhs_ref, sa_ref, sw_ref, out_ref, acc_ref, *, k_steps):
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
                acc = acc + jax.lax.dot_general(
                    lhs_ref[a, c],
                    rhs_ref[b, c],
                    dimension_numbers=(((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.int32,
                )
            acc_ref[a, b] = acc

    @pl.when(k == k_steps - 1)
    def _epilogue():
        # Per tile: acc (M0, N0) * s_a column (M0, 1) * s_w row (1, N0).
        for a in range(bm1):
            for b in range(bn1):
                out_ref[a, b] = (
                    acc_ref[a, b].astype(jnp.float32) * sa_ref[a] * sw_ref[b]
                ).astype(out_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("blocks", "out_dtype", "interpret")
)
def mmt4d_q8_pallas(
    lhs4_q: jnp.ndarray,   # (M1, K1, M0, K0) int8
    rhs4_q: jnp.ndarray,   # (N1, K1, N0, K0) int8
    s_a: jnp.ndarray,      # (M1, M0) f32 per-row scales
    s_w: jnp.ndarray,      # (N1, N0) f32 per-channel scales
    *,
    blocks: tuple[int, int, int] = (1, 1, 1),
    out_dtype=jnp.float32,
    interpret: bool = False,
) -> jnp.ndarray:
    m1, k1, m0, k0 = lhs4_q.shape
    n1, k1r, n0, k0r = rhs4_q.shape
    assert (k1, k0) == (k1r, k0r)
    bm1, bn1, bk1 = blocks
    assert m1 % bm1 == 0 and n1 % bn1 == 0 and k1 % bk1 == 0
    grid = (m1 // bm1, n1 // bn1, k1 // bk1)

    return pl.pallas_call(
        functools.partial(_mmt4d_q8_kernel, k_steps=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm1, bk1, m0, k0), lambda i, j, k: (i, k, 0, 0)),
            pl.BlockSpec((bn1, bk1, n0, k0), lambda i, j, k: (j, k, 0, 0)),
            # Scales as (M1, M0, 1) columns and (N1, 1, N0) rows: blocks
            # of these are legal for any bm1/bn1 (a (bn1, N0) block of a
            # 2-D array would need 8 | bn1).
            pl.BlockSpec((bm1, m0, 1), lambda i, j, k: (i, 0, 0)),
            pl.BlockSpec((bn1, 1, n0), lambda i, j, k: (j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((bm1, bn1, m0, n0), lambda i, j, k: (i, j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((m1, n1, m0, n0), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm1, bn1, m0, n0), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="mmt4d_q8",
    )(lhs4_q, rhs4_q, s_a.reshape(m1, m0, 1), s_w.reshape(n1, 1, n0))
