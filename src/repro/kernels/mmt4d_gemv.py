"""Pallas TPU microkernel: linalg.mmt4d, decode (GEMV-class) variant.

The paper ships a *separate* decode microkernel (M0=1, N0=VLEN/4): decode is a
weight-streaming, bandwidth-bound GEMV.  TPU analogue: the packed activation
row-block (all of K for the <=sublane-group of live batch rows) stays resident
in VMEM for the whole kernel; the grid walks N only, so every packed weight
byte moves HBM->VMEM exactly once and there is no K-revisit of the accumulator
(single-shot dot per grid step — no scratch, no grid-minor accumulation).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu


def _mmt4d_gemv_kernel(lhs_ref, rhs_ref, out_ref):
    """One grid step: out[0, b] = sum_k1 lhs[0, k1] @ rhs[b, k1]^T (full K)."""
    k1 = lhs_ref.shape[1]
    bn1 = rhs_ref.shape[0]
    for b in range(bn1):
        acc = jnp.zeros(out_ref.shape[2:], out_ref.dtype)
        for c in range(k1):
            acc = acc + jax.lax.dot_general(
                lhs_ref[0, c],
                rhs_ref[b, c],
                dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=out_ref.dtype,
            )
        out_ref[0, b] = acc


@functools.partial(
    jax.jit,
    static_argnames=("bn1", "out_dtype", "interpret"),
)
def mmt4d_gemv_pallas(
    lhs4: jnp.ndarray,
    rhs4: jnp.ndarray,
    *,
    bn1: int = 1,
    out_dtype=jnp.float32,
    interpret: bool = False,
) -> jnp.ndarray:
    """Packed-layout GEMV. lhs4 must have M1 == 1 (decode row block).

    bn1 = packed N tiles per grid step; must divide N1.
    """
    m1, k1, m0, k0 = lhs4.shape
    n1, k1r, n0, k0r = rhs4.shape
    assert m1 == 1, f"decode kernel expects a single packed row block, got M1={m1}"
    assert (k1, k0) == (k1r, k0r), (lhs4.shape, rhs4.shape)
    assert n1 % bn1 == 0, (n1, bn1)
    grid = (n1 // bn1,)

    return pl.pallas_call(
        _mmt4d_gemv_kernel,
        grid=grid,
        in_specs=[
            # Full K row block, resident across the whole grid.
            pl.BlockSpec((1, k1, m0, k0), lambda j: (0, 0, 0, 0)),
            # Weight stream: each block visited exactly once.
            pl.BlockSpec((bn1, k1, n0, k0), lambda j: (j, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bn1, m0, n0), lambda j: (0, j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, n1, m0, n0), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
        name="mmt4d_gemv",
    )(lhs4, rhs4)
