"""Fused decode GEMV (kernels/fused_gemv.py): parity vs the mmt4d oracle
across ragged M/N/K (padding edges), bf16/f32 and int8, plus the ops.py
routing contract (decode -> fused GEMV, prefill -> fused GEMM slab path)."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.encoding import Phase
from repro.kernels import fused_gemv, ops, ref


def _rand(shape, dtype, seed=0):
    x = np.random.RandomState(seed).randn(*shape)
    return jnp.asarray(x, dtype)


# Odd M/N/K on purpose: every tile-padding edge (rows, lanes, K) is exercised.
MNK_SWEEP = [
    (1, 256, 128),       # aligned single row (the pure GEMV shape)
    (1, 130, 70),        # ragged N and K
    (3, 100, 300),       # ragged everything, M < sublane group
    (5, 384, 200),       # ragged K only
    (8, 640, 256),       # multi-row decode (8 live slots)
    (17, 129, 257),      # all dims one past a tile boundary
]


@pytest.mark.parametrize("mnk", MNK_SWEEP)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_gemv_matches_mmt4d_oracle(mnk, dtype):
    m, n, k = mnk
    x = _rand((m, k), dtype, seed=m + n)
    w_t = _rand((n, k), dtype, seed=k)
    rhs4 = ops.pack_rhs(w_t)
    # Oracle: the full unfused rewrite (pack -> ref.mmt4d -> unpack).
    n1, k1, n0, k0 = rhs4.shape
    lhs4 = ref.pack(jnp.pad(x, ((0, 0), (0, k1 * k0 - k))), (8, k0))
    want = ref.unpack(ref.mmt4d(lhs4, rhs4), (8 * lhs4.shape[0], n1 * n0))[:m, :n]
    got = ops.encoded_matmul(
        x, rhs4, n=n, phase=Phase.DECODE, backend="fused",
        out_dtype=jnp.float32, interpret=True,
    )
    assert got.shape == (m, n)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=tol,
        atol=tol * max(1.0, float(jnp.abs(want).max())),
    )


@pytest.mark.parametrize("mnk", [(1, 256, 128), (4, 132, 70), (9, 700, 310)])
def test_fused_gemv_q8_matches_packed_q8(mnk):
    """int8 path: fused epilogue (in-kernel s_a*s_w) == packed q8 kernel path."""
    m, n, k = mnk
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(m, k), jnp.float32)
    w_t = jnp.asarray(rng.randn(n, k), jnp.float32)
    rhs4_q, s_w = ops.pack_rhs_q8(w_t)
    want = ops.encoded_matmul_q8(
        x, rhs4_q, s_w, n=n, phase=Phase.DECODE, backend="xla",
        out_dtype=jnp.float32,
    )
    got = ops.encoded_matmul_q8(
        x, rhs4_q, s_w, n=n, phase=Phase.DECODE, backend="fused",
        out_dtype=jnp.float32, interpret=True,
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


def test_fused_gemv_kernel_direct_bn1_sweep():
    """Direct kernel call: bn1 streaming widths agree.

    Not bitwise: the slab width changes how XLA's interpreter fuses and
    orders the f32 accumulation across K, which moves the last bits.  The
    tolerance is a few f32 ulps of the K = 256 sums of unit-scale products
    (|out| ~ 16): far below any indexing or tiling error, which would be
    O(1)."""
    m, n, k = 8, 1024, 256
    x = _rand((m, k), jnp.float32)
    rhs4 = ops.pack_rhs(_rand((n, k), jnp.float32, seed=7))
    n1 = rhs4.shape[0]
    outs = [
        fused_gemv.fused_gemv_pallas(x, rhs4, bn1=b, interpret=True)
        for b in (1, 2, 4, 8)
        if n1 % b == 0
    ]
    for o in outs[1:]:
        np.testing.assert_allclose(
            np.asarray(o), np.asarray(outs[0]), rtol=1e-6, atol=1e-4
        )


def test_fused_backend_prefill_still_uses_gemm_slab():
    """The fused backend keeps serving prefill GEMMs (row-slab path): big-M
    fused calls agree with the reference too."""
    m, n, k = 200, 136, 264
    x = _rand((m, k), jnp.float32, seed=2)
    w_t = _rand((n, k), jnp.float32, seed=3)
    rhs4 = ops.pack_rhs(w_t)
    want = ref.matmul_reference(x, w_t)
    got = ops.encoded_matmul(
        x, rhs4, n=n, phase=Phase.PREFILL, backend="fused",
        out_dtype=jnp.float32, interpret=True,
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-4)
