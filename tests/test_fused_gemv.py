"""Fused decode GEMV (kernels/fused_gemv.py): parity vs the mmt4d oracle
across ragged M/N/K (padding edges), bf16/f32 and int8, plus the ops.py
routing contract (decode -> fused GEMV, prefill -> fused GEMM slab path)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.core.encoding import Phase
from repro.core.packed import EncodingConfig
from repro.kernels import fused_gemv, ops, ref
from repro.models import transformer as T


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


@pytest.mark.parametrize("k1", [1, 3])
@pytest.mark.parametrize("bn1", [1, 2, 7])
@pytest.mark.parametrize("m", [1, 8, 64])
def test_fused_gemv_wide_dot_schedule_matches_mmt4d_oracle(m, bn1, k1):
    """The bf16 kernel called directly at each streaming width: every K
    slab of the rows against all bn1 N tiles of the block in one dot,
    summed into the resident output block.  N1 = 14 tiles, so bn1 = 7
    streams two blocks; k1 = 3 runs the K loop and its unrolled tail."""
    n1, k0 = 14, 128
    n, k = n1 * 128, k1 * k0
    x = _rand((m, k), jnp.bfloat16, seed=m + bn1)
    rhs4 = ops.pack_rhs(_rand((n, k), jnp.bfloat16, seed=k1))
    want = ref.unpack(ref.mmt4d(ref.pack(x, (8, k0)), rhs4), (max(m, 8), n))[:m]
    got = fused_gemv.fused_gemv_pallas(x, rhs4, bn1=bn1, interpret=True)
    assert got.shape == (m, n) and got.dtype == jnp.float32
    tol = 3e-2
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=tol,
        atol=tol * max(1.0, float(jnp.abs(want).max())),
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


@pytest.mark.parametrize("backend,phase,m", [
    ("fused", Phase.DECODE, 8),     # the GEMV streams the layer in place
    ("fused", Phase.PREFILL, 200),  # the GEMM slab takes a slice
    ("xla", Phase.DECODE, 8),
])
def test_encoded_matmul_reads_one_layer_of_a_stack(backend, phase, m):
    """A layer scan hands `encoded_matmul` the stacked packed weights and a
    traced layer index: every path computes with that layer's weight, the
    fused GEMV bitwise as from the weight alone."""
    n, k, layers = 384, 256, 3
    x = _rand((m, k), jnp.float32, seed=5)
    stack = jnp.stack([ops.pack_rhs(_rand((n, k), jnp.float32, seed=s)) for s in range(layers)])
    mm = jax.jit(lambda x, w, l: ops.encoded_matmul(
        x, w, n=n, phase=phase, backend=backend, out_dtype=jnp.float32,
        interpret=True, layer=l,
    ))
    for layer in range(layers):
        got = mm(x, stack, jnp.int32(layer))
        want = mm(x, stack[layer], None)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_decode_in_place_weights_match_xla_decode():
    """The serving layer scan with the fused decode GEMV reading each
    layer's packed weights from the stack agrees with the XLA path, which
    slices them: prefill, then decode steps on one cache each."""
    cfg = registry.get_reduced("qwen2-1.5b")
    encs = [EncodingConfig(enabled=True, backend=b, interpret=True) for b in ("xla", "fused")]
    params = T.model_init(jax.random.PRNGKey(0), cfg, encs[0])
    b, s, sp = 2, 8, 4
    toks = jax.random.randint(jax.random.PRNGKey(1), (b, s), 1, cfg.vocab_size)
    logits = []
    for enc in encs:
        caches = T.cache_init(cfg, b, max_seq=s)
        _, caches, _ = T.forward(
            params, {"tokens": toks[:, :sp]}, cfg=cfg, enc=enc,
            phase=Phase.PREFILL, caches=caches,
        )
        steps = []
        for i in range(sp, s):
            out, caches, _ = T.forward(
                params, {"tokens": toks[:, i : i + 1]}, cfg=cfg, enc=enc,
                phase=Phase.DECODE, caches=caches, pos=i,
            )
            steps.append(out)
        logits.append(jnp.concatenate(steps, axis=1))
    np.testing.assert_allclose(
        np.asarray(logits[1]), np.asarray(logits[0]), rtol=1e-4, atol=1e-4
    )
