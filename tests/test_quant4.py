"""w4a8 group-quantized serving path (kernels/mmt4d_q4.py): quantizer
properties, nibble pack/unpack, kernel-vs-oracle parity, and the model-level
decision-preservation harness (margin-aware, the Table-1 bar at 4-bit)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry as cfg_registry
from repro.core.encoding import Phase
from repro.core.packed import EncodingConfig
from repro.kernels import ops, ref
from repro.models import transformer as T


def test_quantize_rows_q4_grouped_bounds_and_shapes():
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(24, 100) * 5, jnp.float32)
    q, s = ref.quantize_rows_q4_grouped(x, group=16)
    assert q.dtype == jnp.int8 and q.shape == x.shape
    assert s.shape == (24, 7)  # ceil(100/16)
    assert int(jnp.max(q)) <= 7 and int(jnp.min(q)) >= -7
    # Half-step reconstruction bound holds on the clip-free (absmax) path;
    # the default MSE clip search deliberately trades outlier error for
    # in-range resolution, so it is exempt from this bound.
    q1, s1 = ref.quantize_rows_q4_grouped(x, group=16, ratios=(1.0,))
    sg = np.repeat(np.asarray(s1), 16, axis=1)[:, :100]
    err = np.abs(np.asarray(q1, np.float32) * sg - np.asarray(x))
    assert float(err.max()) <= float(sg.max()) / 2 + 1e-5
    # And the MSE-clip default never does worse than absmax in MSE.
    sgd = np.repeat(np.asarray(s), 16, axis=1)[:, :100]
    mse_clip = np.square(np.asarray(q, np.float32) * sgd - np.asarray(x)).mean()
    mse_abs = np.square(np.asarray(q1, np.float32) * sg - np.asarray(x)).mean()
    assert mse_clip <= mse_abs + 1e-9, (mse_clip, mse_abs)


def test_group_scales_beat_per_row_scales():
    """The point of grouping: one outlier costs its group, not the row."""
    rng = np.random.RandomState(1)
    x = np.asarray(rng.randn(16, 256), np.float32)
    x[:, 0] *= 50.0  # per-row outlier column
    xj = jnp.asarray(x)
    q_g, s_g = ref.quantize_rows_q4_grouped(xj, group=16)
    sg = np.repeat(np.asarray(s_g), 16, axis=1)
    err_g = np.square(np.asarray(q_g, np.float32) * sg - x).mean()
    # Per-row int4 baseline: one scale across all 256 columns.
    q_r, s_r = ref.quantize_rows_q4_grouped(xj, group=256)
    sr = np.repeat(np.asarray(s_r), 256, axis=1)
    err_r = np.square(np.asarray(q_r, np.float32) * sr - x).mean()
    assert err_g < err_r / 10, (err_g, err_r)


def test_pack_unpack_nibbles_roundtrip():
    rng = np.random.RandomState(2)
    q = jnp.asarray(rng.randint(-8, 8, (3, 5, 64)), jnp.int8)
    packed = ref.pack_nibbles(q)
    assert packed.dtype == jnp.uint8 and packed.shape == (3, 5, 32)
    back = ref.unpack_nibbles(packed)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(q, np.int32))


# Ragged M/N/K on purpose: rows, lanes, K and group-boundary padding edges.
MNK_SWEEP = [
    (1, 256, 128),
    (1, 130, 70),
    (4, 132, 200),
    (9, 700, 310),
    (130, 140, 150),
]


@pytest.mark.parametrize("mnk", MNK_SWEEP)
@pytest.mark.parametrize("group", [16, 32])
def test_q4_kernels_match_oracle(mnk, group):
    """fused GEMV and packed mmt4d Pallas kernels == the xla oracle, for the
    default group and the llama.cpp-Q4_0-style g=32."""
    m, n, k = mnk
    rng = np.random.RandomState(m + n)
    x = jnp.asarray(rng.randn(m, k), jnp.float32)
    w_t = jnp.asarray(rng.randn(n, k), jnp.float32)
    rhs4_p, s_w4 = ops.pack_rhs_q4(w_t, group=group)
    want = ops.encoded_matmul_q4(
        x, rhs4_p, s_w4, n=n, phase=Phase.DECODE, group=group,
        backend="xla", out_dtype=jnp.float32,
    )
    got_f = ops.encoded_matmul_q4(
        x, rhs4_p, s_w4, n=n, phase=Phase.DECODE, group=group,
        backend="fused", out_dtype=jnp.float32, interpret=True,
    )
    got_p = ops.encoded_matmul_q4(
        x, rhs4_p, s_w4, n=n, phase=Phase.PREFILL, group=group,
        backend="pallas", out_dtype=jnp.float32, interpret=True,
    )
    np.testing.assert_allclose(
        np.asarray(got_f), np.asarray(want), rtol=1e-5, atol=1e-4
    )
    np.testing.assert_allclose(
        np.asarray(got_p), np.asarray(want), rtol=1e-5, atol=1e-4
    )


def test_q4_close_to_full_precision():
    m, n, k = 16, 512, 384
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(m, k), jnp.float32)
    w_t = jnp.asarray(rng.randn(n, k), jnp.float32)
    exact = ref.matmul_reference(x, w_t)
    rhs4_p, s_w4 = ops.pack_rhs_q4(w_t)
    q4 = ops.encoded_matmul_q4(
        x, rhs4_p, s_w4, n=n, phase=Phase.PREFILL, backend="xla",
        out_dtype=jnp.float32,
    )
    rel = float(jnp.linalg.norm(q4 - exact) / jnp.linalg.norm(exact))
    assert rel < 0.12, rel  # int4 grouped: ~4x the w8a8 bound, still tight


def test_model_level_w4a8_decision_preservation():
    """The decision-preservation harness at 4 bits (margin-aware).

    Bitwise argmax equality at EVERY position is not a 4-bit property — a
    random-init reduced model has near-tied top-2 logits at some positions
    where any rounding flips the pick.  The claims that hold, asserted here:
      * logits stay close: relative MSE < 0.05 (measured 0.036 at the g=16
        serving default; g=32 doubles it — docs/DESIGN.md),
      * token-identical to the fp reference at every DECIDED position:
        fp top-2 margin above two standard deviations of the int4 noise on
        a logit difference (2 * sqrt(2) * rms logit error).  Below that the
        two candidates are a near-tie that weight rounding may order either
        way, so a flip there is not a defect,
      * bounded regret at flip positions: the w4a8 pick's fp logit is within
        the fp max-margin of the optimum (never a materially worse token),
      * END-TO-END decode continuity: greedy decode through the serving
        cache path emits exactly the tokens the same w4a8 model picks with
        full-context prefill."""
    cfg = cfg_registry.get_reduced("llama3.2-1b")
    enc_fp = EncodingConfig(enabled=True, backend="xla")
    enc_q4 = EncodingConfig(enabled=True, backend="xla", weight_quant="int4")
    p_fp = T.model_init(jax.random.PRNGKey(0), cfg, enc_fp)
    p_q4 = T.model_init(jax.random.PRNGKey(0), cfg, enc_q4)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 1, cfg.vocab_size)
    l_fp, _, _ = T.forward(
        p_fp, {"tokens": toks}, cfg=cfg, enc=enc_fp, phase=Phase.PREFILL
    )
    l_q4, _, _ = T.forward(
        p_q4, {"tokens": toks}, cfg=cfg, enc=enc_q4, phase=Phase.PREFILL
    )
    rel_mse = float(
        jnp.sum(jnp.square(l_q4 - l_fp)) / jnp.sum(jnp.square(l_fp))
    )
    assert rel_mse < 0.05, rel_mse

    am_fp = jnp.argmax(l_fp, -1)
    am_q4 = jnp.argmax(l_q4, -1)
    top2 = jax.lax.top_k(l_fp, 2)[0]
    margin = top2[..., 0] - top2[..., 1]
    rms_err = jnp.sqrt(jnp.mean(jnp.square(l_q4 - l_fp)))
    decided = margin > 2.0 * jnp.sqrt(2.0) * rms_err
    assert int(jnp.sum(decided)) >= 4, (int(jnp.sum(decided)), float(rms_err))
    agree = jnp.sum((am_fp == am_q4) & decided) / jnp.sum(decided)
    assert float(agree) == 1.0, float(agree)
    # Bounded regret everywhere (in fp logit units).
    l_of_q4 = jnp.take_along_axis(l_fp, am_q4[..., None], axis=-1)[..., 0]
    l_of_fp = jnp.take_along_axis(l_fp, am_fp[..., None], axis=-1)[..., 0]
    regret = float(jnp.max(l_of_fp - l_of_q4))
    assert regret <= float(jnp.max(margin)), (regret, float(jnp.max(margin)))

    # End-to-end w4a8 serving continuity: prefill 8 tokens into the cache,
    # greedy-decode 4 more; each decoded argmax must equal the w4a8 model's
    # own full-context prefill argmax at that position.
    sp, b, s = 8, *toks.shape
    caches = T.cache_init(cfg, b, max_seq=s)
    _, caches, _ = T.forward(
        p_q4, {"tokens": toks[:, :sp]}, cfg=cfg, enc=enc_q4,
        phase=Phase.PREFILL, caches=caches,
    )
    for i in range(sp, s):
        l_d, caches, _ = T.forward(
            p_q4, {"tokens": toks[:, i : i + 1]}, cfg=cfg, enc=enc_q4,
            phase=Phase.DECODE, caches=caches, pos=i,
        )
        assert bool(
            (jnp.argmax(l_d[:, 0], -1) == jnp.argmax(l_q4[:, i], -1)).all()
        ), i


def test_w4a8_weight_stream_wins_vs_w8a8():
    """The acceptance bar as a unit test: at the serving default the w4a8
    decode weight stream is >= 1.5x smaller than w8a8 (bytes model)."""
    from repro.core import encoding

    n, k = 2048, 1024
    b8 = encoding.quant_weight_stream_bytes(n, k, quant="w8a8")
    b4 = encoding.quant_weight_stream_bytes(
        n, k, quant="w4a8", group=ref.Q4_GROUP, scale_itemsize=2
    )
    assert b8 / b4 >= 1.5, (b8, b4)
    bf = encoding.quant_weight_stream_bytes(n, k, quant="none")
    assert bf / b4 >= 3.0, (bf, b4)
