"""Pure-jnp oracles for every kernel in this package.

These are (a) the correctness references the Pallas kernels are validated
against in tests, and (b) the `xla` backend used by the 512-device dry-run —
XLA lowers the einsum on the packed 4-D layout directly, which keeps
cost_analysis faithful to the mmt4d compute while avoiding interpret-mode
blow-up at dry-run scale.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def pack(x: jnp.ndarray, tile: tuple[int, int]) -> jnp.ndarray:
    """tensor.pack: (R, C) -> (R1, C1, T0, T1), zero-padded, tiles contiguous."""
    t0, t1 = tile
    r, c = x.shape
    r1 = math.ceil(r / t0)
    c1 = math.ceil(c / t1)
    xp = jnp.pad(x, ((0, r1 * t0 - r), (0, c1 * t1 - c)))
    return xp.reshape(r1, t0, c1, t1).transpose(0, 2, 1, 3)


def unpack(y: jnp.ndarray, shape: tuple[int, int]) -> jnp.ndarray:
    """tensor.unpack: (R1, C1, T0, T1) -> (R, C), cropping pad."""
    r1, c1, t0, t1 = y.shape
    r, c = shape
    return y.transpose(0, 2, 1, 3).reshape(r1 * t0, c1 * t1)[:r, :c]


def mmt4d(lhs4: jnp.ndarray, rhs4: jnp.ndarray, acc_dtype=jnp.float32) -> jnp.ndarray:
    """linalg.mmt4d: lhs (M1,K1,M0,K0) x rhs (N1,K1,N0,K0) -> (M1,N1,M0,N0).

    out[m1,n1,m0,n0] = sum_{k1,k0} lhs[m1,k1,m0,k0] * rhs[n1,k1,n0,k0]
    (rhs is the transposed operand — the trailing 't').  f32 accumulation,
    matching the paper's f16xf16->f32 microkernels.
    """
    return jnp.einsum(
        "mkac,nkbc->mnab",
        lhs4,
        rhs4,
        preferred_element_type=acc_dtype,
    )


def mmt4d_unfused(
    lhs: jnp.ndarray,
    rhs_t: jnp.ndarray,
    tiles: tuple[int, int, int],
    acc_dtype=jnp.float32,
) -> jnp.ndarray:
    """Full encoded matmul on 2-D operands: pack -> mmt4d -> unpack.

    lhs: (M, K); rhs_t: (N, K) (already transposed, as stored by PackedLinear).
    Returns (M, N) in acc_dtype.
    """
    m0, n0, k0 = tiles
    m, k = lhs.shape
    n, k2 = rhs_t.shape
    assert k == k2, (lhs.shape, rhs_t.shape)
    lhs4 = pack(lhs, (m0, k0))
    rhs4 = pack(rhs_t, (n0, k0))
    out4 = mmt4d(lhs4, rhs4, acc_dtype=acc_dtype)
    return unpack(out4, (m, n))


def matmul_reference(lhs: jnp.ndarray, rhs_t: jnp.ndarray, acc_dtype=jnp.float32) -> jnp.ndarray:
    """The un-encoded baseline (upstream-IREE analogue): plain contraction."""
    return jnp.einsum("mk,nk->mn", lhs, rhs_t, preferred_element_type=acc_dtype)


# ---- int8 serving quantization (beyond paper; kernels/mmt4d_q8.py) ---------


def quantize_rows(x2d: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Symmetric per-row int8: returns (q (R, C) int8, scale (R,) f32)."""
    s = jnp.maximum(jnp.max(jnp.abs(x2d.astype(jnp.float32)), axis=1), 1e-8) / 127.0
    q = jnp.clip(jnp.round(x2d.astype(jnp.float32) / s[:, None]), -127, 127)
    return q.astype(jnp.int8), s


_CLIP_RATIOS = (1.0, 0.95, 0.9, 0.85, 0.8, 0.75, 0.7)


def quantize_rows_mse(
    x2d: jnp.ndarray, ratios: tuple[float, ...] = _CLIP_RATIOS
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Symmetric per-row int8 with MSE-optimal clipping.

    Absmax scales waste resolution on per-row outliers; searching a few clip
    ratios and keeping the min-MSE quantization per row roughly halves weight
    reconstruction error.  One-time cost — used for WEIGHT packing
    (ops.pack_rhs_q8); dynamic activation quant keeps plain absmax."""
    xf = x2d.astype(jnp.float32)
    amax = jnp.maximum(jnp.max(jnp.abs(xf), axis=1), 1e-8)
    best_err = best_q = best_s = None
    for r in ratios:
        s = amax * (r / 127.0)
        q = jnp.clip(jnp.round(xf / s[:, None]), -127, 127)
        err = jnp.sum(jnp.square(q * s[:, None] - xf), axis=1)
        if best_err is None:
            best_err, best_q, best_s = err, q, s
        else:
            upd = err < best_err
            best_q = jnp.where(upd[:, None], q, best_q)
            best_s = jnp.where(upd, s, best_s)
            best_err = jnp.minimum(err, best_err)
    return best_q.astype(jnp.int8), best_s


def mmt4d_q8(lhs4_q, rhs4_q, s_a, s_w) -> jnp.ndarray:
    """Oracle for kernels/mmt4d_q8.py (same operand layout)."""
    acc = jnp.einsum(
        "mkac,nkbc->mnab",
        lhs4_q.astype(jnp.int32),
        rhs4_q.astype(jnp.int32),
    ).astype(jnp.float32)
    return acc * s_a[:, None, :, None] * s_w[None, :, None, :]


# ---- int4 group-quantized serving (w4a8; kernels/mmt4d_q4.py) --------------

# K elements sharing one int4 scale.  16 is the serving default: on the
# reduced-model decision-preservation harness it halves the logit MSE of the
# llama.cpp-Q4_0-style g=32 (rel MSE 0.035 vs 0.078) for +1/16 scale byte per
# weight (bf16 scales) — see docs/DESIGN.md §w4a8 group-quantized decode.
Q4_GROUP = 16


def quantize_rows_q4_grouped(
    x2d: jnp.ndarray,
    group: int = Q4_GROUP,
    ratios: tuple[float, ...] = _CLIP_RATIOS,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Symmetric per-(row, K-group) int4 with MSE-optimal clipping.

    Returns (q (R, C) int8 in [-7, 7], scales (R, ceil(C/group)) f32).  A
    per-group scale is the whole point of 4-bit: one outlier only costs its
    own `group` neighbours resolution, not the full row.  C is zero-padded to
    a group multiple internally; padded columns quantize to 0 and never
    contribute (their dequant is 0 * scale)."""
    r, c = x2d.shape
    gcount = math.ceil(c / group)
    cp = gcount * group
    xf = jnp.pad(x2d.astype(jnp.float32), ((0, 0), (0, cp - c)))
    xg = xf.reshape(r, gcount, group)
    amax = jnp.maximum(jnp.max(jnp.abs(xg), axis=2), 1e-8)  # (R, G)
    best_err = best_q = best_s = None
    for ratio in ratios:
        s = amax * (ratio / 7.0)
        q = jnp.clip(jnp.round(xg / s[..., None]), -7, 7)
        err = jnp.sum(jnp.square(q * s[..., None] - xg), axis=2)
        if best_err is None:
            best_err, best_q, best_s = err, q, s
        else:
            upd = err < best_err
            best_q = jnp.where(upd[..., None], q, best_q)
            best_s = jnp.where(upd, s, best_s)
            best_err = jnp.minimum(err, best_err)
    q2d = best_q.reshape(r, cp)[:, :c].astype(jnp.int8)
    return q2d, best_s


def pack_nibbles(q: jnp.ndarray, axis: int = -1) -> jnp.ndarray:
    """int4-valued int8 -> uint8 with `axis` halved, two's-complement nibbles.

    Byte j along `axis` holds elements j (low nibble) and j + C/2 (high
    nibble) of that axis, so unpacking is a concatenation of the two nibble
    planes.  Packed weights pack the N0 axis (-2): the planes then stack
    along sublanes inside the kernel.  C must be even."""
    axis = axis % q.ndim
    c = q.shape[axis]
    assert c % 2 == 0, q.shape
    qi = q.astype(jnp.int32) & 0xF
    lo = jax.lax.slice_in_dim(qi, 0, c // 2, axis=axis)
    hi = jax.lax.slice_in_dim(qi, c // 2, c, axis=axis)
    return (lo | (hi << 4)).astype(jnp.uint8)


def unpack_nibbles(b: jnp.ndarray, axis: int = -1) -> jnp.ndarray:
    """uint8 -> int32 in [-8, 7] with `axis` doubled; inverse of
    pack_nibbles."""
    bi = b.astype(jnp.int32)
    lo = ((bi & 0xF) ^ 8) - 8
    hi = ((bi >> 4) ^ 8) - 8
    return jnp.concatenate([lo, hi], axis=axis)


def dequant_rhs4_q4(
    rhs4_p: jnp.ndarray, s_w4: jnp.ndarray, group: int = Q4_GROUP
) -> jnp.ndarray:
    """Nibble-packed rhs (N1, K1, N0/2, K0) + scales (N1, K1, N0, K0/group)
    -> f32 (N1, K1, N0, K0): the dequantized packed weight."""
    w = unpack_nibbles(rhs4_p, axis=-2).astype(jnp.float32)
    n1, k1, n0, k0 = w.shape
    s = jnp.broadcast_to(
        s_w4.astype(jnp.float32)[..., :, None], (*s_w4.shape, group)
    ).reshape(n1, k1, n0, k0)
    return w * s


def mmt4d_q4(lhs4_q, rhs4_p, s_a, s_w4, group: int = Q4_GROUP) -> jnp.ndarray:
    """Oracle for kernels/mmt4d_q4.py: w4a8 mmt4d on packed operands.

    lhs4_q (M1, K1, M0, K0) int8 activations + per-row scales s_a (M1, M0);
    rhs4_p nibble-packed int4 weights + per-group scales s_w4 (see
    dequant_rhs4_q4).  The per-K-group weight scale cannot factor out of the
    contraction (unlike w8a8's per-channel scale), so the weight dequantizes
    into f32 *inside* the contraction domain and accumulation is f32 — the
    products are exact in f32 (|a_q| <= 127, |w_q| <= 7)."""
    w = dequant_rhs4_q4(rhs4_p, s_w4, group)
    acc = jnp.einsum(
        "mkac,nkbc->mnab",
        lhs4_q.astype(jnp.float32),
        w,
        preferred_element_type=jnp.float32,
    )
    return acc * s_a[:, None, :, None]
