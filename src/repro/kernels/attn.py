"""Pallas TPU attention microkernels — the attention op class.

PRs 1-4 microkernel-ized every matmul in the serving path, but attention
stayed plain XLA: `attention_decode` softmaxes over the full cache and the
paged path materializes the whole logical KV view (`paged_gather`, a fresh
(B, NB*bs, KV, D) dense copy) on EVERY decode dispatch — at long contexts
that gather traffic dominates the weight stream the matmul kernels shrank
(V-Seek's point: optimized-GEMV decode is attention/KV-bound).  This module
gives all three attention phases a hand-tiled kernel:

  paged_decode_attention  decode against the page pool DIRECTLY: the block
                          table rides as a scalar-prefetch operand and the
                          kernel's BlockSpec index_map gathers K/V pages
                          tile-by-tile inside the dispatch — no materialized
                          logical view, and only the slot's LIVE pages are
                          streamed (beyond-live grid steps clamp their index
                          map to the last live page, so the pipelined copy is
                          elided, and their compute is `pl.when`-skipped).
  dense_decode_attention  the dense-cache analogue: K/V chunks streamed with
                          the same online softmax, ring-window mask included.
  flash_prefill_attention tiled causal GQA flash attention (the Pallas
                          analogue of layers.attention_chunked), q-offset
                          aware so chunked prefill rides the same kernel.

All three share one online-softmax accumulator (`_online_update`), keep the
running (m, l, acc) state in VMEM scratch across the innermost grid
dimension, and support per-row position vectors and the L > 1 masked-causal
spec-decode verify window.  A fully-masked chunk is an EXACT no-op of the
accumulator (m unchanged -> corr == 1.0, p == 0), which makes skip-by-mask
bitwise identical to skip-by-guard — the paged and dense kernels produce
bit-identical outputs whenever their streaming granularity matches
(dense kv_chunk == page block size; tests/test_attn_kernels.py pins this).

Quantized KV layouts (core/encoding.KVLayout, kv8/kv4): the paged and dense
decode kernels ride the per-page scale arrays as extra BlockSpec operands —
same index maps as their data pages, so a scale tile arrives in VMEM with
its page — and dequantize tile-locally before the online-softmax accumulate.
The contraction itself never sees int storage, and nothing dequantized is
ever written back to HBM.  Prefill writes quantized through the engine's
scatter path (models/layers.py quantizes per page on write); chunked-prefill
continuation reads its prior pages back through these same dequantizing
decode kernels.

Dispatch routing lives in kernels/registry.py (`select_attn`, the second op
class: attn|phase|S-bucket[|kv]|target); models/layers.py consults it per
call.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import encoding


def _online_update(q, k, v, valid, m_ref, l_ref, acc_ref, i):
    """One online-softmax step of state row-block `i` over a scored chunk.

    q: (R, D) pre-scaled queries; k: (C, D) keys; v: (C, D) f32 values;
    valid: bool (R, C); scratch m/l: (.., R, 1), acc: (.., R, D).  Every
    contraction is a plain 2-D dot (the MXU's native form, whatever the GQA
    group size).  Fully-masked chunks leave (m, l, acc) bitwise unchanged
    (corr == 1)."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    s = jnp.where(valid, s, -jnp.inf)
    m_prev = m_ref[i]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    # Guard rows with no valid key yet: keep the exponent argument finite.
    m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    p = jnp.where(valid, jnp.exp(s - m_safe), 0.0)
    corr = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - m_safe), 0.0)
    l_ref[i] = l_ref[i] * corr + p.sum(axis=-1, keepdims=True)
    acc_ref[i] = acc_ref[i] * corr + jnp.dot(
        p, v, preferred_element_type=jnp.float32
    )
    m_ref[i] = m_new


def _init_state(m_ref, l_ref, acc_ref):
    m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)


def _finalize(out_ref, l_ref, acc_ref):
    out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
    out_ref[0] = out.astype(out_ref.dtype)


def _group_rows(q, kvh):
    """(B, L, H, D) -> (B, KV, L*G, D): each KV head's query rows as one 2-D
    slab, row l*G + r = query head kv*G + r at window position l."""
    b, L, h, d = q.shape
    g = h // kvh
    return q.reshape(b, L, kvh, g, d).transpose(0, 2, 1, 3, 4).reshape(
        b, kvh, L * g, d
    )


def _ungroup_rows(out, L):
    """Inverse of _group_rows."""
    b, kvh, lg, d = out.shape
    g = lg // L
    return out.reshape(b, kvh, L, g, d).transpose(0, 2, 1, 3, 4).reshape(
        b, L, kvh * g, d
    )


def _norm_pos(pos, b) -> jnp.ndarray:
    """Scalar or (B,) position of q[:, 0] -> (B,) int32."""
    p = jnp.asarray(pos, jnp.int32)
    return jnp.broadcast_to(jnp.atleast_1d(p), (b,))


def _dequant_kv(kv_quant: str, k_raw, v_raw, ks, vs):
    """VMEM-tile dequantization: int storage tiles + their scale tiles ->
    float32 (bs, KV, D) chunks the shared online-softmax body consumes.
    bf16 passes the raw tiles through untouched."""
    if kv_quant == "bf16":
        return k_raw, v_raw
    lay = encoding.kv_layout(kv_quant)
    return lay.dequantize(k_raw, ks), lay.dequantize(v_raw, vs)


def _head_tiles(kv_quant, kh, k_ref, v_ref, ks_ref, vs_ref):
    """KV head `kh`'s (C, D) key tile and f32 value tile of one streamed
    (1, C, KV, Ds) chunk, dequantized in VMEM for quantized layouts."""
    k, v = _dequant_kv(
        kv_quant, k_ref[0, :, kh, :], v_ref[0, :, kh, :],
        None if ks_ref is None else ks_ref[0, :, kh, :],
        None if vs_ref is None else vs_ref[0, :, kh, :],
    )
    return k, v.astype(jnp.float32)


# ---------------------------------------------------------------------------
# Fused paged-decode attention (in-kernel block-table gather)


def _paged_decode_kernel(
    table_ref, pos_ref, q_ref, k_ref, v_ref, *refs,
    bs: int, L: int, kvh: int, g: int, scale: float, kv_quant: str,
):
    if kv_quant == "bf16":
        ks_ref = vs_ref = None
        out_ref, m_ref, l_ref, acc_ref = refs
    else:
        ks_ref, vs_ref, out_ref, m_ref, l_ref, acc_ref = refs
    b = pl.program_id(0)
    j = pl.program_id(1)
    nb = pl.num_programs(1)

    @pl.when(j == 0)
    def _():
        _init_state(m_ref, l_ref, acc_ref)

    pos_b = pos_ref[b]
    last = pos_b + L - 1  # last written position of this row's verify window

    # Beyond-live pages are never attended (their index map already clamps
    # to the last live page, so no fresh bytes moved either).
    @pl.when(j * bs <= last)
    def _():
        rows = (L * g, bs)
        slot = j * bs + jax.lax.broadcasted_iota(jnp.int32, rows, 1)
        qpos = pos_b + jax.lax.broadcasted_iota(jnp.int32, rows, 0) // g
        valid = slot <= qpos  # masked-causal inside the verify window
        # ONE pool page (+ its scale page) per step, gathered via the index
        # map; each KV head's (bs, D) slice serves its G query heads.
        for kh in range(kvh):
            k, v = _head_tiles(kv_quant, kh, k_ref, v_ref, ks_ref, vs_ref)
            q = (q_ref[0, kh] * scale).astype(k.dtype)
            _online_update(q, k, v, valid, m_ref, l_ref, acc_ref, kh)

    @pl.when(j == nb - 1)
    def _():
        _finalize(out_ref, l_ref, acc_ref)


@functools.partial(jax.jit, static_argnames=("kv_quant", "interpret"))
def paged_decode_attention(
    q: jnp.ndarray,       # (B, L, H, D)
    k_pool: jnp.ndarray,  # (P, bs, KV, Ds) physical pages (Ds = stored D)
    v_pool: jnp.ndarray,  # (P, bs, KV, Ds)
    table: jnp.ndarray,   # (B, NB) int32 page ids (logical block -> page)
    pos: jnp.ndarray,     # () or (B,) int32 position of q[:, 0]
    *,
    k_scale: jnp.ndarray | None = None,  # (P, bs, KV, 1) f32 scale pages
    v_scale: jnp.ndarray | None = None,
    kv_quant: str = "bf16",
    interpret: bool = False,
) -> jnp.ndarray:
    """Decode attention straight off the page pool: gathers each row's live
    K/V pages inside the kernel (scalar-prefetched block table drives the
    BlockSpec index map), online softmax over the page stream, per-row
    positions, full attention only (the paged cache excludes ring windows).
    L > 1 is the spec-decode verify window (masked-causal; the caller has
    already scattered all L K/V pairs into the pool).

    Streams ceil((pos+L)/bs) pages per row instead of materializing the
    (B, NB*bs, KV, D) `paged_gather` view — the O(pool) -> O(live) win.

    Quantized layouts (kv_quant "kv8"/"kv4"): the pools hold int storage
    (kv4 packs two nibbles per byte along D) and `k_scale`/`v_scale` are
    the matching scale pages; each grid step's scale tile rides the SAME
    index map as its data page and is dequantized in VMEM right before
    the score contraction — the scale stream adds 4 bytes/token/head
    against the >= 2x shrink of the data stream.
    """
    b, L, h, d = q.shape
    _, bs, kvh, ds = k_pool.shape
    nb = table.shape[1]
    g = h // kvh
    scale = d**-0.5
    posv = _norm_pos(pos, b)
    quantized = kv_quant != "bf16"
    assert (k_scale is not None) == quantized, (kv_quant, k_scale is None)

    def live_block(bi, j, tbl, pv):
        # Clamp beyond-live steps to the last live page: the block index is
        # then unchanged from the previous step and the copy is elided.
        return tbl[bi, jnp.minimum(j, (pv[bi] + L - 1) // bs)]

    def page_spec(width):
        return pl.BlockSpec(
            (1, bs, kvh, width),
            lambda bi, j, tbl, pv: (live_block(bi, j, tbl, pv), 0, 0, 0),
        )

    # Query rows grouped per KV head: (B, KV, L*G, D) slabs in and out.
    q_spec = pl.BlockSpec((1, kvh, L * g, d), lambda bi, j, tbl, pv: (bi, 0, 0, 0))
    in_specs = [q_spec, page_spec(ds), page_spec(ds)]
    operands = [_group_rows(q, kvh), k_pool, v_pool]
    if quantized:
        in_specs += [page_spec(1), page_spec(1)]
        operands += [k_scale, v_scale]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, nb),
        in_specs=in_specs,
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((kvh, L * g, 1), jnp.float32),
            pltpu.VMEM((kvh, L * g, 1), jnp.float32),
            pltpu.VMEM((kvh, L * g, d), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _paged_decode_kernel,
        bs=bs, L=L, kvh=kvh, g=g, scale=scale, kv_quant=kv_quant,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvh, L * g, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="paged_decode_attention",
    )(table.astype(jnp.int32), posv, *operands)
    return _ungroup_rows(out, L)


# ---------------------------------------------------------------------------
# Dense-cache decode attention (ring-window aware)


def _dense_decode_kernel(
    pos_ref, q_ref, k_ref, v_ref, *refs,
    kc: int, s_c: int, window: int, L: int, kvh: int, g: int, scale: float,
    kv_quant: str,
):
    if kv_quant == "bf16":
        ks_ref = vs_ref = None
        out_ref, m_ref, l_ref, acc_ref = refs
    else:
        ks_ref, vs_ref, out_ref, m_ref, l_ref, acc_ref = refs
    b = pl.program_id(0)
    j = pl.program_id(1)
    nk = pl.num_programs(1)

    @pl.when(j == 0)
    def _():
        _init_state(m_ref, l_ref, acc_ref)

    pos_b = pos_ref[b]
    last = pos_b + L - 1
    # Full attention skips chunks past the newest written slot; a ring cache
    # may hold valid (wrapped) positions in every chunk, so it visits all.
    run = (j * kc <= last) if window == 0 else (j >= 0)

    @pl.when(run)
    def _():
        rows = (L * g, kc)
        slot = j * kc + jax.lax.broadcasted_iota(jnp.int32, rows, 1)
        qpos = pos_b + jax.lax.broadcasted_iota(jnp.int32, rows, 0) // g
        # Tail guard: when kc does not divide S_c the last block reads past
        # the cache (Pallas pads the edge block; content is undefined) —
        # mask those columns out of the scores AND zero their V rows so no
        # garbage bit pattern (even a NaN encoding, pre- or post-dequant)
        # can reach the accumulator through 0 * v.
        in_range = slot < s_c
        v_row = j * kc + jax.lax.broadcasted_iota(jnp.int32, (kc, 1), 0)
        if window > 0:
            # Same mask as layers.attention_decode: rows still inside the
            # window take the cheap prefix mask (nothing wrapped or aged
            # out yet); only wrapped rows pay the ring-age mod.
            age = jnp.mod(qpos - slot, s_c)
            ring = age < jnp.minimum(qpos + 1, window)
            valid = jnp.where(qpos < window, slot <= qpos, ring) & in_range
        else:
            valid = (slot <= qpos) & in_range
        for kh in range(kvh):
            k, v = _head_tiles(kv_quant, kh, k_ref, v_ref, ks_ref, vs_ref)
            v = jnp.where(v_row < s_c, v, 0.0)
            q = (q_ref[0, kh] * scale).astype(k.dtype)
            _online_update(q, k, v, valid, m_ref, l_ref, acc_ref, kh)

    @pl.when(j == nk - 1)
    def _():
        _finalize(out_ref, l_ref, acc_ref)


@functools.partial(
    jax.jit, static_argnames=("window", "kv_chunk", "kv_quant", "interpret")
)
def dense_decode_attention(
    q: jnp.ndarray,        # (B, L, H, D)
    k_cache: jnp.ndarray,  # (B, S_c, KV, Ds)
    v_cache: jnp.ndarray,  # (B, S_c, KV, Ds)
    pos: jnp.ndarray,      # () or (B,) int32 position of q[:, 0]
    *,
    window: int = 0,
    kv_chunk: int | None = None,
    k_scale: jnp.ndarray | None = None,  # (B, S_c, KV, 1) f32
    v_scale: jnp.ndarray | None = None,
    kv_quant: str = "bf16",
    interpret: bool = False,
) -> jnp.ndarray:
    """Dense-cache decode attention: K/V streamed in kv_chunk slabs with the
    same online softmax as the paged kernel (kv_chunk == page block size
    gives bit-identical outputs — kv8 included, since both kernels dequantize
    the identical tile values in the identical accumulate order), ring-window
    mask for sliding-window caches, per-row positions, L > 1 masked-causal
    verify window (window == 0 only — the same contract
    layers.attention_decode enforces).  Quantized layouts stream the scale
    slabs alongside their K/V chunks and dequantize in VMEM; ring windows
    stay bf16 (the paged pool owns the quantized serving path)."""
    b, L, h, d = q.shape
    _, s_c, kvh, ds = k_cache.shape
    assert L == 1 or window == 0, (L, window)
    quantized = kv_quant != "bf16"
    assert (k_scale is not None) == quantized, (kv_quant, k_scale is None)
    assert window == 0 or not quantized, (window, kv_quant)
    g = h // kvh
    scale = d**-0.5
    posv = _norm_pos(pos, b)
    kc = min(s_c, kv_chunk or 128)
    # No host-side padding: a ragged tail would force a full HBM copy of
    # both caches per dispatch; the kernel masks the edge block instead.
    nk = pl.cdiv(s_c, kc)

    def live_chunk(bi, j, pv):
        if window > 0:
            return j  # ring chunks are all potentially live
        return jnp.minimum(j, (pv[bi] + L - 1) // kc)

    def chunk_spec(width):
        return pl.BlockSpec(
            (1, kc, kvh, width),
            lambda bi, j, pv: (bi, live_chunk(bi, j, pv), 0, 0),
        )

    q_spec = pl.BlockSpec((1, kvh, L * g, d), lambda bi, j, pv: (bi, 0, 0, 0))
    in_specs = [q_spec, chunk_spec(ds), chunk_spec(ds)]
    operands = [_group_rows(q, kvh), k_cache, v_cache]
    if quantized:
        in_specs += [chunk_spec(1), chunk_spec(1)]
        operands += [k_scale, v_scale]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, nk),
        in_specs=in_specs,
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((kvh, L * g, 1), jnp.float32),
            pltpu.VMEM((kvh, L * g, 1), jnp.float32),
            pltpu.VMEM((kvh, L * g, d), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _dense_decode_kernel,
        kc=kc, s_c=s_c, window=window, L=L, kvh=kvh, g=g, scale=scale,
        kv_quant=kv_quant,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvh, L * g, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="dense_decode_attention",
    )(posv, *operands)
    return _ungroup_rows(out, L)


# ---------------------------------------------------------------------------
# Flash prefill (tiled causal GQA)


def _flash_prefill_kernel(
    q_ref, k_ref, v_ref, out_ref, m_ref, l_ref, acc_ref,
    *, qc: int, kc: int, sk: int, q_offset: int, causal: bool, window: int,
    g: int, scale: float,
):
    """One (batch, KV head, q band, kv chunk) step.  Blocks are head-major
    2-D slabs — q (G, qc, D) for the G query heads sharing this KV head, k/v
    (kc, D) — so every contraction is a plain 2-D dot, whatever the group
    size (qwen2's 12/2 heads give G = 6, which no sublane split divides)."""
    i = pl.program_id(2)
    j = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(j == 0)
    def _():
        _init_state(m_ref, l_ref, acc_ref)

    q_end = q_offset + (i + 1) * qc - 1  # last query position of this band
    run = (j * kc <= q_end) if (causal and window == 0) else (j >= 0)

    @pl.when(run)
    def _():
        qpos = q_offset + i * qc + jax.lax.broadcasted_iota(jnp.int32, (qc, kc), 0)
        kpos = j * kc + jax.lax.broadcasted_iota(jnp.int32, (qc, kc), 1)
        # Edge-block guard (kc may not divide Sk): mask the scores and zero
        # the V tail so undefined padded reads can never reach the
        # accumulator (see the dense kernel note).
        valid = kpos < sk
        if causal:
            valid = valid & (kpos <= qpos)
        if window > 0:
            valid = valid & (kpos > qpos - window)
        k = k_ref[0, 0]
        v_row = j * kc + jax.lax.broadcasted_iota(jnp.int32, (kc, 1), 0)
        v = jnp.where(v_row < sk, v_ref[0, 0].astype(jnp.float32), 0.0)
        for h in range(g):
            _online_update(
                q_ref[0, h] * scale, k, v, valid, m_ref, l_ref, acc_ref, h
            )

    @pl.when(j == nk - 1)
    def _():
        _finalize(out_ref, l_ref, acc_ref)


@functools.partial(
    jax.jit,
    static_argnames=(
        "causal", "window", "q_offset", "q_chunk", "kv_chunk", "interpret"
    ),
)
def flash_prefill_attention(
    q: jnp.ndarray,  # (B, Sq, H, D)
    k: jnp.ndarray,  # (B, Sk, KV, D)
    v: jnp.ndarray,  # (B, Sk, KV, D)
    *,
    causal: bool = True,
    window: int = 0,
    q_offset: int = 0,
    q_chunk: int = 128,
    kv_chunk: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """Tiled flash prefill: the Pallas analogue of layers.attention_chunked.
    Causal GQA with sliding-window and q-offset support (chunked prefill
    passes the absolute offset of q[:, 0]); upper-triangle KV chunks are
    skipped (index map clamps, compute is pl.when-guarded).  The kernel
    reads head-major (B, H, S, D) operands; the transposes in and out are
    XLA ops on this side of the call."""
    b, sq, h, d = q.shape
    _, sk, kvh, _ = k.shape
    g = h // kvh
    scale = d**-0.5
    qc = min(q_chunk, sq)
    kc = min(kv_chunk, sk)
    # No host-side padding (full Q/K/V HBM copies); edge blocks are masked
    # in-kernel, and out-of-range output rows are masked writes.
    nq = pl.cdiv(sq, qc)
    nk = pl.cdiv(sk, kc)

    def k_block(bi, kv, i, j):
        if causal and window == 0:
            # Clamp beyond-diagonal chunks to the band's last needed chunk.
            j = jnp.minimum(j, (q_offset + (i + 1) * qc - 1) // kc)
        return (bi, kv, j, 0)

    kernel = functools.partial(
        _flash_prefill_kernel,
        qc=qc, kc=kc, sk=sk, q_offset=q_offset, causal=causal, window=window,
        g=g, scale=scale,
    )
    # Query head kv*g + r serves KV head kv: a G-head block at block index kv.
    q_spec = pl.BlockSpec((1, g, qc, d), lambda bi, kv, i, j: (bi, kv, i, 0))
    kv_spec = pl.BlockSpec((1, 1, kc, d), k_block)
    out = pl.pallas_call(
        kernel,
        grid=(b, kvh, nq, nk),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((g, qc, 1), jnp.float32),
            pltpu.VMEM((g, qc, 1), jnp.float32),
            pltpu.VMEM((g, qc, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_prefill_attention",
    )(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3))
    return out.transpose(0, 2, 1, 3)
