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
                          kernel gathers each row's K/V pages itself, a
                          block of `pages_per_block` pages (~256 tokens) per
                          online-softmax update — no materialized logical
                          view, and only the slot's LIVE pages are copied
                          (beyond-live blocks copy and compute nothing).
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
(dense kv_chunk == pages_per_block x page size; tests/test_attn_kernels.py
pins this).

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


def _attend_chunk(
    head_tiles, j, kc: int, s_c: int, window: int, pos_b, q_ref,
    m_ref, l_ref, acc_ref, *, L: int, kvh: int, g: int, scale: float,
):
    """Online-softmax update of one row's state over its chunk `j` of `kc`
    cache positions (of `s_c`): `head_tiles(kh)` gives KV head kh's (kc, D)
    key tile and f32 value tile.  Shared by the dense and paged decode
    kernels, so equal tiles give bitwise-equal outputs."""
    rows = (L * g, kc)
    slot = j * kc + jax.lax.broadcasted_iota(jnp.int32, rows, 1)
    qpos = pos_b + jax.lax.broadcasted_iota(jnp.int32, rows, 0) // g
    # Tail guard: a chunk may reach past the cache (a dense cache's padded
    # edge block, whose content is undefined; a paged table's partial last
    # block) — mask those columns out of the scores AND zero their V rows
    # so no garbage bit pattern (even a NaN encoding, pre- or post-dequant)
    # can reach the accumulator through 0 * v.
    in_range = slot < s_c
    v_row = j * kc + jax.lax.broadcasted_iota(jnp.int32, (kc, 1), 0)
    if window > 0:
        # Same mask as layers.attention_decode: rows still inside the
        # window take the cheap prefix mask (nothing wrapped or aged out
        # yet); only wrapped rows pay the ring-age mod.
        age = jnp.mod(qpos - slot, s_c)
        ring = age < jnp.minimum(qpos + 1, window)
        valid = jnp.where(qpos < window, slot <= qpos, ring) & in_range
    else:
        valid = (slot <= qpos) & in_range  # masked-causal in the verify window
    # Each KV head's (kc, D) tile serves its G query heads.
    for kh in range(kvh):
        k, v = head_tiles(kh)
        v = jnp.where(v_row < s_c, v, 0.0)
        q = (q_ref[0, kh] * scale).astype(k.dtype)
        _online_update(q, k, v, valid, m_ref, l_ref, acc_ref, kh)


# ---------------------------------------------------------------------------
# Fused paged-decode attention (in-kernel block-table gather)

# Tokens of one row in one block of the paged kernel (one online-softmax
# update): enough bytes per block to amortize its fixed cost, few enough
# that the partly-live last block of a short row stays cheap.
_BLOCK_TOKENS = 256


def pages_per_block(bs: int, nb: int) -> int:
    """Pages of one row that one block of `paged_decode_attention` streams:
    about _BLOCK_TOKENS tokens of `bs`-token pages, at least one, never more
    than the table width `nb` (tiny tables: one block a row)."""
    return max(1, min(nb, _BLOCK_TOKENS // bs))


def copyable_pages(pool) -> bool:
    """True when Mosaic can copy one (bs, KV, Ds) page of `pool` out of HBM
    with an explicit DMA, as whole (8, 128) tiles of 32-bit words: the
    page's minor dims fill whole (sublane packing, 128 lane) tiles.  A page
    whose tile is padded (head_dim 64, a single KV head, int8 storage over
    2 KV heads, the (.., 1) scale pages) arrives only through a BlockSpec."""
    packing = max(1, 4 // jnp.dtype(pool.dtype).itemsize)
    _, bs, kvh, ds = pool.shape
    return ds % 128 == 0 and kvh % packing == 0 and bs * kvh // packing % 8 == 0


def _flat_head(buf, half, kh: int, kvh: int):
    """KV head kh's (C, D) rows, as exact float32, of one half of a flat
    block buffer of 32-bit words (half, C*KV/pack, D): row t*KV + kh of the
    block holds token t's head kh, `pack` rows to a word (2 for bf16, row
    2m in the low half).  A strided load picks the head's words; for bf16
    a shift or mask then yields the values, with no per-token relayout."""
    if buf.dtype != jnp.uint32:  # float32 pages, one row a word
        c = buf.shape[1] // kvh
        return buf[half, pl.ds(kh, c, stride=kvh), :]
    c = buf.shape[1] * 2 // kvh
    rows = buf[half, pl.ds(kh // 2, c, stride=kvh // 2), :]
    bits = rows << 16 if kh % 2 == 0 else rows & jnp.uint32(0xFFFF0000)
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def _paged_dma_kernel(
    table_ref, pos_ref, q_ref, k_hbm, v_hbm, out_ref, m_ref, l_ref, acc_ref,
    k_buf, v_buf, sem, half_ref, *, nb: int, bs: int, ppb: int, **attn,
):
    """Grid (B, ceil(NB / ppb)): one block of ppb pages a step, gathered
    from the flat (P, bs*KV, D) pools by explicit page DMAs into the
    32-bit (2, ppb*bs*KV/pack, D) buffers (`_flat_head`); `sem` holds a
    DMA semaphore per buffer half, `half_ref` (SMEM) the half the current
    block lands in."""
    L, kvh = attn["L"], attn["kvh"]
    dtype = k_hbm.dtype

    def flat(pool):
        """(P, bs, KV, D) pages as (P, bs*KV/pack, D) 32-bit rows: the same
        bytes, as the pool's tiling packs row pairs into words."""
        pool = pool.reshape(pool.shape[0], bs * kvh, pool.shape[-1])
        return pool if pool.dtype == k_buf.dtype else pool.bitcast(k_buf.dtype)

    k_hbm, v_hbm = flat(k_hbm), flat(v_hbm)
    page_rows = k_buf.shape[1] // ppb  # 32-bit rows of one page
    b = pl.program_id(0)
    j = pl.program_id(1)

    def live_pages(bi):
        return jnp.minimum((pos_ref[bi] + L - 1) // bs + 1, nb)

    def slot_rows(i):
        return pl.ds(pl.multiple_of(i * page_rows, page_rows), page_rows)

    def block_copies(bi, jb, half, act):
        """act(copy) for the page copies of row bi's block jb: the whole K
        and V page of each of its live pages into its slot of the half."""
        n = jnp.minimum(live_pages(bi) - jb * ppb, ppb)

        def page(i, carry):
            p = table_ref[bi, jb * ppb + i]
            for pool, buf in ((k_hbm, k_buf), (v_hbm, v_buf)):
                act(pltpu.make_async_copy(
                    pool.at[p], buf.at[half, slot_rows(i)], sem.at[half]
                ))
            return carry

        jax.lax.fori_loop(0, n, page, 0)

    live_blocks = pl.cdiv(live_pages(b), ppb)

    @pl.when(j == 0)
    def _():
        _init_state(m_ref, l_ref, acc_ref)

    # Beyond-live blocks issue no copy and do no compute.
    @pl.when(j < live_blocks)
    def _():
        @pl.when((b == 0) & (j == 0))
        def _():  # the grid's first block; every later one was prefetched
            half_ref[0] = 0
            block_copies(b, j, 0, lambda c: c.start())

        half = half_ref[0]
        # Prefetch the grid's next live block (this row's next, else the
        # next row's first) into the other half while this one computes.
        row_done = j + 1 >= live_blocks
        nxt_b = jnp.where(row_done, b + 1, b)
        nxt_j = jnp.where(row_done, 0, j + 1)

        @pl.when(nxt_b < pl.num_programs(0))
        def _():
            block_copies(nxt_b, nxt_j, 1 - half, lambda c: c.start())

        block_copies(b, j, half, lambda c: c.wait())

        def zero(i, carry):  # slots past the row's last page (last block)
            for buf in (k_buf, v_buf):
                buf[half, slot_rows(i)] = jnp.zeros(
                    (page_rows, buf.shape[2]), buf.dtype)
            return carry

        jax.lax.fori_loop(live_pages(b) - j * ppb, ppb, zero, 0)

        def head_tiles(kh):
            return (_flat_head(k_buf, half, kh, kvh).astype(dtype),
                    _flat_head(v_buf, half, kh, kvh))

        _attend_chunk(
            head_tiles, j, ppb * bs, nb * bs, 0, pos_ref[b], q_ref,
            m_ref, l_ref, acc_ref, **attn,
        )
        half_ref[0] = 1 - half

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        _finalize(out_ref, l_ref, acc_ref)


def _paged_spec_kernel(
    table_ref, pos_ref, q_ref, *refs, nb: int, bs: int, ppb: int,
    kv_quant: str, **attn,
):
    """Grid (B, ceil(NB / ppb), ppb): page i of block j arrives through the
    BlockSpec index map (k, v[, k_scale, v_scale] page tiles) and is stored
    into rows [i*bs, (i+1)*bs) of the block buffers (zeros past the row's
    last page); at its last page step the block is attended like one
    dense-kernel chunk.  The buffers hold the pages as stored, dequantized
    with the block, except packed (kv4) pages: a whole block's nibble
    unpack overflows VMEM, so those are dequantized page by page into f32
    K and V buffers."""
    n = 2 if kv_quant == "bf16" else 4
    pages, bufs = refs[:n], refs[n + 4 :]
    out_ref, m_ref, l_ref, acc_ref = refs[n : n + 4]
    per_page = len(bufs) < n  # packed pages: an f32 K and V buffer only
    L = attn["L"]
    b = pl.program_id(0)
    j = pl.program_id(1)
    i = pl.program_id(2)
    pos_b = pos_ref[b]
    live = jnp.minimum((pos_b + L - 1) // bs + 1, nb)

    @pl.when((j == 0) & (i == 0))
    def _():
        _init_state(m_ref, l_ref, acc_ref)

    rows = pl.ds(pl.multiple_of(i * bs, bs), bs)

    @pl.when(j * ppb + i < live)
    def _():
        if not per_page:
            for page, buf in zip(pages, bufs):
                buf[0, rows] = page[0]
            return
        for kh in range(attn["kvh"]):
            k, v = _head_tiles(kv_quant, kh, *pages)
            bufs[0][0, rows, kh, :] = k
            bufs[1][0, rows, kh, :] = v

    @pl.when((j * ppb < live) & (j * ppb + i >= live))
    def _():  # past the row's last page, in its last block
        for buf in bufs:
            buf[0, rows] = jnp.zeros((bs,) + buf.shape[2:], buf.dtype)

    def head_tiles(kh):
        if per_page:
            return bufs[0][0, :, kh, :], bufs[1][0, :, kh, :]
        return _head_tiles(kv_quant, kh, *bufs[:2], *(bufs[2:] or (None, None)))

    @pl.when((i == ppb - 1) & (j * ppb < live))
    def _():
        _attend_chunk(
            head_tiles, j, ppb * bs, nb * bs, 0, pos_b, q_ref,
            m_ref, l_ref, acc_ref, **attn,
        )

    @pl.when((j == pl.num_programs(1) - 1) & (i == ppb - 1))
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
    """Decode attention straight off the page pool: the kernel gathers each
    row's live K/V pages itself (the scalar-prefetched block table names
    them), a block of `pages_per_block(bs, NB)` pages at a time, with one
    online-softmax update per block, per-row positions, full attention only
    (the paged cache excludes ring windows).  L > 1 is the spec-decode
    verify window (masked-causal; the caller has already scattered all L
    K/V pairs into the pool).

    Only the ceil((pos+L)/bs) live pages of a row are copied, and nothing
    like the (B, NB*bs, KV, D) `paged_gather` view is materialized.  Where
    every pool's pages are copyable (`copyable_pages`: bf16 with head_dim
    a multiple of 128 over an even number of KV heads) the pools are read
    in place (memory space ANY) and the grid is (B, ceil(NB / ppb)): each
    live block's page DMAs are started one grid step ahead, into the other
    half of a double-buffered VMEM block, so the copy of block n+1 overlaps
    the compute of block n (JAX's TPU `paged_attention` scheme, across rows
    too); beyond-live blocks are empty steps.  A block lands as flat rows
    of 32-bit words, the pool's own bytes, from which each KV head's rows
    come out by a strided load and a shift (`_flat_head`).  Otherwise the
    pages arrive one grid step each through the BlockSpec index map
    (clamped to the row's last live page, so beyond-live steps copy
    nothing), grid (B, ceil(NB / ppb), ppb), and are attended in the same
    blocks.

    Quantized layouts (kv_quant "kv8"/"kv4"): the pools hold int storage
    (kv4 packs two nibbles per byte along D) and `k_scale`/`v_scale` are
    the matching scale pages, streamed with their data pages and
    dequantized in VMEM right before the score contraction.
    """
    b, L, h, d = q.shape
    _, bs, kvh, ds = k_pool.shape
    nb = table.shape[1]
    g = h // kvh
    ppb = pages_per_block(bs, nb)
    nbb = pl.cdiv(nb, ppb)
    posv = _norm_pos(pos, b)
    quantized = kv_quant != "bf16"
    assert (k_scale is not None) == quantized, (kv_quant, k_scale is None)
    pools = [k_pool, v_pool] + ([k_scale, v_scale] if quantized else [])
    # Scale pages are never copyable: the DMA path streams unquantized pages.
    dma = all(copyable_pages(p) for p in pools)

    def q_map(bi, *rest):
        return (bi, 0, 0, 0)

    # Query rows grouped per KV head: (B, KV, L*G, D) slabs in and out.
    q_spec = pl.BlockSpec((1, kvh, L * g, d), q_map)
    state = [
        pltpu.VMEM((kvh, L * g, 1), jnp.float32),
        pltpu.VMEM((kvh, L * g, 1), jnp.float32),
        pltpu.VMEM((kvh, L * g, d), jnp.float32),
    ]
    if dma:
        # Double-buffered blocks of ppb pages as flat rows of 32-bit words
        # (token-major, then head; `_flat_head`).
        pack = 4 // jnp.dtype(k_pool.dtype).itemsize
        grid = (b, nbb)
        page_specs = [pl.BlockSpec(memory_space=pl.ANY)] * 2
        word = jnp.uint32 if pack == 2 else k_pool.dtype
        scratch = state + [
            pltpu.VMEM((2, ppb * bs * kvh // pack, ds), word)
        ] * 2 + [
            pltpu.SemaphoreType.DMA((2,)), pltpu.SMEM((1,), jnp.int32)
        ]
        body = _paged_dma_kernel
        # The prefetch crosses rows: both grid axes run in order.
        semantics = ("arbitrary", "arbitrary")
    else:
        def page_map(bi, j, i, tbl, pv):
            # Clamp beyond-live pages to the row's last live page: the block
            # index is then unchanged from the previous step and the copy
            # is elided.
            last = jnp.minimum((pv[bi] + L - 1) // bs, nb - 1)
            return (tbl[bi, jnp.minimum(j * ppb + i, last)], 0, 0, 0)

        grid = (b, nbb, ppb)
        page_specs = [
            pl.BlockSpec((1, bs, kvh, p.shape[-1]), page_map) for p in pools
        ]
        if encoding.kv_layout(kv_quant).pack_ratio > 1:
            bufs = [pltpu.VMEM((1, ppb * bs, kvh, d), jnp.float32)] * 2
        else:
            bufs = [pltpu.VMEM((1, ppb * bs) + p.shape[2:], p.dtype) for p in pools]
        scratch = state + bufs
        body = functools.partial(_paged_spec_kernel, kv_quant=kv_quant)
        semantics = ("parallel", "arbitrary", "arbitrary")

    kernel = functools.partial(
        body, nb=nb, bs=bs, ppb=ppb, L=L, kvh=kvh, g=g, scale=d**-0.5,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[q_spec] + page_specs,
            out_specs=q_spec,
            scratch_shapes=scratch,
        ),
        out_shape=jax.ShapeDtypeStruct((b, kvh, L * g, d), q.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=semantics),
        interpret=interpret,
        name="paged_decode_attention",
    )(table.astype(jnp.int32), posv, _group_rows(q, kvh), *pools)
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
        _attend_chunk(
            lambda kh: _head_tiles(kv_quant, kh, k_ref, v_ref, ks_ref, vs_ref),
            j, kc, s_c, window, pos_b, q_ref, m_ref, l_ref, acc_ref,
            L=L, kvh=kvh, g=g, scale=scale,
        )

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
