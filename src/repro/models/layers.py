"""Shared model layers.  Every dense projection routes through PackedLinear,
so the paper's encoding applies uniformly across the zoo (DESIGN.md §4).
"""

from __future__ import annotations


import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import encoding as encoding_lib
from repro.core import packed
from repro.core import targets as targets_lib
from repro.core.encoding import Phase
from repro.kernels import attn as attn_kernels
from repro.kernels import registry as registry_lib
from repro.parallel import constraints

# ---------------------------------------------------------------------------
# Norms


def norm_init(cfg: ModelConfig, dim: int | None = None) -> dict:
    d = dim or cfg.d_model
    p = {"scale": jnp.ones((d,), jnp.float32)}
    if cfg.norm_kind == "layernorm":
        p["bias"] = jnp.zeros((d,), jnp.float32)
    return p


def norm_apply(params: dict, x: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    if cfg.norm_kind == "layernorm":
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + cfg.norm_eps)
        y = y * params["scale"] + params["bias"]
    else:
        ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(ms + cfg.norm_eps) * params["scale"]
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE


def rope_apply(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: (B, S, H, D), positions: (B, S) int32."""
    d = x.shape[-1]
    half = d // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (B, S, half)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Memory-efficient attention (online softmax over KV chunks)


def _chunk_mask(q_pos, k_pos, *, causal: bool, window: int, k_valid):
    """q_pos: (qc,), k_pos: (kc,) global positions; returns (qc, kc) bool."""
    m = jnp.broadcast_to(k_valid[None, :], (q_pos.shape[0], k_pos.shape[0]))
    if causal:
        m = m & (k_pos[None, :] <= q_pos[:, None])
    if window > 0:
        m = m & (k_pos[None, :] > q_pos[:, None] - window)
    return m


def attention_chunked(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool,
    window: int,
    q_chunk: int,
    kv_chunk: int,
    q_offset: int = 0,
    expand_kv: bool = False,
    causal_bands: int = 1,
    pad_heads_to: int = 0,
    keep_padded_heads: bool = False,
) -> jnp.ndarray:
    """q: (B, Sq, H, D); k, v: (B, Sk, KV, D).  Returns (B, Sq, H, D).

    Flash-style two-level chunking: outer scan over query chunks, inner scan
    over KV chunks with running (max, denom, acc) — peak live memory is one
    (q_chunk x kv_chunk) score block per (batch, head), never Sq x Sk.

    Beyond-paper levers (EXPERIMENTS.md §Perf):
      expand_kv    — repeat KV heads to H so both contractions shard over the
                     full TP axis when kv_heads < TP degree (GQA).
      causal_bands — static query bands whose KV scans stop at the band's own
                     diagonal, skipping always-masked upper-triangle chunks.
    """
    b, sq, h, d = q.shape
    _, sk, kv, _ = k.shape
    scale = d**-0.5

    qc = min(q_chunk, sq)
    kc = min(kv_chunk, sk)
    nq = -(-sq // qc)
    nk = -(-sk // kc)
    q_pad, k_pad = nq * qc - sq, nk * kc - sk
    if q_pad:
        q = jnp.pad(q, ((0, 0), (0, q_pad), (0, 0), (0, 0)))
    if k_pad:
        k = jnp.pad(k, ((0, 0), (0, k_pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, k_pad), (0, 0), (0, 0)))

    h_true = h
    if expand_kv:
        if kv != h:
            k = jnp.repeat(k, h // kv, axis=2)  # kv-major: matches q head order
            v = jnp.repeat(v, h // kv, axis=2)
        if pad_heads_to and h % pad_heads_to:
            hp = h + (-h) % pad_heads_to
            padw = ((0, 0), (0, 0), (0, hp - h), (0, 0))
            q = jnp.pad(q, padw)  # zero q -> uniform softmax -> sliced off below
            k = jnp.pad(k, padw)
            v = jnp.pad(v, padw)
            h = hp
        k = constraints.shard(k, ("data", "pod"), None, "model")
        v = constraints.shard(v, ("data", "pod"), None, "model")
        q = constraints.shard(q, ("data", "pod"), None, "model")
        kv_eff, g = h, 1
    else:
        kv_eff, g = kv, h // kv

    qr = q.reshape(b, nq, qc, kv_eff, g, d)
    kr = k.reshape(b, nk, kc, kv_eff, d)
    vr = v.reshape(b, nk, kc, kv_eff, d)
    k_len = sk

    def q_step(qi, nk_lim):
        qblk = qr[:, qi] * scale  # (B, qc, KV, G, D)
        q_pos = q_offset + qi * qc + jnp.arange(qc)

        def kv_step(carry, ki):
            m_run, l_run, acc = carry
            kblk = kr[:, ki]  # (B, kc, KV, D)
            vblk = vr[:, ki]
            k_pos = ki * kc + jnp.arange(kc)
            s = jnp.einsum(
                "bqkgd,bckd->bkgqc", qblk, kblk, preferred_element_type=jnp.float32
            )  # (B, KV, G, qc, kc)
            mask = _chunk_mask(
                q_pos, k_pos, causal=causal, window=window, k_valid=k_pos < k_len
            )
            s = jnp.where(mask[None, None, None], s, -jnp.inf)
            m_new = jnp.maximum(m_run, s.max(axis=-1))
            # Guard fully-masked rows (no valid keys yet): keep m finite.
            m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
            p = jnp.exp(s - m_safe[..., None])
            p = jnp.where(mask[None, None, None], p, 0.0)
            corr = jnp.where(
                jnp.isfinite(m_run), jnp.exp(m_run - m_safe), 0.0
            )
            l_new = l_run * corr + p.sum(axis=-1)
            pv = jnp.einsum(
                "bkgqc,bckd->bkgqd", p, vblk, preferred_element_type=jnp.float32
            )
            acc_new = acc * corr[..., None] + pv
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, kv_eff, g, qc), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((b, kv_eff, g, qc), jnp.float32)
        a0 = jnp.zeros((b, kv_eff, g, qc, d), jnp.float32)
        (m_f, l_f, acc_f), _ = jax.lax.scan(kv_step, (m0, l0, a0), jnp.arange(nk_lim))
        out = acc_f / jnp.maximum(l_f, 1e-30)[..., None]
        return out  # (B, KV, G, qc, D)

    bands = causal_bands if (causal and window == 0 and q_offset == 0) else 1
    bands = max(1, min(bands, nq))
    if bands == 1:
        outs = jax.lax.map(lambda qi: q_step(qi, nk), jnp.arange(nq))
    else:
        per = -(-nq // bands)
        pieces = []
        for bnd in range(bands):
            lo = bnd * per
            hi = min(nq, lo + per)
            if lo >= hi:
                break
            # KV chunks visible to the last query row of this band.
            nk_lim = min(nk, -(-(hi * qc) // kc))
            pieces.append(
                jax.lax.map(lambda qi: q_step(qi, nk_lim), jnp.arange(lo, hi))
            )
        outs = jnp.concatenate(pieces, axis=0)
    out = outs.transpose(1, 0, 4, 2, 3, 5).reshape(b, -1, h, d)
    if keep_padded_heads:
        return out[:, :sq].astype(q.dtype)  # (B, Sq, h_padded, D)
    return out[:, :sq, :h_true].astype(q.dtype)


def paged_gather(
    pool: jnp.ndarray, table: jnp.ndarray, *, nb_blocks: int | None = None
) -> jnp.ndarray:
    """Gather a slot-logical dense cache view from a paged pool.

    pool: (P, bs, KV, D) physical pages; table: (B, NB) int32 page ids.
    Returns (B, NB*bs, KV, D) — row b's logical positions in order, exactly
    the dense cache slice the slot would hold (positions past the slot's
    allocated blocks read whatever page the table points at — the decode
    mask `slot <= pos` never attends them).

    `nb_blocks` bounds the gather to the first nb_blocks logical blocks
    (static): short sequences should not pay for empty trailing table
    entries even on this reference/fallback path.  The serving engine
    narrows the table leaf itself to the live page count
    (engine._with_tables), so its fallback gathers are bounded for free;
    the kernel path (kernels/attn.py paged_decode_attention) never
    materializes this view at all."""
    if nb_blocks is not None and nb_blocks < table.shape[1]:
        table = table[:, :nb_blocks]
    b, nb = table.shape
    g = pool[table]  # (B, NB, bs, KV, D)
    return g.reshape(b, nb * pool.shape[1], *pool.shape[2:])


def _masked_softmax(s: jnp.ndarray, valid: jnp.ndarray) -> jnp.ndarray:
    """Softmax over the last axis with `valid` masking, safe for rows with
    NO valid entry (all -inf): those rows come back all-zero instead of NaN
    — a padded admission slot must never poison the batch."""
    s = jnp.where(valid, s, -jnp.inf)
    m = s.max(axis=-1, keepdims=True)
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.exp(s - m_safe)
    p = jnp.where(valid, p, 0.0)
    return p / jnp.maximum(p.sum(axis=-1, keepdims=True), 1e-30)


def attention_decode(
    q: jnp.ndarray,
    k_cache: jnp.ndarray,
    v_cache: jnp.ndarray,
    *,
    pos: jnp.ndarray,
    window: int,
) -> jnp.ndarray:
    """Decode-phase attention against a (ring-buffered) cache.

    q: (B, L, H, D); caches: (B, S_c, KV, D); pos: () shared position, or (B,)
    per-row positions of the FIRST query token (position-vectorized decode:
    every batch row attends its own history length; the caller has already
    written the L new tokens' K/V at slots (pos + j) % S_c).

    L == 1 is the plain one-token decode.  L > 1 is the speculative-decode
    verify window: query j sits at position pos + j and the `slot <= pos + j`
    mask makes the window masked-causal — draft token j attends the committed
    history plus drafts 0..j (their K/V were scattered into the cache by the
    same dispatch before this read), never drafts j+1..L-1.
    """
    b, L, h, d = q.shape
    _, s_c, kvh, _ = k_cache.shape
    # Ring caches hold only the last `window` positions: a draft key at slot
    # (pos+i) % s_c would alias INSIDE an earlier query's age window, so the
    # mask below cannot express causality for L > 1 — reject loudly instead
    # of attending future drafts (spec decode is full-attention only).
    assert L == 1 or window == 0, (
        "multi-token decode (spec-decode verify) requires window == 0; "
        f"got L={L}, window={window}"
    )
    g = h // kvh
    scale = d**-0.5
    qg = q.reshape(b, L, kvh, g, d) * scale
    s = jnp.einsum(
        "blkgd,bskd->blkgs", qg, k_cache, preferred_element_type=jnp.float32
    )
    slot = jnp.arange(s_c)
    pos = jnp.asarray(pos)
    qpos = (pos[:, None] if pos.ndim == 1 else pos) + jnp.arange(L)
    qpos = jnp.atleast_2d(qpos)  # (B, L) vectorized | (1, L) shared-pos
    if window > 0:
        # Ring buffer: slots hold positions qpos-age; valid while age < window
        # and the position exists.  age = (qpos - slot) mod S_c.  Rows still
        # inside their first window (qpos < window — nothing has wrapped or
        # aged out) reduce exactly to the cheap prefix mask: slot s holds
        # position s, age = qpos - s >= 0 and < window iff s <= qpos.  Only
        # wrapped rows pay the mod.
        age = jnp.mod(qpos[..., None] - slot, s_c)
        ring = age < jnp.minimum(qpos[..., None] + 1, window)
        valid = jnp.where(qpos[..., None] < window, slot <= qpos[..., None], ring)
    else:
        valid = slot <= qpos[..., None]
    # valid: (B, L, S_c) vectorized, (1, L, S_c) shared-pos.  The guarded
    # softmax keeps fully-masked rows (padded admission slots) at zero
    # instead of NaN.
    p = _masked_softmax(s, valid[:, :, None, None, :])
    out = jnp.einsum(
        "blkgs,bskd->blkgd", p, v_cache, preferred_element_type=jnp.float32
    )
    return out.reshape(b, L, h, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# Attention layer (projections + cache plumbing)


def attention_init(key, cfg: ModelConfig, enc: packed.EncodingConfig, *, cross: bool = False) -> dict:
    ks = jax.random.split(key, 4)
    d, hd = cfg.d_model, cfg.head_dim
    h, kvh = cfg.num_heads, cfg.num_kv_heads
    dt = cfg.activation_dtype
    return {
        "wq": packed.linear_init(ks[0], d, h * hd, enc=enc, use_bias=cfg.qkv_bias, dtype=dt),
        "wk": packed.linear_init(ks[1], d, kvh * hd, enc=enc, use_bias=cfg.qkv_bias, dtype=dt),
        "wv": packed.linear_init(ks[2], d, kvh * hd, enc=enc, use_bias=cfg.qkv_bias, dtype=dt),
        "wo": packed.linear_init(ks[3], h * hd, d, enc=enc, dtype=dt),
    }


def attention_apply(
    params: dict,
    x: jnp.ndarray,
    *,
    cfg: ModelConfig,
    enc: packed.EncodingConfig,
    phase: Phase,
    cache: dict | None = None,
    pos: jnp.ndarray | int = 0,
    kv_src: jnp.ndarray | None = None,
    causal: bool = True,
    use_rope: bool = True,
    window: int | None = None,
):
    """Returns (out, new_cache). kv_src != None -> cross attention (no cache write).

    `pos` may be a scalar (all rows share a position — prefill offset or
    uniform decode) or a (B,) vector (position-vectorized decode: each batch
    row carries its own position of x[:, 0]; DECODE only).  At DECODE, S > 1
    is a per-row masked-causal window — row b's S tokens occupy positions
    pos_b .. pos_b+S-1, all S K/V pairs are written, and attention is
    masked-causal inside the window; full attention only (window == 0 —
    attention_decode rejects ring caches for S > 1).  Two callers ride it:
    the speculative-decode verify window, and the token-budget mixed step
    (serving/engine.py), where decode rows carry 1 real token (+ drafts) and
    chunked-prefill rows carry a window of prompt tokens at pos_b = tokens
    already cached — `slot <= pos_b + j` is exactly chunked-prefill masking
    (full history + causal-in-window), so one dispatch serves both phases.
    Window positions past a row's real content (padding to the rectangular
    S) write garbage K/V at FUTURE positions only — masked until a later
    real write lands there first, the same contract rejected spec drafts
    rely on.  The engine caps S so pos_b + S <= max cache length for every
    participating row; the write indexing below still clamps defensively so
    an out-of-contract pad can never scatter outside the row's cache.
    """
    b, s, d = x.shape
    h, kvh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    window = cfg.sliding_window if window is None else window
    pos_vec = jnp.asarray(pos).ndim == 1  # per-row positions

    q = packed.linear_apply(params["wq"], x, n=h * hd, phase=phase, enc=enc)
    kv_in = kv_src if kv_src is not None else x
    k = packed.linear_apply(params["wk"], kv_in, n=kvh * hd, phase=phase, enc=enc)
    v = packed.linear_apply(params["wv"], kv_in, n=kvh * hd, phase=phase, enc=enc)
    q = q.reshape(b, s, h, hd)
    k = k.reshape(b, kv_in.shape[1], kvh, hd)
    v = v.reshape(b, kv_in.shape[1], kvh, hd)
    if cfg.tp_attn_expand_kv:
        # SP/TP: query heads over the model axis (divisibility-sanitized).
        q = constraints.shard(q, ("data", "pod"), None, "model")

    if use_rope and kv_src is None:
        if pos_vec:
            positions = jnp.asarray(pos)[:, None] + jnp.arange(s)[None, :]
        else:
            positions = pos + jnp.arange(s)[None, :]
            positions = jnp.broadcast_to(positions, (b, s))
        q = rope_apply(q, positions, cfg.rope_theta)
        k = rope_apply(k, positions, cfg.rope_theta)

    new_cache = cache
    if (
        phase is Phase.DECODE and cache is not None and kv_src is None
        and "table" in cache
    ):
        # Paged KV cache: pool (P, bs, KV, D) + per-slot block table (B, NB).
        # The serving scan may pass every layer's pages stacked, (L*P, bs,
        # KV, D), with a table that already names this layer's ones.
        # Row b writes token j into page table[b, (pos+j)//bs] at offset
        # (pos+j) % bs (the engine guarantees every written page exists and is
        # private to the slot — shared prefix pages are immutable full
        # blocks), then attends the table-gathered logical view with the SAME
        # per-row `pos` masking as the dense path.  S > 1 is the speculative-
        # decode verify window: all S positions scatter before the gather, so
        # draft keys are visible to later draft queries (masked-causal).
        # Idle rows point at the scratch page.
        assert window == 0, "paged cache excludes sliding-window configs"
        table = cache["table"]
        bs_page = cache["k"].shape[1]
        # The cache pytree is self-describing: int8 pools are kv8, packed
        # uint8 pools are kv4 (core/encoding.KVLayout) — jitted model code
        # never needs the engine config threaded through.
        layout = encoding_lib.kv_layout_for_storage(cache["k"].dtype)
        posv = jnp.asarray(pos)
        posm = (posv[:, None] if posv.ndim == 1 else posv) + jnp.arange(s)
        posm = jnp.broadcast_to(posm, (b, s))
        # Window pads past the last logical block clamp to the final table
        # entry (scratch unless the row's table is full — and the engine
        # caps the window so a full row never pads past max_seq).
        blk = jnp.minimum(posm // bs_page, table.shape[1] - 1)
        pg = table[jnp.arange(b)[:, None], blk]  # (B, S)
        off = posm % bs_page
        # The B x S new rows are one scatter per leaf, which XLA writes in
        # place in the pool's own layout, also into the whole stacked pool
        # the serving scan carries.
        with jax.named_scope("kv_write"):
            if layout.quantized:
                # Quantize on write: the pool only ever holds int storage
                # plus the per-token scale pages riding at the same page ids.
                kq, ksc = layout.quantize(k)
                vq, vsc = layout.quantize(v)
                k_pool = cache["k"].at[pg, off].set(kq)
                v_pool = cache["v"].at[pg, off].set(vq)
                k_scale = cache["k_scale"].at[pg, off].set(ksc)
                v_scale = cache["v_scale"].at[pg, off].set(vsc)
            else:
                k_pool = cache["k"].at[pg, off].set(k)
                v_pool = cache["v"].at[pg, off].set(v)
                k_scale = v_scale = None
        choice = registry_lib.select_attn(
            phase=Phase.DECODE, s=table.shape[1] * bs_page, target=enc.target,
            requested=enc.attn_backend, kv=layout.name,
        )
        if choice.backend == "pallas":
            # Fused paged-decode kernel: K/V pages gathered INSIDE the
            # dispatch (scalar-prefetched block table), a block of
            # attn.pages_per_block pages per online-softmax update, only
            # the slot's live pages copied — the (B, NB*bs, KV, D) logical
            # view is never materialized.  Quantized layouts stream the
            # scale pages alongside and dequantize in VMEM.
            out = attn_kernels.paged_decode_attention(
                q, k_pool, v_pool, table, posm[:, 0],
                k_scale=k_scale, v_scale=v_scale, kv_quant=layout.name,
                interpret=targets_lib.resolve_interpret(enc.interpret),
            )
        elif layout.quantized:
            # XLA fallback: gather the quantized view AND its scale view,
            # dequantize, then run the reference decode — the page stream
            # and the codec stay identical to the kernel path, only the
            # gather materialization differs.
            out = attention_decode(
                q,
                layout.dequantize(
                    paged_gather(k_pool, table), paged_gather(k_scale, table)
                ),
                layout.dequantize(
                    paged_gather(v_pool, table), paged_gather(v_scale, table)
                ),
                pos=pos, window=0,
            )
        else:
            out = attention_decode(
                q, paged_gather(k_pool, table), paged_gather(v_pool, table),
                pos=pos, window=0,
            )
        new_cache = {"k": k_pool, "v": v_pool, "table": table}
        if layout.quantized:
            new_cache["k_scale"] = k_scale
            new_cache["v_scale"] = v_scale
    elif phase is Phase.DECODE and cache is not None and kv_src is None:
        s_c = cache["k"].shape[1]
        if pos_vec:
            # Per-row scatter: row i writes its own S cache slots (one token
            # per position pos_i + j; S > 1 is the spec-decode verify window
            # or a mixed-step prefill chunk, whose beyond-content writes stay
            # masked until overwritten).  Full-attention windows clamp at the
            # cache edge: the engine caps S per row, so a clamped index is
            # only ever a pad colliding with other pads.
            positions = jnp.asarray(pos)[:, None] + jnp.arange(s)  # (B, S)
            wslot = (
                jnp.mod(positions, s_c) if window > 0
                else jnp.minimum(positions, s_c - 1)
            )
            k_cache = cache["k"].at[jnp.arange(b)[:, None], wslot].set(k)
            v_cache = cache["v"].at[jnp.arange(b)[:, None], wslot].set(v)
        else:
            slot = jnp.mod(jnp.asarray(pos), s_c) if window > 0 else jnp.asarray(pos)
            k_cache = jax.lax.dynamic_update_slice(cache["k"], k, (0, slot, 0, 0))
            v_cache = jax.lax.dynamic_update_slice(cache["v"], v, (0, slot, 0, 0))
        new_cache = {"k": k_cache, "v": v_cache}
        choice = registry_lib.select_attn(
            phase=Phase.DECODE, s=s_c, target=enc.target,
            requested=enc.attn_backend,
        )
        if choice.backend == "pallas" and (s == 1 or window == 0):
            out = attn_kernels.dense_decode_attention(
                q, k_cache, v_cache, jnp.asarray(pos, jnp.int32),
                window=window,
                kv_chunk=choice.blocks[1] if choice.blocks else None,
                interpret=targets_lib.resolve_interpret(enc.interpret),
            )
        else:
            out = attention_decode(q, k_cache, v_cache, pos=pos, window=window)
    else:
        # When W_o's packed K-padding already covers the padded head count,
        # the padded heads flow straight into the (zero) padding rows of W_o —
        # no slice, no reshard (EXPERIMENTS.md §Perf, qwen iteration 2).
        keep_pad = False
        wo_w = params["wo"].get("w_packed", params["wo"].get("w_q"))
        if cfg.tp_attn_expand_kv and cfg.pad_attn_heads_to and wo_w is not None:
            hp = h + (-h) % cfg.pad_attn_heads_to
            k1_cap = wo_w.shape[-3] * wo_w.shape[-1]
            keep_pad = hp * hd <= k1_cap
        # Chunked prefill: attend over previously-cached positions too
        # (static pos offset; dense cache only — window ring excluded).
        q_off = 0
        k_att, v_att = k, v
        prior = isinstance(pos, int) and pos > 0 and cache is not None
        if prior and kv_src is None and window == 0:
            k_att = jnp.concatenate([cache["k"][:, :pos], k], axis=1)
            v_att = jnp.concatenate([cache["v"][:, :pos], v], axis=1)
            q_off = pos
        # Flash prefill eligibility: inference-side plain self-attention
        # only — the TP expand_kv reshard and cross attention keep the
        # chunked reference (the kernel has no sharding constraints inside
        # it), and TRAIN needs autodiff through the attention, which the
        # forward-only Pallas kernel does not provide.
        choice = registry_lib.select_attn(
            phase=Phase.PREFILL, s=k_att.shape[1], target=enc.target,
            requested=enc.attn_backend,
        )
        if (
            choice.backend == "pallas"
            and phase is not Phase.TRAIN
            and kv_src is None
            and not cfg.tp_attn_expand_kv
        ):
            qc, kc = (choice.blocks or (cfg.q_chunk, cfg.kv_chunk))[:2]
            out = attn_kernels.flash_prefill_attention(
                q, k_att, v_att,
                causal=causal,
                window=window,
                q_offset=q_off,
                q_chunk=qc,
                kv_chunk=kc,
                interpret=targets_lib.resolve_interpret(enc.interpret),
            )
        else:
            out = attention_chunked(
                q, k_att, v_att,
                causal=causal and kv_src is None,
                window=window,
                q_chunk=cfg.q_chunk,
                kv_chunk=cfg.kv_chunk,
                q_offset=q_off,
                expand_kv=cfg.tp_attn_expand_kv,
                causal_bands=cfg.causal_bands,
                pad_heads_to=cfg.pad_attn_heads_to,
                keep_padded_heads=keep_pad,
            )
        if cache is not None and kv_src is None:
            assert "table" not in cache, (
                "paged caches are decode-only; the engine prefills into a "
                "temporary dense cache and scatters blocks into the pool"
            )
            s_c = cache["k"].shape[1]
            if window > 0 and s >= s_c:
                new_cache = {"k": k[:, -s_c:], "v": v[:, -s_c:]}
            else:
                off = q_off if window == 0 else 0
                k_cache = jax.lax.dynamic_update_slice(cache["k"], k[:, -s_c:], (0, off, 0, 0))
                v_cache = jax.lax.dynamic_update_slice(cache["v"], v[:, -s_c:], (0, off, 0, 0))
                new_cache = {"k": k_cache, "v": v_cache}

    out = out.reshape(b, s, out.shape[2] * hd)
    return packed.linear_apply(params["wo"], out, n=d, phase=phase, enc=enc), new_cache


def attn_cache_init(cfg: ModelConfig, batch: int, max_seq: int) -> dict:
    s_c = min(max_seq, cfg.sliding_window) if cfg.sliding_window else max_seq
    dt = cfg.activation_dtype
    return {
        "k": jnp.zeros((batch, s_c, cfg.num_kv_heads, cfg.head_dim), dt),
        "v": jnp.zeros((batch, s_c, cfg.num_kv_heads, cfg.head_dim), dt),
    }


def attn_paged_cache_init(
    cfg: ModelConfig, batch: int, max_seq: int, *, block_size: int,
    num_pages: int, kv_quant: str = "bf16",
) -> dict:
    """Paged attention cache: a page pool + per-slot block table, replacing
    the dense (batch, max_seq) reservation.  Page 0 is the scratch page idle
    rows write to (serving/paged.py); tables init to it.

    `kv_quant` selects the KVLayout (core/encoding): bf16 keeps today's
    activation-dtype pools bit-for-bit; kv8/kv4 store int pools (kv4 packs
    two nibbles per byte along head_dim) plus float32 `k_scale`/`v_scale`
    SCALE PAGES with the same (num_pages, block) page geometry — one page
    id addresses a token block's data and its scales together, so
    alloc/free/COW/rollback in serving/paged.py manage both in lockstep."""
    assert cfg.sliding_window == 0, "paged cache excludes sliding-window configs"
    nb = -(-max_seq // block_size)
    layout = encoding_lib.kv_layout(kv_quant)
    dt = cfg.activation_dtype if not layout.quantized else layout.storage_dtype
    hd = (
        cfg.head_dim if not layout.quantized
        else layout.storage_head_dim(cfg.head_dim)
    )
    out = {
        "k": jnp.zeros((num_pages, block_size, cfg.num_kv_heads, hd), dt),
        "v": jnp.zeros((num_pages, block_size, cfg.num_kv_heads, hd), dt),
        "table": jnp.zeros((batch, nb), jnp.int32),
    }
    if layout.quantized:
        sshape = layout.scale_shape((num_pages, block_size), cfg.num_kv_heads)
        out["k_scale"] = jnp.zeros(sshape, jnp.float32)
        out["v_scale"] = jnp.zeros(sshape, jnp.float32)
    return out


# ---------------------------------------------------------------------------
# MLP


def mlp_init(key, cfg: ModelConfig, enc: packed.EncodingConfig) -> dict:
    d, f = cfg.d_model, cfg.d_ff
    dt = cfg.activation_dtype
    if cfg.mlp_kind == "swiglu":
        k1, k2, k3 = jax.random.split(key, 3)
        return {
            "w_gate": packed.linear_init(k1, d, f, enc=enc, dtype=dt),
            "w_up": packed.linear_init(k2, d, f, enc=enc, dtype=dt),
            "w_down": packed.linear_init(k3, f, d, enc=enc, dtype=dt),
        }
    k1, k2 = jax.random.split(key, 2)
    return {
        "w_up": packed.linear_init(k1, d, f, enc=enc, use_bias=True, dtype=dt),
        "w_down": packed.linear_init(k2, f, d, enc=enc, use_bias=True, dtype=dt),
    }


def mlp_apply(params, x, *, cfg: ModelConfig, enc, phase: Phase) -> jnp.ndarray:
    d, f = cfg.d_model, cfg.d_ff
    if cfg.mlp_kind == "swiglu":
        gate = packed.linear_apply(params["w_gate"], x, n=f, phase=phase, enc=enc)
        up = packed.linear_apply(params["w_up"], x, n=f, phase=phase, enc=enc)
        hidden = jax.nn.silu(gate.astype(jnp.float32)).astype(x.dtype) * up
    else:
        up = packed.linear_apply(params["w_up"], x, n=f, phase=phase, enc=enc)
        hidden = jax.nn.gelu(up.astype(jnp.float32)).astype(x.dtype)
    return packed.linear_apply(params["w_down"], hidden, n=d, phase=phase, enc=enc)


# ---------------------------------------------------------------------------
# MoE (token-choice top-k, capacity-bounded scatter dispatch)


def moe_init(key, cfg: ModelConfig, enc: packed.EncodingConfig) -> dict:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.num_experts
    dt = cfg.activation_dtype
    kr, kg, ku, kd = jax.random.split(key, 4)

    def stack_init(k, din, dout):
        keys = jax.random.split(k, e)
        # Stacked per-expert linear params (works for packed / int8 / plain).
        return jax.vmap(
            lambda kk: packed.linear_init(kk, din, dout, enc=enc, dtype=dt)
        )(keys)

    return {
        "router": packed.linear_init(kr, d, e, enc=enc, dtype=jnp.float32),
        "w_gate": stack_init(kg, d, f),   # dict of (E, ...) leaves
        "w_up": stack_init(ku, d, f),
        "w_down": stack_init(kd, f, d),
    }


def _expert_matmul(w_stack, x, *, n, phase, enc):
    """x: (E, ..., D) batched over experts; w_stack: dict of (E, ...) leaves."""

    def one(w, xe):
        return packed.linear_apply(w, xe, n=n, phase=phase, enc=enc)

    return jax.vmap(one)(w_stack, x)


def _dp_axes_and_size():
    """Ambient-mesh DP axes for shard_map dispatch; (None, 1) when no mesh."""
    try:
        am = jax.sharding.get_abstract_mesh()
    except Exception:
        return None, ()
    if am is None or getattr(am, "empty", True):
        return None, ()
    dp = tuple(a for a in ("pod", "data") if a in am.axis_names)
    return am, dp


def moe_apply(params, x, *, cfg: ModelConfig, enc, phase: Phase):
    """Returns (out, aux_loss). Capacity-bounded token-choice top-k routing.

    Beyond-paper §Perf levers:
      cfg.moe_dispatch_groups > 1 — group-local ranking/scatter aligned to the
        DP shards (capacity per group).
      cfg.moe_shard_map — dispatch/combine under shard_map: shard-local by
        construction; expert FFNs remain auto-SPMD (TP-sharded weights).
    """
    b, s, d = x.shape
    e, k = cfg.num_experts, cfg.experts_per_token
    f = cfg.d_ff
    t = b * s
    xt = x.reshape(t, d)

    logits = packed.linear_apply(
        params["router"], xt, n=e, phase=phase, enc=enc, out_dtype=jnp.float32
    )
    probs = jax.nn.softmax(logits, axis=-1)
    gate, eidx = jax.lax.top_k(probs, k)  # (T, k)
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)

    if cfg.moe_dense_decode and phase is Phase.DECODE:
        # Dispatch-free decode: every expert sees every live token.
        xe = jnp.broadcast_to(xt[None], (e, t, d))
        gate_h = _expert_matmul(params["w_gate"], xe, n=f, phase=phase, enc=enc)
        up_h = _expert_matmul(params["w_up"], xe, n=f, phase=phase, enc=enc)
        hidden = jax.nn.silu(gate_h.astype(jnp.float32)).astype(x.dtype) * up_h
        ys = _expert_matmul(params["w_down"], hidden, n=d, phase=phase, enc=enc)
        # Combine: per-token gate over its top-k experts, zero elsewhere.
        wfull = jnp.zeros((t, e), jnp.float32)
        wfull = wfull.at[jnp.arange(t)[:, None], eidx].set(gate)
        out = jnp.einsum("etd,te->td", ys.astype(jnp.float32), wfull)
        onehot = jax.nn.one_hot(eidx, e, dtype=jnp.float32)
        aux = e * jnp.sum(probs.mean(0) * onehot.sum(1).mean(0))
        return out.astype(x.dtype).reshape(b, s, d), aux

    if cfg.moe_shard_map:
        mesh, dp = _dp_axes_and_size()
        dp_size = 1
        if mesh is not None and dp:
            for a in dp:
                dp_size *= mesh.shape[a]
        if mesh is not None and dp and dp_size > 1 and t % dp_size == 0:
            out, aux = _moe_shard_map_apply(
                params, xt, gate, eidx, probs,
                cfg=cfg, enc=enc, phase=phase, mesh=mesh, dp=dp, dp_size=dp_size,
            )
            return out.reshape(b, s, d), aux

    groups = cfg.moe_dispatch_groups if cfg.moe_dispatch_groups > 1 else 1
    if t % groups:
        groups = 1
    tg = t // groups
    cap = max(1, int(cfg.capacity_factor * tg * k / e))

    # Position of each (token, slot) in its expert queue; slot-major priority,
    # group-local rank when groups > 1.
    onehot = jax.nn.one_hot(eidx, e, dtype=jnp.float32)  # (T, k, E)
    oh_g = onehot.reshape(groups, tg, k, e).transpose(0, 2, 1, 3).reshape(
        groups, k * tg, e
    )  # slot-major within group
    pos_flat = (jnp.cumsum(oh_g, axis=1) - oh_g) * oh_g
    position = (
        pos_flat.sum(-1).reshape(groups, k, tg).transpose(0, 2, 1).astype(jnp.int32)
    )  # (G, tg, k)
    keep = position < cap
    eidx_g = eidx.reshape(groups, tg, k)
    gate_g = gate.reshape(groups, tg, k)
    xt_g = xt.reshape(groups, tg, d)

    # Dispatch: scatter tokens into (G, E, C, D) buffers; groups shard over
    # the data axes (token-parallel side of the EP layout, DESIGN.md §5).
    buf = constraints.shard(
        jnp.zeros((groups, e, cap, d), x.dtype), ("data", "pod")
    )
    safe_pos = jnp.where(keep, position, cap - 1)
    contrib = jnp.where(keep[..., None], 1.0, 0.0).astype(x.dtype)
    gsel = jnp.arange(groups)[:, None, None]
    buf = buf.at[gsel, eidx_g, safe_pos].add(
        xt_g[:, :, None, :] * contrib, mode="drop"
    )
    buf = constraints.shard(buf, ("data", "pod"))

    # Expert FFNs (batched over E; group dim folds into the row dim).
    buf_e = buf.transpose(1, 0, 2, 3)  # (E, G, C, D)
    gate_h = _expert_matmul(params["w_gate"], buf_e, n=f, phase=phase, enc=enc)
    up_h = _expert_matmul(params["w_up"], buf_e, n=f, phase=phase, enc=enc)
    hidden = jax.nn.silu(gate_h.astype(jnp.float32)).astype(x.dtype) * up_h
    ys = _expert_matmul(params["w_down"], hidden, n=d, phase=phase, enc=enc)
    ys = constraints.shard(ys, None, ("data", "pod"))  # (E, G, C, D)

    # Combine: gather back and weight.
    gathered = ys.transpose(1, 0, 2, 3)[gsel, eidx_g, safe_pos]  # (G, tg, k, D)
    w = (gate_g * keep).astype(jnp.float32)[..., None]
    out = (gathered.astype(jnp.float32) * w).sum(axis=2).astype(x.dtype)

    # Load-balance aux loss (Switch-style).
    me = probs.mean(axis=0)
    ce = onehot.sum(axis=1).mean(axis=0)
    aux = e * jnp.sum(me * ce)
    return out.reshape(b, s, d), aux


def _moe_shard_map_apply(params, xt, gate, eidx, probs, *, cfg, enc, phase,
                         mesh, dp, dp_size):
    """shard_map dispatch/combine (see moe_apply docstring)."""
    from jax.sharding import PartitionSpec as P

    e, k, d, f = cfg.num_experts, cfg.experts_per_token, cfg.d_model, cfg.d_ff
    t = xt.shape[0]
    tg = t // dp_size
    cap = max(1, int(cfg.capacity_factor * tg * k / e))

    def dispatch(xt_s, eidx_s):
        # All arrays here are one DP shard's slice: (tg, ...).
        onehot = jax.nn.one_hot(eidx_s, e, dtype=jnp.float32)        # (tg,k,e)
        flat = onehot.transpose(1, 0, 2).reshape(k * tg, e)          # slot-major
        pos = ((jnp.cumsum(flat, 0) - flat) * flat).sum(-1)
        pos = pos.reshape(k, tg).transpose(1, 0).astype(jnp.int32)   # (tg,k)
        keep = pos < cap
        safe = jnp.where(keep, pos, cap - 1)
        contrib = jnp.where(keep[..., None], 1.0, 0.0).astype(xt_s.dtype)
        buf = jnp.zeros((e, cap, d), xt_s.dtype)
        buf = buf.at[eidx_s, safe].add(xt_s[:, None, :] * contrib, mode="drop")
        return buf, safe, keep

    buf, safe_pos, keep = jax.shard_map(
        dispatch, mesh=mesh,
        in_specs=(P(dp), P(dp)),
        out_specs=(P(None, dp), P(dp), P(dp)),
    )(xt, eidx)
    # buf: (E, dp_size*cap, D), capacity sharded over the DP axes.

    gate_h = _expert_matmul(params["w_gate"], buf, n=f, phase=phase, enc=enc)
    up_h = _expert_matmul(params["w_up"], buf, n=f, phase=phase, enc=enc)
    hidden = jax.nn.silu(gate_h.astype(jnp.float32)).astype(xt.dtype) * up_h
    ys = _expert_matmul(params["w_down"], hidden, n=d, phase=phase, enc=enc)
    ys = constraints.shard(ys, None, ("pod", "data"))  # keep capacity on DP

    def combine(ys_s, eidx_s, safe_s, keep_s, gate_s):
        gathered = ys_s[eidx_s, safe_s]  # (tg, k, d) — local capacity slice
        w = (gate_s * keep_s).astype(jnp.float32)[..., None]
        return (gathered.astype(jnp.float32) * w).sum(axis=1).astype(ys_s.dtype)

    out = jax.shard_map(
        combine, mesh=mesh,
        in_specs=(P(None, dp), P(dp), P(dp), P(dp), P(dp)),
        out_specs=P(dp),
    )(ys, eidx, safe_pos, keep, gate)

    onehot = jax.nn.one_hot(eidx, e, dtype=jnp.float32)
    me = probs.mean(axis=0)
    ce = onehot.sum(axis=1).mean(axis=0)
    aux = e * jnp.sum(me * ce)
    return out.astype(xt.dtype), aux
