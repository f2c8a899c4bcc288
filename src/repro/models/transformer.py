"""Full model assembly: embeddings, grouped layer scan, head; all families.

Layer stacking uses a *grouped scan*: the layer list is `block_pattern`
repeated (e.g. ("rec","rec","attn") for RecurrentGemma); full pattern groups
are stacked and driven by one `lax.scan` (small HLO, fast 512-device compiles),
a partial tail group (when num_layers % len(pattern) != 0) is applied inline.
Under Phase.TRAIN each scan body is rematerialized (jax.checkpoint).

Frontends (audio frames / vision patches) are stubs per the assignment: the
caller provides precomputed embeddings; whisper additionally runs its real
encoder stack over the provided frame embeddings.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import packed
from repro.core.encoding import Phase
from repro.models import layers as L
from repro.models.blocks import BLOCKS


# ---------------------------------------------------------------------------
# Layer grouping


def _pattern_layout(cfg: ModelConfig) -> tuple[int, tuple[str, ...]]:
    p = cfg.block_pattern
    return cfg.num_layers // len(p), tuple(p[: cfg.num_layers % len(p)])


def _group_init(key, cfg, enc, pattern):
    parts = []
    for i, t in enumerate(pattern):
        parts.append(BLOCKS[t][0](jax.random.fold_in(key, i), cfg, enc))
    return tuple(parts)


def _stacked_group_init(key, cfg, enc, pattern, n):
    keys = jax.random.split(key, n)
    return jax.vmap(lambda k: _group_init(k, cfg, enc, pattern))(keys)


def _group_cache_init(cfg, pattern, batch, max_seq):
    return tuple(BLOCKS[t][2](cfg, batch, max_seq) for t in pattern)


def _stack_caches(cache, n):
    return jax.tree.map(lambda x: jnp.broadcast_to(x, (n,) + x.shape), cache)


# ---------------------------------------------------------------------------
# Model


def model_init(key: jax.Array, cfg: ModelConfig, enc: packed.EncodingConfig) -> dict:
    ks = jax.random.split(key, 8)
    d, v = cfg.d_model, cfg.vocab_size
    dt = cfg.activation_dtype
    n_groups, tail = _pattern_layout(cfg)

    # Vocab rows padded to a shardable multiple; ids never index the pad and
    # tied-head logits are sliced back to vocab_size.
    v_pad = v + ((-v) % max(256, enc.shard_multiple))
    params: dict[str, Any] = {
        "embed": (d**-0.5) * jax.random.normal(ks[0], (v_pad, d), jnp.float32).astype(dt),
        "final_norm": L.norm_init(cfg),
        "groups": _stacked_group_init(ks[1], cfg, enc, cfg.block_pattern, n_groups),
    }
    if tail:
        params["tail"] = _group_init(ks[2], cfg, enc, tail)
    if not cfg.tie_embeddings:
        params["head"] = packed.linear_init(ks[3], d, v, enc=enc, dtype=dt)

    if cfg.family == "encdec":
        params["enc_layers"] = _stacked_group_init(
            ks[4], cfg, enc, ("enc_attn",), cfg.encoder_layers
        )
        params["enc_final_norm"] = L.norm_init(cfg)
        params["dec_pos_embed"] = 0.02 * jax.random.normal(
            ks[5], (cfg.max_pos_embed, d), jnp.float32
        ).astype(dt)
    if cfg.family == "vlm":
        fd = cfg.frontend_dim or d
        params["projector"] = {
            "ln": L.norm_init(cfg, fd),
            "fc1": packed.linear_init(ks[6], fd, d, enc=enc, dtype=dt),
            "fc2": packed.linear_init(ks[7], d, d, enc=enc, dtype=dt),
        }
    return params


def cache_init(
    cfg: ModelConfig,
    batch: int,
    max_seq: int,
    *,
    cache_mode: str = "dense",
    block_size: int = 16,
    num_pages: int | None = None,
    kv_quant: str = "bf16",
) -> dict:
    """Serving caches for every layer.

    cache_mode="dense": per-slot (batch, max_seq) KV rows (the PR-1 layout,
    kept as the parity baseline; the only mode for recurrent state).
    cache_mode="paged": per-layer page pool (num_pages, block_size) + block
    table — attention-only, no sliding window; the engine owns the page
    allocator (serving/paged.py) and threads tables through the cache leaves.
    kv_quant ("bf16"/"kv8"/"kv4"): the paged pool's KVLayout — quantized
    layouts add per-page float32 scale leaves next to the int pools
    (layers.attn_paged_cache_init); dense caches stay bf16 (the engine
    config downgrades kv_quant for dense mode).
    """
    assert cache_mode in ("dense", "paged"), cache_mode
    assert cache_mode == "paged" or kv_quant == "bf16", (
        "quantized KV layouts require the paged cache", cache_mode, kv_quant
    )
    n_groups, tail = _pattern_layout(cfg)
    if cache_mode == "paged":
        assert all(t == "attn" for t in cfg.block_pattern), (
            "paged KV cache requires an attention-only pattern; recurrent "
            "families keep dense state"
        )
        if num_pages is None:
            # Parity default: full dense coverage (+ scratch page 0).
            num_pages = 1 + batch * (-(-max_seq // block_size))

        def one(_t):
            return L.attn_paged_cache_init(
                cfg, batch, max_seq, block_size=block_size,
                num_pages=num_pages, kv_quant=kv_quant,
            )

        g = tuple(one(t) for t in cfg.block_pattern)
        caches = {"groups": _stack_caches(g, n_groups)}
        if tail:
            caches["tail"] = tuple(one(t) for t in tail)
        return caches
    g = _group_cache_init(cfg, cfg.block_pattern, batch, max_seq)
    caches = {"groups": _stack_caches(g, n_groups)}
    if tail:
        caches["tail"] = _group_cache_init(cfg, tail, batch, max_seq)
    return caches


def _run_encoder(params, frames, cfg, enc, phase):
    """Whisper encoder over precomputed frame embeddings (conv frontend stub)."""
    x = frames.astype(cfg.activation_dtype)
    # Sinusoidal positions.
    t = x.shape[1]
    pos = jnp.arange(t)[:, None]
    i = jnp.arange(cfg.d_model // 2)[None, :]
    ang = pos / jnp.power(10000.0, 2 * i / cfg.d_model)
    pe = jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1).astype(x.dtype)
    x = x + pe[None]

    apply = BLOCKS["enc_attn"][1]

    def body(xc, layer_params):
        y, _, _ = apply(layer_params, xc, cfg=cfg, enc=enc, phase=phase, cache=None, pos=0)
        return y, None

    x, _ = jax.lax.scan(lambda c, p: body(c, p[0]), x, params["enc_layers"])
    return L.norm_apply(params["enc_final_norm"], x, cfg)


def forward(
    params: dict,
    batch: dict,
    *,
    cfg: ModelConfig,
    enc: packed.EncodingConfig,
    phase: Phase,
    caches: dict | None = None,
    pos: jnp.ndarray | int = 0,
    last_logits_only: bool = False,
    logits_idx: jnp.ndarray | None = None,
) -> tuple[jnp.ndarray, dict | None, jnp.ndarray]:
    """Returns (logits, new_caches, aux_loss).

    batch: {"tokens": (B, S)} (+ "frames" (B,T,D) for audio, "patches"
    (B,P,Dv) for vision).  For decode, S == 1 and `pos` is the position of the
    incoming token — either a scalar shared by every row, or a (B,) vector of
    per-row positions (position-vectorized decode: one dispatch serves batch
    rows at different sequence depths; serving/engine.py).  S > 1 at DECODE is
    a masked-causal window (the spec-decode verify window, or the token-budget
    mixed step's per-row chunk of prompt tokens riding the same machinery).
    last_logits_only: emit logits for the final position only (serving
    prefill — avoids materializing the (B, S, V) tensor).  logits_idx: (B, K)
    int32 — emit logits only at these per-row window positions (B, K, V);
    the mixed step reads at most 1 + draft_k positions per row, so the head
    matmul must not scale with the chunk width S.  Overrides
    last_logits_only."""
    tokens = batch["tokens"]
    b, s = tokens.shape
    d = cfg.d_model
    dt = cfg.activation_dtype
    x = params["embed"][tokens].astype(dt)

    extra = None
    if cfg.family == "encdec":
        if phase is not Phase.DECODE:
            extra = _run_encoder(params, batch["frames"], cfg, enc, phase)
        # pos > 0 for decode and chunked prefill; (B,) pos for vectorized decode.
        if jnp.asarray(pos).ndim == 1:
            posn = jnp.asarray(pos)[:, None] + jnp.arange(s)[None, :]
            x = x + params["dec_pos_embed"][posn]
        else:
            posn = pos + jnp.arange(s)
            x = x + params["dec_pos_embed"][posn][None]
    elif cfg.family == "vlm" and phase is not Phase.DECODE:
        pj = params["projector"]
        pimg = L.norm_apply(pj["ln"], batch["patches"].astype(dt), cfg)
        pimg = packed.linear_apply(pj["fc1"], pimg, n=d, phase=phase, enc=enc)
        pimg = jax.nn.gelu(pimg.astype(jnp.float32)).astype(dt)
        pimg = packed.linear_apply(pj["fc2"], pimg, n=d, phase=phase, enc=enc)
        x = jnp.concatenate([pimg, x], axis=1)  # image tokens prefix
        s = x.shape[1]

    n_groups, tail = _pattern_layout(cfg)
    pattern = cfg.block_pattern

    def make_body(pat):
        def group_body(carry, xs):
            xc, aux = carry
            gp, gc = xs
            new_gc = []
            for i, t in enumerate(pat):
                apply = BLOCKS[t][1]
                xc, c_new, a = apply(
                    gp[i], xc, cfg=cfg, enc=enc, phase=phase,
                    cache=None if gc is None else gc[i], pos=pos, extra=extra,
                )
                new_gc.append(c_new)
                aux = aux + a
            return (xc, aux), tuple(new_gc)

        if phase is Phase.TRAIN:
            return jax.checkpoint(group_body, prevent_cse=False)
        return group_body

    body = make_body(pattern)
    tail_body = make_body(tail) if tail else None

    aux0 = jnp.zeros((), jnp.float32)
    if caches is None:
        none_caches = tuple([None] * len(pattern))
        (x, aux), _ = jax.lax.scan(
            lambda c, gp: (body(c, (gp, none_caches))[0], None),
            (x, aux0),
            params["groups"],
        )
        new_caches = None
        if tail:
            (x, aux), _ = tail_body((x, aux), (params["tail"], tuple([None] * len(tail))))
    else:
        groups = params["groups"]

        def serve_body(carry, xs):
            # The layer's parameters are sliced here, under their own scope,
            # so that a trace tells the per-layer weight copies apart from
            # the cache's.
            i, gc = xs
            with jax.named_scope("layer_params"):
                gp = jax.tree.map(
                    lambda a: jax.lax.dynamic_index_in_dim(a, i, keepdims=False), groups
                )
            return body(carry, (gp, gc))

        (x, aux), new_group_caches = jax.lax.scan(
            serve_body, (x, aux0), (jnp.arange(n_groups), caches["groups"])
        )
        new_caches = {"groups": new_group_caches}
        if tail:
            xc, aux_c = x, aux
            new_tc = []
            for i, t in enumerate(tail):
                apply = BLOCKS[t][1]
                xc, c_new, a = apply(
                    params["tail"][i], xc, cfg=cfg, enc=enc, phase=phase,
                    cache=caches["tail"][i], pos=pos, extra=extra,
                )
                new_tc.append(c_new)
                aux_c = aux_c + a
            x, aux = xc, aux_c
            new_caches["tail"] = tuple(new_tc)

    if logits_idx is not None:
        # Per-row logit gather: row b keeps positions logits_idx[b] only.
        # (B, S, D) -> (B, K, D) before the head/tied-embed matmul.
        idx = jnp.asarray(logits_idx, jnp.int32)
        x = jnp.take_along_axis(x, idx[..., None], axis=1)
    elif last_logits_only:
        x = x[:, -1:, :]
    with jax.named_scope("final_norm"):
        x = L.norm_apply(params["final_norm"], x, cfg)
    with jax.named_scope("head"):
        if cfg.tie_embeddings:
            logits = jnp.einsum(
                "bsd,vd->bsv", x, params["embed"], preferred_element_type=jnp.float32
            )[..., : cfg.vocab_size]
        else:
            logits = packed.linear_apply(
                params["head"], x, n=cfg.vocab_size, phase=phase, enc=enc,
                out_dtype=jnp.float32,
            )
    return logits, new_caches, aux


def loss_fn(params, batch, *, cfg, enc, rngs=None):
    """Next-token cross-entropy (train_step objective)."""
    logits, _, aux = forward(params, batch, cfg=cfg, enc=enc, phase=Phase.TRAIN)
    labels = batch["labels"]
    if cfg.family == "vlm":
        # Image-prefix positions carry no labels.
        pfx = logits.shape[1] - labels.shape[1]
        logits = logits[:, pfx:]
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = (lse - ll).mean()
    return nll + 0.01 * aux, {"nll": nll, "aux": aux}
