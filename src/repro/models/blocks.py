"""Block-level dispatch: one init/apply/cache-init triple per block type.

Types:
  attn        pre-norm attention + (MLP | MoE)   [dense, MoE, hybrid-attn slots]
  rec         pre-norm RG-LRU + MLP              [RecurrentGemma]
  rwkv        RWKV-6 time-mix + channel-mix      [RWKV]
  encdec_attn decoder block w/ self + cross attention  [Whisper decoder]
  enc_attn    bidirectional encoder block        [Whisper encoder]

All blocks share the signature
  init(key, cfg, enc) -> params
  apply(params, x, *, cfg, enc, phase, cache, pos, extra) -> (x, new_cache, aux)
so the grouped layer scan in transformer.py stays type-agnostic.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import packed
from repro.core.encoding import Phase
from repro.models import layers as L
from repro.models import recurrent as R


def _zero_aux():
    return jnp.zeros((), jnp.float32)


# ---- attn ------------------------------------------------------------------


def attn_block_init(key, cfg: ModelConfig, enc) -> dict:
    k1, k2 = jax.random.split(key)
    p = {
        "ln1": L.norm_init(cfg),
        "attn": L.attention_init(k1, cfg, enc),
        "ln2": L.norm_init(cfg),
    }
    if cfg.num_experts:
        p["moe"] = L.moe_init(k2, cfg, enc)
    else:
        p["mlp"] = L.mlp_init(k2, cfg, enc)
    return p


def attn_block_apply(params, x, *, cfg, enc, phase, cache, pos, extra=None):
    # Named scopes tag every device op of a trace with its region.
    with jax.named_scope("attn"):
        h, new_cache = L.attention_apply(
            params["attn"],
            L.norm_apply(params["ln1"], x, cfg),
            cfg=cfg,
            enc=enc,
            phase=phase,
            cache=cache,
            pos=pos,
        )
        x = x + h
    with jax.named_scope("mlp"):
        y = L.norm_apply(params["ln2"], x, cfg)
        if cfg.num_experts:
            f, aux = L.moe_apply(params["moe"], y, cfg=cfg, enc=enc, phase=phase)
        else:
            f, aux = L.mlp_apply(params["mlp"], y, cfg=cfg, enc=enc, phase=phase), _zero_aux()
        return x + f, new_cache, aux


def attn_cache_init(cfg, batch, max_seq):
    return L.attn_cache_init(cfg, batch, max_seq)


# ---- rec (RG-LRU) ----------------------------------------------------------


def rec_block_init(key, cfg: ModelConfig, enc) -> dict:
    k1, k2 = jax.random.split(key)
    return {
        "ln1": L.norm_init(cfg),
        "rglru": R.rglru_init(k1, cfg, enc),
        "ln2": L.norm_init(cfg),
        "mlp": L.mlp_init(k2, cfg, enc),
    }


def rec_block_apply(params, x, *, cfg, enc, phase, cache, pos, extra=None):
    h, new_cache = R.rglru_apply(
        params["rglru"],
        L.norm_apply(params["ln1"], x, cfg),
        cfg=cfg,
        enc=enc,
        phase=phase,
        state=cache,
    )
    x = x + h
    y = L.norm_apply(params["ln2"], x, cfg)
    f = L.mlp_apply(params["mlp"], y, cfg=cfg, enc=enc, phase=phase)
    return x + f, new_cache, _zero_aux()


def rec_cache_init(cfg, batch, max_seq):
    del max_seq
    return R.rglru_state_init(cfg, batch)


# ---- rwkv ------------------------------------------------------------------


def rwkv_block_init(key, cfg: ModelConfig, enc) -> dict:
    return R.rwkv_init(key, cfg, enc)


def rwkv_block_apply(params, x, *, cfg, enc, phase, cache, pos, extra=None):
    out, new_state = R.rwkv_apply(params, x, cfg=cfg, enc=enc, phase=phase, state=cache)
    return out, new_state, _zero_aux()


def rwkv_cache_init(cfg, batch, max_seq):
    del max_seq
    return R.rwkv_state_init(cfg, batch)


# ---- encoder block (bidirectional) ------------------------------------------


def enc_attn_block_init(key, cfg: ModelConfig, enc) -> dict:
    k1, k2 = jax.random.split(key)
    return {
        "ln1": L.norm_init(cfg),
        "attn": L.attention_init(k1, cfg, enc),
        "ln2": L.norm_init(cfg),
        "mlp": L.mlp_init(k2, cfg, enc),
    }


def enc_attn_block_apply(params, x, *, cfg, enc, phase, cache, pos, extra=None):
    h, _ = L.attention_apply(
        params["attn"],
        L.norm_apply(params["ln1"], x, cfg),
        cfg=cfg,
        enc=enc,
        phase=Phase.PREFILL if phase is Phase.DECODE else phase,
        cache=None,
        causal=False,
        use_rope=False,
    )
    x = x + h
    y = L.norm_apply(params["ln2"], x, cfg)
    f = L.mlp_apply(params["mlp"], y, cfg=cfg, enc=enc, phase=phase)
    return x + f, cache, _zero_aux()


# ---- decoder block with cross attention (Whisper) ---------------------------


def encdec_block_init(key, cfg: ModelConfig, enc) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "ln1": L.norm_init(cfg),
        "self_attn": L.attention_init(k1, cfg, enc),
        "ln_x": L.norm_init(cfg),
        "cross_attn": L.attention_init(k2, cfg, enc),
        "ln2": L.norm_init(cfg),
        "mlp": L.mlp_init(k3, cfg, enc),
    }


def encdec_block_apply(params, x, *, cfg, enc, phase, cache, pos, extra=None):
    """cache = {"self": kv-cache, "cross_k": (B,Te,KV,D), "cross_v": ...};
    extra = encoder output (B, Te, D) (prefill/train) or None (decode, cached)."""
    h, new_self = L.attention_apply(
        params["self_attn"],
        L.norm_apply(params["ln1"], x, cfg),
        cfg=cfg,
        enc=enc,
        phase=phase,
        cache=None if cache is None else cache["self"],
        pos=pos,
        use_rope=False,
    )
    x = x + h

    xq = L.norm_apply(params["ln_x"], x, cfg)
    if extra is not None:
        # Compute (and cache) cross K/V from encoder states.
        ca, _ = L.attention_apply(
            params["cross_attn"], xq, cfg=cfg, enc=enc,
            phase=Phase.PREFILL if phase is Phase.DECODE else phase,
            kv_src=extra, use_rope=False,
        )
        b = x.shape[0]
        kvh, hd = cfg.num_kv_heads, cfg.head_dim
        ck = packed.linear_apply(
            params["cross_attn"]["wk"], extra, n=kvh * hd, phase=Phase.PREFILL, enc=enc
        ).reshape(b, extra.shape[1], kvh, hd)
        cv = packed.linear_apply(
            params["cross_attn"]["wv"], extra, n=kvh * hd, phase=Phase.PREFILL, enc=enc
        ).reshape(b, extra.shape[1], kvh, hd)
        new_cross_k, new_cross_v = ck, cv
    else:
        assert cache is not None
        q = packed.linear_apply(
            params["cross_attn"]["wq"], xq,
            n=cfg.num_heads * cfg.head_dim, phase=phase, enc=enc,
        ).reshape(x.shape[0], x.shape[1], cfg.num_heads, cfg.head_dim)
        te = cache["cross_k"].shape[1]
        ca = L.attention_decode(
            q, cache["cross_k"], cache["cross_v"], pos=jnp.asarray(te - 1), window=0
        )
        ca = ca.reshape(x.shape[0], x.shape[1], cfg.num_heads * cfg.head_dim)
        ca = packed.linear_apply(
            params["cross_attn"]["wo"], ca, n=cfg.d_model, phase=phase, enc=enc
        )
        new_cross_k, new_cross_v = cache["cross_k"], cache["cross_v"]
    x = x + ca

    y = L.norm_apply(params["ln2"], x, cfg)
    f = L.mlp_apply(params["mlp"], y, cfg=cfg, enc=enc, phase=phase)
    new_cache = cache
    if cache is not None:
        new_cache = {"self": new_self, "cross_k": new_cross_k, "cross_v": new_cross_v}
    return x + f, new_cache, _zero_aux()


def encdec_cache_init(cfg, batch, max_seq):
    return {
        "self": L.attn_cache_init(cfg, batch, max_seq),
        "cross_k": jnp.zeros(
            (batch, cfg.frontend_tokens, cfg.num_kv_heads, cfg.head_dim),
            cfg.activation_dtype,
        ),
        "cross_v": jnp.zeros(
            (batch, cfg.frontend_tokens, cfg.num_kv_heads, cfg.head_dim),
            cfg.activation_dtype,
        ),
    }


BLOCKS = {
    "attn": (attn_block_init, attn_block_apply, attn_cache_init),
    "rec": (rec_block_init, rec_block_apply, rec_cache_init),
    "rwkv": (rwkv_block_init, rwkv_block_apply, rwkv_cache_init),
    "enc_attn": (enc_attn_block_init, enc_attn_block_apply, lambda *a: None),
    "encdec_attn": (encdec_block_init, encdec_block_apply, encdec_cache_init),
}
