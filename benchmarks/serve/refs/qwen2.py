"""Qwen2 / Qwen2.5 decoder: seeded plain weights and a float32 reference.

The architecture as published (arXiv:2407.10671; Hugging Face `Qwen2Model`):
token embedding; per layer a pre-norm (RMSNorm) GQA attention block with
biases on Q, K and V, rotary positions (rotate-half, base `rope_theta`) and
an output projection without bias, then a pre-norm SwiGLU MLP; a final
RMSNorm and an output head that is the embedding where
`tie_word_embeddings`.

This module imports nothing of the program under test.  Weights are drawn
from the seed leaf by leaf and layer by layer, so the reference can draw
one layer at a time and get exactly the values the program was loaded
with.  Scales are powers of two, so scaling a bf16 draw is exact.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0  # largest finite float8_e4m3fn


def head_dim(cfg: dict) -> int:
    return int(cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"])


def _pow2_scale(fan_in: int) -> float:
    """2^-round(log2(sqrt(fan_in))): about 1 / sqrt(fan_in), exact in bf16."""
    return 2.0 ** -round(math.log2(math.sqrt(fan_in)))


def layer_leaves(cfg: dict) -> dict[str, tuple[tuple[int, ...], float, float]]:
    """name -> (shape, scale, offset) of each leaf of one layer; a matrix is
    (out, in).  Norm scales are 1 + a small draw, biases a small draw."""
    d, f, hd = cfg["hidden_size"], cfg["intermediate_size"], head_dim(cfg)
    q, kv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    sd, sq, sf = _pow2_scale(d), _pow2_scale(q), _pow2_scale(f)
    return {
        "ln1": ((d,), 2.0 ** -4, 1.0),
        "wq": ((q, d), sd, 0.0), "bq": ((q,), 2.0 ** -4, 0.0),
        "wk": ((kv, d), sd, 0.0), "bk": ((kv,), 2.0 ** -4, 0.0),
        "wv": ((kv, d), sd, 0.0), "bv": ((kv,), 2.0 ** -4, 0.0),
        "wo": ((d, q), sq, 0.0),
        "ln2": ((d,), 2.0 ** -4, 1.0),
        "w_gate": ((f, d), sd, 0.0), "w_up": ((f, d), sd, 0.0),
        "w_down": ((d, f), sf, 0.0),
    }


def global_leaves(cfg: dict) -> dict[str, tuple[tuple[int, ...], float, float]]:
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    out = {"embed": ((v, d), _pow2_scale(d), 0.0), "final_norm": ((d,), 2.0 ** -4, 1.0)}
    if not cfg["tie_word_embeddings"]:
        out["head"] = ((v, d), _pow2_scale(d), 0.0)
    return out


LEAF_IDS = {n: i for i, n in enumerate(
    ["embed", "final_norm", "head", "ln1", "wq", "bq", "wk", "bk", "wv", "bv",
     "wo", "ln2", "w_gate", "w_up", "w_down"])}


def base_key(seed: int) -> jax.Array:
    """A raw threefry key from any whole-number seed (wider than 32 bits)."""
    words = np.random.SeedSequence([int(seed), 5]).generate_state(2, dtype=np.uint32)
    return jnp.asarray(words, jnp.uint32)


def leaf_key(key, name: str, layer=None):
    k = jax.random.fold_in(key, LEAF_IDS[name])
    return k if layer is None else jax.random.fold_in(k, layer)


def draw(key, shape, scale: float, offset: float, dtype=jnp.bfloat16):
    """One leaf: a bf16 normal draw times a power of two, plus `offset`
    (1 for norm scales), in `dtype`."""
    x = jax.random.normal(key, shape, jnp.bfloat16) * jnp.bfloat16(scale)
    if offset:
        return x.astype(jnp.float32) + offset
    return x.astype(dtype)


def draw_layer(cfg: dict, key, layer):
    """All leaves of one layer: norms in float32, the rest in bf16."""
    return {n: draw(leaf_key(key, n, layer), s, sc, off)
            for n, (s, sc, off) in layer_leaves(cfg).items()}


def draw_global(cfg: dict, key):
    return {n: draw(leaf_key(key, n), s, sc, off)
            for n, (s, sc, off) in global_leaves(cfg).items()}


# ---------------------------------------------------------------------------
# Reference forward (float32, matmul precision "highest"), and the control:
# the same forward computed in float8 e4m3 with per-row scales: every
# projection's inputs (weights per output channel, activations per token),
# the residual stream at each block's entry, q, k, v and the attention
# probabilities are rounded to it; sums stay float32.


def _fp8(x, axis=-1):
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / FP8_MAX
    s = jnp.where(s == 0, 1.0, s)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _low(x, low: bool, axis=-1):
    """x rounded to float8 e4m3 (per-row scales) in the control, else x."""
    return _fp8(x, axis) if low else x


def _proj(x, w, low: bool):
    """x (..., K) @ w (N, K)^T in float32."""
    w = w.astype(jnp.float32)
    if low:
        x, w = _fp8(x), _fp8(w)
    return jnp.einsum("...k,nk->...n", x, w, precision=HIGHEST)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    """x (S, H, D), pos (S,): rotate-half rotary embedding."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


Q_CHUNK = 512


def _attention(q, k, v, n_valid, low: bool = False):
    """Causal GQA attention for one sequence: q (S, H, D), k/v (S, KV, D);
    query rows are taken Q_CHUNK at a time so scores stay small."""
    s, h, d = q.shape
    kvh = k.shape[1]
    g = h // kvh
    qg = q.reshape(s, kvh, g, d)
    kpos = jnp.arange(s)

    def chunk(i):
        qc = jax.lax.dynamic_slice_in_dim(qg, i * Q_CHUNK, Q_CHUNK, 0)
        qpos = i * Q_CHUNK + jnp.arange(Q_CHUNK)
        sc = jnp.einsum("qkgd,skd->kgqs", qc, k, precision=HIGHEST) / math.sqrt(d)
        mask = (kpos[None, :] <= qpos[:, None]) & (kpos[None, :] < n_valid)
        sc = jnp.where(mask[None, None], sc, -jnp.inf)
        p = _low(jax.nn.softmax(sc, axis=-1), low)
        return jnp.einsum("kgqs,skd->qkgd", p, v, precision=HIGHEST).reshape(Q_CHUNK, h, d)

    out = jax.lax.map(chunk, jnp.arange(s // Q_CHUNK))
    return out.reshape(s, h, d)


def _layer(cfg: dict, low: bool, x, w, n_valid):
    """One decoder layer on x (S, d) of one sequence."""
    hd = head_dim(cfg)
    h, kvh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    pos = jnp.arange(x.shape[0])
    x = _low(x, low)
    y = _rms(x, w["ln1"], eps)
    q = (_proj(y, w["wq"], low) + w["bq"].astype(jnp.float32)).reshape(-1, h, hd)
    k = (_proj(y, w["wk"], low) + w["bk"].astype(jnp.float32)).reshape(-1, kvh, hd)
    v = (_proj(y, w["wv"], low) + w["bv"].astype(jnp.float32)).reshape(-1, kvh, hd)
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    q, k, v = _low(q, low), _low(k, low), _low(v, low)
    a = _attention(q, k, v, n_valid, low).reshape(x.shape[0], h * hd)
    x = _low(x + _proj(a, w["wo"], low), low)
    y = _rms(x, w["ln2"], eps)
    gate = _proj(y, w["w_gate"], low)
    up = _proj(y, w["w_up"], low)
    return x + _proj(jax.nn.silu(gate) * up, w["w_down"], low)


@partial(jax.jit, static_argnums=(0, 1))
def _run_layer(cfg_items, low, x, key, layer, n_valid):
    cfg = dict(cfg_items)
    w = draw_layer(cfg, key, layer)
    return jax.vmap(lambda xs, n: _layer(cfg, low, xs, w, n))(x, n_valid)


@partial(jax.jit, static_argnums=(0,))
def _embed(cfg_items, tokens, key):
    cfg = dict(cfg_items)
    s, sc, off = global_leaves(cfg)["embed"]
    emb = draw(leaf_key(key, "embed"), s, sc, off)
    return emb[tokens].astype(jnp.float32)


HEAD_CHUNK = 256


@partial(jax.jit, static_argnums=(0, 1))
def _head_stats(cfg_items, low, x, served, key):
    """x (P, d) final hidden rows, served (P,) tokens.  Returns, per row, the
    float32 reference's best logit, its logit of the served token, and the
    token this precision puts first; rows go HEAD_CHUNK at a time."""
    cfg = dict(cfg_items)
    g = draw_global(cfg, key)
    head = g["embed"] if cfg["tie_word_embeddings"] else g["head"]
    x = _rms(x, g["final_norm"], cfg["rms_norm_eps"])

    def chunk(args):
        xc, tc = args
        ref = _proj(xc, head, False)
        top = jnp.argmax(_proj(xc, head, True), axis=-1) if low else jnp.argmax(ref, axis=-1)
        return (jnp.max(ref, axis=-1), jnp.take_along_axis(ref, tc[:, None], 1)[:, 0],
                jnp.take_along_axis(ref, top[:, None], 1)[:, 0])

    p = x.shape[0]
    xs = x.reshape(p // HEAD_CHUNK, HEAD_CHUNK, -1)
    ts = served.reshape(p // HEAD_CHUNK, HEAD_CHUNK)
    best, got, first = jax.lax.map(chunk, (xs, ts))
    return best.reshape(p), got.reshape(p), first.reshape(p)


def _bucket(n: int, q: int) -> int:
    return max(q, 1 << (max(n, 1) - 1).bit_length())


def logit_gaps(cfg: dict, seed: int, sequences, *, low: bool = False, batch: int = 4):
    """For each (prompt, served) pair: the float32 reference, teacher-forced
    on prompt + served[:-1], at each position that produced a served token.

    Returns one array per sequence of the gap by which the served token's
    reference logit lies below the reference's best (low=False), or by
    which the token that the float8 control puts first lies below it
    (low=True).  Sequences go `batch` at a time, padded to a power-of-two
    length, one layer at a time."""
    cfg_items = tuple(sorted((k, v) for k, v in cfg.items()
                             if isinstance(v, (int, float, bool, str))))
    key = base_key(seed)
    out = []
    for lo in range(0, len(sequences), batch):
        chunk = sequences[lo:lo + batch]
        lens = [len(p) + len(s) - 1 for p, s in chunk]
        s = _bucket(max(lens), Q_CHUNK)
        toks = np.zeros((batch, s), np.int32)
        for i, (p, sv) in enumerate(chunk):
            seq = np.concatenate([np.asarray(p, np.int32), np.asarray(sv[:-1], np.int32)])
            toks[i, :len(seq)] = seq
        n_valid = jnp.asarray(lens + [1] * (batch - len(chunk)), jnp.int32)
        x = _embed(cfg_items, jnp.asarray(toks), key)
        for layer in range(cfg["num_hidden_layers"]):
            x = _run_layer(cfg_items, low, x, key, layer, n_valid)
        rows, served = [], []
        for i, (p, sv) in enumerate(chunk):
            pos = len(p) - 1 + np.arange(len(sv))
            rows.append(np.stack([np.full(len(sv), i), pos], 1))
            served.append(np.asarray(sv, np.int32))
        rows = np.concatenate(rows)
        served = np.concatenate(served)
        pad = _bucket(len(rows), HEAD_CHUNK) - len(rows)
        rows_p = np.concatenate([rows, np.zeros((pad, 2), rows.dtype)])
        served_p = np.concatenate([served, np.zeros(pad, np.int32)])
        xr = x[jnp.asarray(rows_p[:, 0]), jnp.asarray(rows_p[:, 1])]
        best, got, first = (np.asarray(a)[:len(rows)] for a in
                            _head_stats(cfg_items, low, xr, jnp.asarray(served_p), key))
        gap = best - (first if low else got)
        off = 0
        for _, sv in chunk:
            out.append(gap[off:off + len(sv)])
            off += len(sv)
        del x
    return out
