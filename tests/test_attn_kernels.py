"""Attention microkernels (kernels/attn.py) vs the jnp references: randomized
parity across GQA ratios, ragged per-row positions, ring windows, the L > 1
spec-decode verify window, paged-vs-dense bit-consistency, and the
attention_apply / engine routing through registry.select_attn."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import registry as cfg_registry
from repro.core.encoding import Phase
from repro.core.packed import EncodingConfig
from repro.kernels import attn as attn_lib
from repro.models import layers as L
from repro.models import transformer as T
from repro.serving import engine as engine_lib

TOL = dict(rtol=2e-5, atol=2e-5)


def _rand(rng, *shape):
    return jnp.asarray(rng.randn(*shape), jnp.float32)


# ---------------------------------------------------------------------------
# Dense decode kernel


@pytest.mark.parametrize("h,kv", [(4, 4), (8, 2), (8, 1)])  # G = 1, 4, 8
def test_dense_decode_parity_gqa_ragged_pos(h, kv):
    """Kernel == attention_decode across GQA ratios with every batch row at
    its own position (position-vectorized decode), ragged S vs kv_chunk."""
    rng = np.random.RandomState(0)
    b, d, s = 3, 16, 37
    q = _rand(rng, b, 1, h, d)
    k = _rand(rng, b, s, kv, d)
    v = _rand(rng, b, s, kv, d)
    pos = jnp.asarray(rng.randint(0, s, b), jnp.int32)
    want = L.attention_decode(q, k, v, pos=pos, window=0)
    got = attn_lib.dense_decode_attention(
        q, k, v, pos, window=0, kv_chunk=8, interpret=True
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)


def test_dense_decode_shared_scalar_pos():
    rng = np.random.RandomState(1)
    b, h, kv, d, s = 2, 4, 2, 8, 24
    q = _rand(rng, b, 1, h, d)
    k = _rand(rng, b, s, kv, d)
    v = _rand(rng, b, s, kv, d)
    want = L.attention_decode(q, k, v, pos=11, window=0)
    got = attn_lib.dense_decode_attention(
        q, k, v, jnp.asarray(11, jnp.int32), window=0, kv_chunk=8,
        interpret=True,
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)


def test_dense_decode_verify_window_matches_reference_and_sequential():
    """L > 1 (spec-decode verify): the kernel's masked-causal window equals
    the reference AND L sequential one-token kernel decodes (query j sees
    exactly the history plus drafts 0..j)."""
    rng = np.random.RandomState(2)
    b, Lq, h, kv, d, s = 2, 3, 8, 2, 16, 32
    q = _rand(rng, b, Lq, h, d)
    k = _rand(rng, b, s, kv, d)
    v = _rand(rng, b, s, kv, d)
    pos = jnp.asarray([5, 20], jnp.int32)
    want = L.attention_decode(q, k, v, pos=pos, window=0)
    got = attn_lib.dense_decode_attention(
        q, k, v, pos, window=0, kv_chunk=8, interpret=True
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)
    # Sequential equivalence is BITWISE: the j-th window query and a lone
    # one-token decode at pos+j share chunk boundaries, and chunks masked
    # for query j are exact no-ops of the online accumulator.
    for j in range(Lq):
        lone = attn_lib.dense_decode_attention(
            q[:, j : j + 1], k, v, pos + j, window=0, kv_chunk=8,
            interpret=True,
        )
        np.testing.assert_array_equal(
            np.asarray(got[:, j : j + 1]), np.asarray(lone)
        )


@pytest.mark.parametrize("positions", [[3, 7], [15, 29], [12, 40]])
def test_dense_decode_ring_window_parity(positions):
    """Sliding-window ring cache: fresh rows (qpos < window) and wrapped rows
    (qpos >= S_c) both match the reference ring-age mask."""
    rng = np.random.RandomState(3)
    b, h, kv, d, w = 2, 4, 2, 8, 12
    s = w  # ring cache holds exactly `window` slots
    q = _rand(rng, b, 1, h, d)
    k = _rand(rng, b, s, kv, d)
    v = _rand(rng, b, s, kv, d)
    pos = jnp.asarray(positions, jnp.int32)
    want = L.attention_decode(q, k, v, pos=pos, window=w)
    got = attn_lib.dense_decode_attention(
        q, k, v, pos, window=w, kv_chunk=4, interpret=True
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)


def test_window_mask_cheap_prefix_equivalence():
    """Satellite: for rows with qpos < window the ring-age mask must reduce
    to the cheap `slot <= qpos` prefix mask — pin the equivalence by
    comparing the windowed reference against the full-attention reference
    while the window has not filled."""
    rng = np.random.RandomState(4)
    b, h, kv, d, w = 2, 4, 2, 8, 16
    s = w
    q = _rand(rng, b, 1, h, d)
    k = _rand(rng, b, s, kv, d)
    v = _rand(rng, b, s, kv, d)
    pos = jnp.asarray([2, 9], jnp.int32)  # both < window
    windowed = L.attention_decode(q, k, v, pos=pos, window=w)
    full = L.attention_decode(q, k, v, pos=pos, window=0)
    np.testing.assert_array_equal(np.asarray(windowed), np.asarray(full))


def test_masked_softmax_all_masked_rows_are_zero_not_nan():
    """Satellite: a fully-masked row (padded admission slot) must come back
    all-zero — never NaN — from the guarded softmax."""
    s = jnp.asarray([[1.0, 2.0, 3.0], [5.0, -1.0, 0.5]], jnp.float32)
    valid = jnp.asarray([[False, False, False], [True, False, True]])
    p = L._masked_softmax(s, valid)
    assert bool(jnp.all(jnp.isfinite(p)))
    np.testing.assert_array_equal(np.asarray(p[0]), np.zeros(3, np.float32))
    np.testing.assert_allclose(float(p[1].sum()), 1.0, rtol=1e-6)
    assert float(p[1, 1]) == 0.0


def test_masked_positions_never_leak_garbage():
    """Poisoned K/V at masked positions (stale drafts, uninitialized pages)
    must not perturb kernel or reference output."""
    rng = np.random.RandomState(5)
    b, h, kv, d, s = 2, 4, 2, 8, 24
    q = _rand(rng, b, 1, h, d)
    k = _rand(rng, b, s, kv, d)
    v = _rand(rng, b, s, kv, d)
    pos = jnp.asarray([7, 15], jnp.int32)
    clean_ref = L.attention_decode(q, k, v, pos=pos, window=0)
    clean_ker = attn_lib.dense_decode_attention(
        q, k, v, pos, window=0, kv_chunk=8, interpret=True
    )
    big = 1e30
    k_poison = k.at[0, 8:].set(big).at[1, 16:].set(-big)
    v_poison = v.at[0, 8:].set(-big).at[1, 16:].set(big)
    np.testing.assert_array_equal(
        np.asarray(L.attention_decode(q, k_poison, v_poison, pos=pos, window=0)),
        np.asarray(clean_ref),
    )
    np.testing.assert_array_equal(
        np.asarray(attn_lib.dense_decode_attention(
            q, k_poison, v_poison, pos, window=0, kv_chunk=8, interpret=True
        )),
        np.asarray(clean_ker),
    )


# ---------------------------------------------------------------------------
# Paged decode kernel


def _paged_case(rng, b, nb, bs, kv, d, h, Lq, share=False):
    pool_k = _rand(rng, 1 + b * nb, bs, kv, d)
    pool_v = _rand(rng, 1 + b * nb, bs, kv, d)
    table = (1 + rng.permutation(b * nb).reshape(b, nb)).astype(np.int32)
    if share and b > 1:
        # prefix reuse: two slots share their first half of pages
        table[1, : max(1, nb // 2)] = table[0, : max(1, nb // 2)]
    table = jnp.asarray(table)
    q = _rand(rng, b, Lq, h, d)
    pos = jnp.asarray(rng.randint(0, nb * bs - Lq + 1, b), jnp.int32)
    return q, pool_k, pool_v, table, pos


# (table width, page size, head_dim): one block a row; a width of 13 pages
# in blocks of 8 (the last block partial) through the BlockSpec page stream
# (head_dim 16) and through the page DMAs (head_dim 128).
PAGED_GEOMETRIES = {
    "one_block": (5, 8, 16),
    "two_blocks_spec": (13, 32, 16),
    "two_blocks_dma": (13, 32, 128),
}


@pytest.mark.parametrize("share", [False, True])
@pytest.mark.parametrize("Lq", [1, 3])
@pytest.mark.parametrize("geom", list(PAGED_GEOMETRIES))
def test_paged_decode_parity_vs_gather_reference(geom, share, Lq):
    """In-kernel block-table gather == paged_gather + attention_decode, for
    shared prefix pages and verify windows: row 0's context ends mid-page
    (with two blocks, two positions short of the block boundary, so the
    L = 3 window crosses it), row 1's ends mid-page in the last, partial
    block, and row 2 is an idle slot whose only page is the scratch page."""
    nb, bs, d = PAGED_GEOMETRIES[geom]
    rng = np.random.RandomState(6)
    b, kv, h = 3, 2, 8
    q, pool_k, pool_v, table, _ = _paged_case(rng, b, nb, bs, kv, d, h, Lq, share)
    ppb = attn_lib.pages_per_block(bs, nb)
    assert (nb > ppb) == geom.startswith("two_blocks")
    assert attn_lib.copyable_pages(pool_k) == geom.endswith("dma")
    row0 = ppb * bs - 2 if nb > ppb else nb * bs // 2 + 3
    pos = jnp.asarray([row0, nb * bs - bs - 5, 0], jnp.int32)
    table = table.at[2].set(0)  # every entry the scratch page
    want = L.attention_decode(
        q, L.paged_gather(pool_k, table), L.paged_gather(pool_v, table),
        pos=pos, window=0,
    )
    got = attn_lib.paged_decode_attention(
        q, pool_k, pool_v, table, pos, interpret=True
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)


@pytest.mark.parametrize("geom", list(PAGED_GEOMETRIES))
def test_paged_vs_dense_kernel_bit_consistency(geom):
    """At matched streaming granularity (dense kv_chunk == the paged
    kernel's block of pages) the paged kernel and the dense kernel on the
    gathered view are BITWISE identical — the in-kernel gather changes
    where bytes come from, never a single float op."""
    nb, bs, d = PAGED_GEOMETRIES[geom]
    rng = np.random.RandomState(7)
    b, kv, h, Lq = 3, 2, 8, 2
    q, pool_k, pool_v, table, pos = _paged_case(rng, b, nb, bs, kv, d, h, Lq)
    paged = attn_lib.paged_decode_attention(
        q, pool_k, pool_v, table, pos, interpret=True
    )
    dense = attn_lib.dense_decode_attention(
        q, L.paged_gather(pool_k, table), L.paged_gather(pool_v, table),
        pos, window=0, kv_chunk=attn_lib.pages_per_block(bs, nb) * bs, interpret=True,
    )
    np.testing.assert_array_equal(np.asarray(paged), np.asarray(dense))


@pytest.mark.parametrize("bs", [8, 16, 32])
def test_pages_per_block_divides_or_clamps(bs):
    """Blocks of _BLOCK_TOKENS tokens where the table is that wide, else
    one block of the whole table; ceil(nb / ppb) blocks cover the table and
    only the last may be partial."""
    for nb in range(1, 129):
        ppb = attn_lib.pages_per_block(bs, nb)
        assert 1 <= ppb <= nb
        if nb * bs >= attn_lib._BLOCK_TOKENS:
            assert ppb * bs == attn_lib._BLOCK_TOKENS
        else:
            assert ppb == nb
        nbb = -(-nb // ppb)
        assert (nbb - 1) * ppb < nb <= nbb * ppb


def test_paged_gather_nb_blocks_bound():
    """Satellite: the bounded fallback gather returns exactly the leading
    slice of the full gather."""
    rng = np.random.RandomState(8)
    pool = _rand(rng, 9, 4, 2, 8)
    table = jnp.asarray(1 + rng.permutation(8).reshape(2, 4), jnp.int32)
    full = L.paged_gather(pool, table)
    for nb in (1, 2, 4, 7):
        got = L.paged_gather(pool, table, nb_blocks=nb)
        eff = min(nb, 4)
        assert got.shape[1] == eff * 4
        np.testing.assert_array_equal(np.asarray(got), np.asarray(full[:, : eff * 4]))


# ---------------------------------------------------------------------------
# Flash prefill kernel


@pytest.mark.parametrize("h,kv", [(4, 4), (8, 2), (8, 1)])
def test_flash_prefill_parity_gqa(h, kv):
    rng = np.random.RandomState(9)
    b, sq, d = 2, 33, 16
    q = _rand(rng, b, sq, h, d)
    k = _rand(rng, b, sq, kv, d)
    v = _rand(rng, b, sq, kv, d)
    want = L.attention_chunked(
        q, k, v, causal=True, window=0, q_chunk=8, kv_chunk=8
    )
    got = attn_lib.flash_prefill_attention(
        q, k, v, causal=True, q_chunk=8, kv_chunk=8, interpret=True
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)


@pytest.mark.parametrize("window,q_offset,causal", [
    (7, 0, True),    # sliding-window prefill
    (0, 8, True),    # chunked prefill: q at an absolute offset into the cache
    (0, 0, False),   # bidirectional (encoder)
])
def test_flash_prefill_parity_modes(window, q_offset, causal):
    rng = np.random.RandomState(10)
    b, sq, h, kv, d = 2, 19, 4, 2, 8
    sk = sq + q_offset
    q = _rand(rng, b, sq, h, d)
    k = _rand(rng, b, sk, kv, d)
    v = _rand(rng, b, sk, kv, d)
    want = L.attention_chunked(
        q, k, v, causal=causal, window=window, q_chunk=8, kv_chunk=8,
        q_offset=q_offset,
    )
    got = attn_lib.flash_prefill_attention(
        q, k, v, causal=causal, window=window, q_offset=q_offset,
        q_chunk=8, kv_chunk=8, interpret=True,
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)


# ---------------------------------------------------------------------------
# attention_apply routing (registry.select_attn) and engine integration


@pytest.fixture(scope="module")
def small_model():
    cfg = cfg_registry.get_reduced("qwen2-1.5b")
    enc = EncodingConfig(enabled=True, backend="xla", attn_backend="xla")
    params = T.model_init(jax.random.PRNGKey(0), cfg, enc)
    return cfg, params


def _forward_logits(cfg, params, enc, tokens, phase, caches, pos=0):
    logits, caches, _ = T.forward(
        params, {"tokens": tokens}, cfg=cfg, enc=enc, phase=phase,
        caches=caches, pos=pos,
    )
    return logits, caches


def test_attention_apply_backends_agree_end_to_end(small_model):
    """Full forward (prefill then vectorized decode) with attn_backend
    "pallas" stays within fp tolerance of "xla" and picks the same argmax."""
    cfg, params = small_model
    rng = np.random.RandomState(11)
    toks = jnp.asarray(rng.randint(1, cfg.vocab_size, (2, 9)), jnp.int32)
    outs = {}
    for be in ("xla", "pallas", "auto"):
        enc = EncodingConfig(enabled=True, backend="xla", attn_backend=be)
        caches = T.cache_init(cfg, 2, max_seq=16)
        lp, caches = _forward_logits(cfg, params, enc, toks, Phase.PREFILL, caches)
        nxt = jnp.argmax(lp[:, -1], -1).astype(jnp.int32)[:, None]
        ld, _ = _forward_logits(
            cfg, params, enc, nxt, Phase.DECODE, caches,
            pos=jnp.asarray([9, 9], jnp.int32),
        )
        outs[be] = (np.asarray(lp[:, -1]), np.asarray(ld[:, -1]))
    for be in ("pallas", "auto"):
        for a, b in zip(outs["xla"], outs[be]):
            np.testing.assert_allclose(a, b, rtol=1e-3, atol=1e-3)
            np.testing.assert_array_equal(a.argmax(-1), b.argmax(-1))
    # auto resolves to the pallas kernels (tuned/default), bitwise equal.
    for a, b in zip(outs["pallas"], outs["auto"]):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("cache_mode", ["paged", "dense"])
def test_engine_tokens_identical_across_attn_backends(small_model, cache_mode):
    """Serving engines emit identical tokens whichever attention backend
    serves them (paged: the in-kernel gather path; dense: the chunked
    kernel), under skewed prompts and multi-wave admission."""
    cfg, params = small_model
    rng = np.random.RandomState(12)
    prompts = [rng.randint(1, cfg.vocab_size, n).astype(np.int32)
               for n in (3, 7, 5, 9)]
    got = {}
    for be in ("xla", "pallas"):
        enc = EncodingConfig(enabled=True, backend="xla", attn_backend=be)
        eng = engine_lib.Engine(
            params, cfg, enc, slots=2, max_seq=32, cache_mode=cache_mode
        )
        for i, p in enumerate(prompts):
            eng.submit(engine_lib.Request(uid=i, prompt=p, max_new_tokens=6))
        done = eng.run()
        eng.audit()
        got[be] = {r.uid: r.generated for r in done}
        assert eng.stats["attn_backend"] == be
    assert got["xla"] == got["pallas"]


def test_engine_spec_decode_on_pallas_attention(small_model):
    """Speculative decode (L > 1 verify window) rides the paged kernel:
    token-identical to the plain engine on the same backend."""
    cfg, params = small_model
    rng = np.random.RandomState(13)
    phrase = rng.randint(1, cfg.vocab_size, 4).astype(np.int32)
    prompt = np.tile(phrase, 4)
    enc = EncodingConfig(enabled=True, backend="xla", attn_backend="pallas")
    gens = {}
    for spec in (False, True):
        eng = engine_lib.Engine(
            params, cfg, enc, slots=1, max_seq=64, spec_decode=spec, draft_k=4
        )
        eng.submit(engine_lib.Request(uid=0, prompt=prompt, max_new_tokens=16))
        done = eng.run()
        gens[spec] = done[0].generated
    assert gens[True] == gens[False]


def test_engine_live_table_width_is_bounded(small_model):
    """Satellite: the table leaf threaded into the decode dispatch covers
    only the live page bucket, not the full block-table width."""
    cfg, params = small_model
    enc = EncodingConfig(enabled=True, backend="xla", attn_backend="pallas")
    eng = engine_lib.Engine(
        params, cfg, enc, slots=2, max_seq=128, cache_mode="paged",
        block_size=8,
    )
    assert eng.cache_mode == "paged"
    rng = np.random.RandomState(14)
    eng.submit(engine_lib.Request(
        uid=0, prompt=rng.randint(1, cfg.vocab_size, 5).astype(np.int32),
        max_new_tokens=4,
    ))
    eng.step()
    width = eng._live_table_width()
    assert width == 1  # 5 prompt + first tokens -> one 8-token page
    assert width < eng.num_blocks
    tables = [leaf for path, leaf in
              jax.tree_util.tree_flatten_with_path(eng.caches)[0]
              if "table" in jax.tree_util.keystr(path)]
    assert tables and all(t.shape[-1] == width for t in tables)
    eng.run()
    eng.audit()
