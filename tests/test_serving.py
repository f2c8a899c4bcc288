"""Serving invariants: prefill->decode continuity per family, engine
continuous batching, parity of encoded vs reference model (Table-1 analog)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.core.encoding import Phase
from repro.core.packed import EncodingConfig
from repro.models import transformer as T
from repro.serving import engine as engine_lib

ENC = EncodingConfig(enabled=True, backend="xla")


def _continuity(arch, tol, **cfg_over):
    cfg = registry.get_reduced(arch, **cfg_over)
    params = T.model_init(jax.random.PRNGKey(0), cfg, ENC)
    b, s = 2, 12
    toks = jax.random.randint(jax.random.PRNGKey(1), (b, s), 1, cfg.vocab_size)
    full = {"tokens": toks}
    pfx = 0  # logits offset for multimodal prefixes
    if cfg.family == "encdec":
        full["frames"] = 0.1 * jax.random.normal(
            jax.random.PRNGKey(2), (b, cfg.frontend_tokens, cfg.d_model)
        )
    if cfg.family == "vlm":
        full["patches"] = 0.1 * jax.random.normal(
            jax.random.PRNGKey(3), (b, cfg.frontend_tokens, cfg.frontend_dim)
        )
        pfx = cfg.frontend_tokens
    logits_full, _, _ = T.forward(params, full, cfg=cfg, enc=ENC, phase=Phase.PREFILL)

    sp = s - 4
    caches = T.cache_init(cfg, b, max_seq=s + pfx)
    part = dict(full)
    part["tokens"] = toks[:, :sp]
    logits_p, caches, _ = T.forward(
        params, part, cfg=cfg, enc=ENC, phase=Phase.PREFILL, caches=caches
    )
    errs = [float(jnp.max(jnp.abs(logits_p - logits_full[:, : pfx + sp])))]
    for i in range(sp, s):
        logits_d, caches, _ = T.forward(
            params, {"tokens": toks[:, i : i + 1]},
            cfg=cfg, enc=ENC, phase=Phase.DECODE, caches=caches, pos=pfx + i,
        )
        errs.append(float(jnp.max(jnp.abs(logits_d[:, 0] - logits_full[:, pfx + i]))))
    assert max(errs) < tol, f"{arch}: prefill/decode diverge: {errs}"


@pytest.mark.parametrize("arch,tol", [
    ("qwen2-1.5b", 1e-4),
    ("yi-9b", 1e-4),
    ("rwkv6-1.6b", 1e-4),
    ("recurrentgemma-9b", 1e-4),
    ("whisper-tiny", 1e-4),
    ("internvl2-26b", 1e-4),
])
def test_prefill_decode_continuity(arch, tol):
    _continuity(arch, tol)


def test_moe_continuity_with_unbounded_capacity():
    """Capacity-based token dropping is batch-dependent (expected divergence);
    with non-binding capacity the MoE path must be exactly continuous too."""
    _continuity("mixtral-8x22b", 1e-4, capacity_factor=8.0)


def test_sliding_window_ring_buffer():
    """Decode beyond the window: ring-buffer cache == full-cache windowed attn."""
    cfg = registry.get_reduced("mixtral-8x22b", capacity_factor=8.0, sliding_window=6)
    params = T.model_init(jax.random.PRNGKey(0), cfg, ENC)
    b, s = 1, 14
    toks = jax.random.randint(jax.random.PRNGKey(1), (b, s), 1, cfg.vocab_size)
    logits_full, _, _ = T.forward(
        params, {"tokens": toks}, cfg=cfg, enc=ENC, phase=Phase.PREFILL
    )
    sp = 4  # prefill less than the window, then decode far past it
    caches = T.cache_init(cfg, b, max_seq=s)
    _, caches, _ = T.forward(
        params, {"tokens": toks[:, :sp]}, cfg=cfg, enc=ENC,
        phase=Phase.PREFILL, caches=caches,
    )
    errs = []
    for i in range(sp, s):
        logits_d, caches, _ = T.forward(
            params, {"tokens": toks[:, i : i + 1]},
            cfg=cfg, enc=ENC, phase=Phase.DECODE, caches=caches, pos=i,
        )
        errs.append(float(jnp.max(jnp.abs(logits_d[:, 0] - logits_full[:, i]))))
    assert max(errs) < 1e-4, errs


def test_engine_continuous_batching():
    cfg = registry.get_reduced("qwen2-1.5b")
    params = T.model_init(jax.random.PRNGKey(0), cfg, ENC)
    eng = engine_lib.Engine(params, cfg, ENC, slots=2, max_seq=48)
    rng = np.random.RandomState(0)
    for i in range(5):
        plen = rng.randint(3, 9)
        eng.submit(engine_lib.Request(
            uid=i, prompt=rng.randint(1, cfg.vocab_size, plen).astype(np.int32),
            max_new_tokens=6,
        ))
    done = eng.run()
    assert len(done) == 5
    assert all(len(r.generated) == 6 for r in done)


def test_engine_matches_sequential_decode():
    """Engine output == naive one-request-at-a-time decode (greedy)."""
    cfg = registry.get_reduced("qwen2-1.5b")
    params = T.model_init(jax.random.PRNGKey(0), cfg, ENC)
    rng = np.random.RandomState(1)
    prompts = [rng.randint(1, cfg.vocab_size, rng.randint(3, 7)).astype(np.int32)
               for _ in range(3)]

    eng = engine_lib.Engine(params, cfg, ENC, slots=2, max_seq=32)
    for i, p in enumerate(prompts):
        eng.submit(engine_lib.Request(uid=i, prompt=p, max_new_tokens=5))
    got = {r.uid: r.generated for r in eng.run()}

    for i, p in enumerate(prompts):
        toks = _sequential_decode(params, cfg, p, 5, 32)
        assert got[i] == toks, f"request {i}: {got[i]} vs {toks}"


_count_calls = engine_lib.count_calls


def _skewed_requests(cfg, n=4, seed=3, max_new=6):
    rng = np.random.RandomState(seed)
    # All prompt lengths distinct: worst case for the per-group dispatch loop.
    return [
        engine_lib.Request(
            uid=i, prompt=rng.randint(1, cfg.vocab_size, 3 + 2 * i).astype(np.int32),
            max_new_tokens=max_new,
        )
        for i in range(n)
    ]


def test_engine_vectorized_matches_grouped_skewed():
    """Position-vectorized decode == naive sequential decode, token for
    token, under maximally skewed prompt lengths."""
    cfg = registry.get_reduced("qwen2-1.5b")
    params = T.model_init(jax.random.PRNGKey(0), cfg, ENC)
    eng = engine_lib.Engine(params, cfg, ENC, slots=4, max_seq=32)
    assert eng.decode_mode == "vectorized"
    reqs = _skewed_requests(cfg)
    for r in reqs:
        eng.submit(dataclasses.replace(r, generated=[]))
    got = {r.uid: r.generated for r in eng.run()}
    for r in reqs:
        assert got[r.uid] == _sequential_decode(
            params, cfg, r.prompt, r.max_new_tokens, 32
        ), r.uid


def test_engine_vectorized_matches_grouped_sliding_window():
    """Per-row ring-buffer scatter + (B,) age mask: vectorized decode matches
    naive sequential decode on a sliding-window config, decoding well past
    the window so every row's ring wraps at a different step."""
    cfg = registry.get_reduced("qwen2-1.5b", sliding_window=6)
    params = T.model_init(jax.random.PRNGKey(0), cfg, ENC)
    rng = np.random.RandomState(5)
    # Prompts shorter than the window, skewed; decode 10 >> window 6.
    reqs = [
        engine_lib.Request(
            uid=i, prompt=rng.randint(1, cfg.vocab_size, 2 + i).astype(np.int32),
            max_new_tokens=10,
        )
        for i in range(4)
    ]
    eng = engine_lib.Engine(params, cfg, ENC, slots=4, max_seq=32)
    assert eng.decode_mode == "vectorized"
    assert not eng.batch_prefill  # windowed: per-slot exact prefill
    for r in reqs:
        eng.submit(dataclasses.replace(r, generated=[]))
    got = {r.uid: r.generated for r in eng.run()}
    for r in reqs:
        assert got[r.uid] == _sequential_decode(
            params, cfg, r.prompt, r.max_new_tokens, 32
        ), r.uid


def test_engine_vectorized_single_decode_dispatch():
    """One engine step == exactly ONE jitted decode call, any position skew."""
    cfg = registry.get_reduced("qwen2-1.5b")
    params = T.model_init(jax.random.PRNGKey(0), cfg, ENC)
    eng = engine_lib.Engine(params, cfg, ENC, slots=4, max_seq=32)
    for r in _skewed_requests(cfg, max_new=8):
        eng.submit(r)
    eng.step()  # admit everything; all four slots now at distinct positions
    assert all(r is not None for r in eng.slot_req)
    assert len({int(p) for p in eng.slot_pos}) == 4  # positions truly skewed
    eng.decode_fn = _count_calls(eng.decode_fn)
    eng.step()
    assert eng.decode_fn.calls == 1
    # Grouped decode (recurrent state) pays one dispatch per distinct
    # position.
    cfg_r = registry.get_reduced("rwkv6-1.6b")
    params_r = T.model_init(jax.random.PRNGKey(0), cfg_r, ENC)
    eng_g = engine_lib.Engine(params_r, cfg_r, ENC, slots=4, max_seq=32)
    assert eng_g.decode_mode == "grouped"
    for r in _skewed_requests(cfg_r, max_new=8):
        eng_g.submit(r)
    eng_g.step()
    eng_g.decode_fn = _count_calls(eng_g.decode_fn)
    eng_g.step()
    assert eng_g.decode_fn.calls == 4


def test_engine_batched_prefill_single_call():
    """Queued requests with skewed lengths admit in ONE padded prefill call."""
    cfg = registry.get_reduced("qwen2-1.5b")
    params = T.model_init(jax.random.PRNGKey(0), cfg, ENC)
    eng = engine_lib.Engine(params, cfg, ENC, slots=4, max_seq=32)
    assert eng.batch_prefill  # attention-only, no sliding window
    for r in _skewed_requests(cfg):
        eng.submit(r)
    eng.prefill_fn = _count_calls(eng.prefill_fn)
    eng.step()
    assert eng.prefill_fn.calls == 1
    assert all(r is not None for r in eng.slot_req)


def test_engine_vectorized_falls_back_for_recurrent_state():
    """Recurrent state has no position mask, so an idle slot's rows would
    absorb token-0 updates each vectorized step and later admissions would
    prefill from that garbage.  The engine must take grouped decode — and a
    late-admitted request must generate what it generates served alone."""
    cfg = registry.get_reduced("rwkv6-1.6b")
    params = T.model_init(jax.random.PRNGKey(0), cfg, ENC)
    rng = np.random.RandomState(7)
    pa = rng.randint(1, cfg.vocab_size, 4).astype(np.int32)
    pb = rng.randint(1, cfg.vocab_size, 5).astype(np.int32)
    eng = engine_lib.Engine(params, cfg, ENC, slots=2, max_seq=32)
    assert eng.decode_mode == "grouped"  # the guard itself
    eng.submit(engine_lib.Request(uid=0, prompt=pa, max_new_tokens=6))
    for _ in range(3):  # slot 1 idles for 3 steps before B arrives
        eng.step()
    eng.submit(engine_lib.Request(uid=1, prompt=pb, max_new_tokens=6))
    eng.run()
    got = {r.uid: r.generated for r in eng.finished}
    alone = engine_lib.Engine(params, cfg, ENC, slots=2, max_seq=32)
    alone.submit(engine_lib.Request(uid=1, prompt=pb, max_new_tokens=6))
    assert got[1] == alone.run()[0].generated


def test_engine_rejects_nonpositive_max_new_tokens():
    """max_new_tokens <= 0 finishes immediately: no decode, no slot, no token."""
    cfg = registry.get_reduced("qwen2-1.5b")
    params = T.model_init(jax.random.PRNGKey(0), cfg, ENC)
    eng = engine_lib.Engine(params, cfg, ENC, slots=2, max_seq=32)
    rng = np.random.RandomState(0)
    eng.submit(engine_lib.Request(
        uid=0, prompt=rng.randint(1, cfg.vocab_size, 4).astype(np.int32),
        max_new_tokens=0,
    ))
    eng.submit(engine_lib.Request(
        uid=1, prompt=rng.randint(1, cfg.vocab_size, 5).astype(np.int32),
        max_new_tokens=3,
    ))
    done = {r.uid: r for r in eng.run()}
    assert done[0].generated == [] and done[0].done
    assert len(done[1].generated) == 3


# ---------------------------------------------------------------------------
# Randomized scheduler-conformance harness: paged/vectorized, dense/vectorized
# and naive sequential decode must emit token-identical outputs
# on fuzzed request streams (skewed prompt lengths, staggered arrivals, mixed
# max_new_tokens, pool sizes that force preemption, shared prefixes).


def _sequential_decode(params, cfg, prompt, max_new, max_seq):
    """Naive one-request-at-a-time greedy decode — the ground truth."""
    if max_new <= 0:
        return []
    caches = T.cache_init(cfg, 1, max_seq=max_seq)
    _, caches, _ = T.forward(
        params, {"tokens": jnp.asarray(prompt)[None]},
        cfg=cfg, enc=ENC, phase=Phase.PREFILL, caches=caches,
    )
    toks = []
    last = int(prompt[-1])
    pos = len(prompt) - 1
    for _ in range(max_new):
        logits, caches, _ = T.forward(
            params, {"tokens": jnp.asarray([[last]], jnp.int32)},
            cfg=cfg, enc=ENC, phase=Phase.DECODE, caches=caches, pos=pos,
        )
        last = int(jnp.argmax(logits[0, -1]))
        toks.append(last)
        pos += 1
        if pos + 1 >= max_seq:
            break
    return toks


def _run_engine_stream(params, cfg, stream, *, audit=False, **engine_kw):
    """Drive an Engine over (arrival_step, Request) pairs; returns
    ({uid: generated}, engine)."""
    eng = engine_lib.Engine(params, cfg, ENC, **engine_kw)
    pending = sorted(stream, key=lambda t: t[0])
    i = step = 0
    while i < len(pending) or eng.queue or any(
        r is not None for r in eng.slot_req
    ):
        while i < len(pending) and pending[i][0] <= step:
            eng.submit(dataclasses.replace(pending[i][1], generated=[]))
            i += 1
        eng.step()
        if audit:
            eng.audit()
        step += 1
        assert step < 2000, "engine failed to drain the stream"
    return {r.uid: r.generated for r in eng.finished}, eng


def _fuzz_stream(cfg, seed, *, n=6, shared_prefix=False):
    """Seeded request stream: skewed prompt lengths (heavy short tail),
    staggered arrivals, mixed max_new_tokens (including degenerate 0)."""
    rng = np.random.RandomState(seed)
    common = rng.randint(1, cfg.vocab_size, 12).astype(np.int32)
    stream = []
    for i in range(n):
        plen = int(rng.choice([2, 3, 4, 5, 8, 13], p=[0.25, 0.2, 0.2, 0.15, 0.1, 0.1]))
        prompt = rng.randint(1, cfg.vocab_size, plen).astype(np.int32)
        if shared_prefix and rng.rand() < 0.6:
            prompt = np.concatenate(
                [common, rng.randint(1, cfg.vocab_size, rng.randint(1, 4)).astype(np.int32)]
            )
        max_new = int(rng.choice([0, 2, 4, 6, 8], p=[0.1, 0.2, 0.3, 0.2, 0.2]))
        arrival = int(rng.randint(0, 5))
        stream.append((arrival, engine_lib.Request(
            uid=i, prompt=prompt, max_new_tokens=max_new,
        )))
    return stream


@pytest.mark.parametrize("seed,pool", [
    (11, "tight"),    # pool sized to force preemption under decode growth
    (12, "loose"),    # full-coverage pool, pure paging parity
    (13, "prefix"),   # shared prompt prefixes -> page reuse + copy-on-write
])
def test_scheduler_conformance_randomized(seed, pool):
    cfg = registry.get_reduced("qwen2-1.5b")
    params = T.model_init(jax.random.PRNGKey(0), cfg, ENC)
    max_seq = 48
    stream = _fuzz_stream(cfg, seed, shared_prefix=(pool == "prefix"))
    paged_kw: dict = dict(cache_mode="paged", block_size=4)
    if pool == "tight":
        # Capacity 5: the widest request alone needs 4 pages, so three
        # concurrent slots cannot all grow — decode growth must preempt.
        paged_kw["pool_pages"] = 6
    got = {}
    got["paged"], eng_paged = _run_engine_stream(
        params, cfg, stream, audit=True, slots=3, max_seq=max_seq, **paged_kw
    )
    got["dense_vec"], _ = _run_engine_stream(
        params, cfg, stream, slots=3, max_seq=max_seq, cache_mode="dense"
    )
    got["sequential"] = {
        req.uid: _sequential_decode(
            params, cfg, req.prompt, req.max_new_tokens, max_seq
        )
        for _, req in stream
    }
    assert got["paged"] == got["dense_vec"] == got["sequential"]
    stats = eng_paged.stats
    if pool == "tight":
        assert stats["preemptions"] > 0, stats  # the stream must exercise eviction
    if pool == "prefix":
        assert stats["shared_hits"] > 0 and stats["cow_events"] > 0, stats
    # Freed-on-finish accounting is exact once the stream drains.
    assert stats["pages_in_use"] == 0 and stats["allocs"] == stats["frees"], stats


def test_encoded_vs_reference_model_parity():
    """Table-1 analog at model level: encoding on vs off — same argmax,
    logits close (f32)."""
    cfg = registry.get_reduced("llama3.2-1b")
    enc_on = EncodingConfig(enabled=True, backend="xla")
    enc_off = EncodingConfig(enabled=False, backend="reference")
    params_on = T.model_init(jax.random.PRNGKey(0), cfg, enc_on)
    params_off = T.model_init(jax.random.PRNGKey(0), cfg, enc_off)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 1, cfg.vocab_size)
    lo, _, _ = T.forward(params_on, {"tokens": toks}, cfg=cfg, enc=enc_on, phase=Phase.PREFILL)
    lr, _, _ = T.forward(params_off, {"tokens": toks}, cfg=cfg, enc=enc_off, phase=Phase.PREFILL)
    np.testing.assert_allclose(np.asarray(lo), np.asarray(lr), rtol=1e-3, atol=1e-3)
    assert bool((jnp.argmax(lo, -1) == jnp.argmax(lr, -1)).all())
