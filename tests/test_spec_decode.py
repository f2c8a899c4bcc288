"""Speculative decode: prompt-lookup drafter, batched multi-token verify,
greedy token-identity (dense + paged, ragged acceptance, preemption in the
stream), paged rollback accounting, EOS early-finish, and sampled decode.

The load-bearing invariant: spec decode commits a draft token ONLY when it
equals the model's own greedy argmax, so engine output is token-identical to
plain greedy decode for ANY drafter — the tests drive the real prompt-lookup
drafter, a full-knowledge oracle (maximum acceptance) and an adversarial
always-wrong drafter (maximum rollback) through the same harness.
"""

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
from repro.serving import spec as spec_lib

ENC = EncodingConfig(enabled=True, backend="xla")


@pytest.fixture(scope="module")
def model():
    cfg = registry.get_reduced("qwen2-1.5b")
    params = T.model_init(jax.random.PRNGKey(0), cfg, ENC)
    return cfg, params


# ---------------------------------------------------------------------------
# Drafter


def test_drafter_proposes_continuation_of_most_recent_match():
    ctx = np.array([5, 6, 7, 8, 5, 6, 7], np.int32)
    np.testing.assert_array_equal(spec_lib.propose(ctx, 3), [8, 5, 6])


def test_drafter_recency_wins():
    # The 2-gram (1, 2) occurs twice; the LATER occurrence's continuation (9)
    # must win over the earlier one's (3).
    ctx = np.array([1, 2, 3, 4, 1, 2, 9, 1, 2], np.int32)
    np.testing.assert_array_equal(spec_lib.propose(ctx, 1, ngram=2), [9])


def test_drafter_falls_back_to_shorter_ngrams():
    # No trailing 3- or 2-gram recurs, but the last token does.
    ctx = np.array([7, 1, 7, 2, 7], np.int32)
    got = spec_lib.propose(ctx, 2, ngram=3)
    np.testing.assert_array_equal(got, [2, 7])  # after the ctx[2] match


def test_drafter_empty_on_no_match_and_degenerate_inputs():
    assert spec_lib.propose(np.array([1, 2, 3, 4], np.int32), 3).size == 0
    assert spec_lib.propose(np.array([1], np.int32), 3).size == 0
    assert spec_lib.propose(np.array([], np.int32), 3).size == 0
    assert spec_lib.propose(np.array([1, 1, 2], np.int32), 0).size == 0


def test_drafter_truncates_to_k():
    ctx = np.array([1, 2, 3, 4, 5, 1, 2], np.int32)
    np.testing.assert_array_equal(spec_lib.propose(ctx, 2, ngram=2), [3, 4])


# ---------------------------------------------------------------------------
# Verify step: one (B, L) decode dispatch == L sequential one-token decodes


def test_verify_step_matches_sequential_decode(model):
    cfg, params = model
    b, sp, L = 2, 6, 4
    toks = np.asarray(
        jax.random.randint(jax.random.PRNGKey(1), (b, sp + L), 1, cfg.vocab_size)
    )
    caches = T.cache_init(cfg, b, max_seq=16)
    _, caches, _ = T.forward(
        params, {"tokens": jnp.asarray(toks[:, :sp])},
        cfg=cfg, enc=ENC, phase=Phase.PREFILL, caches=caches,
    )
    seq = []
    c1 = caches
    for i in range(sp - 1, sp - 1 + L):
        lg, c1, _ = T.forward(
            params, {"tokens": jnp.asarray(toks[:, i : i + 1])},
            cfg=cfg, enc=ENC, phase=Phase.DECODE, caches=c1,
            pos=np.full((b,), i, np.int32),
        )
        seq.append(np.asarray(lg[:, 0]))
    verify = engine_lib.make_verify_step(cfg, ENC)
    lg2, _ = verify(
        params, caches,
        jnp.asarray(toks[:, sp - 1 : sp - 1 + L]),
        jnp.full((b,), sp - 1, jnp.int32),
    )
    err = float(np.max(np.abs(np.asarray(lg2) - np.stack(seq, 1))))
    assert err < 1e-4, err


# ---------------------------------------------------------------------------
# Harness: engine streams, sequential ground truth, drafter plugins


def _sequential_decode(params, cfg, prompt, max_new, max_seq, eos_id=None):
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
        if eos_id is not None and last == eos_id:
            break
        if pos + 1 >= max_seq:
            break
    return toks


def _run_stream(params, cfg, stream, **engine_kw):
    """Drive an Engine over (arrival_step, Request) pairs with an audit every
    step; returns ({uid: generated}, engine)."""
    eng = engine_lib.Engine(params, cfg, ENC, **engine_kw)
    pending = sorted(stream, key=lambda t: t[0])
    i = step = 0
    while i < len(pending) or eng.queue or any(
        r is not None for r in eng.slot_req
    ):
        while i < len(pending) and pending[i][0] <= step:
            eng.submit(dataclasses.replace(
                pending[i][1], generated=[], draft_proposed=0, draft_accepted=0,
            ))
            i += 1
        eng.step()
        eng.audit()
        step += 1
        assert step < 2000, "engine failed to drain the stream"
    return {r.uid: r.generated for r in eng.finished}, eng


def _spec_stream(cfg, seed, *, n=5):
    """Mixed stream: repetition-heavy prompts (prompt-lookup territory, high
    acceptance) interleaved with incompressible random prompts (no drafts —
    the fallback path), staggered arrivals, mixed budgets."""
    rng = np.random.RandomState(seed)
    stream = []
    for i in range(n):
        if i % 2 == 0:
            phrase = rng.randint(1, cfg.vocab_size, rng.randint(2, 4)).astype(np.int32)
            prompt = np.tile(phrase, rng.randint(3, 5))
        else:
            prompt = rng.randint(1, cfg.vocab_size, rng.randint(3, 9)).astype(np.int32)
        max_new = int(rng.choice([2, 4, 6, 8]))
        stream.append((int(rng.randint(0, 4)), engine_lib.Request(
            uid=i, prompt=prompt.astype(np.int32), max_new_tokens=max_new,
        )))
    return stream


def _adversarial_drafter(context, k):
    """Always-wrong drafts (vocab id 1 is never the tiny model's argmax for
    these streams in practice — and even when it is, identity still holds):
    exercises full rejection + rollback every single step."""
    return np.full((k,), 1, np.int32)


# ---------------------------------------------------------------------------
# Token identity: greedy spec decode == baseline greedy decode


@pytest.mark.parametrize("cache_mode,pool", [
    ("dense", None),
    ("paged", None),      # full-coverage pool: pure verify/rollback parity
    ("paged", "tight"),   # draft growth under pool pressure -> preemption
])
def test_spec_decode_token_identity(model, cache_mode, pool):
    cfg, params = model
    max_seq = 48
    stream = _spec_stream(cfg, seed=21)
    kw: dict = dict(slots=3, max_seq=max_seq, cache_mode=cache_mode)
    if cache_mode == "paged":
        kw["block_size"] = 4
        if pool == "tight":
            kw["pool_pages"] = 8  # forces eviction once drafts grow pages
    want = {
        req.uid: _sequential_decode(params, cfg, req.prompt, req.max_new_tokens, max_seq)
        for _, req in stream
    }
    got, eng = _run_stream(
        params, cfg, stream, spec_decode=True, draft_k=3, **kw
    )
    assert got == want
    st = eng.stats["spec"]
    # The repetition-heavy half of the stream must actually speculate (ragged
    # acceptance: proposals exist; with a roomy pool some get accepted —
    # under tight-pool pressure speculation may stand down every step).
    assert st["proposed"] > 0 or (pool == "tight" and st["pool_deferred"] > 0)
    if pool != "tight":
        assert st["accepted"] > 0
    if cache_mode == "paged":
        if pool == "tight":
            # Pool pressure must surface as baseline-growth preemption and/or
            # speculation standing down (drafts must never preempt a live
            # request to fund their pages — engine._draft_pages_fit).
            assert eng.preemptions > 0 or st["pool_deferred"] > 0, eng.stats
        else:
            assert st["pool_deferred"] == 0, eng.stats
        assert eng.stats["pages_in_use"] == 0
        assert eng.stats["allocs"] == eng.stats["frees"]


@pytest.mark.parametrize("arch", ["llama3.2-1b", "yi-9b"])
def test_spec_decode_token_identity_other_archs(arch):
    """The identity invariant holds across the attn-only zoo, not just the
    harness default (GQA ratios and head counts differ per arch)."""
    cfg = registry.get_reduced(arch)
    params = T.model_init(jax.random.PRNGKey(0), cfg, ENC)
    max_seq = 32
    rng = np.random.RandomState(31)
    phrase = rng.randint(1, cfg.vocab_size, 3).astype(np.int32)
    stream = [
        (0, engine_lib.Request(uid=0, prompt=np.tile(phrase, 4), max_new_tokens=6)),
        (1, engine_lib.Request(
            uid=1, prompt=rng.randint(1, cfg.vocab_size, 5).astype(np.int32),
            max_new_tokens=5,
        )),
    ]
    want = {
        req.uid: _sequential_decode(params, cfg, req.prompt, req.max_new_tokens, max_seq)
        for _, req in stream
    }
    got, _ = _run_stream(
        params, cfg, stream, spec_decode=True, draft_k=3,
        slots=2, max_seq=max_seq, cache_mode="paged", block_size=4,
    )
    assert got == want


def test_spec_decode_identity_under_adversarial_drafter(model):
    """Every draft rejected, every step: output must STILL be token-identical
    and the paged allocator must survive constant rollback."""
    cfg, params = model
    max_seq = 48
    stream = _spec_stream(cfg, seed=22, n=4)
    want = {
        req.uid: _sequential_decode(params, cfg, req.prompt, req.max_new_tokens, max_seq)
        for _, req in stream
    }
    got, eng = _run_stream(
        params, cfg, stream, spec_decode=True, draft_k=4,
        drafter=_adversarial_drafter,
        slots=2, max_seq=max_seq, cache_mode="paged", block_size=2,
    )
    assert got == want
    st = eng.stats["spec"]
    assert st["proposed"] > 0
    # Wrong drafts commit exactly the bonus token — plain-decode pace.
    assert st["committed"] == st["slot_steps"] + st["accepted"]
    # Rollback really freed draft-only pages: far more page churn than the
    # committed sequences alone would ever need.
    committed_blocks = sum(
        (len(req.prompt) + len(got[req.uid]) + 1) // 2 + 1 for _, req in stream
    )
    assert eng.stats["frees"] > committed_blocks, eng.stats
    assert eng.stats["pages_in_use"] == 0
    assert eng.stats["allocs"] == eng.stats["frees"]


def test_spec_decode_oracle_drafter_amortizes_dispatches(model):
    """A full-knowledge drafter makes every draft accepted: per-slot verify
    dispatches collapse to ceil(T / (k+1)) — the acceptance->amortization
    contract (docs/DESIGN.md §Speculative decode)."""
    cfg, params = model
    max_seq, max_new, k = 64, 12, 3
    rng = np.random.RandomState(7)
    prompt = rng.randint(2, cfg.vocab_size, 5).astype(np.int32)
    target = _sequential_decode(params, cfg, prompt, max_new, max_seq)
    full = np.concatenate([prompt, np.asarray(target, np.int32)])

    def oracle(context, kk):
        ctx = np.asarray(context, np.int32)
        assert np.array_equal(ctx, full[: ctx.size]), "oracle fed unknown ctx"
        return full[ctx.size : ctx.size + kk]

    eng = engine_lib.Engine(
        params, cfg, ENC, slots=1, max_seq=max_seq,
        spec_decode=True, draft_k=k, drafter=oracle,
    )
    eng.decode_fn = engine_lib.count_calls(eng.decode_fn)
    eng.verify_fn = engine_lib.count_calls(eng.verify_fn)
    eng.submit(engine_lib.Request(uid=0, prompt=prompt, max_new_tokens=max_new))
    done = eng.run()
    assert done[0].generated == target
    st = eng.stats["spec"]
    assert st["accepted"] == st["proposed"] > 0       # oracle: 100% acceptance
    assert st["mean_accepted_len"] > 1.0
    dispatches = eng.decode_fn.calls + eng.verify_fn.calls
    assert dispatches == -(-max_new // (k + 1)), (dispatches, max_new)
    assert done[0].draft_accepted == done[0].draft_proposed > 0


# ---------------------------------------------------------------------------
# EOS / stop tokens


def _eos_from_baseline(params, cfg, prompt, max_seq, idx=2):
    """Pick the token the greedy baseline emits at step `idx` as the EOS —
    guarantees the stream actually hits it mid-request."""
    base = _sequential_decode(params, cfg, prompt, idx + 1, max_seq)
    return base[idx]


@pytest.mark.parametrize("spec", [False, True])
def test_eos_finishes_slot_early_and_decode_continues(model, spec):
    """A request stopping at EOS must (a) keep the EOS, emit nothing after
    it, (b) free its pages, and (c) leave the engine state clean enough that
    a later request decodes token-identically (decode continuity)."""
    cfg, params = model
    max_seq = 48
    rng = np.random.RandomState(11)
    phrase = rng.randint(2, cfg.vocab_size, 3).astype(np.int32)
    p_eos = np.tile(phrase, 4)       # repetition-heavy: spec path exercises
    p_after = rng.randint(2, cfg.vocab_size, 6).astype(np.int32)
    eos = _eos_from_baseline(params, cfg, p_eos, max_seq)
    want_eos = _sequential_decode(params, cfg, p_eos, 10, max_seq, eos_id=eos)
    assert want_eos[-1] == eos and len(want_eos) < 10  # EOS really cuts it short
    want_after = _sequential_decode(params, cfg, p_after, 6, max_seq)

    eng = engine_lib.Engine(
        params, cfg, ENC, slots=1, max_seq=max_seq,
        cache_mode="paged", block_size=4, spec_decode=spec, draft_k=3,
    )
    eng.submit(engine_lib.Request(
        uid=0, prompt=p_eos, max_new_tokens=10, eos_id=eos,
    ))
    eng.submit(engine_lib.Request(uid=1, prompt=p_after, max_new_tokens=6))
    while eng.queue or any(r is not None for r in eng.slot_req):
        eng.step()
        eng.audit()
    got = {r.uid: r.generated for r in eng.finished}
    assert got[0] == want_eos, "post-EOS tokens emitted or EOS missed"
    assert got[1] == want_after, "slot reuse after EOS broke decode continuity"
    assert eng.stats["pages_in_use"] == 0


def test_eos_in_middle_of_accepted_draft_window(model):
    """EOS landing inside an accepted draft run must truncate the commit at
    the EOS even though later drafts also matched."""
    cfg, params = model
    max_seq, max_new, k = 64, 12, 4
    rng = np.random.RandomState(13)
    prompt = rng.randint(2, cfg.vocab_size, 5).astype(np.int32)
    target = _sequential_decode(params, cfg, prompt, max_new, max_seq)
    eos = target[4]  # mid-sequence; with k=4 a draft window can straddle it
    want = target[: target.index(eos) + 1]
    full = np.concatenate([prompt, np.asarray(target, np.int32)])

    def oracle(context, kk):
        ctx = np.asarray(context, np.int32)
        return full[ctx.size : ctx.size + kk]

    eng = engine_lib.Engine(
        params, cfg, ENC, slots=1, max_seq=max_seq,
        spec_decode=True, draft_k=k, drafter=oracle,
    )
    eng.submit(engine_lib.Request(
        uid=0, prompt=prompt, max_new_tokens=max_new, eos_id=eos,
    ))
    done = eng.run()
    assert done[0].generated == want


# ---------------------------------------------------------------------------
# Sampling (make_decode_step sample=...)


def test_temperature_zero_rows_match_greedy(model):
    """sample="temperature" with temp<=0 rows must reproduce argmax exactly."""
    cfg, params = model
    decode_g = jax.jit(engine_lib.make_decode_step(cfg, ENC))
    decode_s = jax.jit(engine_lib.make_decode_step(cfg, ENC, sample="temperature"))
    b, sp = 2, 6
    toks = jax.random.randint(jax.random.PRNGKey(1), (b, sp), 1, cfg.vocab_size)
    caches = T.cache_init(cfg, b, max_seq=16)
    _, caches, _ = T.forward(
        params, {"tokens": toks}, cfg=cfg, enc=ENC, phase=Phase.PREFILL,
        caches=caches,
    )
    tok = toks[:, -1:]
    pos = jnp.asarray(sp - 1, jnp.int32)
    g, _, _ = decode_g(params, caches, tok, pos)
    key = jax.random.PRNGKey(3)
    z, _, _ = decode_s(params, caches, tok, pos, key, jnp.zeros((b,), jnp.float32))
    np.testing.assert_array_equal(np.asarray(g), np.asarray(z))
    # temp > 0 is deterministic given the key...
    t = jnp.full((b,), 5.0, jnp.float32)
    s1, _, _ = decode_s(params, caches, tok, pos, key, t)
    s2, _, _ = decode_s(params, caches, tok, pos, key, t)
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))
    # ...and a hot enough temperature eventually departs from argmax.
    diff = False
    for i in range(8):
        si, _, _ = decode_s(
            params, caches, tok, pos, jax.random.PRNGKey(100 + i),
            jnp.full((b,), 50.0, jnp.float32),
        )
        diff = diff or not np.array_equal(np.asarray(si), np.asarray(g))
    assert diff, "temperature-50 sampling never left the argmax"


def test_engine_sampled_greedy_requests_match_greedy_engine(model):
    """An engine built for sampling serves temperature=0 requests exactly as
    the greedy engine does (PRNG threading must not perturb greedy rows)."""
    cfg, params = model
    rng = np.random.RandomState(17)
    prompts = [rng.randint(1, cfg.vocab_size, 4 + i).astype(np.int32) for i in range(3)]

    def run(sample):
        eng = engine_lib.Engine(
            params, cfg, ENC, slots=2, max_seq=32, sample=sample, seed=9,
        )
        for i, p in enumerate(prompts):
            eng.submit(engine_lib.Request(
                uid=i, prompt=p, max_new_tokens=5, temperature=0.0,
            ))
        return {r.uid: r.generated for r in eng.run()}

    assert run("temperature") == run("greedy")


def test_engine_sampling_deterministic_per_seed(model):
    cfg, params = model
    rng = np.random.RandomState(19)
    prompts = [rng.randint(1, cfg.vocab_size, 5).astype(np.int32) for _ in range(2)]

    def run(seed):
        eng = engine_lib.Engine(
            params, cfg, ENC, slots=2, max_seq=32,
            sample="temperature", seed=seed, cache_mode="dense",
        )
        for i, p in enumerate(prompts):
            eng.submit(engine_lib.Request(
                uid=i, prompt=p, max_new_tokens=6, temperature=2.0,
            ))
        return {r.uid: r.generated for r in eng.run()}

    assert run(5) == run(5)  # same seed, same stream


def test_spec_decode_disabled_under_sampling(model):
    """No greedy target to verify against -> speculation must switch off."""
    cfg, params = model
    eng = engine_lib.Engine(
        params, cfg, ENC, slots=2, max_seq=32,
        sample="temperature", spec_decode=True,
    )
    assert not eng.spec_decode
    # ...and stays on for the greedy twin.
    eng2 = engine_lib.Engine(
        params, cfg, ENC, slots=2, max_seq=32, spec_decode=True,
    )
    assert eng2.spec_decode


def test_make_decode_step_rejects_unknown_sample_mode(model):
    cfg, _ = model
    with pytest.raises(ValueError, match="sample"):
        engine_lib.make_decode_step(cfg, ENC, sample="nucleus")
