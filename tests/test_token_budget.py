"""Token-budget continuous batching (serving/engine.py): the unified mixed
chunked-prefill + decode dispatch, its scheduler (SLO classes, aging,
preemption ordering), and the latent-scheduler-bug sweep that rode along.

The load-bearing contract is TOKEN IDENTITY: for any arrival pattern, the
mixed engine must emit exactly what the phase-split engine emits (which is
itself pinned token-identical between paged / dense / sequential elsewhere) —
chunk boundaries, window padding, budget splits, and spec windows are all
invisible in the output.  On top of that, the stall metric the whole design
exists for: a long prompt admitted mid-decode must cost ZERO decode-stall
steps (every live decoding slot emits every step), gated here.
"""

import jax
import numpy as np
import pytest

from repro.configs import registry
from repro.core.packed import EncodingConfig
from repro.kernels import registry as registry_lib
from repro.models import transformer as T
from repro.serving import engine as engine_lib
from repro.serving import spec as spec_lib

ENC = EncodingConfig(enabled=True, backend="xla")
CFG = registry.get_reduced("qwen2-1.5b")
PARAMS = T.model_init(jax.random.PRNGKey(0), CFG, ENC)


@pytest.fixture(autouse=True)
def _clean_quarantine():
    registry_lib.clear_quarantine()
    yield
    registry_lib.clear_quarantine()


def _prompts(seed=0, n=5, lo=4, hi=12):
    rng = np.random.RandomState(seed)
    return [
        rng.randint(1, CFG.vocab_size, rng.randint(lo, hi)).astype(np.int32)
        for _ in range(n)
    ]


def _engine(**kw):
    kw.setdefault("slots", 3)
    kw.setdefault("max_seq", 64)
    return engine_lib.Engine(PARAMS, CFG, ENC, **kw)


def _drive(eng, budget=400, audit=True):
    steps = 0
    while eng.queue or any(r is not None for r in eng.slot_req):
        assert steps < budget, "engine did not drain"
        eng.step()
        if audit:
            eng.audit()
        steps += 1
    return {r.uid: list(r.generated) for r in eng.finished}


def _submit_all(eng, prompts, max_new=8, **req_kw):
    for i, p in enumerate(prompts):
        assert eng.submit(engine_lib.Request(
            uid=i, prompt=p, max_new_tokens=max_new, **req_kw
        ))


# ---------------------------------------------------------------------------
# Token identity: mixed == sequential, all cache modes, spec on and off
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("cache_mode", ["paged", "dense"])
def test_mixed_token_identity(cache_mode):
    prompts = _prompts()
    ref = _engine(cache_mode=cache_mode)
    _submit_all(ref, prompts)
    gold = _drive(ref)

    eng = _engine(cache_mode=cache_mode, token_budget=10)
    _submit_all(eng, prompts)
    got = _drive(eng)
    assert eng.scheduler is not None
    assert got == gold
    c = eng.stats["continuous"]
    assert c["mixed_steps"] > 0 and c["prefill_tokens"] > 0
    assert c["decode_stall_steps"] == 0


def test_mixed_token_identity_with_spec_decode():
    # Repetitive prompts so the prompt-lookup drafter actually proposes;
    # spec windows and prefill chunks then share one budget.
    rng = np.random.RandomState(3)
    prompts = [
        np.tile(rng.randint(1, CFG.vocab_size, 5), 4).astype(np.int32)
        for _ in range(4)
    ]
    ref = _engine(cache_mode="paged")
    _submit_all(ref, prompts, max_new=10)
    gold = _drive(ref)

    eng = _engine(cache_mode="paged", token_budget=10,
                  spec_decode=True, draft_k=4)
    _submit_all(eng, prompts, max_new=10)
    got = _drive(eng)
    assert eng.spec_decode and eng.scheduler is not None
    assert got == gold
    # Drafts really ran inside mixed windows.
    assert eng.stats["spec"]["proposed"] > 0


def test_mixed_identity_adversarial_arrival():
    """Requests trickle in while the engine is mid-flight — admission order
    and chunk interleavings differ wildly from batch submission, output must
    not."""
    prompts = _prompts(seed=7, n=6, lo=4, hi=30)
    ref = _engine(cache_mode="paged")
    _submit_all(ref, prompts)
    gold = _drive(ref)

    eng = _engine(cache_mode="paged", token_budget=8)
    it = iter(enumerate(prompts))
    uid, p = next(it)
    eng.submit(engine_lib.Request(uid=uid, prompt=p, max_new_tokens=8))
    pending = list(it)
    steps = 0
    while eng.queue or any(r is not None for r in eng.slot_req) or pending:
        if pending and steps % 2 == 0:
            uid, p = pending.pop(0)
            eng.submit(engine_lib.Request(uid=uid, prompt=p, max_new_tokens=8))
        eng.step()
        eng.audit()
        steps += 1
        assert steps < 500
    got = {r.uid: list(r.generated) for r in eng.finished}
    assert got == gold


# ---------------------------------------------------------------------------
# The stall gate: long prompt admitted mid-decode never pauses decode
# ---------------------------------------------------------------------------


def test_long_prompt_admission_zero_decode_stall():
    rng = np.random.RandomState(11)
    short = np.tile(rng.randint(1, CFG.vocab_size, 4), 3).astype(np.int32)
    long_p = rng.randint(1, CFG.vocab_size, 60).astype(np.int32)

    eng = _engine(slots=2, cache_mode="paged", token_budget=8)
    assert eng.submit(engine_lib.Request(uid=0, prompt=short, max_new_tokens=24))
    for _ in range(3):
        eng.step()
        eng.audit()
    tokens_before = len(eng.finished[0].generated) if eng.finished else len(
        next(r for r in eng.slot_req if r is not None).generated
    )
    # Admit a prompt ~8x the per-step budget mid-decode: it must stream in
    # over many steps while slot 0 keeps emitting every single step.
    assert eng.submit(engine_lib.Request(uid=1, prompt=long_p, max_new_tokens=4))
    got = _drive(eng)
    c = eng.stats["continuous"]
    assert c["decode_stall_steps"] == 0
    assert c["completed_prefills"] == 2
    assert c["prefill_tokens"] >= len(long_p)
    assert tokens_before < len(got[0])

    ref = _engine(slots=2, cache_mode="paged")
    ref.submit(engine_lib.Request(uid=0, prompt=short, max_new_tokens=24))
    ref.submit(engine_lib.Request(uid=1, prompt=long_p, max_new_tokens=4))
    assert _drive(ref) == got


# ---------------------------------------------------------------------------
# Scheduler policy: SLO classes, aging, preemption ordering
# ---------------------------------------------------------------------------


def test_slo_admission_order_and_aging():
    sched = engine_lib.TokenBudgetScheduler(16, aging_steps=4)
    inter = engine_lib.Request(uid=0, prompt=np.ones(2, np.int32),
                               max_new_tokens=1, slo_class="interactive")
    batch = engine_lib.Request(uid=1, prompt=np.ones(2, np.int32),
                               max_new_tokens=1, slo_class="batch")
    inter.enqueued_step = 6
    batch.enqueued_step = 0
    # Fresh interactive outranks batch (even one that has already aged a
    # class: batch waited 6-7 steps here -> one class up, still behind)...
    assert sched.queue_key(inter, 6) < sched.queue_key(batch, 6)
    assert sched.queue_key(inter, 7) < sched.queue_key(batch, 7)
    # ...until the batch request has aged 2 classes (8 steps): queued long
    # enough, it overtakes even interactive — starvation-free.
    assert sched.queue_key(batch, 8) < sched.queue_key(inter, 8)
    # Unknown classes rank as standard, never crash.
    odd = engine_lib.Request(uid=2, prompt=np.ones(2, np.int32),
                             max_new_tokens=1, slo_class="mystery")
    assert sched.rank(odd) == engine_lib.SLO_CLASSES["standard"]


def test_slo_admission_integration():
    """With one free slot, a later-submitted interactive request is admitted
    before an earlier batch one."""
    prompts = _prompts(seed=5, n=3, lo=4, hi=8)
    eng = _engine(slots=1, cache_mode="paged", token_budget=8)
    eng.submit(engine_lib.Request(uid=0, prompt=prompts[0], max_new_tokens=4,
                                  slo_class="batch"))
    eng.submit(engine_lib.Request(uid=1, prompt=prompts[1], max_new_tokens=4,
                                  slo_class="batch"))
    eng.submit(engine_lib.Request(uid=2, prompt=prompts[2], max_new_tokens=4,
                                  slo_class="interactive"))
    _drive(eng)
    order = [r.uid for r in eng.finished]
    assert order.index(2) < order.index(1)


def test_slo_preemption_victim_ordering():
    """Preemption evicts by SLO class before admission ticket: a batch row
    admitted EARLIER (older ticket) is still evicted before an interactive
    row — the phase-split rule (latest ticket) would pick the interactive
    one."""
    prompts = _prompts(seed=6, n=2, lo=4, hi=6)
    eng = _engine(slots=2, cache_mode="paged", token_budget=8)
    eng.submit(engine_lib.Request(uid=0, prompt=prompts[0], max_new_tokens=30,
                                  slo_class="batch"))
    eng.step()  # batch admitted first -> earliest ticket
    eng.submit(engine_lib.Request(uid=1, prompt=prompts[1], max_new_tokens=30,
                                  slo_class="interactive"))
    eng.step()
    slots_by_uid = {eng.slot_req[s].uid: s for s in range(2) if eng.slot_req[s]}
    assert set(slots_by_uid) == {0, 1}
    victims = list(slots_by_uid.values())
    victim = max(victims, key=eng._victim_key)
    assert victim == slots_by_uid[0]  # the batch row, despite its older ticket
    # Phase-split engines keep the pure-ticket rule.
    ref = _engine(slots=2, cache_mode="paged")
    assert ref._victim_key(0) == ref.slot_ticket[0]


def test_budget_floor_makes_progress():
    """A budget smaller than the active row count cannot livelock: decode
    rows keep their 1-token floor and every prefill row still gets >= 1
    chunk token per step."""
    sched = engine_lib.TokenBudgetScheduler(2)
    chunks = sched.split_chunks(4, {7: 10, 8: 1, 9: 3}, [7, 8, 9])
    assert chunks == {7: 1, 8: 1, 9: 1}
    prompts = _prompts(seed=9, n=4, lo=8, hi=20)
    eng = _engine(slots=3, cache_mode="paged", token_budget=1)
    _submit_all(eng, prompts, max_new=4)
    gold_eng = _engine(slots=3, cache_mode="paged")
    _submit_all(gold_eng, prompts, max_new=4)
    assert _drive(eng, budget=600) == _drive(gold_eng)


def test_draft_budget_split():
    # No budget: full draft_k stands (phase-split engines).
    assert spec_lib.draft_budget(4, 3, None) == 4
    # Decode floor reserved first, spare split evenly.
    assert spec_lib.draft_budget(4, 3, 9) == 2
    # Budget at the floor: no drafts, decode still proceeds.
    assert spec_lib.draft_budget(4, 3, 3) == 0
    assert spec_lib.draft_budget(4, 3, 2) == 0
    # Clamped to draft_k.
    assert spec_lib.draft_budget(2, 1, 100) == 2


def test_token_budget_degrades_like_spec():
    """Configurations that cannot run a verify window (here a sliding-window
    model) run phase-split (the spec_decode degrade convention), never a
    broken mixed path."""
    cfg = registry.get_reduced("qwen2-1.5b", sliding_window=6)
    params = T.model_init(jax.random.PRNGKey(0), cfg, ENC)

    def engine(**kw):
        return engine_lib.Engine(params, cfg, ENC, slots=3, max_seq=64, **kw)

    eng = engine(token_budget=16)
    assert eng.scheduler is None and eng.token_budget is None
    assert "token_budget:off(needs_verify_window)" in eng.config.downgrades
    prompts = _prompts(seed=2, n=2)
    _submit_all(eng, prompts, max_new=4)
    ref = engine()
    _submit_all(ref, prompts, max_new=4)
    assert _drive(eng) == _drive(ref)


# ---------------------------------------------------------------------------
# Satellite 1: queued-request deadline race at admission time
# ---------------------------------------------------------------------------


class _ScriptedClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def _lapse_after_reap(eng, jump_s):
    """Arm the deadline race: advance the engine clock right AFTER the reap
    sweep runs, so a queued deadline lapses between the sweep's snapshot
    and the same step's admission — the exact window the admission-time
    re-check exists for."""
    orig = eng._reap_lifecycle

    def reap_then_lapse():
        orig()
        # Fire only when admission can actually run (a slot is free) — the
        # lapse then lands squarely between sweep and admission; earlier
        # steps would just hand the reap to the NEXT sweep.
        if eng.queue and any(r is None for r in eng.slot_req):
            eng.clock.t += jump_s

    eng._reap_lifecycle = reap_then_lapse


@pytest.mark.parametrize("budget_mode", [False, True])
def test_deadline_lapse_between_reap_and_admission(budget_mode):
    """A queued request whose deadline lapses after the reap sweep but
    before admission in the SAME step must finish "expired" without ever
    occupying a slot — the pre-fix engine admitted it, burned a prefill
    (and, paged, committed pool pages to a corpse), and only reaped it a
    full step later."""
    clock = _ScriptedClock()
    prompts = _prompts(seed=4, n=2, lo=4, hi=6)
    kw = dict(token_budget=8) if budget_mode else {}
    eng = _engine(slots=1, cache_mode="paged", clock=clock, **kw)
    eng.submit(engine_lib.Request(uid=0, prompt=prompts[0], max_new_tokens=6))
    eng.step()
    eng.submit(engine_lib.Request(uid=1, prompt=prompts[1], max_new_tokens=6,
                                  deadline_ms=500.0))
    _lapse_after_reap(eng, jump_s=600.0)
    _drive(eng)
    by_uid = {r.uid: r for r in eng.finished}
    assert by_uid[0].status == "ok"
    assert by_uid[1].status == "expired"
    # The expired request never ran: no tokens, never occupied a slot.
    assert by_uid[1].generated == []
    assert by_uid[1].error and "at admission" in by_uid[1].error


@pytest.mark.parametrize("budget_mode", [False, True])
def test_cancel_between_reap_and_admission(budget_mode):
    """Same race window, cancel flavour: a cancel landing after the sweep
    is honoured at admission, not a step later."""
    prompts = _prompts(seed=8, n=2, lo=4, hi=6)
    kw = dict(token_budget=8) if budget_mode else {}
    eng = _engine(slots=1, cache_mode="paged", **kw)
    eng.submit(engine_lib.Request(uid=0, prompt=prompts[0], max_new_tokens=6))
    victim = engine_lib.Request(uid=1, prompt=prompts[1], max_new_tokens=6)
    eng.step()
    eng.submit(victim)
    orig = eng._reap_lifecycle

    def reap_then_cancel():
        orig()
        if victim in eng.queue and any(r is None for r in eng.slot_req):
            victim.cancel()

    eng._reap_lifecycle = reap_then_cancel
    _drive(eng)
    by_uid = {r.uid: r for r in eng.finished}
    assert by_uid[1].status == "cancelled"
    assert by_uid[1].generated == []


# ---------------------------------------------------------------------------
# Satellite 2: chunk boundary x paged prefix reuse (COW at a partial block)
# ---------------------------------------------------------------------------


def _prefix_pair(bs):
    """Two prompts sharing a prefix whose length (2.5 blocks) is NOT a
    multiple of the block size or any chunk split — the partial boundary
    block must COW-split, never re-scatter onto the shared page."""
    rng = np.random.RandomState(13)
    prefix = rng.randint(1, CFG.vocab_size, 2 * bs + bs // 2).astype(np.int32)
    p0 = np.concatenate([prefix, rng.randint(1, CFG.vocab_size, 7).astype(np.int32)])
    p1 = np.concatenate([prefix, rng.randint(1, CFG.vocab_size, 9).astype(np.int32)])
    return p0, p1


def test_chunked_prefill_shared_prefix_partial_boundary_block():
    """The second prompt admits while the first (fully prefilled) is still
    resident: its chunks must RESUME at the shared-page boundary — reusing
    both full prefix pages verbatim, COW-splitting the partial boundary
    block — and never rewrite a shared page.  BlockAllocator.audit()
    (refcount-exact) runs every step; token identity closes the loop."""
    bs = 8
    p0, p1 = _prefix_pair(bs)
    ref = _engine(slots=2, cache_mode="paged", block_size=bs)
    _submit_all(ref, [p0, p1], max_new=8)
    gold = _drive(ref)

    eng = _engine(slots=2, cache_mode="paged", block_size=bs, token_budget=6)
    eng.submit(engine_lib.Request(uid=0, prompt=p0, max_new_tokens=8))
    steps = 0
    while int(eng.slot_prefill_done[0]) < len(p0):
        eng.step()
        eng.audit()
        steps += 1
        assert steps < 50
    eng.submit(engine_lib.Request(uid=1, prompt=p1, max_new_tokens=8))
    eng.step()
    eng.audit()
    s1 = next(s for s in range(2)
              if eng.slot_req[s] is not None and eng.slot_req[s].uid == 1)
    # uid 1's chunks resumed at the shared boundary (2 full blocks = 16
    # tokens) — a from-scratch prefill could have covered at most the
    # budget's worth by now.
    assert int(eng.slot_prefill_done[s1]) >= 2 * bs
    got = _drive(eng)
    assert got == gold
    st = eng.stats
    assert st["shared_hits"] >= 2   # both full prefix blocks reused
    assert st["cow_events"] >= 1    # the partial boundary block was split
    assert st["pages_in_use"] == 0  # drained clean: no leak, no double-free


def test_chunked_prefill_shared_prefix_unwritten_pages():
    """Both prefix-sharing prompts arrive together, but the second's
    matching radix-tree pages hold NO content yet (commit_prompt registers
    before chunks write).  A row prefilling from inside an unwritten shared
    block would read garbage history, so admission must not take the share
    early — it DEFERS the second admission until the writer's chunks cover
    the shared prefix, then re-plans into a REAL share (surfaced as
    deferred_hits).  Output is unchanged; no phantom sharing before the
    content lands."""
    bs = 8
    p0, p1 = _prefix_pair(bs)
    ref = _engine(slots=2, cache_mode="paged", block_size=bs)
    _submit_all(ref, [p0, p1], max_new=6)
    gold = _drive(ref)

    eng = _engine(slots=2, cache_mode="paged", block_size=bs, token_budget=6)
    _submit_all(eng, [p0, p1], max_new=6)
    eng.step()  # uid 0 admits; uid 1 defers on the unwritten prefix
    eng.audit()
    assert int(eng.slot_prefill_done.max()) <= 6  # nobody skipped ahead
    assert _drive(eng) == gold
    st = eng.stats
    assert st["prefix_cache"]["deferred_hits"] > 0  # share recovered, not lost
    assert st["shared_hits"] >= 2   # both full prefix blocks reused post-defer
    assert st["pages_in_use"] == 0


# ---------------------------------------------------------------------------
# Satellite 3: spec accounting when EOS lands mid-draft-window
# ---------------------------------------------------------------------------


def _continuation(prompt, n):
    """The model's greedy continuation (via a phase-split reference run)."""
    eng = _engine(slots=1, cache_mode="paged")
    eng.submit(engine_lib.Request(uid=0, prompt=prompt, max_new_tokens=n))
    return _drive(eng)[0]


@pytest.mark.parametrize("budget_mode", [False, True])
def test_spec_accounting_eos_mid_draft_window(budget_mode):
    prompt = _prompts(seed=23, n=1, lo=6, hi=7)[0]
    cont = _continuation(prompt, 8)
    eos = cont[1]
    if eos in cont[:1]:
        pytest.skip("degenerate continuation: EOS would fire before window")

    def oracle_drafter(ctx, k):
        # Proposes the true continuation: every draft token is accepted, so
        # the EOS at continuation index 1 truncates the commit mid-window.
        done = len(ctx) - len(prompt)
        return np.asarray(cont[done : done + k], np.int32)

    kw = dict(token_budget=12) if budget_mode else {}
    eng = _engine(slots=1, cache_mode="paged", spec_decode=True, draft_k=4,
                  drafter=oracle_drafter, **kw)
    eng.submit(engine_lib.Request(uid=0, prompt=prompt, max_new_tokens=8,
                                  eos_id=int(eos)))
    got = _drive(eng)
    assert got[0] == cont[:2]  # truncated at the EOS draft
    st = eng.stats["spec"]
    req = eng.finished[-1]
    # Only the consumed draft tokens count — the scored-but-dead tail is
    # excluded.  Pre-fix: proposed counted the full window here, deflating
    # acceptance_rate on a window that was 100% accepted.  In budget mode
    # cont[0] is the prefill-completion bonus (not spec-counted), so the
    # decode window consumes exactly the one EOS draft; phase-split spec
    # consumes both.
    expected = 1 if budget_mode else 2
    assert req.draft_proposed == req.draft_accepted == expected
    assert st["proposed"] == st["accepted"] == expected
    assert st["committed"] == expected
    assert st["acceptance_rate"] == 1.0


# ---------------------------------------------------------------------------
# Streaming + registry routing
# ---------------------------------------------------------------------------


def test_stream_cb_sees_every_token_in_order():
    prompts = _prompts(seed=15, n=3)
    seen: dict[int, list[int]] = {}

    def cb(req, tok):
        seen.setdefault(req.uid, []).append(tok)

    eng = _engine(cache_mode="paged", token_budget=8, stream_cb=cb)
    _submit_all(eng, prompts, max_new=6)
    got = _drive(eng)
    assert seen == got


def test_mixed_dispatch_key_hits_gemm_bucket():
    """A wide mixed window (slots x L past 64 rows) must key the "big"
    M-bucket, which the registry routes to the packed mmt4d GEMM — the
    fused GEMV fall-through was the mixed-M routing bug."""
    eng = _engine(slots=3, cache_mode="paged", token_budget=40)
    eng._mixed_m = 3 * 32
    _attn_key, mm_key = eng._dispatch_keys("mixed")
    assert "|big|" in mm_key
    assert registry_lib.resolve_key(mm_key).backend != "fused"


def test_deferred_hit_recovers_unwritten_prefix():
    """A request whose tree-matched prefix is still being WRITTEN by an
    in-flight chunked prefill defers instead of forfeiting the reuse: it
    re-checks the tree at its next admission opportunity, admits off the
    now-written blocks once the writer's chunks commit, and the recovered
    blocks are counted in stats["prefix_cache"]["deferred_hits"] — all
    without perturbing the generated tokens."""
    rng = np.random.RandomState(3)
    prompt = rng.randint(1, CFG.vocab_size, 33).astype(np.int32)
    mk = dict(cache_mode="paged", block_size=8, token_budget=8, slots=2)

    gold_eng = _engine(**mk)
    assert gold_eng.submit(engine_lib.Request(
        uid=0, prompt=prompt, max_new_tokens=6))
    gold = _drive(gold_eng)[0]

    eng = _engine(**mk)
    for uid in (0, 1):  # identical prompts: uid 1 races uid 0's prefill
        assert eng.submit(engine_lib.Request(
            uid=uid, prompt=prompt.copy(), max_new_tokens=6))
    out = _drive(eng)
    assert out[0] == gold and out[1] == gold
    pc = eng.stats["prefix_cache"]
    assert pc["deferred_hits"] > 0, "unwritten-prefix share was forfeited"
    assert pc["hit_blocks"] >= pc["deferred_hits"]
