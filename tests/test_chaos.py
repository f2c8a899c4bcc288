"""Chaos-conformance harness + hardened-lifecycle tests (docs/ROBUSTNESS.md).

The conformance contract, replayed from the committed fault schedules in
tests/fault_schedules/: under any schedule drawn from the fault taxonomy
(serving/faults.py), the engine must

  * finish every request with a terminal status (no deadlock — a step budget
    bounds the drive loop),
  * keep survivors TOKEN-IDENTICAL to the fault-free run (greedy decode is
    deterministic; faults may kill requests, never corrupt the others),
  * leak zero pages (allocator audit after every step, pool empty at drain),
  * record every kernel fault in stats["degraded"] with its demotion.

Unit tests below pin the individual lifecycle mechanisms: structured submit
rejection (backpressure), deadlines on an injected clock, cancel mid
speculative-decode, the non-finite logits guard, typed allocator invariant
errors, the decode-step watchdog, and the registry quarantine ladder.
"""

import glob
import json
import os

import jax
import numpy as np
import pytest

from repro.configs import registry
from repro.core.packed import EncodingConfig
from repro.kernels import registry as registry_lib
from repro.models import transformer as T
from repro.runtime import watchdog as watchdog_lib
from repro.serving import engine as engine_lib
from repro.serving import faults as faults_lib
from repro.serving import paged as paged_lib

ENC = EncodingConfig(enabled=True, backend="xla")
SCHEDULE_DIR = os.path.join(os.path.dirname(__file__), "fault_schedules")
SCHEDULES = sorted(glob.glob(os.path.join(SCHEDULE_DIR, "*.json")))

CFG = registry.get_reduced("qwen2-1.5b")
PARAMS = T.model_init(jax.random.PRNGKey(0), CFG, ENC)


@pytest.fixture(autouse=True)
def _clean_quarantine():
    # Kernel quarantine is process-global by design; tests must not bleed
    # demotions into each other (a demoted backend would silently change
    # which kernels every later engine resolves).
    registry_lib.clear_quarantine()
    yield
    registry_lib.clear_quarantine()


def _prompts(seed=0, n=6, repeat=False):
    rng = np.random.RandomState(seed)
    out = []
    for _ in range(n):
        base = rng.randint(1, CFG.vocab_size, rng.randint(4, 10)).astype(np.int32)
        out.append(np.tile(base, 3) if repeat else base)
    return out


def _engine(hooks=None, *, prompts, max_new=8, **kw):
    kw.setdefault("slots", 3)
    kw.setdefault("max_seq", 64)
    eng = engine_lib.Engine(
        PARAMS, CFG, ENC,
        fault_hooks=hooks,
        clock=(hooks.clock if hooks is not None else None),
        **kw,
    )
    for i, p in enumerate(prompts):
        assert eng.submit(engine_lib.Request(uid=i, prompt=p, max_new_tokens=max_new))
    return eng


def _drive(eng, sched=None, budget=300):
    """Step to completion under a hard step budget (the no-deadlock gate),
    auditing the allocator every step."""
    steps = 0
    while eng.queue or any(r is not None for r in eng.slot_req):
        assert steps < budget, "engine deadlocked under faults"
        eng.step()
        eng.audit()
        steps += 1
    if sched is not None:
        sched.drain(eng)
        eng.audit()
    return steps


def _conformance(schedule_path, *, spec=False, cache_mode="paged", **kw):
    prompts = _prompts(repeat=spec)
    mk = dict(prompts=prompts, cache_mode=cache_mode,
              spec_decode=spec, draft_k=3, **kw)
    gold = {r.uid: list(r.generated)
            for r in _drive_to_finish(_engine(**mk))}
    sched = faults_lib.FaultSchedule.from_json(schedule_path)
    eng = _engine(sched, **mk)
    _drive(eng, sched)
    by_uid = {r.uid: r for r in eng.finished}
    # Every request reached a terminal status.
    assert set(by_uid) == set(range(len(prompts)))
    assert all(r.status in engine_lib.REQUEST_STATUSES and r.done
               for r in eng.finished)
    # Survivors are token-identical to the fault-free run.
    for r in eng.finished:
        if r.status == "ok":
            assert list(r.generated) == gold[r.uid], (
                f"uid {r.uid} diverged under faults"
            )
    # Zero leaked pages once the stream drains.
    if cache_mode == "paged":
        assert eng.alloc.in_use() == 0
        assert eng.alloc.available() == eng.alloc.capacity
    # Kernel faults (if the schedule fired any) are in the audit trail.
    if any(e["kind"] == "kernel_fail" for e in sched.log):
        assert eng.stats["degraded"]
        assert all(registry_lib.quarantine_level(d["key"]) > 0
                   for d in eng.stats["degraded"])
    return eng, sched


def _drive_to_finish(eng):
    _drive(eng)
    return eng.finished


# ---------------------------------------------------------------------------
# Conformance replays of the committed schedules
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "path", SCHEDULES, ids=[os.path.basename(p) for p in SCHEDULES]
)
def test_chaos_conformance_paged(path):
    _conformance(path)


def test_chaos_conformance_spec_decode():
    path = os.path.join(SCHEDULE_DIR, "spec_cancel.json")
    eng, _ = _conformance(path, spec=True)
    assert eng.spec_decode  # the spec path actually served this stream


def test_chaos_conformance_dense():
    # pool_spike is paged-only (no pool to seize); everything else must hold
    # identically on the dense cache.
    path = os.path.join(SCHEDULE_DIR, "mixed_paged.json")
    _conformance(path, cache_mode="dense")


def test_chaos_conformance_dense_spec_decode():
    path = os.path.join(SCHEDULE_DIR, "spec_cancel.json")
    eng, _ = _conformance(path, spec=True, cache_mode="dense")
    assert eng.spec_decode


# The same committed schedules replayed through the token-budget mixed
# scheduler (serving/engine.py _mixed_step): the conformance contract —
# terminal statuses, survivor token identity, zero leaked pages, quarantine
# audit trail — must hold when decode and chunked prefill share one
# dispatch.  A kernel_fault during a mixed step quarantines/degrades
# WITHOUT losing the co-scheduled prefill chunks' progress (survivors stay
# token-identical, which they could not if a chunk were dropped or
# double-applied across the retry).
@pytest.mark.parametrize(
    "path", SCHEDULES, ids=[os.path.basename(p) for p in SCHEDULES]
)
def test_chaos_conformance_token_budget(path):
    eng, _ = _conformance(path, token_budget=24)
    assert eng.scheduler is not None
    assert eng.stats["continuous"]["mixed_steps"] > 0


def test_chaos_conformance_token_budget_spec_decode():
    path = os.path.join(SCHEDULE_DIR, "spec_cancel.json")
    eng, _ = _conformance(path, spec=True, token_budget=24)
    assert eng.spec_decode and eng.scheduler is not None


def test_schedule_json_roundtrip(tmp_path):
    sched = faults_lib.FaultSchedule.random(7, steps=12, uids=[0, 1, 2])
    p = sched.to_json(str(tmp_path / "s.json"))
    back = faults_lib.FaultSchedule.from_json(p)
    assert back.seed == sched.seed
    assert [f.to_dict() for f in back.faults] == [f.to_dict() for f in sched.faults]
    # The committed schedules stay regenerable / parseable.
    for path in SCHEDULES:
        with open(path) as f:
            raw = json.load(f)
        assert faults_lib.FaultSchedule.from_dicts(raw["faults"]).faults


def test_fault_kind_validated():
    with pytest.raises(ValueError, match="unknown fault kind"):
        faults_lib.Fault(1, "meteor_strike")


# ---------------------------------------------------------------------------
# Backpressure + admission-time serviceability (satellite a)
# ---------------------------------------------------------------------------


def test_submit_backpressure_queue_full():
    eng = _engine(prompts=[], max_queue=2)
    ok1 = eng.submit(engine_lib.Request(uid=0, prompt=np.arange(1, 5, dtype=np.int32),
                                        max_new_tokens=4))
    ok2 = eng.submit(engine_lib.Request(uid=1, prompt=np.arange(1, 5, dtype=np.int32),
                                        max_new_tokens=4))
    assert ok1 and ok2 and isinstance(ok1, engine_lib.Admitted)
    rej = eng.submit(engine_lib.Request(uid=2, prompt=np.arange(1, 5, dtype=np.int32),
                                        max_new_tokens=4))
    assert not rej and rej.reason == "queue_full"
    assert eng.stats["lifecycle"]["rejected"] == 1
    assert eng.rejected[0].uid == 2 and eng.rejected[0].status == "rejected"
    # The queue drains normally; the rejected request never ran.
    done = {r.uid for r in _drive_to_finish(eng)}
    assert done == {0, 1}


def test_submit_unserviceable_seq_and_pool_boundary():
    eng = _engine(prompts=[], max_seq=32, block_size=4, pool_pages=5)
    too_long = eng.submit(engine_lib.Request(
        uid=0, prompt=np.arange(1, 40, dtype=np.int32), max_new_tokens=1))
    assert not too_long and too_long.reason == "unserviceable_seq"
    # prompt 8 + 9 new = position 16 -> 5 pages > capacity 4: rejected with
    # the worst-case page math in the detail.
    over = eng.submit(engine_lib.Request(
        uid=1, prompt=np.arange(1, 9, dtype=np.int32), max_new_tokens=9))
    assert not over and over.reason == "unserviceable_pool"
    # One token fewer needs exactly 4 pages == capacity: admitted and runs.
    fits = eng.submit(engine_lib.Request(
        uid=2, prompt=np.arange(1, 9, dtype=np.int32), max_new_tokens=8))
    assert fits
    done = _drive_to_finish(eng)
    assert [r.uid for r in done] == [2] and done[0].status == "ok"
    assert eng.alloc.in_use() == 0


# ---------------------------------------------------------------------------
# Deadlines + cancellation (injected clock)
# ---------------------------------------------------------------------------


def test_deadline_expiry_mid_flight():
    t = [0.0]
    eng = engine_lib.Engine(PARAMS, CFG, ENC, slots=2, max_seq=64,
                            clock=lambda: t[0])
    r0 = engine_lib.Request(uid=0, prompt=np.arange(1, 6, dtype=np.int32),
                            max_new_tokens=50, deadline_ms=1000.0)
    r1 = engine_lib.Request(uid=1, prompt=np.arange(1, 6, dtype=np.int32),
                            max_new_tokens=6)
    assert eng.submit(r0) and eng.submit(r1)
    eng.step()  # both admitted + one token
    assert r0.status == "running"
    t[0] = 2.0  # 2s later: past r0's 1s deadline
    eng.step()
    assert r0.done and r0.status == "expired" and "deadline" in r0.error
    assert len(r0.generated) >= 1  # keeps what it produced
    _drive(eng)
    assert r1.status == "ok" and len(r1.generated) == 6
    assert eng.alloc.in_use() == 0


def test_deadline_expiry_while_queued():
    t = [0.0]
    eng = engine_lib.Engine(PARAMS, CFG, ENC, slots=1, max_seq=64,
                            clock=lambda: t[0])
    reqs = [engine_lib.Request(uid=i, prompt=np.arange(1, 6, dtype=np.int32),
                               max_new_tokens=4, deadline_ms=500.0)
            for i in range(3)]
    for r in reqs:
        assert eng.submit(r)
    eng.step()  # uid 0 admitted; 1 and 2 wait
    t[0] = 1.0
    eng.step()
    statuses = {r.uid: r.status for r in reqs}
    assert statuses[1] == "expired" and statuses[2] == "expired"
    assert reqs[1].generated == [] and reqs[2].generated == []
    _drive(eng)
    statuses = {r.uid: r.status for r in reqs}
    assert statuses[0] == "expired"  # slot 0 also blew its 500ms budget


def test_cancel_while_queued_and_running():
    eng = _engine(prompts=_prompts(n=3), slots=1)
    queued = list(eng.queue)
    eng.step()
    running = next(r for r in queued if r.status == "running")
    waiting = next(r for r in queued if r.status == "queued")
    running.cancel()
    waiting.cancel()
    eng.step()
    assert running.status == "cancelled" and running.done
    assert waiting.status == "cancelled" and waiting.generated == []
    _drive(eng)
    assert eng.alloc.in_use() == 0
    assert eng.stats["lifecycle"]["cancelled"] == 2


# ---------------------------------------------------------------------------
# Cancel mid speculative decode (satellite c)
# ---------------------------------------------------------------------------


def test_cancel_mid_spec_decode_frees_draft_pages():
    """A cancel landing while a verify window is in flight: the cancelled
    request emits nothing from that window (its pages — draft positions
    included — return to the pool), and the co-batched slot's stream is
    token-identical to the fault-free run."""
    prompts = _prompts(seed=5, n=2, repeat=True)
    gold = {r.uid: list(r.generated) for r in _drive_to_finish(
        _engine(prompts=prompts, slots=2, spec_decode=True, draft_k=3,
                max_new=10))}

    sched = faults_lib.FaultSchedule(
        [faults_lib.Fault(3, "cancel", uid=0, where="mid")], seed=0)
    eng = _engine(sched, prompts=prompts, slots=2, spec_decode=True,
                  draft_k=3, max_new=10)
    assert eng.spec_decode
    _drive(eng, sched)
    by_uid = {r.uid: r for r in eng.finished}
    assert by_uid[0].status == "cancelled"
    # The mid cancel fired during a dispatch (the schedule logs which).
    mid = [e for e in sched.log if e["kind"] == "cancel"]
    assert mid and mid[0]["where"] == "mid"
    # Cancelled before the window's tokens committed: strictly fewer tokens
    # than the fault-free run of the same request.
    assert len(by_uid[0].generated) < len(gold[0])
    # Co-batched request: byte-for-byte the fault-free stream.
    assert by_uid[1].status == "ok"
    assert list(by_uid[1].generated) == gold[1]
    # Every page (committed AND draft-only) is back in the pool.
    assert eng.alloc.in_use() == 0


def test_spec_survivor_page_truncation_under_cancel():
    """While one slot dies mid-window, the survivor's trailing draft-only
    pages still roll back to exactly its committed need (the
    _truncate_slot_pages path), verified by the per-step audit in _drive."""
    prompts = _prompts(seed=9, n=2, repeat=True)
    sched = faults_lib.FaultSchedule(
        [faults_lib.Fault(2, "cancel", uid=1, where="mid")], seed=0)
    eng = _engine(sched, prompts=prompts, slots=2, spec_decode=True,
                  draft_k=4, max_new=12, block_size=4, pool_pages=32)
    while any(r is not None for r in eng.slot_req) or eng.queue:
        eng.step()
        eng.audit()
        for s in range(eng.slots):
            if eng.slot_req[s] is not None:
                # Never MORE pages than the committed history + next write
                # need: trailing draft-only pages must have rolled back.
                # (Fewer is fine — growth pages allocate lazily next step.)
                need = (int(eng.slot_pos[s]) - 1) // eng.block_size + 1
                assert len(eng.slot_pages[s]) <= need, (
                    "draft-only pages survived the rollback"
                )
    sched.drain(eng)
    assert eng.alloc.in_use() == 0


# ---------------------------------------------------------------------------
# Non-finite logits guard
# ---------------------------------------------------------------------------


def test_guard_quarantines_only_offending_slot():
    prompts = _prompts(seed=2, n=2)
    gold = {r.uid: list(r.generated)
            for r in _drive_to_finish(_engine(prompts=prompts, slots=2))}
    sched = faults_lib.FaultSchedule(
        [faults_lib.Fault(2, "nonfinite_logits", uid=0)], seed=0)
    eng = _engine(sched, prompts=prompts, slots=2)
    _drive(eng, sched)
    by_uid = {r.uid: r for r in eng.finished}
    assert by_uid[0].status == "error" and "non-finite" in by_uid[0].error
    assert by_uid[1].status == "ok" and list(by_uid[1].generated) == gold[1]
    assert eng.stats["lifecycle"]["guard_trips"] == 1


def test_guard_flag_off_skips_check():
    sched = faults_lib.FaultSchedule(
        [faults_lib.Fault(2, "nonfinite_logits", uid=0)], seed=0)
    eng = _engine(sched, prompts=_prompts(n=1), slots=1, logits_guard=False)
    _drive(eng, sched)
    # With the guard off the corruption goes unchecked (nothing trips, the
    # request ends "ok") — the flag exists so benchmarks can measure the
    # guard's own per-step overhead against an unguarded run.
    assert eng.stats["lifecycle"]["guard_trips"] == 0
    assert eng.finished[0].status == "ok"


def test_poisoned_kv_trips_guard_next_step():
    sched = faults_lib.FaultSchedule(
        [faults_lib.Fault(3, "nonfinite_kv", uid=0)], seed=0)
    eng = _engine(sched, prompts=_prompts(n=1), slots=1, max_new=10)
    _drive(eng, sched)
    assert eng.finished[0].status == "error"
    assert eng.stats["lifecycle"]["guard_trips"] >= 1
    assert eng.alloc.in_use() == 0


def test_poisoned_kv_quantized_pages_isolated_to_slot():
    """nonfinite_kv under the kv8 layout: integer data pages cannot hold a
    NaN, so the injection saturates them AND NaNs the float32 scale pages —
    dequantize still goes non-finite, the guard still trips, and it
    quarantines ONLY the offending slot.  Co-batched survivors must stay
    token-identical to the fault-free kv8 run (their pages are private;
    the poison cannot leak through the shared pool)."""
    prompts = _prompts(n=3)
    gold = {r.uid: list(r.generated)
            for r in _drive_to_finish(_engine(prompts=prompts, kv_quant="kv8"))}
    sched = faults_lib.FaultSchedule(
        [faults_lib.Fault(3, "nonfinite_kv", uid=0)], seed=0)
    eng = _engine(sched, prompts=prompts, kv_quant="kv8")
    _drive(eng, sched)
    assert eng.stats["kv_quant"] == "kv8"
    by_uid = {r.uid: r for r in eng.finished}
    assert by_uid[0].status == "error"
    assert eng.stats["lifecycle"]["guard_trips"] >= 1
    for uid in (1, 2):
        assert by_uid[uid].status == "ok", by_uid[uid].error
        assert list(by_uid[uid].generated) == gold[uid], (
            f"survivor uid {uid} diverged under kv8 poison"
        )
    assert eng.alloc.in_use() == 0
    # Scale state tracks the allocated set in lockstep: after the drain the
    # only allocated pages are the refcount-0 blocks parked in the prefix
    # tree, and exactly those keep their scales (for revival on a hit).
    assert eng.alloc.scale_live == eng.alloc.cached


def test_chaos_conformance_kv8():
    """The full conformance contract (terminal statuses, survivor token
    identity, zero leaked pages + zero leaked scale state, quarantine audit
    trail) holds with the quantized layout, replaying the committed
    kv-quant schedule."""
    path = os.path.join(SCHEDULE_DIR, "kv_quant_mix.json")
    eng, _ = _conformance(path, kv_quant="kv8")
    assert eng.stats["kv_quant"] == "kv8"
    # Lockstep invariant: scales survive exactly on tree-cached pages.
    assert eng.alloc.scale_live == eng.alloc.cached


# ---------------------------------------------------------------------------
# Typed allocator invariants (satellite b)
# ---------------------------------------------------------------------------


def test_allocator_double_free_is_typed():
    alloc = paged_lib.BlockAllocator(8, 4)
    p = alloc.alloc(owner=2)
    alloc.free_page(p)
    with pytest.raises(paged_lib.AllocatorInvariantError) as ei:
        alloc.free_page(p, owner=2)
    assert ei.value.page == p and ei.value.owner == 2
    assert f"page {p}" in str(ei.value) and "slot 2" in str(ei.value)
    assert isinstance(ei.value, AssertionError)  # old contracts still hold


def test_allocator_share_unreferenced_is_typed():
    alloc = paged_lib.BlockAllocator(8, 4)
    p = alloc.alloc()
    alloc.free_page(p)
    with pytest.raises(paged_lib.AllocatorInvariantError):
        alloc.share(p)


def test_audit_catches_stale_prefix_tree_entry():
    """A freed page left reachable from the radix tree is the cross-request
    corruption precursor (a recycled page would serve another tenant's KV
    as a cache hit): audit must name it."""
    alloc = paged_lib.BlockAllocator(8, 4)
    prompt = np.arange(1, 10, dtype=np.int32)  # 9 tokens -> 2 shareable blocks
    nblocks, shared = alloc.plan_prompt(prompt)
    plan = alloc.commit_prompt(prompt, nblocks, shared)
    alloc.mark_written(plan.pages)
    alloc.free_pages(plan.pages)  # shareable blocks park in the tree, rc 0
    stale = plan.pages[0]
    assert stale in alloc.cached
    # Simulate the bug: page recycled onto the free list while its tree
    # node survives (reaping skipped on the free path).
    alloc.cached.discard(stale)
    alloc.free.append(stale)
    with pytest.raises(paged_lib.AllocatorInvariantError,
                       match="prefix tree references a freed page"):
        alloc.audit([])


def test_audit_leak_names_owner():
    alloc = paged_lib.BlockAllocator(8, 4)
    p = alloc.alloc(owner=1)
    with pytest.raises(paged_lib.AllocatorInvariantError) as ei:
        alloc.audit([])  # page allocated but referenced by no table: a leak
    assert ei.value.page == p and ei.value.owner == 1


# ---------------------------------------------------------------------------
# Decode-step watchdog
# ---------------------------------------------------------------------------


def test_watchdog_stall_detection_and_percentiles():
    wd = watchdog_lib.DecodeStepWatchdog()
    durations = []
    for _ in range(8):  # warmup + steady 10ms steps
        durations.append(0.010)
        assert wd.observe(0.010) is False
    durations.append(0.200)  # 20x the EWMA: a stall
    assert wd.observe(0.200) is True
    s = wd.summary(durations)
    assert s["stalls"] == 1 and s["stalled"]
    assert s["p50_ms"] == pytest.approx(10.0, rel=0.2)
    assert s["p99_ms"] > s["p50_ms"]
    # The stalled sample was clamped: the EWMA didn't absorb the spike.
    assert s["ewma_ms"] < 50.0
    # Recovery: the next normal step is not a stall.
    assert wd.observe(0.010) is False


def test_watchdog_wired_into_engine_stats():
    eng = _engine(prompts=_prompts(n=2), slots=2)
    _drive(eng)
    wd = eng.stats["watchdog"]
    assert wd["steps"] == eng.stats["steps"] > 0
    assert wd["p50_ms"] >= 0.0 and wd["ewma_ms"] > 0.0


def test_watchdog_sees_injected_clock_skew():
    sched = faults_lib.FaultSchedule(
        [faults_lib.Fault(8, "clock_skew", skew_s=30.0)], seed=0)
    eng = _engine(sched, prompts=_prompts(n=2), slots=2, max_new=12)
    _drive(eng, sched)
    assert eng.stats["watchdog"]["stalls"] >= 1  # the skewed step flagged


# ---------------------------------------------------------------------------
# Kernel quarantine (registry demotion ladder)
# ---------------------------------------------------------------------------


def test_registry_demotes_down_ladder():
    key = registry_lib.dispatch_key(
        "none", engine_lib.Phase.DECODE, 4, "tpu-v5e")
    first = registry_lib.resolve_key(key, requested="xla")
    rec = registry_lib.demote(key, failing=first.backend, requested="xla")
    assert rec["from"] == first.backend and rec["to"] != first.backend
    demoted = registry_lib.resolve_key(key, requested="xla")
    assert demoted.backend == rec["to"]
    assert demoted.source.startswith("quarantined:")
    assert registry_lib.quarantine_level(key) >= 1
    snap = registry_lib.quarantine_snapshot()
    assert key in snap and snap[key]["to"] == demoted.backend


def test_engine_quarantine_survives_for_process_and_records():
    sched = faults_lib.FaultSchedule(
        [faults_lib.Fault(2, "kernel_fail", key="attn|decode|*")], seed=0)
    eng = _engine(sched, prompts=_prompts(n=2), slots=2)
    _drive(eng, sched)
    deg = eng.stats["degraded"]
    assert len(deg) == 1
    d = deg[0]
    assert d["key"].startswith("attn|decode|")
    assert d["from"] != d["to"] and d["reason"]
    assert registry_lib.quarantine_level(d["key"]) == d["level"] == 1
    assert eng.stats["lifecycle"]["kernel_faults"] == 1
    # A second engine in the same process resolves the demoted backend too.
    eng2 = _engine(prompts=_prompts(n=1), slots=1)
    _drive(eng2)
    assert eng2.finished[0].status == "ok"
    assert registry_lib.quarantine_level(d["key"]) == 1


def test_dispatch_exhausting_ladder_raises():
    # Six faults armed at the SAME step: each in-step retry after a demotion
    # consumes (and fires) another one, so the dispatch keeps failing past
    # the bottom of the ladder — the engine must surface the failure rather
    # than loop.
    faultlist = [faults_lib.Fault(1, "kernel_fail", key="*") for _ in range(6)]
    sched = faults_lib.FaultSchedule(faultlist, seed=0)
    eng = _engine(sched, prompts=_prompts(n=1), slots=1)
    with pytest.raises(faults_lib.KernelFaultError):
        for _ in range(10):
            eng.step()
