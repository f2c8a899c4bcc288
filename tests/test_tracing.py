"""The engine's spans, step records and counters (serving/tracing.py), on a
tiny paged engine: one record per step with the phase spans, children
inside their parents, counters that agree with the engine's own state, the
ring's bound, the process's tracer list, and the spans on a profiler
trace's host plane."""

import glob
import os

import jax
import numpy as np
import pytest

from repro.configs import registry
from repro.core.packed import EncodingConfig
from repro.kernels import attn
from repro.models import transformer as T
from repro.serving import engine as engine_lib
from repro.serving import paged
from repro.serving import tracing

ENC = EncodingConfig(enabled=True, backend="xla")
CFG = registry.get_reduced("qwen2-1.5b")
PARAMS = T.model_init(jax.random.PRNGKey(0), CFG, ENC)

STEP_SPANS = {"engine.step", "engine.reap", "engine.admit", "engine.pages",
              "engine.tables", "engine.dispatch", "engine.wait", "engine.commit"}
PREFILL_SPANS = {"engine.prefill", "engine.scatter"}
CHILDREN = {"engine.admit": ("engine.prefill", "engine.scatter"),
            "engine.step": ("engine.reap", "engine.admit", "engine.pages", "engine.tables",
                            "engine.dispatch", "engine.wait", "engine.commit")}


def _engine(n=3, max_new=6, **kw):
    kw.setdefault("slots", 3)
    eng = engine_lib.Engine(PARAMS, CFG, ENC, max_seq=64, cache_mode="paged", **kw)
    rng = np.random.RandomState(0)
    for uid in range(n):
        prompt = rng.randint(1, CFG.vocab_size, rng.randint(4, 12)).astype(np.int32)
        assert eng.submit(engine_lib.Request(uid=uid, prompt=prompt,
                                             max_new_tokens=max_new + uid))
    return eng


@pytest.fixture(scope="module")
def driven():
    """A phase-split paged engine run to completion, with the active slots
    at each decode dispatch and the allocator's pages after each step."""
    eng = _engine(n=5)
    rows, pages = [], []
    decode = eng.decode_fn

    def counted(*args):
        rows.append(sum(r is not None for r in eng.slot_req))
        return decode(*args)

    eng.decode_fn = counted
    while eng.queue or any(r is not None for r in eng.slot_req):
        eng.step()
        pages.append(eng.alloc.in_use())
    return eng, eng.tracer.records(float("-inf"), float("inf")), rows, pages


def test_every_step_has_one_record_with_its_spans(driven):
    eng, recs, _, _ = driven
    assert [r.index for r in recs] == list(range(1, eng.step_count + 1))
    assert STEP_SPANS | PREFILL_SPANS <= set(recs[0].spans)
    for r in recs:
        assert {"engine.step", "engine.reap", "engine.admit"} <= set(r.spans)
        assert r.t0 <= r.t1 and r.span_s("engine.step") == pytest.approx(r.seconds)
        assert r.slots == eng.slots
    assert sum(len(r.admitted) for r in recs) == 5
    assert sorted(u for r in recs for u in r.finished) == list(range(5))
    assert sum(r.emitted for r in recs) == sum(len(q.generated) for q in eng.finished)
    assert sum(r.prefill_tokens for r in recs) == sum(len(q.prompt) for q in eng.finished)


def test_children_take_at_most_their_parent(driven):
    eng, recs, _, _ = driven
    for r in recs:
        for parent, kids in CHILDREN.items():
            assert sum(r.span_s(k) for k in kids) <= r.span_s(parent) + 1e-9
    totals = eng.stats["trace"]["spans"]
    assert totals["engine.prefill"]["parent"] == "engine.admit"
    assert totals["engine.wait"]["parent"] == "engine.step"
    assert totals["engine.step"]["parent"] is None
    for t in totals.values():
        assert -1e-9 <= t["self_seconds"] <= t["seconds"]
    assert totals["engine.step"]["count"] == eng.step_count


def test_counters_agree_with_the_engine(driven):
    eng, recs, rows, pages = driven
    assert [r.decode_rows for r in recs if r.decode_rows] == rows
    assert [r.pages_in_use for r in recs] == pages
    assert all(r.pages_total == eng.alloc.capacity for r in recs)
    counters = eng.stats["trace"]["counters"]
    assert counters["decode_rows"] == sum(rows)
    assert counters["finished"] == 5
    assert counters["tables_refreshed"] == sum(r.tables_refreshed for r in recs) > 0


def test_attn_block_counters_match_the_block_table():
    """attn_blocks_live / attn_blocks_grid equal a brute-force count from
    the host block table at each decode dispatch: live pages per active
    row (non-scratch entries of the dispatched width) in blocks of
    pages_per_block, and every row's blocks across the table's width.  One
    prompt spans more than one block (block_size 2: 128 pages a block)."""
    eng = engine_lib.Engine(PARAMS, CFG, ENC, max_seq=512, cache_mode="paged",
                            slots=3, block_size=2)
    rng = np.random.RandomState(1)
    for uid, n in enumerate((300, 40, 5)):
        prompt = rng.randint(1, CFG.vocab_size, n).astype(np.int32)
        assert eng.submit(engine_lib.Request(uid=uid, prompt=prompt, max_new_tokens=3))
    want = []
    decode = eng.decode_fn

    def counted(params, caches, *args):
        width = caches["groups"][0]["table"].shape[-1]
        ppb = attn.pages_per_block(eng.block_size, width)
        live = 0
        for s in range(eng.slots):
            if eng.slot_req[s] is not None:
                pages = int((eng.block_table[s, :width] != paged.SCRATCH_PAGE).sum())
                live += -(-pages // ppb)
        want.append((live, eng.slots * -(-width // ppb)))
        return decode(params, caches, *args)

    eng.decode_fn = counted
    while eng.queue or any(r is not None for r in eng.slot_req):
        eng.step()
    recs = [r for r in eng.tracer.records(float("-inf"), float("inf")) if r.decode_rows]
    got = [(r.attn_blocks_live, r.attn_blocks_grid) for r in recs]
    assert got == want
    assert all(0 < live <= grid for live, grid in got)
    assert any(live > eng.slots for live, _ in got)  # a row with two live blocks
    counters = eng.stats["trace"]["counters"]
    assert counters["attn_blocks_live"] == sum(live for live, _ in want)
    assert counters["attn_blocks_grid"] == sum(grid for _, grid in want)


def test_watchdog_reads_the_step_span(driven):
    eng, recs, _, _ = driven
    wd = eng.stats["watchdog"]
    assert wd["steps"] == eng.step_count
    assert wd["last_ms"] == pytest.approx(1e3 * recs[-1].seconds)
    assert wd["p50_ms"] == pytest.approx(1e3 * np.percentile([r.seconds for r in recs], 50))


def test_tracers_lists_the_engines_tracer(driven):
    eng, recs, _, _ = driven
    assert eng.tracer in tracing.tracers()
    assert eng.tracer.records(recs[1].t1, recs[2].t1) == recs[1:3]


def test_ring_is_bounded_and_records_refuse_a_dropped_window():
    t = [0.0]

    def clock():
        t[0] += 1.0
        return t[0]

    tr = tracing.Tracer(clock)
    first = None
    for i in range(tracing.RING_STEPS + 3):
        with tr.step(i, 1) as rec:
            with tr.span("engine.wait"):
                pass
        first = first or rec
    assert len(tr.recent(tracing.RING_STEPS + 10)) == tracing.RING_STEPS
    assert tr.steps == tracing.RING_STEPS + 3
    with pytest.raises(LookupError):
        tr.records(first.t1, float("inf"))
    oldest_kept = tr.recent(tracing.RING_STEPS)[0]
    assert oldest_kept.index == 3  # steps 0, 1 and 2 left the ring
    assert len(tr.records(oldest_kept.t1, float("inf"))) == tracing.RING_STEPS


def test_spans_land_on_a_profiler_host_plane(tmp_path):
    eng = _engine(n=2)
    eng.step()  # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.step()
        eng.step()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"), recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    names = {ev.name for plane in pd.planes if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events}
    assert {"engine.step", "engine.dispatch", "engine.wait"} <= names


@pytest.mark.parametrize("kw", [dict(token_budget=8), dict(spec_decode=True, draft_k=2)],
                         ids=["token_budget", "spec_decode"])
def test_other_step_paths_use_the_same_names(kw):
    eng = _engine(n=4, **kw)
    while eng.queue or any(r is not None for r in eng.slot_req):
        eng.step()
    recs = eng.tracer.records(float("-inf"), float("inf"))
    seen = set().union(*(r.spans for r in recs))
    assert STEP_SPANS <= seen
    assert seen <= STEP_SPANS | PREFILL_SPANS
    assert all(r.decode_rows <= r.slots for r in recs)
    assert all(0 < r.attn_blocks_live <= r.attn_blocks_grid for r in recs if r.decode_rows)
    assert sum(r.emitted for r in recs) == sum(len(q.generated) for q in eng.finished)
    assert sum(r.prefill_tokens for r in recs) == sum(len(q.prompt) for q in eng.finished)
    assert all(r.prefill_padded_tokens >= r.prefill_tokens for r in recs)


def test_scatter_pages_counter_and_padded_arg():
    """Each admission's `scatter_pages` counts the prompt pages written into
    the pool (here no prompt shares a block, so all of them), and its
    `engine.scatter` span's args give the same count and `padded`, the
    length of its index vectors: every block of the temp prefill cache."""
    eng = _engine(n=5, slots=2, block_size=2)
    args = []
    span = eng.tracer.span

    def recorded(name, **kw):
        if name == "engine.scatter":
            args.append(kw)
        return span(name, **kw)

    eng.tracer.span = recorded
    while eng.queue or any(r is not None for r in eng.slot_req):
        eng.step()
    prompts = {q.uid: len(q.prompt) for q in eng.finished}
    recs = [r for r in eng.tracer.records(float("-inf"), float("inf")) if r.admitted]
    assert [r.scatter_pages for r in recs] == [a["pages"] for a in args]
    for r, a in zip(recs, args):
        lp = 1 << (max(prompts[u] for u in r.admitted) - 1).bit_length()
        assert a["padded"] == len(r.admitted) * lp // eng.block_size
    assert any(a["padded"] > a["pages"] for a in args)
    want = sum(-(-n // eng.block_size) for n in prompts.values())
    assert sum(a["pages"] for a in args) == want
    assert eng.stats["trace"]["counters"]["scatter_pages"] == want


@pytest.mark.parametrize("cache_mode,slots", [("paged", 3), ("dense", 3), ("dense", 1)],
                         ids=["paged_batched", "dense_batched", "per_slot"])
def test_prefill_padded_tokens_count_the_rows_the_prefill_computes(cache_mode, slots):
    """`prefill_padded_tokens` is sequences x padded length of each prefill,
    and the `engine.prefill` span's args give both; `prefill_tokens` stays
    the real prompt tokens.  A paged batch pads to a power of two of at
    least one block, a dense batch to a power of two, and a dense
    admission of one request is prefilled alone, unpadded."""
    eng = engine_lib.Engine(PARAMS, CFG, ENC, max_seq=64, cache_mode=cache_mode, slots=slots)
    rng = np.random.RandomState(2)
    for uid, n in enumerate((5, 20, 9, 33, 12)):
        prompt = rng.randint(1, CFG.vocab_size, n).astype(np.int32)
        assert eng.submit(engine_lib.Request(uid=uid, prompt=prompt, max_new_tokens=3))
    args = []
    span = eng.tracer.span

    def recorded(name, **kw):
        if name == "engine.prefill":
            args.append(kw)
        return span(name, **kw)

    eng.tracer.span = recorded
    while eng.queue or any(r is not None for r in eng.slot_req):
        eng.step()
    prompts = {q.uid: len(q.prompt) for q in eng.finished}
    recs = [r for r in eng.tracer.records(float("-inf"), float("inf")) if r.admitted]
    want_args = []
    for r in recs:
        lens = [prompts[u] for u in r.admitted]
        pow2 = 1 << (max(lens) - 1).bit_length()
        if cache_mode == "paged":
            pads = [(len(lens), max(eng.block_size, pow2))]
        elif len(lens) > 1:
            pads = [(len(lens), min(pow2, eng.max_seq))]
        else:
            pads = [(1, n) for n in lens]
        want_args += [{"seqs": s, "padded": p} for s, p in pads]
        assert r.prefill_tokens == sum(lens)
        assert r.prefill_padded_tokens == sum(s * p for s, p in pads)
    assert args == want_args
    assert len(recs) > 1 and any(r.prefill_padded_tokens > r.prefill_tokens for r in recs) == (
        slots > 1)
    counters = eng.stats["trace"]["counters"]
    assert counters["prefill_padded_tokens"] == sum(r.prefill_padded_tokens for r in recs)
    assert counters["prefill_tokens"] == sum(prompts.values())
