"""Paged KV-cache allocator invariants (serving/paged.py) and the paged
engine's page accounting: no double allocation, exact freed-on-finish
refcounts, copy-on-write only at the first divergent block, preempted
requests finishing with correct tokens."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.core import encoding
from repro.core.encoding import Phase
from repro.core.packed import EncodingConfig
from repro.models import layers as L
from repro.models import transformer as T
from repro.serving import engine as engine_lib
from repro.serving import paged as paged_lib

ENC = EncodingConfig(enabled=True, backend="xla")


# ---------------------------------------------------------------------------
# Pure allocator


def test_allocator_no_double_allocation_fuzz():
    """Random alloc/free interleavings: a page is never handed out twice,
    and free + in-use always partitions the pool exactly."""
    rng = np.random.RandomState(0)
    alloc = paged_lib.BlockAllocator(num_pages=9, block_size=4)
    held: list[int] = []
    for _ in range(500):
        if held and (rng.rand() < 0.45 or not alloc.available()):
            alloc.free_page(held.pop(rng.randint(len(held))))
        else:
            page = alloc.alloc()
            if page is None:
                assert alloc.available() == 0
                continue
            assert page not in held, "double-allocated page"
            assert page != paged_lib.SCRATCH_PAGE
            held.append(page)
        alloc.audit([held])
    for p in list(held):
        alloc.free_page(p)
    alloc.audit([])
    assert alloc.available() == alloc.capacity


def test_allocator_prefix_share_and_cow_first_divergence():
    """Two prompts sharing exactly two full blocks: the leading two pages are
    refcount-shared, copy-on-write triggers exactly once — at the first
    divergent block — and every later block allocates privately."""
    bs = 4
    alloc = paged_lib.BlockAllocator(num_pages=17, block_size=bs)
    a = np.arange(1, 14, dtype=np.int32)           # 13 tokens: 4 blocks
    nb_a, shared_a = alloc.plan_prompt(a)
    assert (nb_a, shared_a) == (4, {})             # empty registry: no reuse
    plan_a = alloc.commit_prompt(a, nb_a, shared_a)
    assert plan_a.shared == [False] * 4
    assert alloc.stats["cow_events"] == 0

    b = a.copy()
    b[2 * bs] += 1                                  # diverge at block 2
    nb_b, shared_b = alloc.plan_prompt(b)
    assert nb_b == 4 and set(shared_b) == {0, 1}    # blocks 0,1 reusable
    assert [shared_b[j] for j in (0, 1)] == plan_a.pages[:2]
    plan_b = alloc.commit_prompt(b, nb_b, shared_b)
    assert plan_b.shared == [True, True, False, False]
    assert plan_b.pages[:2] == plan_a.pages[:2]
    assert not set(plan_b.pages[2:]) & set(plan_a.pages), "divergent blocks share"
    assert alloc.stats["cow_events"] == 1           # exactly one CoW point
    assert alloc.refcount[plan_a.pages[0]] == 2
    alloc.audit([plan_a.pages, plan_b.pages])

    # A prompt divergent from block 0 shares nothing and triggers no CoW.
    c = a.copy()
    c[0] += 1
    nb_c, shared_c = alloc.plan_prompt(c)
    assert shared_c == {}
    alloc.commit_prompt(c, nb_c, shared_c)
    assert alloc.stats["cow_events"] == 1


def test_allocator_partial_last_block_never_shared():
    """The block holding position plen-1 is appendable (decode rewrites it),
    so it must never enter the prefix registry."""
    bs = 4
    alloc = paged_lib.BlockAllocator(num_pages=9, block_size=bs)
    a = np.arange(1, 9, dtype=np.int32)    # 8 tokens: blocks 0,1 full
    nb_a, shared_a = alloc.plan_prompt(a)
    plan_a = alloc.commit_prompt(a, nb_a, shared_a)
    assert plan_a is not None
    # shareable = (8-1)//4 = 1: only block 0 registered, block 1 appendable.
    nb, shared = alloc.plan_prompt(a.copy())
    assert set(shared) == {0}


def test_allocator_commit_rolls_back_when_pool_dry():
    alloc = paged_lib.BlockAllocator(num_pages=3, block_size=4)  # capacity 2
    long = np.arange(1, 14, dtype=np.int32)  # needs 4 blocks
    nb, shared = alloc.plan_prompt(long)
    assert alloc.commit_prompt(long, nb, shared) is None
    alloc.audit([])                           # rollback left nothing behind
    assert alloc.available() == alloc.capacity


# ---------------------------------------------------------------------------
# Engine-level accounting


def _drain(eng, *, audit=True):
    steps = 0
    while eng.queue or any(r is not None for r in eng.slot_req):
        eng.step()
        if audit:
            eng.audit()
        steps += 1
        assert steps < 1000
    return {r.uid: r.generated for r in eng.finished}


def test_engine_freed_on_finish_exact():
    cfg = registry.get_reduced("qwen2-1.5b")
    params = T.model_init(jax.random.PRNGKey(0), cfg, ENC)
    eng = engine_lib.Engine(
        params, cfg, ENC, slots=3, max_seq=32, cache_mode="paged", block_size=4
    )
    rng = np.random.RandomState(3)
    for i in range(6):
        eng.submit(engine_lib.Request(
            uid=i, prompt=rng.randint(1, cfg.vocab_size, rng.randint(2, 10)).astype(np.int32),
            max_new_tokens=int(rng.randint(1, 7)),
        ))
    done = _drain(eng)
    assert len(done) == 6
    stats = eng.stats
    assert stats["pages_in_use"] == 0
    assert stats["pages_free"] == stats["pages_total"]
    assert stats["allocs"] == stats["frees"]          # every page returned once
    assert all(int(p) == paged_lib.SCRATCH_PAGE for p in eng.block_table.ravel())


def test_engine_preempted_requests_finish_with_correct_tokens():
    """A pool too small for concurrent growth forces eviction + replay; the
    preempted requests must still produce exactly the dense engine's tokens."""
    cfg = registry.get_reduced("qwen2-1.5b")
    params = T.model_init(jax.random.PRNGKey(0), cfg, ENC)
    rng = np.random.RandomState(4)
    reqs = [
        engine_lib.Request(
            uid=i, prompt=rng.randint(1, cfg.vocab_size, 5 + i).astype(np.int32),
            max_new_tokens=8,
        )
        for i in range(3)
    ]
    import dataclasses
    eng_d = engine_lib.Engine(params, cfg, ENC, slots=3, max_seq=32, cache_mode="dense")
    for r in reqs:
        eng_d.submit(dataclasses.replace(r, generated=[]))
    want = _drain(eng_d, audit=False)

    eng_p = engine_lib.Engine(
        params, cfg, ENC, slots=3, max_seq=32, cache_mode="paged",
        block_size=4, pool_pages=5,   # capacity 4 = one request's worst case
    )
    for r in reqs:
        eng_p.submit(dataclasses.replace(r, generated=[]))
    got = _drain(eng_p)
    assert eng_p.stats["preemptions"] > 0, eng_p.stats
    assert got == want


def test_engine_rejects_unserviceable_request():
    cfg = registry.get_reduced("qwen2-1.5b")
    params = T.model_init(jax.random.PRNGKey(0), cfg, ENC)
    eng = engine_lib.Engine(
        params, cfg, ENC, slots=2, max_seq=64, cache_mode="paged",
        block_size=4, pool_pages=4,
    )
    # Rejected at submit, before any page could be committed: a half-admitted
    # batch must never be abandoned mid-flight.  Structured backpressure:
    # submit returns a falsy Rejected(reason) rather than raising.
    res = eng.submit(engine_lib.Request(
        uid=0, prompt=np.arange(1, 30, dtype=np.int32), max_new_tokens=8,
    ))
    assert not res
    assert isinstance(res, engine_lib.Rejected)
    assert res.reason == "unserviceable_pool"
    assert "pool" in res.detail
    eng.audit()
    assert eng.alloc.available() == eng.alloc.capacity
    assert not eng.queue and eng.rejected[0].status == "rejected"


def test_engine_tenant_quota_fairness_under_flood():
    """One tenant floods the queue; with tenant_quota set, its worst-case
    reservations are capped so the other tenant is admitted alongside it
    (quota-blocked requests are SKIPPED, not head-of-line blockers), no
    tenant's reserved or charged pages ever exceed the quota, and a request
    whose own worst case outgrows the quota is rejected at submit."""
    cfg = registry.get_reduced("qwen2-1.5b")
    params = T.model_init(jax.random.PRNGKey(0), cfg, ENC)
    quota = 6
    eng = engine_lib.Engine(
        params, cfg, ENC, slots=3, max_seq=32, cache_mode="paged",
        block_size=4, pool_pages=25, tenant_quota=quota,
    )
    rng = np.random.RandomState(7)
    # Each request's worst case is min(6+6, 32)-1 = pos 11 -> 3 pages, so the
    # quota admits at most two per tenant concurrently.  t0 floods first.
    uid = 0
    for tenant, n in (("t0", 5), ("t1", 2)):
        for _ in range(n):
            assert eng.submit(engine_lib.Request(
                uid=uid, tenant=tenant,
                prompt=rng.randint(1, cfg.vocab_size, 6).astype(np.int32),
                max_new_tokens=6,
            ))
            uid += 1
    saw_fair = False
    steps = 0
    while eng.queue or any(r is not None for r in eng.slot_req):
        eng.step()
        eng.audit()
        for pages in eng._tenant_reserved.values():
            assert pages <= quota
        for u in eng.alloc.tenant_usage().values():
            assert u <= quota + 1e-9
        running = {r.tenant for r in eng.slot_req if r is not None}
        if "t1" in running and any(r.tenant == "t0" for r in eng.queue):
            saw_fair = True        # t1 runs while t0 still has queued work
    assert saw_fair
    assert len(eng.finished) == 7 and not eng._tenant_reserved
    assert eng.stats["prefix_cache"]["tenant_quota"] == quota

    # Worst case 7 pages > quota 6 (but < pool): rejected up front rather
    # than queued to starve behind an admission gate it can never pass.
    res = eng.submit(engine_lib.Request(
        uid=99, tenant="t0", prompt=np.arange(1, 18, dtype=np.int32),
        max_new_tokens=8,
    ))
    assert isinstance(res, engine_lib.Rejected)
    assert res.reason == "unserviceable_quota"


# ---------------------------------------------------------------------------
# Gather correctness + capacity math (non-hypothesis seeds; the hypothesis
# sweep lives in tests/test_paged_property.py)


def test_paged_gather_matches_dense_slice_seeded():
    rng = np.random.RandomState(5)
    b, nb, bs, kv, hd = 3, 4, 4, 2, 6
    dense = rng.randn(b, nb * bs, kv, hd).astype(np.float32)
    pool = np.zeros((1 + b * nb, bs, kv, hd), np.float32)
    table = np.zeros((b, nb), np.int32)
    page = 1
    for i in range(b):
        for j in range(nb):
            pool[page] = dense[i, j * bs : (j + 1) * bs]
            table[i, j] = page
            page += 1
    got = L.paged_gather(jnp.asarray(pool), jnp.asarray(table))
    np.testing.assert_array_equal(np.asarray(got), dense)


def test_kv_capacity_math():
    cap = encoding.kv_capacity_requests(
        hbm_budget=16 * (1 << 20), max_seq=2048, mean_tokens=256,
        block_size=16, num_layers=16, num_kv_heads=2, head_dim=64,
    )
    # 256-token requests against a 2048-token worst case: 8x the requests.
    assert cap["paged"] == 8 * cap["dense"]
    assert cap["bytes_per_token"] == 2 * 16 * 2 * 64 * 2


# ---------------------------------------------------------------------------
# Admission's compiled, pool-donating prefill scatter (engine.scatter_prefill)


def _eager_scatter(pool, tmp, rows, blocks, pages, kv_quant):
    """The eager per-leaf write admission ran before the compiled scatter,
    kept here as its reference: gather the non-shared blocks of the temp
    dense cache, quantize them under kv8/kv4, `.at[pages].set` into a new
    copy of each pool leaf."""
    bs = next(x for x in jax.tree.leaves(pool)).shape[-3]
    ria, bia, pga = (jnp.asarray(a, jnp.int32) for a in (rows, blocks, pages))
    tmp_by_path = {jax.tree_util.keystr(p): v
                   for p, v in jax.tree_util.tree_flatten_with_path(tmp)[0]}
    layout = encoding.kv_layout(kv_quant)
    pending = {}

    def one(path, leaf):
        name = str(path[-1].key)
        key = jax.tree_util.keystr(path)
        stacked = engine_lib._batch_axis(path) == 1
        if name in ("k_scale", "v_scale"):
            sc = pending.pop(key)
            return leaf.at[:, pga].set(sc) if stacked else leaf.at[pga].set(sc)
        part = tmp_by_path[key]
        if stacked:
            g, nb, lpad, kvh, hd = part.shape
            got = part.reshape(g, nb, lpad // bs, bs, kvh, hd)[:, ria, bia]
        else:
            nb, lpad, kvh, hd = part.shape
            got = part.reshape(nb, lpad // bs, bs, kvh, hd)[ria, bia]
        if layout.quantized:
            got, sc = layout.quantize(got)
            pending[key.replace(f"['{name}']", f"['{name}_scale']")] = sc
        return leaf.at[:, pga].set(got) if stacked else leaf.at[pga].set(got)

    out = jax.tree_util.tree_map_with_path(one, pool)
    assert not pending
    return out


def _check_scatter_against_eager(eng, kv_quant):
    """Wrap the engine's compiled scatter: every call must write the pool
    bitwise as the eager reference does, size its index vectors to every
    block of the temp cache, write each real page once and never a page
    that already holds written K/V (shared prefix pages are never
    rewritten).  Returns the list of real page counts, one per call."""
    fn, calls = eng.scatter_fn, []

    def checked(pool, tmp, rows, blocks, pages, count):
        n = int(count)
        b, lp = jax.tree.leaves(tmp)[0].shape[-4:-2]
        assert len(pages) == b * lp // eng.block_size >= n > 0
        assert len(set(pages[:n].tolist())) == n
        assert not any(eng.alloc.is_written(int(p)) for p in pages[:n])
        want = jax.block_until_ready(
            _eager_scatter(pool, tmp, rows[:n], blocks[:n], pages[:n], kv_quant))
        want = {jax.tree_util.keystr(p): v
                for p, v in jax.tree_util.tree_flatten_with_path(want)[0]}
        got = fn(pool, tmp, rows, blocks, pages, count)
        for path, leaf in jax.tree_util.tree_flatten_with_path(got)[0]:
            key = jax.tree_util.keystr(path)
            np.testing.assert_array_equal(np.asarray(leaf), np.asarray(want[key]), err_msg=key)
        calls.append(n)
        return got

    eng.scatter_fn = checked
    return calls


@pytest.mark.parametrize("kv_quant", ["bf16", "kv8", "kv4"])
def test_compiled_scatter_matches_eager_reference(kv_quant):
    """Admissions of differing page counts, a batch in which one prompt
    shares the other's leading blocks, and (bf16) a later radix-cache
    suffix prefill: the pool matches the eager scatter bitwise after each,
    on stacked ("groups") and unstacked ("tail") cache leaves alike; under
    bf16 the engine's tokens match the dense engine's."""
    import dataclasses
    cfg = dataclasses.replace(registry.get_reduced("qwen2-1.5b"),
                              block_pattern=("attn", "attn"), num_layers=5)
    enc = dataclasses.replace(ENC, attn_backend="pallas") if kv_quant == "kv4" else ENC
    params = T.model_init(jax.random.PRNGKey(0), cfg, enc)
    eng = engine_lib.Engine(params, cfg, enc, slots=3, max_seq=64, cache_mode="paged",
                            block_size=4, kv_quant=kv_quant)
    assert eng.kv_quant == kv_quant
    assert {"groups", "tail"} <= set(eng.caches)
    calls = _check_scatter_against_eager(eng, kv_quant)
    suffix = eng.suffix_prefill_fn = engine_lib.count_calls(eng.suffix_prefill_fn)
    rng = np.random.RandomState(11)
    base = rng.randint(1, cfg.vocab_size, 40).astype(np.int32)
    waves = [
        [rng.randint(1, cfg.vocab_size, n).astype(np.int32) for n in (13, 30)],
        # One batch: the later prompts share the first's leading blocks,
        # which the same scatter writes once (7 + 1 + 1 of 12 pages).
        [base[:26], np.concatenate([base[:8], rng.randint(1, cfg.vocab_size, 3)])
                      .astype(np.int32), base[:5]],
        # Six shared blocks already written (bf16: a suffix prefill).
        [np.concatenate([base[:24], rng.randint(1, cfg.vocab_size, 9)]).astype(np.int32)],
    ]
    uid = 0
    for wave in waves:
        for prompt in wave:
            assert eng.submit(engine_lib.Request(uid=uid, prompt=prompt, max_new_tokens=2))
            uid += 1
        _drain(eng)
    assert calls == [12, 9, 3]
    assert eng.stats["prefix_cache"]["hit_blocks"] > 0, eng.stats["prefix_cache"]
    assert (suffix.calls > 0) == (kv_quant == "bf16")
    if kv_quant != "bf16":
        return
    dense = engine_lib.Engine(params, cfg, enc, slots=3, max_seq=64, cache_mode="dense")
    uid = 0
    for wave in waves:
        for prompt in wave:
            dense.submit(engine_lib.Request(uid=uid, prompt=prompt, max_new_tokens=2))
            uid += 1
        _drain(dense, audit=False)
    assert {r.uid: r.generated for r in eng.finished} == \
        {r.uid: r.generated for r in dense.finished}


def test_compiled_scatter_programs_follow_the_prefill_shapes():
    """Admissions at each of 1..64 prompt page counts compile one scatter
    (and one temp-cache) program per padded prefill length, 7 here, not one
    per page count, and each donates the pool: the old leaves are deleted,
    written in place."""
    cfg = registry.get_reduced("qwen2-1.5b")
    params = T.model_init(jax.random.PRNGKey(0), cfg, ENC)
    eng = engine_lib.Engine(params, cfg, ENC, slots=1, max_seq=128, cache_mode="paged",
                            block_size=2)
    rng = np.random.RandomState(12)
    for n in range(1, 65):
        prompt = rng.randint(1, cfg.vocab_size, 2 * n - 1).astype(np.int32)
        assert eng.submit(engine_lib.Request(uid=n, prompt=prompt, max_new_tokens=1))
    old = jax.tree.leaves(eng.caches["groups"][0]["k"])[0]
    # jit objects of one function share their compiled programs in-process;
    # no other test here scatters into a pool of this shape.
    before = eng.scatter_fn._cache_size()
    steps = 0
    while eng.queue or any(r is not None for r in eng.slot_req):
        eng.step()
        steps += 1
    assert old.is_deleted()
    assert len(eng.finished) == 64 and steps == 64
    assert eng.stats["trace"]["counters"]["scatter_pages"] == sum(range(1, 65))
    lengths = 7  # prompts padded to 2, 4, ..., 128 tokens
    assert eng.scatter_fn._cache_size() - before == lengths
    assert eng.prefill_cache_fn._cache_size() == lengths


# ---------------------------------------------------------------------------
# The serving scan's carried pool against per-layer pools


def _layerwise_decode(params, caches, tokens, pos, *, cfg, enc):
    """The decode forward with each layer run on its own pool: the scan
    slices every layer's pool out of the stacked leaves (xs) and stacks the
    written pools back (ys), the layout the serving scan keeps the pool out
    of."""
    from repro.models.blocks import BLOCKS

    apply = BLOCKS["attn"][1]

    def layers(x, lps, lcs):
        new = []
        for lp, lc in zip(lps, lcs):
            x, lc, _ = apply(lp, x, cfg=cfg, enc=enc, phase=Phase.DECODE, cache=lc, pos=pos)
            new.append(lc)
        return x, tuple(new)

    x = params["embed"][tokens].astype(cfg.activation_dtype)
    x, groups = jax.lax.scan(lambda x, xs: layers(x, *xs), x,
                             (params["groups"], caches["groups"]))
    new = {"groups": groups}
    if "tail" in caches:
        x, new["tail"] = layers(x, params["tail"], caches["tail"])
    x = L.norm_apply(params["final_norm"], x, cfg)
    logits = jnp.einsum("bsd,vd->bsv", x, params["embed"],
                        preferred_element_type=jnp.float32)[..., : cfg.vocab_size]
    return logits, new


@pytest.mark.parametrize("kv_quant,window,attn_backend", [
    ("bf16", 1, "pallas"), ("bf16", 4, "pallas"), ("bf16", 1, "xla"), ("bf16", 4, "xla"),
    ("kv8", 1, "pallas"), ("kv8", 4, "pallas"), ("kv4", 1, "pallas"), ("kv4", 4, "pallas"),
])
def test_carried_pool_decode_matches_layerwise_pools(kv_quant, window, attn_backend):
    """Three decode (window 1) or verify (window 4) steps of `forward`
    against a reference that runs every layer on its own sliced pool: the
    logits and every pool leaf agree bitwise, on two scanned groups of two
    layers and a tail layer, with an idle row writing the scratch page.
    bf16 pools of head_dim 128 over 2 KV heads ride the scan's carry whole
    (the kernel's page DMAs and the XLA gather both read the stacked pool
    by offset page ids); kv8/kv4 pools keep their per-layer slices."""
    import dataclasses

    cfg = dataclasses.replace(
        registry.get_reduced("qwen2-1.5b"), block_pattern=("attn", "attn"), num_layers=5,
        num_kv_heads=2, head_dim=128, dtype="bfloat16",
    )
    enc = dataclasses.replace(ENC, attn_backend=attn_backend)
    params = T.model_init(jax.random.PRNGKey(0), cfg, enc)
    slots, bs, nb = 4, 8, 4
    caches = T.cache_init(cfg, slots, nb * bs, cache_mode="paged", block_size=bs,
                          num_pages=1 + 3 * nb, kv_quant=kv_quant)
    carried = T._flat_pool(caches["groups"][0]) is not None
    assert carried == (kv_quant == "bf16")

    # Random pages everywhere (scratch page included); rows 0-2 own pages
    # 1-12 in a shuffled order, row 3 is idle on the scratch page.
    rng = np.random.RandomState(3)
    table = np.zeros((slots, nb), np.int32)
    table[:3] = 1 + rng.permutation(3 * nb).reshape(3, nb)

    def fill(path, leaf):
        if jax.tree_util.keystr(path).endswith("['table']"):
            return jnp.broadcast_to(jnp.asarray(table), leaf.shape)
        if jnp.issubdtype(leaf.dtype, jnp.integer):
            info = jnp.iinfo(leaf.dtype)
            return jnp.asarray(rng.randint(info.min, info.max + 1, leaf.shape), leaf.dtype)
        return jnp.asarray(rng.uniform(0.01, 1.0, leaf.shape), leaf.dtype)

    caches = jax.tree_util.tree_map_with_path(fill, caches)
    assert "tail" in caches
    forward = jax.jit(lambda p, c, t, q: T.forward(
        p, {"tokens": t}, cfg=cfg, enc=enc, phase=Phase.DECODE, caches=c, pos=q)[:2])
    reference = jax.jit(lambda p, c, t, q: _layerwise_decode(p, c, t, q, cfg=cfg, enc=enc))
    got = want = caches
    pos = np.array([5, 13, 18, 0], np.int32)  # the last write stays below nb * bs
    for _ in range(3):
        tokens = jnp.asarray(rng.randint(1, cfg.vocab_size, (slots, window)), jnp.int32)
        logits, got = forward(params, got, tokens, jnp.asarray(pos))
        ref_logits, want = reference(params, want, tokens, jnp.asarray(pos))
        np.testing.assert_array_equal(np.asarray(logits), np.asarray(ref_logits))
        assert jax.tree.structure(got) == jax.tree.structure(want)
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        pos[:3] += window
