"""Property-based round-trip tests (hypothesis; skipped when absent, run in
CI): block-table gathers reproduce dense cache slices for arbitrary valid
tables, the encoding round-trip (pack/unpack + encoded_matmul) holds over
ragged shapes, the paged attention KERNEL path (in-kernel block-table
gather) stays bit-consistent with the dense kernel on the gathered view,
and the radix-tree prefix cache (serving/paged.py) survives randomized
admit/finish/evict/COW storms with exact audits, LCP lookups matching a
brute-force oracle, and kv8 scale pages moving in lockstep."""

import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

import jax.numpy as jnp  # noqa: E402

from repro.core import encoding as encoding_lib  # noqa: E402
from repro.core.encoding import Phase  # noqa: E402
from repro.kernels import attn as attn_lib  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.models import layers as L  # noqa: E402
from repro.serving import paged as paged_lib  # noqa: E402

_SETTINGS = dict(max_examples=25, deadline=None)


@settings(**_SETTINGS)
@given(
    b=st.integers(1, 4),
    nb=st.integers(1, 5),
    bs=st.sampled_from([1, 2, 4, 8]),
    kv=st.integers(1, 2),
    hd=st.integers(1, 8),
    share=st.booleans(),
    seed=st.integers(0, 2**31 - 1),
)
def test_block_table_gather_equals_dense_slice(b, nb, bs, kv, hd, share, seed):
    """For ANY valid block table — including tables where slots share pages —
    paged_gather(pool, table) is exactly the dense (B, NB*bs, ...) cache the
    tables describe."""
    rng = np.random.RandomState(seed)
    pool = rng.randn(1 + b * nb, bs, kv, hd).astype(np.float32)
    if share and b > 1:
        # Slots 0 and 1 share their leading block's page (prefix reuse).
        table = rng.randint(1, pool.shape[0], size=(b, nb)).astype(np.int32)
        table[1, 0] = table[0, 0]
    else:
        table = (1 + rng.permutation(b * nb)).reshape(b, nb).astype(np.int32)
    dense = pool[table].reshape(b, nb * bs, kv, hd)  # definitionally dense
    got = L.paged_gather(jnp.asarray(pool), jnp.asarray(table))
    np.testing.assert_array_equal(np.asarray(got), dense)
    # Slot-sliced view == dense row slice, any slot, any position range.
    s = int(rng.randint(b))
    np.testing.assert_array_equal(np.asarray(got[s]), dense[s])


@settings(**_SETTINGS)
@given(
    r=st.integers(1, 40),
    c=st.integers(1, 40),
    t0=st.sampled_from([1, 2, 4, 8]),
    t1=st.sampled_from([1, 2, 4, 8]),
    seed=st.integers(0, 2**31 - 1),
)
def test_pack_unpack_roundtrip_ragged(r, c, t0, t1, seed):
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randn(r, c), jnp.float32)
    back = ref.unpack(ref.pack(x, (t0, t1)), (r, c))
    np.testing.assert_array_equal(np.asarray(back), np.asarray(x))


@settings(**_SETTINGS)
@given(
    m=st.integers(1, 20),
    n=st.integers(1, 80),
    k=st.integers(1, 80),
    phase=st.sampled_from([Phase.PREFILL, Phase.DECODE]),
    seed=st.integers(0, 2**31 - 1),
)
def test_encoded_matmul_parity_ragged(m, n, k, phase, seed):
    """pack -> mmt4d -> unpack == plain contraction for arbitrary ragged
    (M, N, K) — the encoding is a pure layout change, never a value change."""
    rng = np.random.RandomState(seed)
    x = jnp.asarray(0.1 * rng.randn(m, k), jnp.float32)
    w_t = jnp.asarray(0.1 * rng.randn(n, k), jnp.float32)
    want = np.asarray(ref.matmul_reference(x, w_t))
    got = np.asarray(ops.encoded_matmul(
        x, ops.pack_rhs(w_t), n=n, phase=phase, backend="xla",
        out_dtype=jnp.float32,
    ))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


@settings(max_examples=15, deadline=None)
@given(
    b=st.integers(1, 3),
    nb=st.integers(1, 4),
    bs=st.sampled_from([2, 4, 8]),
    kv=st.integers(1, 2),
    g=st.sampled_from([1, 2, 4]),
    lq=st.integers(1, 3),
    share=st.booleans(),
    seed=st.integers(0, 2**31 - 1),
)
def test_paged_kernel_bit_consistent_with_dense_kernel(
    b, nb, bs, kv, g, lq, share, seed
):
    """For ANY valid block table (shared prefix pages included), per-row
    positions and verify-window widths, the paged-decode kernel's in-kernel
    gather is BITWISE the dense-decode kernel run on the materialized
    `paged_gather` view at matched streaming granularity — and both stay
    within fp tolerance of the jnp reference."""
    if lq > nb * bs:
        lq = 1
    rng = np.random.RandomState(seed)
    d, h = 8, kv * g
    pool_k = jnp.asarray(rng.randn(1 + b * nb, bs, kv, d), np.float32)
    pool_v = jnp.asarray(rng.randn(1 + b * nb, bs, kv, d), np.float32)
    table = (1 + rng.permutation(b * nb).reshape(b, nb)).astype(np.int32)
    if share and b > 1:
        table[1, 0] = table[0, 0]
    table = jnp.asarray(table)
    q = jnp.asarray(rng.randn(b, lq, h, d), np.float32)
    pos = jnp.asarray(rng.randint(0, nb * bs - lq + 1, b), jnp.int32)

    paged = attn_lib.paged_decode_attention(
        q, pool_k, pool_v, table, pos, interpret=True
    )
    dense = attn_lib.dense_decode_attention(
        q, L.paged_gather(pool_k, table), L.paged_gather(pool_v, table),
        pos, window=0, kv_chunk=attn_lib.pages_per_block(bs, nb) * bs, interpret=True,
    )
    np.testing.assert_array_equal(np.asarray(paged), np.asarray(dense))
    want = L.attention_decode(
        q, L.paged_gather(pool_k, table), L.paged_gather(pool_v, table),
        pos=pos, window=0,
    )
    np.testing.assert_allclose(
        np.asarray(paged), np.asarray(want), rtol=2e-5, atol=2e-5
    )


@settings(max_examples=15, deadline=None)
@given(
    b=st.integers(1, 3),
    nb=st.integers(2, 5),
    bs=st.sampled_from([2, 4]),
    nb_bound=st.integers(1, 5),
    seed=st.integers(0, 2**31 - 1),
)
def test_paged_gather_bound_is_prefix_of_full_gather(b, nb, bs, nb_bound, seed):
    """paged_gather(nb_blocks=) == the leading slice of the full gather, for
    any bound (larger-than-table bounds are clamped)."""
    rng = np.random.RandomState(seed)
    pool = jnp.asarray(rng.randn(1 + b * nb, bs, 1, 4), np.float32)
    table = jnp.asarray(
        (1 + rng.permutation(b * nb).reshape(b, nb)).astype(np.int32)
    )
    full = L.paged_gather(pool, table)
    got = L.paged_gather(pool, table, nb_blocks=nb_bound)
    eff = min(nb_bound, nb)
    assert got.shape[1] == eff * bs
    np.testing.assert_array_equal(np.asarray(got), np.asarray(full[:, : eff * bs]))


# ---- KVLayout codec (core/encoding.py kv8/kv4) -----------------------------


@settings(**_SETTINGS)
@given(
    name=st.sampled_from(["kv8", "kv4"]),
    bs=st.sampled_from([2, 4, 8]),
    kv=st.integers(1, 3),
    hd=st.sampled_from([2, 4, 8, 16]),
    scale_pow=st.integers(-6, 6),
    seed=st.integers(0, 2**31 - 1),
)
def test_kv_layout_roundtrip_error_bound(name, bs, kv, hd, scale_pow, seed):
    """pack -> unpack stays within half a quantization step of the input,
    per (token, head) row, at ANY magnitude: the per-row absmax scale makes
    the codec exact up to |x|_max / (2 * qmax) + rounding slack."""
    layout = encoding_lib.kv_layout(name)
    rng = np.random.RandomState(seed)
    x = (2.0 ** scale_pow) * rng.randn(bs, kv, hd).astype(np.float32)
    q, scale = layout.quantize(jnp.asarray(x))
    assert q.dtype == layout.storage_dtype
    assert q.shape[-1] == layout.storage_head_dim(hd)
    assert scale.shape == (bs, kv, 1)
    deq = np.asarray(layout.dequantize(q, scale))
    assert deq.shape == x.shape
    amax = np.abs(x).max(axis=-1, keepdims=True)
    # Half a step per row, plus float slack for the scale multiply.
    bound = amax / (2.0 * layout.qmax) + 1e-6 * np.maximum(amax, 1.0)
    assert np.all(np.abs(deq - x) <= bound + 1e-12)


@settings(**_SETTINGS)
@given(
    name=st.sampled_from(["kv8", "kv4"]),
    bs=st.sampled_from([4, 8]),
    tail=st.integers(1, 7),
    kv=st.integers(1, 2),
    hd=st.sampled_from([4, 8]),
    seed=st.integers(0, 2**31 - 1),
)
def test_kv_layout_ragged_tail_rows_independent(name, bs, tail, kv, hd, seed):
    """A ragged last page (only `tail` of `bs` token rows written) decodes
    its written rows identically to a full page holding the same values:
    scales are per (token, head) row, so garbage/zero tail rows can never
    perturb real rows."""
    tail = min(tail, bs)
    layout = encoding_lib.kv_layout(name)
    rng = np.random.RandomState(seed)
    full = rng.randn(bs, kv, hd).astype(np.float32)
    ragged = full.copy()
    ragged[tail:] = 0.0  # unwritten tail rows (zeros, as cache_init leaves)
    qf, sf = layout.quantize(jnp.asarray(full))
    qr, sr = layout.quantize(jnp.asarray(ragged))
    np.testing.assert_array_equal(np.asarray(qf)[:tail], np.asarray(qr)[:tail])
    np.testing.assert_array_equal(np.asarray(sf)[:tail], np.asarray(sr)[:tail])
    deq_f = np.asarray(layout.dequantize(qf, sf))
    deq_r = np.asarray(layout.dequantize(qr, sr))
    np.testing.assert_array_equal(deq_f[:tail], deq_r[:tail])
    np.testing.assert_array_equal(deq_r[tail:], np.zeros_like(deq_r[tail:]))


@settings(**_SETTINGS)
@given(
    name=st.sampled_from(["kv8", "kv4"]),
    seed=st.integers(0, 2**31 - 1),
)
def test_kv_layout_requantize_idempotent(name, seed):
    """Re-quantizing a dequantized page is a fixed point: quantize(deq(q, s))
    returns the same codes bit-for-bit (the absmax row survives the round
    trip, so the recovered scale matches and every code re-rounds to
    itself)."""
    layout = encoding_lib.kv_layout(name)
    rng = np.random.RandomState(seed)
    x = rng.randn(4, 2, 8).astype(np.float32)
    q, s = layout.quantize(jnp.asarray(x))
    deq = layout.dequantize(q, s)
    q2, s2 = layout.quantize(deq)
    np.testing.assert_array_equal(np.asarray(q), np.asarray(q2))
    np.testing.assert_allclose(np.asarray(s), np.asarray(s2), rtol=1e-6)


@settings(max_examples=15, deadline=None)
@given(
    b=st.integers(1, 3),
    nb=st.integers(1, 4),
    bs=st.sampled_from([2, 4, 8]),
    kv=st.integers(1, 2),
    g=st.sampled_from([1, 2]),
    lq=st.integers(1, 3),
    seed=st.integers(0, 2**31 - 1),
)
def test_paged_kernel_bit_consistent_with_dense_kernel_kv8(
    b, nb, bs, kv, g, lq, seed
):
    """The kv8 paged-decode kernel (scale pages ride the block table, tiles
    dequantized in VMEM) is BITWISE the kv8 dense-decode kernel on the
    gathered quantized view at matched streaming granularity — the same
    contract the bf16 kernels pin above, extended to the quantized layout."""
    if lq > nb * bs:
        lq = 1
    layout = encoding_lib.kv_layout("kv8")
    rng = np.random.RandomState(seed)
    d, h = 8, kv * g
    k_raw = jnp.asarray(rng.randn(1 + b * nb, bs, kv, d), np.float32)
    v_raw = jnp.asarray(rng.randn(1 + b * nb, bs, kv, d), np.float32)
    pool_k, ks = layout.quantize(k_raw)
    pool_v, vs = layout.quantize(v_raw)
    table = jnp.asarray(
        (1 + rng.permutation(b * nb).reshape(b, nb)).astype(np.int32)
    )
    q = jnp.asarray(rng.randn(b, lq, h, d), np.float32)
    pos = jnp.asarray(rng.randint(0, nb * bs - lq + 1, b), jnp.int32)

    paged = attn_lib.paged_decode_attention(
        q, pool_k, pool_v, table, pos,
        k_scale=ks, v_scale=vs, kv_quant="kv8", interpret=True,
    )
    dense = attn_lib.dense_decode_attention(
        q, L.paged_gather(pool_k, table), L.paged_gather(pool_v, table),
        pos, window=0, kv_chunk=attn_lib.pages_per_block(bs, nb) * bs,
        k_scale=L.paged_gather(ks, table), v_scale=L.paged_gather(vs, table),
        kv_quant="kv8", interpret=True,
    )
    np.testing.assert_array_equal(np.asarray(paged), np.asarray(dense))
    # Both agree with the jnp reference run on the dequantized view.
    want = L.attention_decode(
        q,
        layout.dequantize(L.paged_gather(pool_k, table),
                          L.paged_gather(ks, table)),
        layout.dequantize(L.paged_gather(pool_v, table),
                          L.paged_gather(vs, table)),
        pos=pos, window=0,
    )
    np.testing.assert_allclose(
        np.asarray(paged), np.asarray(want), rtol=2e-5, atol=2e-5
    )


# ---- Radix-tree prefix cache (serving/paged.py) ----------------------------


def _blocks(prompt, bs):
    return [tuple(int(t) for t in prompt[j * bs:(j + 1) * bs])
            for j in range(max(0, (len(prompt) - 1) // bs))]


@settings(**_SETTINGS)
@given(
    bs=st.sampled_from([2, 4]),
    nprompts=st.integers(1, 6),
    seed=st.integers(0, 2**31 - 1),
)
def test_radix_lcp_matches_bruteforce_oracle(bs, nprompts, seed):
    """plan_prompt's shared run is EXACTLY the longest-common-prefix of full
    immutable blocks against everything ever committed — computed here by a
    brute-force prefix-set oracle, with page identity pinned per chain.  A
    tiny token alphabet forces dense prefix collisions; the pool is sized so
    eviction never fires and the oracle stays monotone."""
    rng = np.random.RandomState(seed)
    alloc = paged_lib.BlockAllocator(1 + 48, bs)
    oracle_page: dict[tuple, int] = {}  # block-chain -> page
    live = []
    for _ in range(nprompts):
        prompt = rng.randint(1, 4, size=rng.randint(1, 4 * bs + 4)).astype(
            np.int32
        )
        chain = _blocks(prompt, bs)
        nblocks, shared = alloc.plan_prompt(prompt)
        # Oracle LCP: longest leading run of chains already registered.
        lcp = 0
        while lcp < len(chain) and tuple(chain[: lcp + 1]) in oracle_page:
            lcp += 1
        assert sorted(shared) == list(range(lcp)), (
            f"shared run {sorted(shared)} != oracle LCP {lcp}"
        )
        for j in range(lcp):
            assert shared[j] == oracle_page[tuple(chain[: j + 1])], (
                f"block {j}: page {shared[j]} != oracle"
            )
        plan = alloc.commit_prompt(prompt, nblocks, shared)
        assert plan is not None
        alloc.mark_written(plan.pages)
        for j in range(len(chain)):
            oracle_page.setdefault(tuple(chain[: j + 1]), plan.pages[j])
        live.append(plan)
        # Randomly finish some earlier requests: their immutable blocks park
        # in the tree (never leave the oracle — the pool never evicts here).
        while len(live) > 1 and rng.rand() < 0.5:
            done = live.pop(int(rng.randint(len(live))))
            alloc.free_pages(done.pages)
        alloc.audit([p.pages for p in live])


@settings(**_SETTINGS)
@given(
    bs=st.sampled_from([2, 4]),
    pool=st.integers(8, 16),
    seed=st.integers(0, 2**31 - 1),
)
def test_eviction_never_touches_live_chains(bs, pool, seed):
    """Draining the pool with raw allocs evicts ONLY cold cached leaves:
    pages of the one live plan are never handed out again, and once just the
    live chain remains, alloc() returns None instead of preempting it."""
    rng = np.random.RandomState(seed)
    alloc = paged_lib.BlockAllocator(1 + pool, bs)
    # Warm the tree with a few finished (cached) chains...
    for _ in range(3):
        prompt = rng.randint(1, 4, size=rng.randint(1, 3 * bs)).astype(np.int32)
        nblocks, shared = alloc.plan_prompt(prompt)
        plan = alloc.commit_prompt(prompt, nblocks, shared)
        if plan is None:
            continue
        alloc.mark_written(plan.pages)
        alloc.free_pages(plan.pages)
    # ...and keep ONE plan live.
    prompt = rng.randint(1, 4, size=2 * bs + 1).astype(np.int32)
    nblocks, shared = alloc.plan_prompt(prompt)
    plan = alloc.commit_prompt(prompt, nblocks, shared)
    if plan is None:
        return  # tiny pool + warm chains left no room: nothing to protect
    alloc.mark_written(plan.pages)
    livepages = set(plan.pages)
    held = []
    while True:
        page = alloc.alloc(owner=7)
        if page is None:
            break
        assert page not in livepages, "eviction recycled a live page"
        held.append(page)
        alloc.audit([plan.pages, held])
    # Pool exhausted: everything except the live chain was reclaimable.
    assert len(held) + len(plan.pages) == alloc.capacity
    for p in livepages:
        assert alloc.refcount[p] > 0
    alloc.free_pages(held, owner=7)
    alloc.free_pages(plan.pages)
    alloc.audit([])


@settings(max_examples=20, deadline=None)
@given(
    bs=st.sampled_from([2, 4]),
    pool=st.integers(6, 14),
    kv_quant=st.sampled_from(["bf16", "kv8"]),
    quota=st.sampled_from([None, 4]),
    nops=st.integers(10, 60),
    seed=st.integers(0, 2**31 - 1),
)
def test_audit_exact_under_admit_finish_evict_cow_storm(
    bs, pool, kv_quant, quota, nops, seed
):
    """Randomized storms of admit (partial writes included), finish, COW
    shares, and raw-alloc pool pressure (forcing evictions) keep audit()
    exact after EVERY op — and under kv8 the scale pages track the allocated
    set (referenced + cached) in lockstep throughout."""
    rng = np.random.RandomState(seed)
    alloc = paged_lib.BlockAllocator(
        1 + pool, bs, kv_quant=kv_quant, tenant_quota=quota
    )
    live: list[tuple[list, str]] = []   # (pages, tenant) per virtual slot
    held: list[int] = []                # raw-alloc'd pressure pages
    for _ in range(nops):
        op = rng.choice(["admit", "finish", "cow", "pressure", "release"])
        tenant = str(rng.choice(["a", "b"]))
        if op == "admit":
            prompt = rng.randint(1, 4, size=rng.randint(1, 3 * bs + 2)).astype(
                np.int32
            )
            nblocks, shared = alloc.plan_prompt(prompt)
            plan = alloc.commit_prompt(prompt, nblocks, shared, tenant=tenant)
            if plan is not None:
                # Partial write: only a leading run lands (mirrors chunked
                # prefill); unwritten registered blocks must unregister
                # their whole subtree when freed early.
                k = int(rng.randint(0, len(plan.pages) + 1))
                alloc.mark_written(plan.pages[:k])
                live.append((plan.pages, tenant))
        elif op == "finish" and live:
            pages, t = live.pop(int(rng.randint(len(live))))
            alloc.free_pages(pages, tenant=t)
        elif op == "cow" and live:
            # Sharing only ever flows through the tree (plan/commit): pick a
            # REGISTERED live page, as a second reader of its prefix would.
            pages, t = live[int(rng.randint(len(live)))]
            p = pages[int(rng.randint(len(pages)))]
            if alloc.refcount[p] > 0 and alloc.is_registered(p):
                alloc.share(p, tenant=tenant)
                live.append(([p], tenant))
        elif op == "pressure":
            page = alloc.alloc(owner=9, tenant=tenant)
            if page is not None:
                held.append(page)
        elif op == "release" and held:
            alloc.free_page(held.pop(), owner=9)
        tables = [pages for pages, _ in live] + ([held] if held else [])
        alloc.audit(tables)
        if kv_quant != "bf16":
            referenced = {
                p for p in range(1, alloc.num_pages) if alloc.refcount[p] > 0
            }
            assert alloc.scale_live == referenced | alloc.cached, (
                "kv8 scale pages out of lockstep"
            )
    for pages, t in live:
        alloc.free_pages(pages, tenant=t)
    alloc.free_pages(held, owner=9)
    alloc.audit([])
    assert alloc.in_use() == 0
    assert alloc.stats["allocs"] == alloc.stats["frees"]


def test_pool_spike_chaos_against_warm_cache():
    """Replay pool_spike seizures (serving/faults.py) against a WARM prefix
    cache: a second wave of shared-prefix requests admits off cached chains
    while the fault schedule drains the free list, forcing evictions to race
    revivals.  Survivors stay token-identical to the fault-free warm run,
    the audit stays exact every step, and the drain leaks nothing."""
    import jax
    from repro.configs import registry
    from repro.core.packed import EncodingConfig
    from repro.models import transformer as T
    from repro.serving import engine as engine_lib
    from repro.serving import faults as faults_lib

    cfg = registry.get_reduced("qwen2-1.5b")
    enc = EncodingConfig(enabled=True, backend="xla")
    params = T.model_init(jax.random.PRNGKey(0), cfg, enc)
    rng = np.random.RandomState(7)
    base = rng.randint(1, cfg.vocab_size, 16).astype(np.int32)  # 2 blocks @ 8
    prompts = [
        np.concatenate([base, rng.randint(1, cfg.vocab_size,
                                          4 + i).astype(np.int32)])
        for i in range(4)
    ]

    def run(sched):
        eng = engine_lib.Engine(
            params, cfg, enc, fault_hooks=sched,
            slots=2, max_seq=64, block_size=8, pool_pages=14,
        )
        for wave in range(2):
            for i, p in enumerate(prompts):
                assert eng.submit(engine_lib.Request(
                    uid=wave * 10 + i, prompt=p, max_new_tokens=6,
                    tenant=f"t{i % 2}",
                ))
            steps = 0
            while eng.queue or any(r is not None for r in eng.slot_req):
                assert steps < 300, "engine deadlocked under pool_spike"
                eng.step()
                eng.audit()
                steps += 1
        if sched is not None:
            sched.drain(eng)
            eng.audit()
        return eng

    gold = run(None)
    want = {r.uid: list(r.generated) for r in gold.finished}
    assert gold.alloc.stats["hit_blocks"] > 0, "second wave never hit"

    sched = faults_lib.FaultSchedule(
        [faults_lib.Fault(s, "pool_spike", pages=3, hold=2)
         for s in (2, 9, 16, 23, 30)],
        seed=7,
    )
    eng = run(sched)
    assert {r.uid for r in eng.finished} == set(want)
    for r in eng.finished:
        assert r.status == "ok", (r.uid, r.status, r.error)
        assert list(r.generated) == want[r.uid], (
            f"uid {r.uid} diverged under pool_spike on a warm cache"
        )
    assert eng.alloc.in_use() == 0
    assert eng.alloc.stats["allocs"] == eng.alloc.stats["frees"]
