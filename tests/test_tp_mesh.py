"""Tensor-parallel serving over a device mesh.

Deviceless units: build_serving_mesh error surface, the shard-aware kernel
quarantine table, and ShardedBlockAllocator mirroring.

Subprocess integration (``XLA_FLAGS=--xla_force_host_platform_device_count=4``
set before the first jax import — the same CPU emulation the CI
mesh-conformance job uses): mesh=2/4 decode must be token-identical to
mesh=1 across paged/dense x spec x token-budget, per-shard allocator audits
must stay exact through pool-pressure preemption, and a chaos schedule with a
shard-attributed kernel fault must demote ONLY that shard's quarantine entry
while the engine keeps serving.
"""

import os
import subprocess
import sys
import textwrap

import jax
import pytest

from repro.core.encoding import Phase
from repro.kernels import registry as registry_lib
from repro.launch import mesh as mesh_lib
from repro.serving import paged as paged_lib
from repro.serving.config import EngineConfig


# ---- build_serving_mesh ----------------------------------------------------

def test_serving_mesh_rejects_undersized_device_set():
    dev = jax.devices()[:1]
    with pytest.raises(ValueError) as ei:
        mesh_lib.build_serving_mesh((2,), devices=dev)
    msg = str(ei.value)
    # The error must be actionable: name the flag, never fall back to mesh=1.
    assert "xla_force_host_platform_device_count=2" in msg
    assert "2 devices" in msg


def test_serving_mesh_axis_naming():
    dev = jax.devices()[:1]
    m = mesh_lib.build_serving_mesh((1,), devices=dev)
    assert m.axis_names == ("model",)
    m2 = mesh_lib.build_serving_mesh((1, 1), devices=dev)
    assert m2.axis_names == ("data", "model")


def test_serving_mesh_rejects_bad_shapes():
    with pytest.raises(ValueError):
        mesh_lib.build_serving_mesh(())
    with pytest.raises(ValueError):
        mesh_lib.build_serving_mesh((0,))
    with pytest.raises(ValueError):
        mesh_lib.build_serving_mesh((1, 1, 1, 1))


def test_engine_config_mesh_fields():
    c = EngineConfig(mesh_shape=(2, 4))
    assert c.tp_shards == 4 and c.mesh_devices == 8
    with pytest.raises(ValueError, match="tp_axis"):
        EngineConfig(mesh_shape=(2,), tp_axis="rows")


# ---- shard-aware quarantine ------------------------------------------------

def test_shard_local_demotion_is_max_for_spmd_but_local_per_shard():
    registry_lib.clear_quarantine()
    try:
        key = registry_lib.attn_dispatch_key(Phase.DECODE, 64, "cpu")
        registry_lib.demote(key, failing="pallas", reason="chaos",
                            requested="pallas", shard=1)
        # The SPMD dispatch (shard=None) must honour the worst shard...
        assert registry_lib.quarantine_level(key) > 0
        # ...but shard 0's own view stays clean, shard 1's does not.
        assert registry_lib.quarantine_level(key, shard=0) == 0
        assert registry_lib.quarantine_level(key, shard=1) > 0
        snap = registry_lib.quarantine_snapshot()
        assert f"{key}@shard1" in snap
        assert snap[f"{key}@shard1"]["shard"] == 1
        assert key not in snap  # no global entry was created
    finally:
        registry_lib.clear_quarantine()


def test_global_demotion_applies_to_every_shard():
    registry_lib.clear_quarantine()
    try:
        key = registry_lib.attn_dispatch_key(Phase.DECODE, 64, "cpu")
        registry_lib.demote(key, failing="pallas", reason="global",
                            requested="pallas")
        assert registry_lib.quarantine_level(key, shard=0) > 0
        assert registry_lib.quarantine_level(key, shard=3) > 0
    finally:
        registry_lib.clear_quarantine()


# ---- ShardedBlockAllocator -------------------------------------------------

def test_sharded_allocator_mirrors_and_audits():
    alloc = paged_lib.ShardedBlockAllocator(16, 8, shards=2)
    assert alloc.capacity == paged_lib.BlockAllocator(16, 8).capacity
    assert len(alloc.shards) == 2
    pages = [alloc.alloc() for _ in range(3)]
    assert alloc.in_use() == 3
    assert alloc.stats["tp_shards"] == 2
    per = alloc.per_shard_stats()
    assert len(per) == 2 and per[0]["allocs"] == per[1]["allocs"] == 3
    alloc.audit([pages])
    alloc.free_pages(pages)
    alloc.audit([])


def test_sharded_allocator_detects_divergence():
    alloc = paged_lib.ShardedBlockAllocator(16, 8, shards=2)
    a = alloc.alloc()
    # Simulate a shard drifting out of lockstep (the invariant a real TP
    # deployment must never violate): free the page on ONE shard only.
    alloc.shards[1].free_page(a)
    with pytest.raises(paged_lib.AllocatorInvariantError, match="diverged"):
        alloc.alloc()


def test_sharded_allocator_per_shard_audit_failure_names_shard():
    alloc = paged_lib.ShardedBlockAllocator(16, 8, shards=2)
    a = alloc.alloc()
    alloc.shards[1].free_page(a)
    with pytest.raises(paged_lib.AllocatorInvariantError, match="shard 1"):
        alloc.audit([[a]])


# ---- multi-device SPMD integration (subprocess) ----------------------------

_ENV_HEADER = """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax
    import numpy as np
    from repro.configs import registry
    from repro.core.packed import EncodingConfig
    from repro.models import transformer as T
    from repro.serving import engine as engine_lib
    from repro.serving.config import EngineConfig

    ENC = EncodingConfig(enabled=True, backend="xla")
    # num_kv_heads=4 so the KV-head axis actually divides at 2 and 4 shards
    # (the stock reduced configs are GQA with a single KV head, which
    # sanitize correctly replicates — exercising the divisible case is the
    # point here).
    CFG = registry.get_reduced("qwen2-1.5b", num_kv_heads=4)
    PARAMS = T.model_init(jax.random.PRNGKey(0), CFG, ENC)

    def run(shards, *, prompts, max_new=6, audit_every_step=False, **kw):
        eng = engine_lib.Engine(
            PARAMS, CFG, ENC,
            config=EngineConfig(mesh_shape=(shards,), **kw))
        for i, p in enumerate(prompts):
            eng.submit(engine_lib.Request(
                uid=i, prompt=np.asarray(p, np.int32), max_new_tokens=max_new))
        if audit_every_step:
            while eng.queue or any(r is not None for r in eng.slot_req):
                eng.step()
                eng.audit()
        else:
            eng.run()
            eng.audit()
        assert all(r.status == "ok" for r in eng.finished), [
            (r.uid, r.status, r.error) for r in eng.finished]
        return {r.uid: list(r.generated) for r in eng.finished}, eng

    PROMPTS = [((np.arange(5 + 3 * i) * 7 + i) % (CFG.vocab_size - 1) + 1)
               for i in range(4)]
"""

_TP_IDENTITY_SCRIPT = textwrap.dedent(_ENV_HEADER + """
    MATRIX = [
        ("paged", dict(slots=2, max_seq=64, cache_mode="paged", block_size=8)),
        ("dense", dict(slots=2, max_seq=64, cache_mode="dense")),
        ("spec", dict(slots=2, max_seq=64, cache_mode="paged", block_size=8,
                      spec_decode=True, draft_k=3)),
        ("budget", dict(slots=2, max_seq=64, cache_mode="paged", block_size=8,
                        token_budget=16)),
        # Quantized paged KV: scale pages shard alongside their KV pages
        # (parallel/sharding.serving_cache_shardings); the xla attention
        # fallback dequantizes identically at every mesh degree, so kv8
        # serving must stay token-identical to its own mesh=1 run.
        ("kv8", dict(slots=2, max_seq=64, cache_mode="paged", block_size=8,
                     kv_quant="kv8")),
    ]
    for name, kw in MATRIX:
        base, beng = run(1, prompts=PROMPTS, **kw)
        for shards in (2, 4):
            got, eng = run(shards, prompts=PROMPTS, **kw)
            assert got == base, (name, shards, base, got)
            assert eng.tp_shards == shards
            assert eng.stats["tp"]["shards"] == shards
            if name == "kv8":
                assert eng.stats["kv_quant"] == "kv8"
        print("IDENT_OK", name)
    # head_dim 128: the paged pool rides the layer scan's carry whole
    # (models/transformer._flat_pool), sharded on its KV heads.
    CFG = registry.get_reduced("qwen2-1.5b", num_kv_heads=4, head_dim=128)
    PARAMS = T.model_init(jax.random.PRNGKey(0), CFG, ENC)
    kw = dict(slots=2, max_seq=64, cache_mode="paged", block_size=8)
    base, beng = run(1, prompts=PROMPTS, **kw)
    assert T._flat_pool(beng.caches["groups"][0]) is not None
    for shards in (2, 4):
        got, eng = run(shards, prompts=PROMPTS, **kw)
        assert got == base, ("carried", shards, base, got)
    print("IDENT_OK carried")
    print("TP_IDENTITY_OK")
""")

_TP_PREEMPT_SCRIPT = textwrap.dedent(_ENV_HEADER + """
    # A pool too small for every request at once forces preemption + replay;
    # the mirrored per-shard allocators and per-shard audit must stay exact
    # through it, and output must still match mesh=1.
    kw = dict(slots=3, max_seq=64, cache_mode="paged", block_size=8,
              pool_pages=6)
    base, e1 = run(1, prompts=PROMPTS, max_new=8, audit_every_step=True, **kw)
    got, e2 = run(2, prompts=PROMPTS, max_new=8, audit_every_step=True, **kw)
    assert got == base, (base, got)
    assert e2.preemptions == e1.preemptions
    assert e1.preemptions > 0, "pool was meant to force preemption"
    per = e2.stats["tp"]["per_shard_pages"]
    assert per[0] == per[1], per  # lockstep shards: identical counters
    print("TP_PREEMPT_OK", e2.preemptions)
""")

_TP_CHAOS_SCRIPT = textwrap.dedent(_ENV_HEADER + """
    from repro.kernels import registry as registry_lib
    from repro.serving import faults as faults_lib

    sched = faults_lib.FaultSchedule(
        [faults_lib.Fault(2, "kernel_fail", key="attn|decode|*", shard=1)],
        seed=0)
    eng = engine_lib.Engine(
        PARAMS, CFG, ENC,
        config=EngineConfig(slots=2, max_seq=64, cache_mode="paged",
                            block_size=8, mesh_shape=(2,)),
        fault_hooks=sched, clock=sched.clock)
    for i, p in enumerate(PROMPTS):
        eng.submit(engine_lib.Request(
            uid=i, prompt=np.asarray(p, np.int32), max_new_tokens=6))
    eng.run()
    eng.audit()
    assert all(r.status == "ok" for r in eng.finished)

    # The demotion landed shard-local, not globally.
    snap = registry_lib.quarantine_snapshot()
    shard_keys = [k for k in snap if "@shard1" in k]
    assert shard_keys, snap
    assert all("@shard" in k or snap[k].get("shard") == 1 for k in snap), snap
    s = eng.stats
    assert s["lifecycle"]["kernel_faults"] == 1
    # Per-shard degradation trail: the fault shows on shard 1 only.
    assert s["degraded"][1] and not s["degraded"][0], s["degraded"]
    assert s["degraded"][1][0]["shard"] == 1
    # Shard 0 still resolves its requested rung; the SPMD dispatch honours
    # shard 1's demotion (max over shards).
    key = s["degraded"][1][0]["key"]
    assert registry_lib.quarantine_level(key, shard=0) == 0
    assert registry_lib.quarantine_level(key) > 0
    print("TP_CHAOS_OK")
""")


def _run_sub(script):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src")
    )
    env.pop("XLA_FLAGS", None)  # the script sets its own device count
    r = subprocess.run(
        [sys.executable, "-c", script], env=env,
        capture_output=True, text=True, timeout=900,
    )
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-4000:]
    return r.stdout


def test_tp_token_identity_subprocess():
    """mesh=2/4 decode is token-identical to mesh=1 across paged/dense x
    spec x token-budget (4 emulated CPU devices)."""
    out = _run_sub(_TP_IDENTITY_SCRIPT)
    assert "TP_IDENTITY_OK" in out


def test_tp_preemption_audit_subprocess():
    """Per-shard allocator audits stay exact through preemption/replay on a
    2-shard mesh, with identical output and preemption count to mesh=1."""
    out = _run_sub(_TP_PREEMPT_SCRIPT)
    assert "TP_PREEMPT_OK" in out


def test_tp_shard_local_chaos_subprocess():
    """A kernel fault attributed to shard 1 demotes only that shard's
    quarantine entry; shard 0 stays clean and serving completes."""
    out = _run_sub(_TP_CHAOS_SCRIPT)
    assert "TP_CHAOS_OK" in out
