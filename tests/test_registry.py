"""Dispatch registry (kernels/registry.py): key resolution, tuned-table JSON
round-trip, unknown-key fallback, and registry-vs-direct-call output parity
across all quant modes."""

import dataclasses
import json

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import targets as targets_lib
from repro.core.encoding import Phase
from repro.kernels import ops, registry


@pytest.fixture(autouse=True)
def _fresh_table_cache():
    registry.clear_cache()
    yield
    registry.clear_cache()


def test_m_bucket_boundaries():
    assert registry.m_bucket(1) == "m1"
    assert registry.m_bucket(2) == "m8"
    assert registry.m_bucket(8) == "m8"
    assert registry.m_bucket(9) == "m32"
    assert registry.m_bucket(32) == "m32"
    assert registry.m_bucket(33) == "m64"
    assert registry.m_bucket(64) == "m64"
    assert registry.m_bucket(65) == "big"


def test_verify_bucket_routes_to_mmt4d_not_gemv(tmp_path):
    """The spec-decode verify regime (m32: slots x draft window) must route
    to the packed mmt4d GEMM, not the VMEM-row-resident fused GEMV — both by
    static policy and in the checked-in tuned table."""
    for quant in registry.QUANTS:
        # Monotonic in M: GEMV-like row counts keep the fused GEMV, all
        # multi-row decode (verify window and beyond) routes mmt4d.
        assert registry.default_backend(quant, Phase.DECODE, "m1") == "fused"
        assert registry.default_backend(quant, Phase.DECODE, "m8") == "fused"
        assert registry.default_backend(quant, Phase.DECODE, "m32") == "pallas"
        assert registry.default_backend(quant, Phase.DECODE, "m64") == "pallas"
        # The token-budget mixed step packs slots x window rows — "big" must
        # stay on the GEMM side of the monotonic policy, not fall through to
        # the fused GEMV like it once did.
        assert registry.default_backend(quant, Phase.DECODE, "big") == "pallas"
    # A target that measured the fused GEMV faster at a multi-row bucket
    # overrides the policy through its tuned entry (tpu-v5e m64).
    m64 = registry.select(quant="none", phase=Phase.DECODE, m=48)
    assert m64.backend == "fused" and m64.source == "tuned"
    # Policy applies when no tuned entry exists (empty table)...
    empty = str(tmp_path / "empty.json")
    registry.save_table({"entries": {}}, empty)
    choice = registry.select(
        quant="none", phase=Phase.DECODE, m=20, table_path=empty
    )
    assert choice.backend == "pallas" and choice.source == "default"
    # ...and the committed tuned table agrees for every quant mode.
    for quant in registry.QUANTS:
        tuned = registry.select(quant=quant, phase=Phase.DECODE, m=20)
        assert tuned.backend == "pallas", quant


def test_unknown_target_falls_back_to_reference():
    weird = dataclasses.replace(targets_lib.TPU_V5E, name="weird-accelerator")
    choice = registry.select(quant="none", phase=Phase.DECODE, m=1, target=weird)
    assert choice.backend == "reference"
    assert choice.source == "fallback"
    assert choice.blocks is None


class _Device:
    def __init__(self, kind):
        self.device_kind = kind


@pytest.mark.parametrize(
    "kind,expected",
    [("TPU v5 lite", targets_lib.TPU_V5E), ("TPU v5e", targets_lib.TPU_V5E),
     ("TPU v9 ultra", None)],
)
def test_attached_tpu_target_is_keyed_by_device_kind(monkeypatch, kind, expected):
    """On a TPU backend, select()'s default target comes from the device
    kind; a kind missing from DEVICE_TARGETS is an error, never another
    chip's figures."""
    import jax

    monkeypatch.setattr(targets_lib, "has_tpu_backend", lambda: True)
    monkeypatch.setattr(jax, "devices", lambda *a: [_Device(kind)])
    targets_lib.current.cache_clear()
    try:
        if expected is None:
            with pytest.raises(ValueError, match=kind):
                registry.select(quant="none", phase=Phase.DECODE, m=1)
        else:
            assert targets_lib.resolve(None) is expected
            choice = registry.select(quant="none", phase=Phase.DECODE, m=1)
            assert choice.source != "fallback"
    finally:
        targets_lib.current.cache_clear()


def test_broken_runtime_does_not_turn_on_interpret_mode(monkeypatch):
    """A JAX whose backend fails to start raises; it is not taken for a CPU
    host with interpret mode switched on."""
    import jax

    def broken():
        raise RuntimeError("TPU backend failed to initialize")

    monkeypatch.setattr(jax, "default_backend", broken)
    targets_lib.has_tpu_backend.cache_clear()
    try:
        with pytest.raises(RuntimeError, match="failed to initialize"):
            targets_lib.resolve_interpret(None)
    finally:
        targets_lib.has_tpu_backend.cache_clear()


def test_unknown_quant_falls_back_to_reference():
    choice = registry.select(quant="w2a2", phase=Phase.DECODE, m=1)
    assert choice.backend == "reference"
    assert choice.source == "fallback"


def test_quant_fallback_is_oracle_backend():
    """For quantized modes the no-data fallback is the xla oracle path."""
    weird = dataclasses.replace(targets_lib.TPU_V5E, name="weird-accelerator")
    for quant in ("w8a8", "w4a8"):
        choice = registry.select(quant=quant, phase=Phase.DECODE, m=4, target=weird)
        assert choice.backend == "xla", quant


def test_requested_backend_always_wins(tmp_path):
    """An explicit backend= pins the path even when a tuned entry disagrees."""
    path = str(tmp_path / "table.json")
    key = registry.dispatch_key("none", Phase.DECODE, 4, "tpu-v5e")
    registry.save_table(
        {"entries": {key: {"backend": "xla", "blocks": [1, 2, 1]}}}, path
    )
    choice = registry.select(
        quant="none", phase=Phase.DECODE, m=4, requested="fused", table_path=path
    )
    assert choice.backend == "fused"
    assert choice.source == "requested"
    # ...but tuned blocks still flow in when the caller supplied none.
    assert choice.blocks == (1, 2, 1)


def test_tuned_table_json_roundtrip(tmp_path):
    path = str(tmp_path / "table.json")
    entries = {
        registry.dispatch_key("w4a8", Phase.DECODE, 8, "tpu-v5e"): {
            "backend": "fused", "blocks": [1, 4, 1], "us": 12.5,
        },
        registry.dispatch_key("w8a8", Phase.PREFILL, 128, "tpu-v5e"): {
            "backend": "pallas", "blocks": [2, 2, 2],
        },
    }
    registry.save_table({"entries": entries}, path)
    registry.clear_cache()
    loaded = registry.load_table(path)
    assert loaded["entries"] == json.loads(json.dumps(entries))  # value-identical
    choice = registry.select(quant="w4a8", phase=Phase.DECODE, m=8, table_path=path)
    assert choice.backend == "fused"
    assert choice.blocks == (1, 4, 1)
    assert choice.source == "tuned"


def test_corrupt_table_falls_back_to_policy(tmp_path):
    path = str(tmp_path / "table.json")
    with open(path, "w") as f:
        f.write("{not json")
    choice = registry.select(quant="none", phase=Phase.DECODE, m=1, table_path=path)
    assert choice.backend == "fused"  # static default policy, not a crash
    assert choice.source == "default"


def test_checked_in_table_is_loadable_and_typed():
    """The committed tuned_table.json parses and every entry is well-formed
    (both op classes: matmul quant keys and attn|phase|S-bucket keys)."""
    table = registry.load_table()
    assert table["entries"], "checked-in tuned table should not be empty"
    seen_attn = 0
    for key, entry in table["entries"].items():
        head = key.split("|", 1)[0]
        b = entry["blocks"]
        if head == registry.ATTN_OP:
            # Attn keys are 4-part (legacy, implied bf16) or 5-part (with
            # the kv-quant axis); split_attn_key validates either form.
            seen_attn += 1
            _phase, bucket, kv, _target = registry.split_attn_key(key)
            assert bucket in registry.S_BUCKETS, key
            assert kv in registry.KV_QUANTS, key
            assert entry["backend"] in registry.ATTN_BACKENDS, key
            assert len(b) == 2 and all(isinstance(v, int) and v >= 1 for v in b), key
        else:
            head, phase, bucket, target = key.split("|")
            assert head in registry.QUANTS, key
            assert bucket in registry.M_BUCKETS, key
            assert entry["backend"] in registry.BACKENDS_BY_QUANT[head], key
            assert len(b) == 3 and all(isinstance(v, int) and v >= 1 for v in b), key
    assert seen_attn, "tuned table must cover the attention op class"


@pytest.mark.parametrize("phase", [Phase.DECODE, Phase.PREFILL])
def test_registry_vs_direct_call_parity_all_quants(phase):
    """backend="auto" (registry-resolved) must produce the same output as the
    direct explicit-backend call it resolves to, for every quant mode."""
    m = 4 if phase is Phase.DECODE else 40
    n, k = 384, 256
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(m, k), jnp.float32)
    w_t = jnp.asarray(rng.randn(n, k), jnp.float32)

    rhs4 = ops.pack_rhs(w_t)
    rhs4_q, s_w = ops.pack_rhs_q8(w_t)
    rhs4_p, s_w4 = ops.pack_rhs_q4(w_t)

    cases = {
        "none": (
            lambda be: ops.encoded_matmul(
                x, rhs4, n=n, phase=phase, backend=be,
                out_dtype=jnp.float32, interpret=True,
            )
        ),
        "w8a8": (
            lambda be: ops.encoded_matmul_q8(
                x, rhs4_q, s_w, n=n, phase=phase, backend=be,
                out_dtype=jnp.float32, interpret=True,
            )
        ),
        "w4a8": (
            lambda be: ops.encoded_matmul_q4(
                x, rhs4_p, s_w4, n=n, phase=phase, backend=be,
                out_dtype=jnp.float32, interpret=True,
            )
        ),
    }
    for quant, call in cases.items():
        resolved = registry.select(quant=quant, phase=phase, m=m)
        auto = call("auto")
        direct = call(resolved.backend)
        np.testing.assert_array_equal(
            np.asarray(auto), np.asarray(direct), err_msg=f"{quant}/{phase}"
        )
        # And the resolved path agrees numerically with the oracle backend.
        oracle = call("xla" if quant != "none" else "reference")
        np.testing.assert_allclose(
            np.asarray(auto), np.asarray(oracle), rtol=2e-4, atol=2e-4,
            err_msg=f"{quant}/{phase} vs oracle",
        )


# ---------------------------------------------------------------------------
# Attention op class (select_attn)


def test_s_bucket_boundaries():
    assert registry.s_bucket(1) == "s256"
    assert registry.s_bucket(256) == "s256"
    assert registry.s_bucket(257) == "s1k"
    assert registry.s_bucket(1024) == "s1k"
    assert registry.s_bucket(1025) == "s4k"
    assert registry.s_bucket(4096) == "s4k"
    assert registry.s_bucket(4097) == "sbig"


def test_attn_requested_backend_wins_and_invalid_raises(tmp_path):
    empty = str(tmp_path / "empty.json")
    registry.save_table({"entries": {}}, empty)
    for be in registry.ATTN_BACKENDS:
        choice = registry.select_attn(
            phase=Phase.DECODE, s=64, requested=be, table_path=empty
        )
        assert choice.backend == be and choice.source == "requested"
    with pytest.raises(ValueError):
        registry.select_attn(phase=Phase.DECODE, s=64, requested="fused")


def test_attn_policy_and_tuned_resolution(tmp_path):
    # Static policy on an empty table: pallas for every phase/bucket.
    empty = str(tmp_path / "empty.json")
    registry.save_table({"entries": {}}, empty)
    for phase in (Phase.DECODE, Phase.PREFILL):
        for s in (64, 512, 2048, 9000):
            choice = registry.select_attn(phase=phase, s=s, table_path=empty)
            assert choice.backend == "pallas" and choice.source == "default"
    # A tuned entry (2-int blocks = (q_chunk, kv_chunk)) outranks the policy.
    path = str(tmp_path / "table.json")
    key = registry.attn_dispatch_key(Phase.DECODE, 512, "tpu-v5e")
    registry.save_table(
        {"entries": {key: {"backend": "xla", "blocks": [1, 64]}}}, path
    )
    choice = registry.select_attn(phase=Phase.DECODE, s=512, table_path=path)
    assert choice.backend == "xla" and choice.source == "tuned"
    assert choice.blocks == (1, 64)
    # Explicit blocks= beat tuned blocks (mirrors the matmul class).
    choice = registry.select_attn(
        phase=Phase.DECODE, s=512, blocks=(1, 32), table_path=path
    )
    assert choice.blocks == (1, 32)


def test_attn_unknown_target_falls_back_to_xla(tmp_path):
    empty = str(tmp_path / "empty.json")
    registry.save_table({"entries": {}}, empty)
    alien = dataclasses.replace(targets_lib.TPU_V5E, name="gpu-h100")
    choice = registry.select_attn(
        phase=Phase.DECODE, s=512, target=alien, table_path=empty
    )
    assert choice.backend == "xla" and choice.source == "fallback"


def test_attn_key_kv_axis_forms():
    """bf16 keys keep the legacy 4-segment form; kv8/kv4 insert the kv axis
    before the target.  split_attn_key inverts both and rejects junk."""
    k_bf16 = registry.attn_dispatch_key(Phase.DECODE, 512, "tpu-v5e")
    assert k_bf16 == "attn|decode|s1k|tpu-v5e"
    assert registry.split_attn_key(k_bf16) == ("decode", "s1k", "bf16", "tpu-v5e")
    k8 = registry.attn_dispatch_key(Phase.DECODE, 512, "tpu-v5e", kv="kv8")
    assert k8 == "attn|decode|s1k|kv8|tpu-v5e"
    assert registry.split_attn_key(k8) == ("decode", "s1k", "kv8", "tpu-v5e")
    assert registry.attn_dispatch_key(
        Phase.PREFILL, 64, "tpu-v5e", kv="bf16"
    ) == "attn|prefill|s256|tpu-v5e"
    with pytest.raises(ValueError):
        registry.attn_dispatch_key(Phase.DECODE, 512, "tpu-v5e", kv="kv2")
    with pytest.raises(ValueError, match="malformed attn key"):
        registry.split_attn_key("attn|decode|s1k|not-a-kv|x|y")
    with pytest.raises(ValueError):
        registry.split_attn_key("none|decode|m8|tpu-v5e")


def test_attn_kv_key_inherits_bf16_tuned_blocks(tmp_path):
    """A kv8/kv4 key with no tuned entry of its own falls back to the
    legacy bf16 entry's blocks (chunk geometry is dtype-independent), while
    an exact 5-part entry outranks the inherited one."""
    path = str(tmp_path / "table.json")
    key_bf16 = registry.attn_dispatch_key(Phase.DECODE, 512, "tpu-v5e")
    registry.save_table(
        {"entries": {key_bf16: {"backend": "pallas", "blocks": [1, 64]}}},
        path,
    )
    choice = registry.select_attn(
        phase=Phase.DECODE, s=512, kv="kv8", table_path=path
    )
    assert choice.source == "tuned" and choice.blocks == (1, 64)
    # Exact kv-specific entry wins over the inherited bf16 one.
    key_kv8 = registry.attn_dispatch_key(Phase.DECODE, 512, "tpu-v5e", kv="kv8")
    registry.save_table(
        {"entries": {
            key_bf16: {"backend": "pallas", "blocks": [1, 64]},
            key_kv8: {"backend": "xla", "blocks": [1, 32]},
        }},
        path,
    )
    registry.clear_cache()
    choice = registry.select_attn(
        phase=Phase.DECODE, s=512, kv="kv8", table_path=path
    )
    assert choice.backend == "xla" and choice.blocks == (1, 32)
    # The bf16 resolution is untouched by the kv8 entry.
    choice = registry.select_attn(phase=Phase.DECODE, s=512, table_path=path)
    assert choice.backend == "pallas" and choice.blocks == (1, 64)


def test_attn_kv_key_quarantine_is_per_layout(tmp_path):
    """Demoting the kv8 decode key must not quarantine the bf16 path (and
    vice versa): a kernel failing on int8 pages stays available for raw
    bf16 serving."""
    empty = str(tmp_path / "empty.json")
    registry.save_table({"entries": {}}, empty)
    key8 = registry.attn_dispatch_key(Phase.DECODE, 512, "tpu-v5e", kv="kv8")
    before = registry.resolve_key(key8, table_path=empty)
    assert before.backend == "pallas"
    record = registry.demote(key8, failing="pallas", reason="test")
    try:
        assert record["to"] == "xla"
        after = registry.resolve_key(key8, table_path=empty)
        assert after.backend == "xla"
        bf16 = registry.resolve_key(
            registry.attn_dispatch_key(Phase.DECODE, 512, "tpu-v5e"),
            table_path=empty,
        )
        assert bf16.backend == "pallas"  # untouched
    finally:
        registry.clear_quarantine()


def test_attn_checked_in_table_covers_serving_buckets():
    """The committed table carries tuned attn entries for the decode and
    prefill serving regimes (kernel_bench --tune-attn writes them)."""
    for phase in (Phase.DECODE, Phase.PREFILL):
        for s in (256, 768, 2048):
            choice = registry.select_attn(phase=phase, s=s)
            assert choice.source == "tuned", (phase, s)
            assert choice.backend in registry.ATTN_BACKENDS


def test_kernel_bench_tune_refuses_interpret_mode_timings(monkeypatch, tmp_path):
    """`kernel_bench --tune` writes tpu-v5e keys, so without a TPU backend
    (interpret-mode kernels) it refuses before measuring and leaves the
    table unwritten."""
    from benchmarks import kernel_bench

    monkeypatch.setattr(targets_lib, "has_tpu_backend", lambda: False)
    out = tmp_path / "tuned_table.json"
    with pytest.raises(SystemExit, match="interpret"):
        kernel_bench.tune(str(out))
    assert not out.exists()
