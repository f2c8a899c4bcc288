"""Per-kernel microbenchmark: correctness (interpret) + wall time (XLA path)
across the paper's shape regimes, plus the VMEM/block report for each
configuration (the structural profile used in §Perf).

`--tune` is the registry's autotune pass: for every dispatch key
(quant, phase, M-bucket, target) it measures the candidate kernel-block
shapes on a representative shape and persists the winners to the checked-in
tuned table (src/repro/kernels/tuned_table.json) that
`repro.kernels.registry.select` consults at dispatch time.  The table's keys
name the tpu-v5e target, so `--tune` runs only where JAX's backend is a TPU:
without one every kernel would run in Pallas's interpreter, whose timings
say nothing of the chip, and it exits without writing the table."""

from __future__ import annotations

import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import encoding, targets
from repro.core.encoding import Phase
from repro.kernels import attn as attn_lib
from repro.kernels import ops, ref
from repro.kernels import registry as registry_lib


def _time(fn, *args, iters=3, warmup=1):
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


SHAPES = [
    # (phase, M, N, K) — prefill GEMM and decode GEMV regimes
    (Phase.PREFILL, 512, 2048, 1024),
    (Phase.PREFILL, 2048, 2048, 2048),
    (Phase.DECODE, 1, 4096, 1024),
    (Phase.DECODE, 8, 8192, 2048),
]


def main():
    rows = []
    for phase, m, n, k in SHAPES:
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randn(m, k), jnp.float32)
        w_t = jnp.asarray(rng.randn(n, k), jnp.float32)
        rhs4 = ops.pack_rhs(w_t)

        # correctness in interpret mode (the Pallas kernel body itself)
        want = ref.matmul_reference(x, w_t)
        got = ops.encoded_matmul(
            x, rhs4, n=n, phase=phase, backend="pallas",
            out_dtype=jnp.float32, interpret=True,
        )
        err = float(jnp.max(jnp.abs(got - want)))

        # wall time of the XLA-lowered packed path vs reference
        f_mmt = jax.jit(lambda a, r: ops.encoded_matmul(
            a, r, n=n, phase=phase, backend="xla", out_dtype=jnp.float32))
        f_ref = jax.jit(lambda a, w: ref.matmul_reference(a, w))
        t_mmt = _time(f_mmt, x, rhs4)
        t_ref = _time(f_ref, x, w_t)

        # structural: selected kernel blocks + VMEM footprint
        n1, k1 = rhs4.shape[0], rhs4.shape[1]
        m0 = 128 if phase is not Phase.DECODE else min(8, m)
        kb = encoding.select_kernel_blocks(
            encoding.TileSizes(m0, 128, 128), phase,
            m1=max(1, m // m0), n1=n1, k1=k1, lhs_itemsize=4, rhs_itemsize=4,
        )
        vmem = (
            kb.bm1 * kb.bk1 * m0 * 128 * 4
            + kb.bn1 * kb.bk1 * 128 * 128 * 4
            + kb.bm1 * kb.bn1 * m0 * 128 * 4
        )
        tag = f"{phase.value}_m{m}_n{n}_k{k}"
        rows.append((f"kernel/{tag}/interpret_err", err, "allclose"))
        rows.append((f"kernel/{tag}/xla_mmt4d_us", t_mmt * 1e6, f"blocks={kb.bm1}x{kb.bn1}x{kb.bk1}"))
        rows.append((f"kernel/{tag}/xla_reference_us", t_ref * 1e6, ""))
        rows.append((f"kernel/{tag}/vmem_bytes", vmem, f"fits={vmem <= targets.TPU_V5E.vmem_bytes // 2}"))

        if phase is Phase.DECODE:
            # Decode fast path: fused GEMV correctness.
            got_f = ops.encoded_matmul(
                x, rhs4, n=n, phase=phase, backend="fused",
                out_dtype=jnp.float32, interpret=True,
            )
            err_f = float(jnp.max(jnp.abs(got_f - want)))
            rows.append((f"kernel/{tag}/fused_gemv_interpret_err", err_f, "allclose"))
    for name, val, derived in rows:
        print(f"{name},{val},{derived}")
    return rows


# ---- registry autotune (kernel_bench --tune) --------------------------------

# Representative live-row count per M-bucket (registry.m_bucket boundaries).
# "m32" is the spec-decode verify regime: slots x (draft_k + 1) rows.
_BUCKET_REPS = {"m1": 1, "m8": 8, "m32": 20, "m64": 48, "big": 192}

# Candidate kernel blocks (BM1, BN1, BK1) per phase kind.  Decode candidates
# sweep the GEMV streaming width BN1; prefill candidates sweep the VMEM-
# resident block.  All candidates divide the tune shape's tile counts.
_DECODE_CANDIDATES = [(1, 1, 1), (1, 2, 1), (1, 4, 1), (1, 8, 1)]
_PREFILL_CANDIDATES = [(1, 2, 1), (2, 2, 2), (1, 4, 2), (2, 8, 2)]


# Attention op-class candidates: (q_chunk, kv_chunk) streaming granularity
# (decode kernels use kv_chunk only; the stored blocks keep the 2-tuple).
# S reps land one representative context length inside each tuned bucket
# ("sbig" stays policy-routed — an 8k+ interpret sweep buys no information
# the s4k point does not already carry).
_ATTN_S_REPS = {"s256": 256, "s1k": 768, "s4k": 2048}
_ATTN_DECODE_CANDIDATES = [(1, 32), (1, 64), (1, 128)]
_ATTN_PREFILL_CANDIDATES = [(64, 64), (64, 128), (128, 128)]


def _tune_attn(entries: dict, *, iters: int) -> None:
    """Measure attention-kernel chunk candidates per (phase, S-bucket) key
    and add them to `entries` (kernels/attn.py dense decode + flash
    prefill; the paged kernel streams at page granularity and shares the
    decode entries' backend).

    Decode keys are measured across the kv-quant axis too (bf16 emits the
    legacy 4-segment key, kv8/kv4 the 5-segment form): the quantized kernels
    stream packed K/V plus scale slabs, so the winning chunk size can differ
    from bf16's.  Prefill stays bf16-only — flash prefill reads the
    full-precision temp cache; quantization happens at scatter.

    Like the matmul tuner, the recorded backend is the STATIC POLICY, never
    a cross-backend measurement at the tuner's toy shapes.  A target where
    the reference genuinely wins a bucket gets its entry pinned by a
    measurement at the served shapes (the same convention as the
    hand-pinned tpu-v5e m64 "fused" matmul entries)."""
    target = targets.TPU_V5E
    rng = np.random.RandomState(0)
    b, kvh, g, d = 1, 2, 4, 32
    for phase in (Phase.DECODE, Phase.PREFILL):
        cands = (
            _ATTN_DECODE_CANDIDATES if phase is Phase.DECODE
            else _ATTN_PREFILL_CANDIDATES
        )
        kv_axis = registry_lib.KV_QUANTS if phase is Phase.DECODE else ("bf16",)
        for bucket, s_rep in _ATTN_S_REPS.items():
            backend = registry_lib.default_attn_backend(phase, bucket)
            k = jnp.asarray(rng.randn(b, s_rep, kvh, d), jnp.float32)
            v = jnp.asarray(rng.randn(b, s_rep, kvh, d), jnp.float32)
            for kvq in kv_axis:
                key = registry_lib.attn_dispatch_key(
                    phase, s_rep, target.name, kv=kvq
                )
                layout = encoding.kv_layout(kvq)
                if layout.quantized:
                    kq, ks = layout.quantize(k)
                    vq, vs = layout.quantize(v)
                best = None
                for qc, kc in cands:
                    if phase is Phase.DECODE:
                        q = jnp.asarray(
                            rng.randn(b, 1, kvh * g, d), jnp.float32)
                        pos = jnp.asarray([s_rep - 1], jnp.int32)
                        if layout.quantized:
                            fn = lambda: attn_lib.dense_decode_attention(
                                q, kq, vq, pos, k_scale=ks, v_scale=vs,
                                kv_quant=kvq, kv_chunk=kc, interpret=False,
                            )
                        else:
                            fn = lambda: attn_lib.dense_decode_attention(
                                q, k, v, pos, kv_chunk=kc, interpret=False
                            )
                    else:
                        sq = min(s_rep, 256)  # prefill band; KV carries S
                        q = jnp.asarray(
                            rng.randn(b, sq, kvh * g, d), jnp.float32)
                        off = s_rep - sq
                        fn = lambda: attn_lib.flash_prefill_attention(
                            q, k, v, causal=True, q_offset=off,
                            q_chunk=qc, kv_chunk=kc, interpret=False,
                        )
                    t = _time(fn, iters=iters, warmup=1)
                    print(f"tune/{key}/blocks={qc}x{kc},{t * 1e6:.1f},us")
                    if best is None or t < best[0]:
                        best = (t, (qc, kc))
                entries[key] = {
                    "backend": backend,
                    "blocks": list(best[1]),
                    "us": round(best[0] * 1e6, 1),
                    "shape_bsd": [b, s_rep, kvh * g * d],
                }


def tune(
    out_path: str | None = None,
    *,
    iters: int = 2,
    op_classes: tuple[str, ...] = ("matmul", "attn"),
) -> str:
    """Measure candidate tile/block shapes per dispatch key and persist the
    winning table.  Returns the path written.

    `op_classes` picks which classes to re-measure; keys of classes NOT
    re-measured this run are carried over from the existing table unchanged
    (a partial retune must not drop the other class's entries).

    Refuses (SystemExit, table untouched) without a TPU backend: the
    entries are keyed tpu-v5e, and interpret-mode timings cannot stand for
    the chip."""
    if not targets.has_tpu_backend():
        raise SystemExit(
            "kernel_bench --tune: no TPU backend, so the kernels would run in "
            "Pallas's interpreter; refusing to write tpu-v5e entries from "
            "interpret-mode timings"
        )
    target = targets.TPU_V5E
    n, k = 1024, 256  # N1=8, K1=2: every candidate divides the tile counts
    rng = np.random.RandomState(0)
    w_t = jnp.asarray(rng.randn(n, k), jnp.float32)
    packed = {
        "none": (ops.pack_rhs(w_t),),
        "w8a8": ops.pack_rhs_q8(w_t),
        "w4a8": ops.pack_rhs_q4(w_t),
    }

    def run(quant, phase, m, backend, blocks):
        # Measurement pins the POLICY backend explicitly — "auto" would read
        # the very table being regenerated.
        x = jnp.asarray(rng.randn(m, k), jnp.float32)
        if quant == "none":
            fn = lambda: ops.encoded_matmul(
                x, packed[quant][0], n=n, phase=phase, backend=backend,
                blocks=blocks, out_dtype=jnp.float32, interpret=False,
            )
        elif quant == "w8a8":
            fn = lambda: ops.encoded_matmul_q8(
                x, *packed[quant], n=n, phase=phase, backend=backend,
                blocks=blocks, out_dtype=jnp.float32, interpret=False,
            )
        else:
            fn = lambda: ops.encoded_matmul_q4(
                x, *packed[quant], n=n, phase=phase, backend=backend,
                blocks=blocks, out_dtype=jnp.float32, interpret=False,
            )
        return _time(fn, iters=iters, warmup=1)

    # Carry over entries this run will not re-measure.  The matmul class is
    # dropped wholesale when re-measured (every matmul key is regenerated
    # below), but the attn class merges at KEY level: _tune_attn overwrites
    # exactly the keys it measures, and any other attn entry — a 5-part
    # kv-quant key pinned on real hardware, another target's key — is
    # preserved.  Dropping those on every retune would silently erase the
    # kv axis of the table.
    entries = {
        k: dict(v)
        for k, v in registry_lib.load_table(out_path)["entries"].items()
        if k.startswith("attn|") or "matmul" not in op_classes
    }
    if "attn" in op_classes:
        _tune_attn(entries, iters=iters)
    if "matmul" not in op_classes:
        path = registry_lib.save_table({"entries": entries}, out_path)
        print(f"tune/table_written,{len(entries)},{path}")
        return path
    for quant in registry_lib.QUANTS:
        for phase in (Phase.DECODE, Phase.PREFILL):
            cands = (
                _DECODE_CANDIDATES if phase is Phase.DECODE else _PREFILL_CANDIDATES
            )
            buckets = ("m1", "m8", "m32", "m64") if phase is Phase.DECODE else (
                "m64", "big"
            )
            for bucket in buckets:
                m = _BUCKET_REPS[bucket]
                key = registry_lib.dispatch_key(quant, phase, m, target.name)
                # Backend comes from the static policy, NOT select(): select
                # reads the existing tuned table, and copying its backend
                # would let a stale entry survive every retune.
                backend = registry_lib.default_backend(quant, phase, bucket)
                best = None
                for cand in cands:
                    t = run(quant, phase, m, backend, cand)
                    print(
                        f"tune/{key}/blocks={cand[0]}x{cand[1]}x{cand[2]},"
                        f"{t * 1e6:.1f},us"
                    )
                    if best is None or t < best[0]:
                        best = (t, cand)
                entries[key] = {
                    "backend": backend,
                    "blocks": list(best[1]),
                    "us": round(best[0] * 1e6, 1),
                    "shape_mnk": [m, n, k],
                }
    path = registry_lib.save_table({"entries": entries}, out_path)
    print(f"tune/table_written,{len(entries)},{path}")
    return path


if __name__ == "__main__":
    if "--tune" in sys.argv[1:] or "--tune-attn" in sys.argv[1:]:
        out = None
        if "--out" in sys.argv[1:]:
            out = sys.argv[sys.argv.index("--out") + 1]
        classes = ("attn",) if "--tune-attn" in sys.argv[1:] else ("matmul", "attn")
        tune(out, op_classes=classes)
    else:
        main()
