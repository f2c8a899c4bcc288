"""Table 2 analog: prefill/decode tokens-per-second across matmul paths.

Paper columns {llama.cpp, upstream IREE, 10x-IREE} map to:
  naive      weights stored (K, N), transposed+packed EVERY call — the
             unprepared-layout baseline (llama.cpp-class data movement)
  reference  plain jnp contraction, weights (N, K) — upstream-XLA analogue
  mmt4d      weights pre-packed once, einsum on the packed 4-D layout — the
             paper's path ("10x-IREE")

CPU wall-clock is directionally meaningful only (this container is not the
TPU target); the TPU projection lives in EXPERIMENTS.md §Roofline.  The
paper's thread sweep (1 vs 8) has no analogue on this 1-core container and is
replaced by the mesh sweep in the dry-run.
"""

from __future__ import annotations

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import registry
from repro.core import encoding
from repro.core.encoding import Phase, decode_projection_hbm_bytes
from repro.core.packed import EncodingConfig
from repro.kernels import ops, ref
from repro.kernels import registry as kernel_registry
from repro.models import transformer as T
from repro.serving import engine as engine_lib
from repro.serving import faults as faults_lib


def _time(fn, *args, iters=5, warmup=2):
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def model_throughput(arch: str = "llama3.2-1b", prefill_len: int = 64, decode_steps: int = 8):
    """End-to-end model tokens/s for reference vs mmt4d paths."""
    cfg = registry.get_reduced(arch)
    rows = []
    for label, enc in (
        ("reference", EncodingConfig(enabled=False, backend="reference")),
        ("mmt4d", EncodingConfig(enabled=True, backend="xla")),
    ):
        params = T.model_init(jax.random.PRNGKey(0), cfg, enc)
        toks = jnp.ones((1, prefill_len), jnp.int32)
        caches = T.cache_init(cfg, 1, max_seq=prefill_len + decode_steps + 1)
        prefill = jax.jit(engine_lib.make_prefill_step(cfg, enc))
        decode = jax.jit(engine_lib.make_decode_step(cfg, enc))

        t_pre = _time(lambda: prefill(params, toks, caches)[0])
        rows.append((f"table2/prefill_tok_s/{label}", prefill_len / t_pre))

        _, caches2 = prefill(params, toks, caches)
        tok = jnp.ones((1, 1), jnp.int32)

        def dec_loop():
            c = caches2
            t = tok
            for i in range(decode_steps):
                t, _, c = decode(params, c, t, jnp.asarray(prefill_len + i - 1, jnp.int32))
            return t

        t_dec = _time(dec_loop)
        rows.append((f"table2/decode_tok_s/{label}", decode_steps / t_dec))
    return rows


def op_level_throughput(d_model: int = 1024, d_ff: int = 4096, batch: int = 1):
    """Per-matmul decode GEMV: the paper's core claim at op granularity.

    naive repacks the weight every call (what a runtime without device
    encodings does); mmt4d packs once at load."""
    rows = []
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(batch, d_model), jnp.float32)
    w_kn = jnp.asarray(rng.randn(d_model, d_ff), jnp.float32)   # (K, N) layout
    w_nk = jnp.asarray(w_kn.T)                                   # (N, K) layout
    rhs4 = ops.pack_rhs(w_nk)                                    # packed once

    @jax.jit
    def naive(x, w_kn):
        rhs = ref.pack(w_kn.T, (128, 128))  # per-call transpose + pack
        return ops.encoded_matmul(x, rhs, n=d_ff, phase=Phase.DECODE,
                                  backend="xla", out_dtype=jnp.float32)

    @jax.jit
    def reference(x, w_nk):
        return ref.matmul_reference(x, w_nk)

    @jax.jit
    def mmt4d(x, rhs4):
        return ops.encoded_matmul(x, rhs4, n=d_ff, phase=Phase.DECODE,
                                  backend="xla", out_dtype=jnp.float32)

    t_naive = _time(naive, x, w_kn)
    t_ref = _time(reference, x, w_nk)
    t_mmt = _time(mmt4d, x, rhs4)
    rows.append(("table2/op_decode_us/naive_repack", t_naive * 1e6))
    rows.append(("table2/op_decode_us/reference", t_ref * 1e6))
    rows.append(("table2/op_decode_us/mmt4d_prepacked", t_mmt * 1e6))
    rows.append(("table2/op_decode_speedup_vs_naive", t_naive / t_mmt))
    return rows


# ---- decode fast path (fused GEMV + position-vectorized engine) ------------


def _engine_decode_tok_s(
    params, cfg, enc, *, decode_mode, prompts, timed_steps
):
    """Steady-state decode tokens/s with every slot active (skewed positions).

    Returns (tok_s, decode_calls_per_step)."""
    eng = engine_lib.Engine(
        params, cfg, enc,
        slots=len(prompts),
        max_seq=max(len(p) for p in prompts) + timed_steps + 4,
        decode_mode=decode_mode,
        cache_mode="dense",   # this bench isolates dispatch vectorization
    )
    for i, p in enumerate(prompts):
        eng.submit(engine_lib.Request(uid=i, prompt=p, max_new_tokens=timed_steps + 2))
    eng.step()  # admit + first decode: compile outside the timed region
    eng.decode_fn = engine_lib.count_calls(eng.decode_fn)
    jax.block_until_ready(jax.tree.leaves(eng.caches)[0])
    t0 = time.perf_counter()
    emitted = 0
    for _ in range(timed_steps):
        emitted += eng.step()
    jax.block_until_ready(jax.tree.leaves(eng.caches)[0])
    dt = time.perf_counter() - t0
    return emitted / dt, eng.decode_fn.calls / timed_steps


def decode_fastpath_bench(
    arch: str = "qwen2-1.5b",
    *,
    quick: bool = False,
    out_json: str = "BENCH_decode.json",
):
    """Decode-path comparison for the paper's headline regime:

      op level   : unfused (pack -> GEMV -> unpack) vs fused GEMV, wall time
                   (interpret-mode Pallas on CPU — directional) + the TPU HBM
                   traffic model (exact bytes, core/encoding.py).
      engine     : grouped (per-position-group dispatch loop) vs vectorized
                   (one jitted decode per step) tokens/s under skewed prompt
                   lengths — real wall-clock on any backend.

    Emits BENCH_decode.json and returns the CSV rows."""
    rows = []
    result: dict = {"meta": {
        "arch": arch,
        "mode": "quick" if quick else "full",
        "note": (
            "tok_s/us are CPU wall-clock (op timings run interpret-mode "
            "Pallas); hbm_bytes_* are the TPU traffic model"
        ),
    }}

    # --- engine: grouped vs vectorized under position skew ---
    cfg = registry.get_reduced(arch)
    enc = EncodingConfig(enabled=True, backend="xla")
    params = T.model_init(jax.random.PRNGKey(0), cfg, enc)
    rng = np.random.RandomState(0)
    plens = [3, 5, 7, 9]  # all distinct: grouped pays one dispatch per slot
    prompts = [rng.randint(1, cfg.vocab_size, n).astype(np.int32) for n in plens]
    timed_steps = 4 if quick else 16
    eng_stats = {}
    for mode in ("grouped", "vectorized"):
        tok_s, calls = _engine_decode_tok_s(
            params, cfg, enc, decode_mode=mode, prompts=prompts,
            timed_steps=timed_steps,
        )
        eng_stats[mode] = {"tok_s": tok_s, "decode_calls_per_step": calls}
        rows.append((f"decode/engine_tok_s/{mode}", tok_s))
        rows.append((f"decode/engine_calls_per_step/{mode}", calls))
    eng_stats["vectorized_vs_grouped_speedup"] = (
        eng_stats["vectorized"]["tok_s"] / eng_stats["grouped"]["tok_s"]
    )
    eng_stats["prompt_lens"] = plens
    eng_stats["timed_steps"] = timed_steps
    rows.append(
        ("decode/engine_vectorized_speedup", eng_stats["vectorized_vs_grouped_speedup"])
    )
    result["engine"] = eng_stats

    # --- op level: fused vs unfused decode GEMV ---
    m = len(plens)
    n, k = (512, 256) if quick else (2048, 1024)
    x = jnp.asarray(rng.randn(m, k), jnp.float32)
    w_t = jnp.asarray(rng.randn(n, k), jnp.float32)
    rhs4 = ops.pack_rhs(w_t)
    rhs4_q, s_w = ops.pack_rhs_q8(w_t)
    rhs4_p4, s_w4 = ops.pack_rhs_q4(w_t)
    iters = 1 if quick else 3

    def unfused(a):
        return ops.encoded_matmul(
            a, rhs4, n=n, phase=Phase.DECODE, backend="pallas",
            out_dtype=jnp.float32, interpret=True,
        )

    def fused(a):
        return ops.encoded_matmul(
            a, rhs4, n=n, phase=Phase.DECODE, backend="fused",
            out_dtype=jnp.float32, interpret=True,
        )

    def q8_unfused(a):
        return ops.encoded_matmul_q8(
            a, rhs4_q, s_w, n=n, phase=Phase.DECODE, backend="pallas",
            out_dtype=jnp.float32, interpret=True,
        )

    def q8_fused(a):
        return ops.encoded_matmul_q8(
            a, rhs4_q, s_w, n=n, phase=Phase.DECODE, backend="fused",
            out_dtype=jnp.float32, interpret=True,
        )

    t_unf = _time(unfused, x, iters=iters, warmup=1)
    t_fus = _time(fused, x, iters=iters, warmup=1)
    t_q8u = _time(q8_unfused, x, iters=iters, warmup=1)
    t_q8f = _time(q8_fused, x, iters=iters, warmup=1)
    # Itemsizes match the f32 operands timed above (kernel_bench agrees).
    hbm = decode_projection_hbm_bytes(m, n, k, act_itemsize=4, weight_itemsize=4)
    op_stats = {
        "m": m, "n": n, "k": k,
        "unfused_us": t_unf * 1e6,
        "fused_us": t_fus * 1e6,
        "fused_vs_unfused_speedup": t_unf / t_fus,
        "q8_unfused_us": t_q8u * 1e6,
        "q8_fused_us": t_q8f * 1e6,
        "q8_fused_vs_unfused_speedup": t_q8u / t_q8f,
        "hbm_bytes_unfused": hbm["unfused"],
        "hbm_bytes_fused": hbm["fused"],
        "hbm_bytes_saved": hbm["saved"],
        "hbm_savings_frac": hbm["saved"] / hbm["unfused"],
    }
    result["op"] = op_stats
    for key in ("unfused_us", "fused_us", "fused_vs_unfused_speedup",
                "q8_fused_vs_unfused_speedup", "hbm_bytes_saved",
                "hbm_savings_frac"):
        rows.append((f"decode/op_{key}", op_stats[key]))

    # --- quant ladder: bf16 vs w8a8 vs w4a8 at equal batch ---
    # Wall-clock is interpret-mode-directional only; the decision row is the
    # weight-stream roofline (deterministic TPU traffic model): decode re-reads
    # every weight byte per token, so model tok/s ∝ 1/weight_stream_bytes.
    def q4_fused(a):
        return ops.encoded_matmul_q4(
            a, rhs4_p4, s_w4, n=n, phase=Phase.DECODE, backend="fused",
            out_dtype=jnp.float32, interpret=True,
        )

    t_q4f = _time(q4_fused, x, iters=iters, warmup=1)
    group = ref.Q4_GROUP
    stream = {
        "bf16": encoding.quant_weight_stream_bytes(n, k, quant="none"),
        "w8a8": encoding.quant_weight_stream_bytes(n, k, quant="w8a8"),
        "w4a8": encoding.quant_weight_stream_bytes(
            n, k, quant="w4a8", group=group,
            scale_itemsize=jnp.dtype(s_w4.dtype).itemsize,
        ),
    }
    model_tok_s = {
        q: encoding.decode_weight_stream_tok_s(b) for q, b in stream.items()
    }
    quant_stats = {
        "m": m, "n": n, "k": k, "group": group,
        "q8_fused_us": op_stats["q8_fused_us"],
        "q4_fused_us": t_q4f * 1e6,
        "weight_stream_bytes": stream,
        "model_tok_s": model_tok_s,
        "w4a8_vs_w8a8_model_tok_s_ratio": (
            model_tok_s["w4a8"] / model_tok_s["w8a8"]
        ),
        "w4a8_vs_bf16_model_tok_s_ratio": (
            model_tok_s["w4a8"] / model_tok_s["bf16"]
        ),
    }
    result["quant"] = quant_stats
    rows.append(("decode/quant_w4a8_model_tok_s", model_tok_s["w4a8"]))
    rows.append(("decode/quant_w8a8_model_tok_s", model_tok_s["w8a8"]))
    rows.append((
        "decode/quant_w4a8_vs_w8a8_tok_s_ratio",
        quant_stats["w4a8_vs_w8a8_model_tok_s_ratio"],
    ))
    rows.append(("decode/quant_w4a8_fused_us", quant_stats["q4_fused_us"]))

    with open(out_json, "w") as f:
        json.dump(result, f, indent=2)
    return rows


# ---- attention op class: fused paged decode vs gather fallback -------------


def attention_bench(
    *,
    quick: bool = False,
    out_json: str = "BENCH_decode.json",
):
    """The attention-kernel headline (kernels/attn.py): decode-attention HBM
    bytes per token, fused paged kernel vs the `paged_gather` fallback.

      bytes model : deterministic TPU traffic (encoding.decode_attn_hbm_bytes)
                    at 4k context on llama3.2-1b-class KV geometry — the
                    CI-gated ratio (fused <= 0.5x gather) plus the short-
                    context rows showing the bounded-fallback win.
      parity      : paged kernel vs the jnp reference path on a randomized
                    GQA/ragged-position case, dense kernel vs reference, and
                    paged-vs-dense bit-consistency at matched granularity —
                    parity == 1.0 is CI-gated.
      crossover   : context length where fused attention traffic overtakes
                    the w4a8 weight stream (the "attention is the next
                    roofline" number, docs/PERF.md).

    Merges an "attn" section into BENCH_decode.json and returns CSV rows."""
    from repro.kernels import attn as attn_lib
    from repro.kernels import registry as registry_lib
    from repro.models import layers as L

    # llama3.2-1b-class KV geometry (full-size, for the traffic model).
    kvh, hd, layers, itemsize = 8, 64, 16, 2
    ctx, max_seq, bs = 4096, 4096, 16
    model_4k = encoding.decode_attn_hbm_bytes(
        ctx, max_seq=max_seq, block_size=bs, num_kv_heads=kvh, head_dim=hd,
        num_layers=layers, itemsize=itemsize,
    )
    model_short = encoding.decode_attn_hbm_bytes(
        256, max_seq=max_seq, block_size=bs, num_kv_heads=kvh, head_dim=hd,
        num_layers=layers, itemsize=itemsize,
    )
    # w4a8 weight stream of the same model class: every projection byte per
    # token (param_count ~ 1.1e9 at full size; use the projection total).
    w4a8_bytes = encoding.quant_weight_stream_bytes(1, 1_100_000_000, quant="w4a8")
    crossover = encoding.attn_weight_crossover_tokens(
        w4a8_bytes, num_kv_heads=kvh, head_dim=hd, num_layers=layers,
        itemsize=itemsize,
    )

    # Kernel parity on a randomized reduced case (interpret-mode Pallas).
    rng = np.random.RandomState(0)
    b, L_q, h, kv, d, pbs, nb = 3, 2, 8, 2, 16, 8, 4
    pool_shape = (1 + b * nb, pbs, kv, d)
    k_pool = jnp.asarray(rng.randn(*pool_shape), jnp.float32)
    v_pool = jnp.asarray(rng.randn(*pool_shape), jnp.float32)
    table = jnp.asarray(1 + rng.permutation(b * nb).reshape(b, nb), jnp.int32)
    q = jnp.asarray(rng.randn(b, L_q, h, d), jnp.float32)
    pos = jnp.asarray(rng.randint(0, nb * pbs - L_q + 1, b), jnp.int32)

    t_paged = _time(
        lambda: attn_lib.paged_decode_attention(
            q, k_pool, v_pool, table, pos, interpret=True
        ),
        iters=1 if quick else 3, warmup=1,
    )
    got = attn_lib.paged_decode_attention(
        q, k_pool, v_pool, table, pos, interpret=True
    )
    gathered_k = L.paged_gather(k_pool, table)
    gathered_v = L.paged_gather(v_pool, table)
    t_gather = _time(
        lambda: L.attention_decode(
            q, L.paged_gather(k_pool, table), L.paged_gather(v_pool, table),
            pos=pos, window=0,
        ),
        iters=1 if quick else 3, warmup=1,
    )
    want = L.attention_decode(q, gathered_k, gathered_v, pos=pos, window=0)
    err_paged = float(jnp.max(jnp.abs(got - want)))
    dense_kernel = attn_lib.dense_decode_attention(
        q, gathered_k, gathered_v, pos, kv_chunk=pbs, interpret=True
    )
    err_dense = float(jnp.max(jnp.abs(dense_kernel - want)))
    bit_consistent = bool(jnp.all(got == dense_kernel))
    parity = 1.0 if (err_paged < 1e-4 and err_dense < 1e-4 and bit_consistent) else 0.0

    choice = registry_lib.select_attn(
        phase=encoding.Phase.DECODE, s=max_seq, requested="auto"
    )
    attn_stats = {
        "kv_geometry": {
            "num_kv_heads": kvh, "head_dim": hd, "num_layers": layers,
            "itemsize": itemsize, "block_size": bs, "max_seq": max_seq,
        },
        "hbm_bytes_per_token_4k": model_4k,
        "hbm_bytes_per_token_256": model_short,
        "paged_bytes_ratio_4k": model_4k["ratio"],
        "bounded_fallback_ratio_256": (
            model_short["bounded_gather"] / model_short["gather"]
        ),
        "w4a8_weight_stream_bytes": w4a8_bytes,
        "attn_weight_crossover_tokens": crossover,
        "kernel_parity": parity,
        "paged_vs_dense_bit_consistent": 1.0 if bit_consistent else 0.0,
        "max_abs_err_paged": err_paged,
        "max_abs_err_dense": err_dense,
        "paged_kernel_us": t_paged * 1e6,
        "gather_reference_us": t_gather * 1e6,
        "registry_backend_4k": choice.backend,
        "registry_source_4k": choice.source,
    }
    try:
        with open(out_json) as f:
            result = json.load(f)
    except (OSError, ValueError):
        result = {}
    result["attn"] = attn_stats
    with open(out_json, "w") as f:
        json.dump(result, f, indent=2)
    return [
        ("attn/paged_bytes_ratio_4k", attn_stats["paged_bytes_ratio_4k"]),
        ("attn/fused_mb_per_token_4k", model_4k["fused"] / 1e6),
        ("attn/gather_mb_per_token_4k", model_4k["gather"] / 1e6),
        ("attn/crossover_tokens_vs_w4a8", crossover),
        ("attn/kernel_parity", parity),
        ("attn/paged_kernel_us", attn_stats["paged_kernel_us"]),
    ]


# ---- speculative decode: prompt-lookup draft + batched verify --------------


def spec_decode_bench(
    arch: str = "qwen2-1.5b",
    *,
    quick: bool = False,
    out_json: str = "BENCH_decode.json",
):
    """Speculative decode on a repetition-heavy workload (the regime
    prompt-lookup drafting targets: templated/loopy continuations — here the
    reduced model's own greedy cycle, which the drafter reads out of the
    generated history).

    Headline metric: measured decode DISPATCHES per generated token —
    (decode_fn + verify_fn calls) / tokens on a single slot.  In the paper's
    memory-bound decode regime every dispatch re-streams the full weight set,
    so model tok/s scales as its inverse (docs/PERF.md §Speculative decode);
    CPU wall-clock is reported but not gated (interpret-mode CPU is
    compute-bound — the verify's extra FLOPs are ~free on TPU, not here).

    Merges a "spec" section into BENCH_decode.json (decode_fastpath_bench
    writes the file first) and returns CSV rows."""
    cfg = registry.get_reduced(arch)
    enc = EncodingConfig(enabled=True, backend="xla")
    params = T.model_init(jax.random.PRNGKey(0), cfg, enc)
    rng = np.random.RandomState(0)
    phrase = rng.randint(1, cfg.vocab_size, 4).astype(np.int32)
    prompt = np.tile(phrase, 8)
    # Long enough that the greedy cycle dominates the drafter's warmup (the
    # first ~30 tokens are incompressible); quick mode keeps the same length
    # because the metric, not the wall-clock, is the point.
    max_new, draft_k = 96, 6
    runs = {}
    gens = {}
    for label, spec in (("plain", False), ("spec", True)):
        eng = engine_lib.Engine(
            params, cfg, enc, slots=1, max_seq=160,
            spec_decode=spec, draft_k=draft_k,
        )
        eng.decode_fn = engine_lib.count_calls(eng.decode_fn)
        if spec:
            eng.verify_fn = engine_lib.count_calls(eng.verify_fn)
        eng.submit(engine_lib.Request(uid=0, prompt=prompt, max_new_tokens=max_new))
        t0 = time.perf_counter()
        done = eng.run()
        dt = time.perf_counter() - t0
        gens[label] = done[0].generated
        tokens = len(done[0].generated)
        dispatches = eng.decode_fn.calls + (eng.verify_fn.calls if spec else 0)
        runs[label] = {
            "tokens": tokens,
            "dispatches": dispatches,
            "dispatches_per_token": dispatches / tokens,
            "tok_s_wall": tokens / dt,
        }
        if spec:
            st = eng.stats["spec"]
            runs[label].update(
                mean_accepted_len=st["mean_accepted_len"],
                acceptance_rate=st["acceptance_rate"],
                proposed=st["proposed"],
                accepted=st["accepted"],
            )
    identical = gens["spec"] == gens["plain"]
    spec_stats = {
        "arch": arch,
        "draft_k": draft_k,
        "max_new": max_new,
        "prompt_len": int(len(prompt)),
        "plain": runs["plain"],
        "dispatches_per_token": runs["spec"]["dispatches_per_token"],
        "mean_accepted_len": runs["spec"]["mean_accepted_len"],
        "acceptance_rate": runs["spec"]["acceptance_rate"],
        "tok_s_wall": runs["spec"]["tok_s_wall"],
        # Weight-stream projection: each dispatch re-reads every weight byte,
        # so memory-bound model tok/s scales with tokens per dispatch.
        "model_tok_s_uplift": 1.0 / runs["spec"]["dispatches_per_token"],
        "token_identical": 1.0 if identical else 0.0,
    }
    # Merge into the decode-bench JSON (decode_fastpath_bench ran first).
    try:
        with open(out_json) as f:
            result = json.load(f)
    except (OSError, ValueError):
        result = {}
    result["spec"] = spec_stats
    with open(out_json, "w") as f:
        json.dump(result, f, indent=2)
    return [
        ("spec/dispatches_per_token", spec_stats["dispatches_per_token"]),
        ("spec/mean_accepted_len", spec_stats["mean_accepted_len"]),
        ("spec/acceptance_rate", spec_stats["acceptance_rate"]),
        ("spec/model_tok_s_uplift", spec_stats["model_tok_s_uplift"]),
        ("spec/token_identical", spec_stats["token_identical"]),
    ]


# ---- chaos conformance + guard overhead ------------------------------------


def chaos_bench(
    arch: str = "qwen2-1.5b",
    *,
    quick: bool = False,
    out_json: str = "BENCH_decode.json",
):
    """Robustness gates (docs/ROBUSTNESS.md), as bench numbers:

      token_identical_under_faults — 1.0 iff every request that SURVIVES the
          committed adversarial fault schedule (tests/fault_schedules/
          mixed_paged.json) emits exactly the fault-free run's tokens.
          Gated at 1.0: faults may kill requests, never corrupt neighbours.
      pages_leaked — pool pages still held once the faulted stream drains.
          Gated at 0: every lifecycle exit path frees through the allocator.
      guard_overhead_frac — wall-clock cost of the per-step non-finite
          logits guard (guarded / unguarded - 1 on a clean decode stream).
          Reported, not gated (CPU wall-clock; the guard is one (B,) device
          reduction + transfer per step) — cited by docs/ROBUSTNESS.md.

    Merges a "chaos" section into BENCH_decode.json and returns CSV rows."""
    cfg = registry.get_reduced(arch)
    enc = EncodingConfig(enabled=True, backend="xla")
    params = T.model_init(jax.random.PRNGKey(0), cfg, enc)
    rng = np.random.RandomState(0)
    n_req = 4 if quick else 6
    max_new = 6 if quick else 10
    prompts = [
        rng.randint(1, cfg.vocab_size, rng.randint(4, 10)).astype(np.int32)
        for _ in range(n_req)
    ]

    def run(hooks=None, *, guard=True):
        eng = engine_lib.Engine(
            params, cfg, enc, slots=3, max_seq=64,
            fault_hooks=hooks,
            clock=(hooks.clock if hooks is not None else None),
            logits_guard=guard,
        )
        for i, p in enumerate(prompts):
            assert eng.submit(
                engine_lib.Request(uid=i, prompt=p, max_new_tokens=max_new)
            )
        steps = 0
        t0 = time.perf_counter()
        while eng.queue or any(r is not None for r in eng.slot_req):
            assert steps < 400, "chaos bench deadlocked"
            eng.step()
            steps += 1
        dt = time.perf_counter() - t0
        if hooks is not None:
            hooks.drain(eng)
        eng.audit()
        return eng, dt

    # The quarantine is process-global; isolate this bench's demotions.
    kernel_registry.clear_quarantine()
    gold_eng, _ = run()
    gold = {r.uid: list(r.generated) for r in gold_eng.finished}
    sched_path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "..", "tests", "fault_schedules", "mixed_paged.json",
    )
    sched = faults_lib.FaultSchedule.from_json(sched_path)
    eng, _ = run(sched)
    survivors = [r for r in eng.finished if r.status == "ok"]
    identical = all(list(r.generated) == gold[r.uid] for r in survivors)
    leaked = eng.alloc.in_use()
    kernel_registry.clear_quarantine()

    # Guard overhead on a clean stream: jit caches are warm after the runs
    # above, so the delta is the guard's own reduction + host transfer.
    _, t_guard = run(guard=True)
    _, t_noguard = run(guard=False)
    overhead = t_guard / max(t_noguard, 1e-9) - 1.0

    chaos_stats = {
        "arch": arch,
        "mode": "quick" if quick else "full",
        "schedule": "tests/fault_schedules/mixed_paged.json",
        "requests": n_req,
        "survivors": len(survivors),
        "statuses": {r.uid: r.status for r in eng.finished},
        "token_identical_under_faults": 1.0 if identical else 0.0,
        "pages_leaked": float(leaked),
        "degraded_keys": len(eng.stats["degraded"]),
        "lifecycle": eng.stats["lifecycle"],
        "watchdog": eng.stats["watchdog"],
        "guard_overhead_frac": overhead,
    }
    try:
        with open(out_json) as f:
            result = json.load(f)
    except (OSError, ValueError):
        result = {}
    result["chaos"] = chaos_stats
    with open(out_json, "w") as f:
        json.dump(result, f, indent=2)
    return [
        ("chaos/token_identical_under_faults",
         chaos_stats["token_identical_under_faults"]),
        ("chaos/pages_leaked", chaos_stats["pages_leaked"]),
        ("chaos/survivors", float(len(survivors))),
        ("chaos/degraded_keys", float(chaos_stats["degraded_keys"])),
        ("chaos/guard_overhead_frac", overhead),
    ]


# ---- continuous batching: token-budget mixed prefill+decode ----------------


def continuous_bench(
    arch: str = "qwen2-1.5b",
    *,
    quick: bool = False,
    out_json: str = "BENCH_decode.json",
):
    """Token-budget continuous batching (serving/engine.py): a LONG prompt is
    admitted while two short requests are mid-decode, and the whole stream
    runs through the unified mixed chunked-prefill + decode dispatch.

      decode_stall_steps — steps where a live decoding slot emitted nothing
          (the metric the scheduler exists for).  Gated at 0: the budget
          reserves a 1-token floor per decode row before any chunk is
          packed, so prefill NEVER pauses decode.
      token_identical — 1.0 iff every request (the long one included) emits
          exactly what the phase-split engine emits on the same arrival
          pattern.  Gated at 1.0.
      pages_leaked — pool pages still held after drain.  Gated at 0.
      p99_step_ms_* — per-step wall clock, mixed vs phase-split.  The
          phase-split engine prefills the long prompt in ONE dispatch, so
          its tail step is the whole prefill; the mixed engine's steps are
          budget-bounded.  Reported, not gated (CPU wall clock, compiles
          included) — cited by docs/PERF.md §Token-budget scheduling.

    Merges a "continuous" section into BENCH_decode.json, returns CSV rows."""
    cfg = registry.get_reduced(arch)
    enc = EncodingConfig(enabled=True, backend="xla")
    params = T.model_init(jax.random.PRNGKey(0), cfg, enc)
    rng = np.random.RandomState(0)
    long_len = 256 if quick else 4096
    budget = 32 if quick else 128
    # Shorts must still be decoding when the long prefill finishes, or the
    # stall gate would have nothing to measure: prefill takes about
    # long_len / (budget - decode_rows) mixed steps.
    max_new_short = 24 if quick else 48
    shorts = [
        rng.randint(1, cfg.vocab_size, 8).astype(np.int32) for _ in range(2)
    ]
    long_p = rng.randint(1, cfg.vocab_size, long_len).astype(np.int32)
    max_seq = long_len + 64

    def run(token_budget):
        eng = engine_lib.Engine(
            params, cfg, enc, slots=3, max_seq=max_seq,
            cache_mode="paged", block_size=16, token_budget=token_budget,
        )
        for i, p in enumerate(shorts):
            assert eng.submit(
                engine_lib.Request(uid=i, prompt=p, max_new_tokens=max_new_short)
            )
        step_ms: list[float] = []
        steps = 0
        while eng.queue or any(r is not None for r in eng.slot_req):
            assert steps < 4000, "continuous bench deadlocked"
            if steps == 2:  # long prompt arrives mid-decode
                assert eng.submit(
                    engine_lib.Request(uid=9, prompt=long_p, max_new_tokens=4)
                )
            t0 = time.perf_counter()
            eng.step()
            step_ms.append((time.perf_counter() - t0) * 1e3)
            steps += 1
        eng.audit()
        return eng, np.asarray(step_ms)

    kernel_registry.clear_quarantine()
    split_eng, split_ms = run(None)
    gold = {r.uid: list(r.generated) for r in split_eng.finished}
    mix_eng, mix_ms = run(budget)
    got = {r.uid: list(r.generated) for r in mix_eng.finished}
    identical = got == gold
    kernel_registry.clear_quarantine()

    c = mix_eng.stats["continuous"]
    cont_stats = {
        "arch": arch,
        "mode": "quick" if quick else "full",
        "token_budget": budget,
        "long_prompt_len": long_len,
        "decode_stall_steps": float(c["decode_stall_steps"]),
        "token_identical": 1.0 if identical else 0.0,
        "pages_leaked": float(mix_eng.alloc.in_use()),
        "mixed_steps": c["mixed_steps"],
        "chunked_admissions": c["chunked_admissions"],
        "completed_prefills": c["completed_prefills"],
        "prefill_tokens": c["prefill_tokens"],
        "decode_tokens": c["decode_tokens"],
        "steps_mixed": int(mix_ms.size),
        "steps_split": int(split_ms.size),
        "p99_step_ms_mixed": float(np.percentile(mix_ms, 99)),
        "p99_step_ms_split": float(np.percentile(split_ms, 99)),
        "max_step_ms_mixed": float(mix_ms.max()),
        "max_step_ms_split": float(split_ms.max()),
    }
    try:
        with open(out_json) as f:
            result = json.load(f)
    except (OSError, ValueError):
        result = {}
    result["continuous"] = cont_stats
    with open(out_json, "w") as f:
        json.dump(result, f, indent=2)
    return [
        ("continuous/decode_stall_steps", cont_stats["decode_stall_steps"]),
        ("continuous/token_identical", cont_stats["token_identical"]),
        ("continuous/pages_leaked", cont_stats["pages_leaked"]),
        ("continuous/p99_step_ms_mixed", cont_stats["p99_step_ms_mixed"]),
        ("continuous/p99_step_ms_split", cont_stats["p99_step_ms_split"]),
    ]


# ---- tensor parallelism: sharded serving over a device mesh ----------------


_TP_BENCH_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json, sys, time
import jax
import numpy as np
from repro.configs import registry
from repro.core.packed import EncodingConfig
from repro.models import transformer as T
from repro.serving import engine as engine_lib
from repro.serving.config import EngineConfig

quick = sys.argv[1] == "quick"
# num_kv_heads=4 so the KV-head axis divides at 2 and 4 shards (the stock
# reduced config's single KV head would replicate — correct, no capacity win).
cfg = registry.get_reduced("qwen2-1.5b", num_kv_heads=4)
enc = EncodingConfig(enabled=True, backend="xla")
params = T.model_init(jax.random.PRNGKey(0), cfg, enc)
rng = np.random.RandomState(0)
prompts = [rng.randint(1, cfg.vocab_size, n).astype(np.int32)
           for n in (5, 8, 11, 14)]
max_new = 6 if quick else 12

def run(shards):
    eng = engine_lib.Engine(
        params, cfg, enc,
        config=EngineConfig(slots=len(prompts), max_seq=64,
                            cache_mode="paged", block_size=8,
                            mesh_shape=(shards,)))
    for i, p in enumerate(prompts):
        eng.submit(engine_lib.Request(uid=i, prompt=p, max_new_tokens=max_new))
    eng.step()  # admit + first decode: compile outside the timed region
    t0 = time.perf_counter()
    emitted = 0
    while eng.queue or any(r is not None for r in eng.slot_req):
        emitted += eng.step()
    jax.block_until_ready(jax.tree.leaves(eng.caches)[0])
    dt = time.perf_counter() - t0
    eng.audit()
    return {r.uid: list(r.generated) for r in eng.finished}, emitted / dt

out = {}
base = None
for shards in (1, 2, 4):
    gens, tok_s = run(shards)
    if base is None:
        base = gens
    out[str(shards)] = {"tok_s": tok_s,
                        "token_identical": 1.0 if gens == base else 0.0}
print("TP_BENCH_JSON " + json.dumps(out))
"""


def tp_bench(
    *,
    quick: bool = False,
    out_json: str = "BENCH_decode.json",
):
    """Tensor-parallel serving (docs/PERF.md §Tensor-parallel capacity math):

      token_identical     — mesh=2/4 decode emits exactly the mesh=1 stream
                            (4 emulated CPU devices in a subprocess; the
                            same CI-gated identity tests/test_tp_mesh.py
                            pins).  Gated at 1.0.
      kv_capacity_scaling — analytic paged request capacity at a FIXED
                            per-shard HBM budget, relative to 1 shard
                            (encoding.tp_kv_capacity_requests): head-parallel
                            KV shrinks each shard's bytes/token by the shard
                            count, so capacity scales with shards when the
                            kv heads divide.  Gated >= 1.8 at 2 shards.
      tok_s               — emulated-CPU wall clock per shard count.
                            Directional only (host devices share one core);
                            reported, not gated.

    Merges a "tp" section into BENCH_decode.json and returns CSV rows."""
    import subprocess
    import sys

    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))]
        + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else [])
    )
    env.pop("XLA_FLAGS", None)  # the child pins its own device count
    # The child is a CPU emulation by design; on a TPU host the parent (which
    # imported JAX) holds the chip, so the child must never reach for it.
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, "-c", _TP_BENCH_SCRIPT, "quick" if quick else "full"],
        env=env, capture_output=True, text=True, timeout=900,
    )
    if r.returncode != 0:
        raise RuntimeError(f"tp bench subprocess failed:\n{r.stderr[-4000:]}")
    line = next(
        l for l in r.stdout.splitlines() if l.startswith("TP_BENCH_JSON ")
    )
    measured = json.loads(line[len("TP_BENCH_JSON "):])

    # Analytic capacity at one fixed per-shard budget (full-size llama3.2-1b
    # KV geometry: 8 kv heads x 64 head_dim x 16 layers, bf16).
    kvh, hd, layers, itemsize = 8, 64, 16, 2
    max_seq, block_size, mean_tokens = 4096, 16, 512
    budget = encoding.dense_kv_hbm_bytes(
        4, max_seq, layers, kvh, hd, itemsize=itemsize
    )
    capacity = {
        str(s): encoding.tp_kv_capacity_requests(
            budget, shards=s, max_seq=max_seq, mean_tokens=mean_tokens,
            block_size=block_size, num_layers=layers, num_kv_heads=kvh,
            head_dim=hd, itemsize=itemsize,
        )
        for s in (1, 2, 4)
    }
    token_identical = min(
        measured[s]["token_identical"] for s in ("1", "2", "4")
    )
    tp_stats = {
        "mode": "quick" if quick else "full",
        "emulation": "--xla_force_host_platform_device_count=4",
        "kv_geometry": {
            "num_kv_heads": kvh, "head_dim": hd, "num_layers": layers,
            "itemsize": itemsize, "max_seq": max_seq,
            "block_size": block_size, "mean_tokens": mean_tokens,
        },
        "hbm_budget_per_shard": int(budget),
        "shards": {
            s: {
                "tok_s": measured[s]["tok_s"],
                "token_identical": measured[s]["token_identical"],
                "capacity_requests": capacity[s]["paged"],
                "bytes_per_token_per_shard":
                    capacity[s]["bytes_per_token_per_shard"],
            }
            for s in ("1", "2", "4")
        },
        "token_identical": token_identical,
        "kv_capacity_scaling_2": capacity["2"]["scaling_vs_1"],
        "kv_capacity_scaling_4": capacity["4"]["scaling_vs_1"],
    }
    try:
        with open(out_json) as f:
            result = json.load(f)
    except (OSError, ValueError):
        result = {}
    result["tp"] = tp_stats
    with open(out_json, "w") as f:
        json.dump(result, f, indent=2)
    return [
        ("tp/token_identical", token_identical),
        ("tp/kv_capacity_scaling_2", tp_stats["kv_capacity_scaling_2"]),
        ("tp/kv_capacity_scaling_4", tp_stats["kv_capacity_scaling_4"]),
        ("tp/capacity_requests_1", capacity["1"]["paged"]),
        ("tp/capacity_requests_2", capacity["2"]["paged"]),
        ("tp/capacity_requests_4", capacity["4"]["paged"]),
        ("tp/tok_s_1", measured["1"]["tok_s"]),
        ("tp/tok_s_2", measured["2"]["tok_s"]),
        ("tp/tok_s_4", measured["4"]["tok_s"]),
    ]


# ---- paged KV cache: pool utilization + capacity vs dense ------------------


def paged_cache_bench(
    arch: str = "qwen2-1.5b",
    *,
    quick: bool = False,
    out_json: str = "BENCH_paged.json",
):
    """The serving memory plan's headline: under ONE KV HBM budget, how many
    requests can be in flight at once?

      dense  — every slot reserves (max_seq) tokens; capacity = budget /
               (max_seq * bytes_per_token).
      paged  — slots hold ceil(len/block) pages; capacity scales with tokens
               actually in flight.  Measured by running both engines on the
               same short-prompt stream and recording peak concurrency, pool
               utilization, prefix-reuse hits, and preemptions.

    Emits BENCH_paged.json and returns CSV rows."""
    cfg = registry.get_reduced(arch)
    enc = EncodingConfig(enabled=True, backend="xla")
    params = T.model_init(jax.random.PRNGKey(0), cfg, enc)

    max_seq = 64 if quick else 128
    block_size = 8
    dense_slots = 2 if quick else 4
    ptb = encoding.kv_bytes_per_token(
        cfg.num_layers, cfg.num_kv_heads, cfg.head_dim,
        itemsize=jnp.dtype(cfg.activation_dtype).itemsize,
    )
    hbm_budget = encoding.dense_kv_hbm_bytes(
        dense_slots, max_seq, cfg.num_layers, cfg.num_kv_heads, cfg.head_dim,
        itemsize=jnp.dtype(cfg.activation_dtype).itemsize,
    )
    pool_pages = hbm_budget // (block_size * ptb)  # same budget, page-granular
    paged_slots = min(int(pool_pages), 8 if quick else 12)

    rng = np.random.RandomState(0)
    n_req = 8 if quick else 16
    max_new = 6 if quick else 10
    common = rng.randint(1, cfg.vocab_size, 8).astype(np.int32)

    def stream():
        reqs = []
        for i in range(n_req):
            plen = int(rng.randint(4, 13))
            p = rng.randint(1, cfg.vocab_size, plen).astype(np.int32)
            if i % 3 == 0:
                p = np.concatenate([common, p[:4]])  # shared prefix cohort
            reqs.append(engine_lib.Request(uid=i, prompt=p, max_new_tokens=max_new))
        return reqs

    def run(eng):
        for r in stream():
            eng.submit(r)
        util = []
        t0 = time.perf_counter()
        steps = 0
        while eng.queue or any(r is not None for r in eng.slot_req):
            eng.step()
            steps += 1
            if eng.cache_mode == "paged":
                util.append(eng.alloc.in_use() / eng.alloc.capacity)
            assert steps < 5000
        dt = time.perf_counter() - t0
        tokens = sum(len(r.generated) for r in eng.finished)
        return tokens / dt, steps, util

    rng = np.random.RandomState(0)
    eng_d = engine_lib.Engine(
        params, cfg, enc, slots=dense_slots, max_seq=max_seq, cache_mode="dense"
    )
    dense_tok_s, dense_steps, _ = run(eng_d)
    dense_peak = dense_slots  # a dense engine is concurrency-capped at slots

    rng = np.random.RandomState(0)
    eng_p = engine_lib.Engine(
        params, cfg, enc, slots=paged_slots, max_seq=max_seq,
        cache_mode="paged", block_size=block_size, pool_pages=int(pool_pages),
    )
    paged_tok_s, paged_steps, util = run(eng_p)
    stats = eng_p.stats

    cap = encoding.kv_capacity_requests(
        hbm_budget, max_seq=max_seq, mean_tokens=16 + max_new,
        block_size=block_size, num_layers=cfg.num_layers,
        num_kv_heads=cfg.num_kv_heads, head_dim=cfg.head_dim,
        itemsize=jnp.dtype(cfg.activation_dtype).itemsize,
    )
    result = {
        "meta": {
            "arch": arch, "mode": "quick" if quick else "full",
            "hbm_budget_bytes": int(hbm_budget),
            "bytes_per_token": int(ptb),
            "max_seq": max_seq, "block_size": block_size,
            "note": "one KV HBM budget; dense reserves worst-case rows, "
                    "paged allocates per-block (serving/paged.py)",
        },
        "concurrent_requests": {
            "dense": dense_peak,
            "paged_peak": stats["peak_active"],
            "paged_vs_dense_ratio": stats["peak_active"] / dense_peak,
        },
        "analytic_capacity": cap,
        "dense": {"tok_s": dense_tok_s, "engine_steps": dense_steps},
        "paged": {
            "tok_s": paged_tok_s, "engine_steps": paged_steps,
            "pool_pages": int(pool_pages),
            "pool_utilization_mean": float(np.mean(util)) if util else 0.0,
            "pool_utilization_peak": float(np.max(util)) if util else 0.0,
            "shared_hits": stats["shared_hits"],
            "cow_events": stats["cow_events"],
            "preemptions": stats["preemptions"],
        },
    }
    with open(out_json, "w") as f:
        json.dump(result, f, indent=2)
    rows = [
        ("paged/concurrent_dense", dense_peak),
        ("paged/concurrent_paged_peak", stats["peak_active"]),
        ("paged/concurrent_ratio", stats["peak_active"] / dense_peak),
        ("paged/pool_utilization_peak", result["paged"]["pool_utilization_peak"]),
        ("paged/shared_hits", stats["shared_hits"]),
        ("paged/preemptions", stats["preemptions"]),
        ("paged/tok_s", paged_tok_s),
        ("paged/dense_tok_s", dense_tok_s),
    ]
    return rows


def prefix_cache_bench(
    arch: str = "qwen2-1.5b",
    *,
    quick: bool = False,
    out_json: str = "BENCH_paged.json",
):
    """Radix-tree prefix cache (docs/PERF.md §Prefix caching) on a
    multi-tenant trace: 3 tenants, each with its own shared system prompt
    and a zipf-reused template library, plus a unique per-request tail.

      hit_rate        — block-level LCP hits / looked-up immutable blocks;
                        the radix tree must clear 0.5 on a trace where the
                        old exact-whole-prefix matcher (computed here as an
                        analytic oracle) scores < 0.1.
      token_identical — the same trace replayed cache-on / cache-off /
                        dense must generate identical tokens per request.
      pressure leg    — a small pool re-serves the trace so cumulative
                        demand fills it >= 3x: evictions must fire, audit()
                        stays exact every step, nothing leaks, and (with a
                        tenant_quota) no tenant's charged usage exceeds the
                        quota while another tenant has queued work.

    Merges a "prefix_cache" section into BENCH_paged.json and returns CSV
    rows; check_regression.py gates hit_rate, token_identical and
    pages_leaked."""
    cfg = registry.get_reduced(arch)
    enc = EncodingConfig(enabled=True, backend="xla")
    params = T.model_init(jax.random.PRNGKey(0), cfg, enc)

    max_seq = 96
    block_size = 8
    n_tenants = 3
    per_tenant = 4 if quick else 8
    max_new = 4 if quick else 8

    rng = np.random.RandomState(0)
    system = {t: rng.randint(1, cfg.vocab_size, 24).astype(np.int32)
              for t in range(n_tenants)}        # 3 full blocks each
    templates = {t: [rng.randint(1, cfg.vocab_size,
                                 8 * (1 + k % 2)).astype(np.int32)
                     for k in range(3)]
                 for t in range(n_tenants)}
    zipf = np.array([1.0, 0.5 ** 1.5, 1.0 / 3 ** 1.5])
    zipf /= zipf.sum()

    def trace():
        """The seeded multi-tenant request stream (tenants interleaved)."""
        r = np.random.RandomState(42)
        reqs = []
        for i in range(n_tenants * per_tenant):
            t = i % n_tenants
            tmpl = templates[t][int(r.choice(3, p=zipf))]
            tail = r.randint(1, cfg.vocab_size,
                             int(r.randint(8, 13))).astype(np.int32)
            reqs.append(engine_lib.Request(
                uid=i, max_new_tokens=max_new, tenant=f"tenant-{t}",
                prompt=np.concatenate([system[t], tmpl, tail]),
            ))
        return reqs

    # Analytic oracle for the OLD exact-whole-prefix matcher: a request's
    # immutable run hits only when that ENTIRE run was registered before.
    seen: set = set()
    exact_hits = exact_lookups = 0
    for req in trace():
        nshare = max(0, (len(req.prompt) - 1) // block_size)
        whole = tuple(int(x) for x in req.prompt[: nshare * block_size])
        if nshare:
            exact_lookups += nshare
            if whole in seen:
                exact_hits += nshare
            seen.add(whole)
    exact_whole_prefix_rate = exact_hits / max(1, exact_lookups)

    def run(**kw):
        eng = engine_lib.Engine(
            params, cfg, enc, slots=4, max_seq=max_seq,
            block_size=block_size, **kw,
        )
        quota = kw.get("tenant_quota")
        quota_violations = 0
        steps = 0
        for req in trace():
            assert eng.submit(req), f"uid {req.uid} rejected"
        while eng.queue or any(r is not None for r in eng.slot_req):
            eng.step()
            steps += 1
            assert steps < 5000
            if kw.get("cache_mode", "paged") == "paged":
                eng.audit()
                if quota is not None and eng.queue:
                    usage = eng.alloc.tenant_usage()
                    if any(u > quota + 1e-9 for u in usage.values()):
                        quota_violations += 1
        assert all(r.status == "ok" for r in eng.finished)
        toks = {r.uid: list(r.generated) for r in eng.finished}
        return eng, toks, quota_violations

    eng_on, gold, _ = run(cache_mode="paged", prefix_cache=True)
    pc = eng_on.stats["prefix_cache"]
    hit_rate = pc["hit_rate"]
    # The tentpole's acceptance bar, self-enforcing: LCP matching must clear
    # 0.5 on a trace where exact-whole-prefix matching is near-useless.
    assert hit_rate >= 0.5, f"radix hit rate {hit_rate:.3f} < 0.5"
    assert exact_whole_prefix_rate < 0.1, (
        f"trace too easy: exact matcher scores {exact_whole_prefix_rate:.3f}"
    )

    _, toks_off, _ = run(cache_mode="paged", prefix_cache=False)
    _, toks_dense, _ = run(cache_mode="dense")
    token_identical = 1.0 if (toks_off == gold and toks_dense == gold) else 0.0

    # Eviction-pressure leg: a pool several times smaller than the trace's
    # cumulative page demand, with a per-tenant quota.  Every step audits.
    pool_pages = 12 if quick else 18
    quota = 10
    eng_pr, toks_pr, violations = run(
        cache_mode="paged", prefix_cache=True, pool_pages=pool_pages,
        tenant_quota=quota, token_budget=32,
    )
    pr = eng_pr.stats
    fill_factor = pr["allocs"] / eng_pr.alloc.capacity
    assert fill_factor >= 3.0, (
        f"pressure leg refilled the pool only {fill_factor:.1f}x"
    )
    pages_leaked = float(eng_pr.alloc.in_use())
    eng_pr.audit()

    section = {
        "trace": {
            "tenants": n_tenants, "requests": n_tenants * per_tenant,
            "block_size": block_size,
            "note": "shared 16-token system prompt per tenant + zipf "
                    "template reuse + unique tails",
        },
        "hit_rate": hit_rate,
        "hit_blocks": pc["hit_blocks"],
        "hit_tokens": pc["hit_tokens"],
        "lookup_blocks": pc["lookup_blocks"],
        "exact_whole_prefix_rate": exact_whole_prefix_rate,
        "token_identical": token_identical,
        "pressure": {
            "pool_pages": pool_pages,
            "fill_factor": fill_factor,
            "evictions": pr["prefix_cache"]["evictions"],
            "deferred_hits": pr["prefix_cache"]["deferred_hits"],
            "cached_pages": pr["prefix_cache"]["cached_pages"],
            "preemptions": pr["preemptions"],
            "tenant_quota": quota,
            "quota_violations": violations,
            "token_identical": 1.0 if toks_pr == gold else 0.0,
        },
        "pages_leaked": pages_leaked,
        "quota_violations": float(violations),
    }
    try:
        with open(out_json) as f:
            result = json.load(f)
    except (OSError, ValueError):
        result = {}
    result["prefix_cache"] = section
    with open(out_json, "w") as f:
        json.dump(result, f, indent=2)
    return [
        ("prefix_cache/hit_rate", hit_rate),
        ("prefix_cache/exact_whole_prefix_rate", exact_whole_prefix_rate),
        ("prefix_cache/hit_tokens", pc["hit_tokens"]),
        ("prefix_cache/token_identical", token_identical),
        ("prefix_cache/evictions", section["pressure"]["evictions"]),
        ("prefix_cache/deferred_hits", section["pressure"]["deferred_hits"]),
        ("prefix_cache/quota_violations", float(violations)),
        ("prefix_cache/pages_leaked", pages_leaked),
    ]


def kv_quant_bench(
    arch: str = "qwen2-1.5b",
    *,
    quick: bool = False,
    out_json: str = "BENCH_decode.json",
    out_paged_json: str = "BENCH_paged.json",
):
    """Quantized paged KV cache (kv8): the capacity-for-accuracy headline.

      decision preservation — serve one seeded stream under bf16 and kv8;
        gold tokens teacher-forced back through the bf16 model give per-
        position top-2 margins, and kv8 must match gold at every CONFIDENT
        position (margin >= the median — the PR-3 margin-aware harness; a
        near-tie flipped by rounding is not a decision change).  The streams
        are free-running, so comparison stops at the first divergence: once
        a near-tie flips, the histories differ and later positions are not
        comparable.  A confident-position flip before any divergence fails
        the metric; the CI gate holds it at 1.0.
      relMSE — codec-level: decode-attention output on the dequantized kv8
        cache vs the raw bf16 cache, same inputs.
      capacity — requests in flight under ONE KV HBM budget, bf16 vs kv8
        pool (encoding.kv_capacity_requests with the layout's bytes/token);
        the CI gate holds the ratio >= 1.8.
      traffic — paged decode fused HBM bytes/token at 4k context, kv8 vs
        bf16 (per-page scales included); gated <= 0.6.

    Merges a "kv8" section into BENCH_decode.json, a "kv_quant" section into
    BENCH_paged.json, and returns CSV rows."""
    from repro.models import layers as L

    cfg = registry.get_reduced(arch)
    enc = EncodingConfig(enabled=True, backend="xla")
    params = T.model_init(jax.random.PRNGKey(0), cfg, enc)

    max_seq = 64
    block_size = 8
    rng = np.random.RandomState(0)
    n_req = 4 if quick else 8
    max_new = 6 if quick else 10
    prompts = [
        rng.randint(1, cfg.vocab_size, int(rng.randint(5, 13))).astype(np.int32)
        for _ in range(n_req)
    ]

    def serve(kv_quant):
        eng = engine_lib.Engine(
            params, cfg, enc,
            slots=3, max_seq=max_seq, cache_mode="paged",
            block_size=block_size, kv_quant=kv_quant,
        )
        for i, p in enumerate(prompts):
            eng.submit(engine_lib.Request(
                uid=i, prompt=p, max_new_tokens=max_new))
        t0 = time.perf_counter()
        eng.run()
        dt = time.perf_counter() - t0
        eng.audit()
        assert all(r.status == "ok" for r in eng.finished)
        toks = {r.uid: list(r.generated) for r in eng.finished}
        return toks, sum(len(g) for g in toks.values()) / dt, eng

    gold, bf16_tok_s, _ = serve("bf16")
    got, kv8_tok_s, eng8 = serve("kv8")
    assert eng8.stats["kv_quant"] == "kv8"

    # Teacher-force each gold continuation through the bf16 model: logits at
    # prompt_end-1 .. end-1 produced each generated token; their top-2
    # margins say where the decision was confident.
    conf_total = conf_match = 0
    all_identical = True
    for uid, g in sorted(gold.items()):
        seq = np.concatenate([prompts[uid], np.asarray(g, np.int32)])
        logits, _, _ = T.forward(
            params, {"tokens": jnp.asarray(seq[None, :])}, cfg=cfg, enc=enc,
            phase=Phase.PREFILL,
        )
        lg = logits[0, len(prompts[uid]) - 1: len(seq) - 1]  # one per gen tok
        top2 = jax.lax.top_k(lg, 2)[0]
        margin = np.asarray(top2[:, 0] - top2[:, 1])
        confident = margin >= np.median(margin)
        for i, (gt, kt) in enumerate(zip(g, got[uid])):
            if gt == kt:
                if confident[i]:
                    conf_total += 1
                    conf_match += 1
                continue
            # First divergence: a confident flip counts against the metric;
            # a near-tie flip is tolerated.  Either way the histories differ
            # from here on, so later positions are not comparable — stop.
            all_identical = False
            if confident[i]:
                conf_total += 1
            break
    token_identical_confident = (
        1.0 if conf_total and conf_match == conf_total else 0.0
    )

    # Codec relMSE on the decode-attention output (dequantized kv8 cache vs
    # the raw cache, identical queries/positions).
    layout = encoding.kv_layout("kv8")
    rng2 = np.random.RandomState(1)
    b, h, kv, d, s = 2, 4, 2, 16, 32
    k_raw = jnp.asarray(rng2.randn(b, s, kv, d), jnp.float32)
    v_raw = jnp.asarray(rng2.randn(b, s, kv, d), jnp.float32)
    q = jnp.asarray(rng2.randn(b, 1, h, d), jnp.float32)
    pos = jnp.asarray(rng2.randint(8, s, b), jnp.int32)
    o_fp = L.attention_decode(q, k_raw, v_raw, pos=pos, window=0)
    kq, ks = layout.quantize(k_raw)
    vq, vs = layout.quantize(v_raw)
    o_q = L.attention_decode(
        q, layout.dequantize(kq, ks), layout.dequantize(vq, vs),
        pos=pos, window=0,
    )
    rel_mse = float(jnp.sum(jnp.square(o_q - o_fp)) / jnp.sum(jnp.square(o_fp)))

    # Capacity under one HBM budget: the paged_cache_bench budget, repriced
    # per layout (scale pages included in bytes/token).
    itemsize = jnp.dtype(cfg.activation_dtype).itemsize
    hbm_budget = encoding.dense_kv_hbm_bytes(
        4, 128, cfg.num_layers, cfg.num_kv_heads, cfg.head_dim,
        itemsize=itemsize,
    )
    cap = {
        kvq: encoding.kv_capacity_requests(
            hbm_budget, max_seq=128, mean_tokens=24, block_size=block_size,
            num_layers=cfg.num_layers, num_kv_heads=cfg.num_kv_heads,
            head_dim=cfg.head_dim, itemsize=itemsize, kv_quant=kvq,
        )
        for kvq in ("bf16", "kv8", "kv4")
    }
    capacity_scaling = (
        cap["kv8"]["paged"] / max(cap["bf16"]["paged"], 1)
    )

    # Paged decode traffic at 4k context, full-size KV geometry (the same
    # geometry attention_bench prices): fused bytes/token kv8 vs bf16.
    kvh, hd, layers = 8, 64, 16
    traffic = {
        kvq: encoding.decode_attn_hbm_bytes(
            4096, max_seq=4096, block_size=16, num_kv_heads=kvh, head_dim=hd,
            num_layers=layers, itemsize=2, kv_quant=kvq,
        )
        for kvq in ("bf16", "kv8", "kv4")
    }
    bytes_ratio_4k = traffic["kv8"]["fused"] / traffic["bf16"]["fused"]

    kv8_stats = {
        "token_identical_confident": token_identical_confident,
        "token_identical_all_positions": 1.0 if all_identical else 0.0,
        "confident_positions": conf_total,
        "rel_mse_attn_out": rel_mse,
        "kv_capacity_scaling": capacity_scaling,
        "kv4_capacity_scaling": (
            cap["kv4"]["paged"] / max(cap["bf16"]["paged"], 1)
        ),
        "paged_bytes_ratio_vs_bf16_4k": bytes_ratio_4k,
        "kv4_bytes_ratio_vs_bf16_4k": (
            traffic["kv4"]["fused"] / traffic["bf16"]["fused"]
        ),
        "bytes_per_cached_token": {
            kvq: traffic[kvq]["bytes_per_cached_token"]
            for kvq in ("bf16", "kv8", "kv4")
        },
        "bf16_tok_s": bf16_tok_s,
        "kv8_tok_s": kv8_tok_s,
    }
    try:
        with open(out_json) as f:
            result = json.load(f)
    except (OSError, ValueError):
        result = {}
    result["kv8"] = kv8_stats
    with open(out_json, "w") as f:
        json.dump(result, f, indent=2)
    # Capacity detail rides with the paged-cache results.
    try:
        with open(out_paged_json) as f:
            presult = json.load(f)
    except (OSError, ValueError):
        presult = {}
    presult["kv_quant"] = {
        "hbm_budget_bytes": int(hbm_budget),
        "capacity_requests": {
            kvq: cap[kvq]["paged"] for kvq in ("bf16", "kv8", "kv4")
        },
        "kv8_capacity_scaling": capacity_scaling,
    }
    with open(out_paged_json, "w") as f:
        json.dump(presult, f, indent=2)
    return [
        ("kv8/token_identical_confident", token_identical_confident),
        ("kv8/rel_mse_attn_out", rel_mse),
        ("kv8/kv_capacity_scaling", capacity_scaling),
        ("kv8/paged_bytes_ratio_vs_bf16_4k", bytes_ratio_4k),
        ("kv8/tok_s", kv8_tok_s),
        ("kv8/bf16_tok_s", bf16_tok_s),
    ]


def main(*, quick: bool = False):
    if not quick:
        for name, val in model_throughput():
            print(f"{name},{val:.4f},cpu-wall-clock")
        for name, val in op_level_throughput():
            print(f"{name},{val:.4f},cpu-wall-clock")
    for name, val in decode_fastpath_bench(quick=quick):
        print(f"{name},{val:.4f},see-BENCH_decode.json")
    for name, val in attention_bench(quick=quick):
        print(f"{name},{val:.4f},see-BENCH_decode.json")
    for name, val in spec_decode_bench(quick=quick):
        print(f"{name},{val:.4f},see-BENCH_decode.json")
    for name, val in chaos_bench(quick=quick):
        print(f"{name},{val:.4f},see-BENCH_decode.json")
    for name, val in continuous_bench(quick=quick):
        print(f"{name},{val:.4f},see-BENCH_decode.json")
    for name, val in tp_bench(quick=quick):
        print(f"{name},{val:.4f},see-BENCH_decode.json")
    for name, val in paged_cache_bench(quick=quick):
        print(f"{name},{val:.4f},see-BENCH_paged.json")
    for name, val in prefix_cache_bench(quick=quick):
        print(f"{name},{val:.4f},see-BENCH_paged.json")
    for name, val in kv_quant_bench(quick=quick):
        print(f"{name},{val:.4f},see-BENCH_decode.json")


if __name__ == "__main__":
    main()
