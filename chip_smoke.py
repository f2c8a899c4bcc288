#!/usr/bin/env python3
"""Chip smoke test: serve qwen2-1.5b at its published widths on a TPU.

    python3 chip_smoke.py            # one chip
    python3 chip_smoke.py --chips 4  # tensor-parallel comparison on four chips

One chip: random seeded weights, then the serving engine's normal path
(`Engine`, `submit`, `run`; paged bf16 KV cache, 8 slots, max_seq 1024,
8 requests of 64-480 prompt tokens and 32 new tokens each) twice:

  * kernel pass — backend="auto", attn_backend="auto", interpret=False:
    every matmul and attention dispatch key must resolve to a Pallas
    kernel ("fused" or "pallas");
  * XLA pass — backend="xla", attn_backend="xla".

Each pass's prefill logits and its decode logits at a few steps are held
to a float32 reference: `T.forward` with `EncodingConfig(enabled=False)`
on the same seeded weights upcast to float32, at matmul precision
"highest", teacher-forced on the tokens the pass served.

`--chips 4` runs one comparison only: the same model served with
mesh_shape=(4,) against mesh_shape=(1,), XLA backends in both (the engine
routes tensor-parallel serving to XLA), in one process.

Exits non-zero, printing no result line, when the platform is not a TPU,
when a kernel-pass key resolved to a non-Pallas backend, when anything was
degraded or downgraded, when a request did not finish "ok", or when a
tolerance failed.  The last line of standard output is then
{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import gc
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import registry  # noqa: E402
from repro.core import targets  # noqa: E402
from repro.core.encoding import Phase  # noqa: E402
from repro.core.packed import EncodingConfig  # noqa: E402
from repro.kernels import registry as kernel_registry  # noqa: E402
from repro.launch import compile_cache  # noqa: E402
from repro.models import transformer as T  # noqa: E402
from repro.serving import engine as engine_lib  # noqa: E402
from repro.serving.config import EngineConfig  # noqa: E402

ARCH = "qwen2-1.5b"
SEED = 0
SLOTS = 8
MAX_SEQ = 1024
BLOCK_SIZE = 16
N_REQUESTS = 8
PROMPT_LENS = (64, 480)
MAX_NEW = 32
CHECK_STEPS = (0, 1, 15, 31)  # decode steps whose logits are checked
PALLAS_BACKENDS = ("pallas", "fused")

# Logit tolerances, per logit row, against the float32 reference.
#
# TOL_REL bounds ||got - ref|| / ||ref||.  The served model keeps bf16
# weights (the reference upcasts the same values, so weights add no
# error) and bf16 activations between f32-accumulated matmuls: every
# rounding of a residual, norm output or projection output moves it by up
# to bf16's unit roundoff, 2^-8.  On a CPU run of this model's widths
# (vocabulary cut to 8192) the worst row was 0.0106 at one layer and 0.0152
# at eight, growing ~0.0015 per doubling of depth: ~0.02 at 28 layers.
# The error scales with the unit roundoff, and fp8 (e4m3, 2^-4) is 16
# times coarser: the one-layer figure alone would be ~0.17, three times
# this bound.
TOL_REL = 0.05
# TOL_MAX bounds max |got - ref| over the row, in units of the reference
# row's RMS (~1 for these random weights).  Gaussian error of RMS 0.02
# over 151936 logits peaks near 5 sigma = 0.1; the bound catches a single
# wrong tile of logits that barely moves the row's RMS error.
TOL_MAX = 0.25


class CompileClock:
    """Seconds XLA spent compiling, process-wide (tracing and lowering are
    not counted; a persistent-cache hit compiles nothing)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration_secs, **_):
        if event == self.EVENT:
            self.seconds += duration_secs


def make_prompts(vocab: int, seed: int, lens=PROMPT_LENS, n=N_REQUESTS):
    """Seeded prompts, lengths drawn from `lens` with both ends present."""
    rng = np.random.RandomState(seed)
    sizes = rng.randint(lens[0], lens[1] + 1, size=n)
    sizes[0], sizes[-1] = lens
    return [rng.randint(1, vocab, size=int(s)).astype(np.int32) for s in sizes]


@contextlib.contextmanager
def record_dispatch():
    """Record what every registry resolution made while tracing picked:
    {dispatch key: backend}, for both op classes."""
    seen: dict[str, str] = {}
    select, select_attn = kernel_registry.select, kernel_registry.select_attn

    def rec_select(**kw):
        choice = select(**kw)
        target = targets.resolve(kw.get("target")).name
        key = kernel_registry.dispatch_key(kw["quant"], kw["phase"], kw["m"], target)
        seen[key] = choice.backend
        return choice

    def rec_select_attn(**kw):
        choice = select_attn(**kw)
        target = targets.resolve(kw.get("target")).name
        key = kernel_registry.attn_dispatch_key(
            kw["phase"], kw["s"], target, kw.get("kv", "bf16")
        )
        seen[key] = choice.backend
        return choice

    kernel_registry.select, kernel_registry.select_attn = rec_select, rec_select_attn
    try:
        yield seen
    finally:
        kernel_registry.select, kernel_registry.select_attn = select, select_attn


def serve(params, cfg, enc, prompts, clock, *, mesh_shape=(1,),
          max_new=MAX_NEW, max_seq=MAX_SEQ, check_steps=CHECK_STEPS):
    """One serving pass through Engine.  The engine's prefill and decode
    dispatches are wrapped to copy out the logits the checks need: every
    prefill row (padded tokens and last-position logits) and each
    request's decode logits at `check_steps`."""
    config = EngineConfig(
        slots=SLOTS, max_seq=max_seq, cache_mode="paged",
        block_size=BLOCK_SIZE, kv_quant="bf16", mesh_shape=mesh_shape,
    )
    eng = engine_lib.Engine(params, cfg, enc, config=config)
    prefill_rows: list[tuple[np.ndarray, np.ndarray]] = []
    decode_rows: dict[tuple[int, int], np.ndarray] = {}
    prefill_fn, decode_fn = eng.prefill_fn, eng.decode_fn

    def prefill_capture(params, tokens, caches, *rest):
        logits, caches = prefill_fn(params, tokens, caches, *rest)
        last = np.asarray(logits[:, -1], np.float32)
        prefill_rows.extend(zip(np.asarray(tokens), last))
        return logits, caches

    def decode_capture(params, caches, token, pos, *rest):
        out = decode_fn(params, caches, token, pos, *rest)
        posv = np.asarray(pos)
        for s, req in enumerate(eng.slot_req):
            step = None if req is None else int(posv[s]) - (len(req.prompt) - 1)
            if step in check_steps:
                decode_rows[(req.uid, step)] = np.asarray(out[1][s, -1], np.float32)
        return out

    eng.prefill_fn, eng.decode_fn = prefill_capture, decode_capture
    compile0 = clock.seconds
    t0 = time.perf_counter()
    for uid, prompt in enumerate(prompts):
        if not eng.submit(engine_lib.Request(uid=uid, prompt=prompt,
                                             max_new_tokens=max_new)):
            raise RuntimeError(f"request {uid} rejected")
    finished = eng.run()
    jax.block_until_ready(eng.caches)
    wall = time.perf_counter() - t0
    compile_s = clock.seconds - compile0
    tokens = {r.uid: list(r.generated) for r in finished}
    placed = {}
    if eng.mesh is not None:
        # The query projection is column-parallel: one N slice per shard.
        wq = eng.params["groups"][0]["attn"]["wq"]["w_packed"]
        placed = {
            "mesh_devices": [d.id for d in eng.mesh.devices.flat],
            "wq_shards": sorted(
                (s.device.id, s.data.shape) for s in wq.addressable_shards
            ),
            "wq_shape": wq.shape,
        }
    out = {
        "tokens": tokens,
        "status": {r.uid: r.status for r in finished},
        "prefill": prefill_rows,
        "decode": decode_rows,
        "degraded": [dict(d) for d in eng.degraded],
        "downgrades": list(eng.config.downgrades) + list(eng.enc_downgrades),
        "compile_s": compile_s,
        "wall_s": wall,
        "tok_s": sum(map(len, tokens.values())) / max(wall - compile_s, 1e-9),
        **placed,
    }
    del eng
    gc.collect()
    return out


def reference_params(cfg, seed):
    """The served model's seeded weights, unpacked and upcast to float32."""
    params = T.model_init(jax.random.PRNGKey(seed), cfg, EncodingConfig(enabled=False))
    return jax.tree.map(lambda x: x.astype(jnp.float32), params)


def reference_logits(params32, cfg, rows, *, batch=SLOTS):
    """Float32 logits of `rows` = [(tokens, positions)], one (len(positions),
    V) array per row, from plain T.forward (no encoding, no cache) at
    matmul precision "highest".  Rows are zero-padded to one shape, so the
    forward compiles once; causal attention keeps the pad out of every
    checked position."""
    cfg32 = dataclasses.replace(cfg, dtype="float32")
    enc = EncodingConfig(enabled=False)
    k = max(len(p) for _, p in rows)
    s = -(-max(len(t) for t, _ in rows) // 128) * 128

    @jax.jit
    def fwd(params, tokens, idx):
        logits, _, _ = T.forward(
            params, {"tokens": tokens}, cfg=cfg32, enc=enc,
            phase=Phase.PREFILL, logits_idx=idx,
        )
        return logits

    out = []
    for lo in range(0, len(rows), batch):
        chunk = rows[lo:lo + batch]
        toks = np.zeros((batch, s), np.int32)
        idx = np.zeros((batch, k), np.int32)
        for i, (t, p) in enumerate(chunk):
            toks[i, :len(t)] = t
            idx[i] = list(p) + [p[-1]] * (k - len(p))
        with jax.default_matmul_precision("highest"):
            logits = np.asarray(fwd(params32, jnp.asarray(toks), jnp.asarray(idx)))
        out.extend(logits[i, :len(p)] for i, (_, p) in enumerate(chunk))
    return out


def check_rows(got: list[np.ndarray], ref: list[np.ndarray]) -> tuple[float, float]:
    """(max relative RMS error, max |error| / reference RMS) over rows."""
    worst_rel = worst_max = 0.0
    for g, r in zip(got, ref):
        g, r = g.astype(np.float64), r.astype(np.float64)
        rms = np.sqrt(np.mean(r * r))
        worst_rel = max(worst_rel, np.sqrt(np.mean((g - r) ** 2)) / rms)
        worst_max = max(worst_max, np.max(np.abs(g - r)) / rms)
    return float(worst_rel), float(worst_max)


def reference_rows(result, prompts):
    """The rows a pass's logits are checked at, and the logits it served:
    each prefill row at its last position, and each request at its checked
    decode steps, teacher-forced on the tokens the pass generated."""
    rows, got = [], []
    for toks, logits in result["prefill"]:
        rows.append((toks, [len(toks) - 1]))
        got.append(logits[None])
    for uid, prompt in enumerate(prompts):
        steps = sorted(j for (u, j) in result["decode"] if u == uid)
        if not steps:
            continue
        seq = np.concatenate([prompt, np.asarray(result["tokens"][uid][:-1], np.int32)])
        rows.append((seq, [len(prompt) - 1 + j for j in steps]))
        got.append(np.stack([result["decode"][(uid, j)] for j in steps]))
    return rows, got


def pass_failures(name, result, n_requests, max_new, *, resolved=None):
    """Everything that makes a pass fail, other than its logit check."""
    bad = []
    if resolved is not None:
        bad += [f"{name}: {k} resolved to {b}"
                for k, b in sorted(resolved.items()) if b not in PALLAS_BACKENDS]
    bad += [f"{name}: degraded {d}" for d in result["degraded"]]
    bad += [f"{name}: downgraded {d}" for d in result["downgrades"]]
    if len(result["status"]) != n_requests:
        bad.append(f"{name}: {len(result['status'])}/{n_requests} requests finished")
    bad += [f"{name}: request {u} finished {s!r}"
            for u, s in sorted(result["status"].items()) if s != "ok"]
    bad += [f"{name}: request {u} generated {len(t)} of {max_new} tokens"
            for u, t in sorted(result["tokens"].items()) if len(t) != max_new]
    return bad


def print_pass(name, result, resolved):
    for key, backend in sorted(resolved.items()):
        print(f"[{name}] dispatch {key} -> {backend}")
    print(f"[{name}] degraded={result['degraded']} "
          f"downgrades={result['downgrades']}")
    print(f"[{name}] compile_s={result['compile_s']:.1f} "
          f"wall_s={result['wall_s']:.1f}")
    print(f"[{name}] smoke tok/s={result['tok_s']:.1f} (one-off wall clock "
          f"excluding compile; not a benchmark)")


def run_one_chip(cfg, clock, *, seed=SEED, interpret=False, prompts=None,
                 max_new=MAX_NEW, max_seq=MAX_SEQ):
    """Kernel pass and XLA pass, each against the float32 reference.
    Returns the list of failures."""
    prompts = make_prompts(cfg.vocab_size, seed) if prompts is None else prompts
    params = T.model_init(jax.random.PRNGKey(seed), cfg, EncodingConfig(enabled=True))
    passes = {
        "kernels": EncodingConfig(enabled=True, backend="auto",
                                  attn_backend="auto", interpret=interpret),
        "xla": EncodingConfig(enabled=True, backend="xla", attn_backend="xla"),
    }
    results, failures = {}, []
    for name, enc in passes.items():
        with record_dispatch() as resolved:
            results[name] = serve(params, cfg, enc, prompts, clock,
                                  max_new=max_new, max_seq=max_seq)
        print_pass(name, results[name], resolved)
        failures += pass_failures(
            name, results[name], len(prompts), max_new,
            resolved=resolved if name == "kernels" else None,
        )
    del params
    gc.collect()

    params32 = reference_params(cfg, seed)
    for name, result in results.items():
        rows, got = reference_rows(result, prompts)
        ref = reference_logits(params32, cfg, rows)
        n_pre = len(result["prefill"])
        for part, sl in (("prefill", slice(0, n_pre)), ("decode", slice(n_pre, None))):
            rel, mx = check_rows(got[sl], ref[sl])
            print(f"[{name}] {part} logits vs f32 reference: max rel err "
                  f"{rel:.5f} (tol {TOL_REL}), max abs err/rms {mx:.5f} "
                  f"(tol {TOL_MAX}), rows {sum(len(r) for r in got[sl])}")
            if not (rel <= TOL_REL and mx <= TOL_MAX):
                failures.append(f"{name}: {part} logits off the reference "
                                f"(rel {rel:.5f}, max {mx:.5f})")
    return failures


def run_four_chips(cfg, clock, *, seed=SEED, prompts=None,
                   max_new=MAX_NEW, max_seq=MAX_SEQ):
    """mesh_shape=(4,) against mesh_shape=(1,) on device 0, XLA backends.
    Returns the list of failures."""
    prompts = make_prompts(cfg.vocab_size, seed) if prompts is None else prompts
    params = T.model_init(jax.random.PRNGKey(seed), cfg, EncodingConfig(enabled=True))
    enc = EncodingConfig(enabled=True, backend="xla", attn_backend="xla")
    results, failures = {}, []
    for shards in (1, 4):
        name = f"mesh{shards}"
        with record_dispatch() as resolved:
            results[name] = serve(params, cfg, enc, prompts, clock,
                                  mesh_shape=(shards,), max_new=max_new,
                                  max_seq=max_seq)
        print_pass(name, results[name], resolved)
        failures += pass_failures(name, results[name], len(prompts), max_new)
    one, four = results["mesh1"], results["mesh4"]
    shards = four["wq_shards"]
    print(f"[mesh4] mesh devices {four['mesh_devices']}; wq {four['wq_shape']} "
          f"shards (device, shape): {shards}")
    if (len(set(four["mesh_devices"])) != 4
            or len({d for d, _ in shards}) != 4
            or any(shape == four["wq_shape"] for _, shape in shards)):
        failures.append("mesh4: wq is not split across four distinct devices")
    if cfg.num_kv_heads % 4:
        print(f"[mesh4] note: {cfg.num_kv_heads} KV heads do not divide 4 "
              f"shards, so the KV cache replicates on every shard")

    # Prefill rows see identical inputs; a decode step is compared only while
    # both meshes have generated the same history (a bf16 near-tie may pick
    # a different token, after which the contexts differ).
    got, ref = [r for _, r in four["prefill"]], [r for _, r in one["prefill"]]
    n_pre = len(got)
    for (uid, step), row in sorted(four["decode"].items()):
        if four["tokens"][uid][:step] == one["tokens"][uid][:step]:
            got.append(row)
            ref.append(one["decode"][(uid, step)])
    identical = sum(four["tokens"][u] == one["tokens"][u] for u in one["tokens"])
    for part, sl in (("prefill", slice(0, n_pre)), ("decode", slice(n_pre, None))):
        rel, mx = check_rows(got[sl], ref[sl])
        print(f"[mesh4] {part} logits vs mesh1: max rel err {rel:.5f} "
              f"(tol {TOL_REL}), max abs err/rms {mx:.5f} (tol {TOL_MAX}), "
              f"rows {len(got[sl])}")
        if not (rel <= TOL_REL and mx <= TOL_MAX):
            failures.append(f"mesh4: {part} logits off mesh1 "
                            f"(rel {rel:.5f}, max {mx:.5f})")
    print(f"[mesh4] token-identical requests {identical}/{len(prompts)}; "
          f"decode rows compared {len(got) - n_pre}/{len(four['decode'])}")
    return failures


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: compare mesh_shape=(4,) with (1,) and nothing else")
    args = ap.parse_args()

    compile_cache.enable()
    devices = jax.devices()
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    print(f"[smoke] devices: {device}")
    if device["platform"] != "tpu":
        print(f"[smoke] FAIL: platform {device['platform']!r} is not a TPU",
              file=sys.stderr)
        return 1
    if device["count"] < args.chips:
        print(f"[smoke] FAIL: {args.chips} chips asked, {device['count']} found",
              file=sys.stderr)
        return 1
    print(f"[smoke] target {targets.current().name}, "
          f"interpret={targets.resolve_interpret(None)}")
    cfg = registry.get_config(ARCH)
    print(f"[smoke] {cfg.name}: layers={cfg.num_layers} d_model={cfg.d_model} "
          f"heads={cfg.num_heads}/{cfg.num_kv_heads} head_dim={cfg.head_dim} "
          f"d_ff={cfg.d_ff} vocab={cfg.vocab_size}")
    clock = CompileClock()
    run = run_four_chips if args.chips == 4 else run_one_chip
    failures = run(cfg, clock)
    for f in failures:
        print(f"[smoke] FAIL: {f}", file=sys.stderr)
    if failures:
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
