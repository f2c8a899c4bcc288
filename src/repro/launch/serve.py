"""End-to-end serving driver: continuous-batching engine.

Serves the architecture at its published widths; `--reduced` serves the
tiny CPU smoke variant of the same family (configs/base.reduced):

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b --requests 8
  PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b --reduced

Token-budget continuous batching (one mixed chunked-prefill + decode
dispatch per step, serving/engine.py) with streamed output:

  PYTHONPATH=src python -m repro.launch.serve --token-budget 64 \
      --slo-class interactive --stream
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import registry
from repro.core.packed import EncodingConfig
from repro.launch import compile_cache
from repro.models import transformer as T
from repro.serving import engine as engine_lib
from repro.serving.config import EngineConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--reduced", action="store_true",
                    help="serve the tiny smoke-test variant of --arch")
    ap.add_argument("--backend", default="xla", choices=["xla", "pallas", "fused", "reference"])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--cache-mode", default="paged", choices=["paged", "dense"])
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--pool-pages", type=int, default=None,
                    help="paged pool size; small values force preemption")
    ap.add_argument("--kv-quant", default="bf16",
                    choices=["bf16", "kv8", "kv4"],
                    help="KV-cache storage layout: raw bf16, int8 + "
                         "per-page scales (kv8), or packed int4 (kv4; "
                         "downgrades to kv8 under an xla attention "
                         "fallback)")
    ap.add_argument("--prefix-cache", dest="prefix_cache",
                    action=argparse.BooleanOptionalAction, default=True,
                    help="radix-tree prefix cache: park finished requests' "
                         "full KV blocks for cross-request longest-common-"
                         "prefix reuse (--no-prefix-cache disables; paged "
                         "cache only)")
    ap.add_argument("--tenant-quota", dest="tenant_quota", type=int,
                    default=None,
                    help="per-tenant page quota (pages): cap any one "
                         "tenant's worst-case page reservation so it "
                         "cannot starve the pool")
    ap.add_argument("--tenants", type=int, default=1,
                    help="number of synthetic tenants; requests are "
                         "assigned round-robin (tenant-0, tenant-1, ...)")
    ap.add_argument("--token-budget", type=int, default=None,
                    help="per-step token budget: run the unified mixed "
                         "chunked-prefill + decode scheduler instead of the "
                         "phase-split engine")
    ap.add_argument("--slo-class", default="standard",
                    choices=sorted(engine_lib.SLO_CLASSES),
                    help="SLO class stamped on every submitted request "
                         "(admission priority under --token-budget)")
    ap.add_argument("--mesh-shape", default="1",
                    help='serving mesh shape: "2" = 2-way tensor parallel, '
                         '"2x4" = 2 data replicas x 4-way TP; the device '
                         "count must cover the product "
                         "(launch/mesh.build_serving_mesh)")
    ap.add_argument("--stream", action="store_true",
                    help="print tokens as they are committed (stream_cb)")
    args = ap.parse_args()

    compile_cache.enable()
    config = EngineConfig.from_args(args)
    cfg = (registry.get_reduced if args.reduced else registry.get_config)(
        args.arch
    )
    # interpret=None: Pallas kernels compile for an attached TPU and run in
    # the interpreter only where there is none (core/targets.py).
    enc = EncodingConfig(enabled=True, backend=args.backend)
    params = T.model_init(jax.random.PRNGKey(args.seed), cfg, enc)

    def stream_cb(req, tok):
        print(f"  [stream] req {req.uid} += {tok} "
              f"({len(req.generated)}/{req.max_new_tokens})")

    eng = engine_lib.Engine(
        params, cfg, enc, config=config,
        stream_cb=stream_cb if args.stream else None,
    )
    if eng.config.downgrades or eng.enc_downgrades:
        print(f"[serve] config downgrades: "
              f"{list(eng.config.downgrades) + list(eng.enc_downgrades)}")
    if eng.tp_shards > 1:
        print(f"[serve] tensor parallel: {eng.tp_shards} shards "
              f"(mesh {'x'.join(map(str, eng.config.mesh_shape))})")

    rng = np.random.RandomState(args.seed)
    t0 = time.time()
    for i in range(args.requests):
        plen = rng.randint(args.prompt_len // 2, args.prompt_len + 1)
        prompt = rng.randint(1, cfg.vocab_size, size=plen).astype(np.int32)
        eng.submit(engine_lib.Request(
            uid=i, prompt=prompt, max_new_tokens=args.max_new,
            slo_class=args.slo_class,
            tenant=f"tenant-{i % max(1, args.tenants)}",
        ))
    done = eng.run()
    dt = time.time() - t0
    total_new = sum(len(r.generated) for r in done)
    print(f"[serve] {len(done)} requests, {total_new} tokens in {dt:.2f}s "
          f"({total_new/dt:.2f} tok/s decode throughput incl. prefill)")
    # stats_view(): shape-stable schema — attn_backend/degraded are always
    # {shard -> value} dicts here, whatever the mesh degree.
    stats = eng.stats_view()
    backends = stats["attn_backend"]
    degraded = stats["degraded"]
    print(f"[serve] kv_quant={stats['kv_quant']} attn_backend="
          + ",".join(f"{k}:{v}" for k, v in sorted(backends.items()))
          + f" degraded={sum(len(v) for v in degraded.values())}")
    if stats["cache_mode"] == "paged":
        print(f"[serve] paged: peak_active={stats['peak_active']} "
              f"pages={stats['pages_total']} peak_in_use={stats['peak_in_use']} "
              f"shared_hits={stats['shared_hits']} preemptions={stats['preemptions']}")
        pc = stats["prefix_cache"]
        line = (f"[serve] prefix_cache: enabled={pc['enabled']} "
                f"hit_rate={pc['hit_rate']:.3f} hit_tokens={pc['hit_tokens']} "
                f"cached_pages={pc['cached_pages']} evictions={pc['evictions']} "
                f"deferred_hits={pc['deferred_hits']}")
        if pc.get("tenant_quota") is not None:
            usage = pc.get("tenant_usage", {})
            line += (f" tenant_quota={pc['tenant_quota']} tenants="
                     + ",".join(f"{t}:{u:.1f}"
                                for t, u in sorted(usage.items())))
        print(line)
    if "continuous" in stats:
        c = stats["continuous"]
        print(f"[serve] continuous: budget={c['token_budget']} "
              f"mixed_steps={c['mixed_steps']} decode_stalls={c['decode_stall_steps']} "
              f"prefill_tok={c['prefill_tokens']} decode_tok={c['decode_tokens']}")
    for r in done[: min(4, len(done))]:
        print(f"  req {r.uid}: prompt[:4]={r.prompt[:4].tolist()} -> gen[:8]={r.generated[:8]}")
    return done


if __name__ == "__main__":
    main()
