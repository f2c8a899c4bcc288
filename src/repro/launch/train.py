"""End-to-end training driver.

CPU-scale by default (reduced config): trains a ~small model for N steps with
checkpointing, restart recovery, straggler watchdog, and optional gradient
compression — the same code path the production mesh would run under pjit.

  PYTHONPATH=src python -m repro.launch.train --arch qwen2-1.5b --steps 50
  PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b --steps 300 \
      --d-model 256 --layers 8   # ~100M-class run (examples/train_100m.py)
"""

from __future__ import annotations

import argparse
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import checkpoint as ckpt_lib
from repro.configs import registry
from repro.core.packed import EncodingConfig
from repro.data import pipeline as data_lib
from repro.launch import compile_cache
from repro.models import transformer as T
from repro.parallel import compression
from repro.runtime import watchdog as wd_lib
from repro.train import optimizer as opt_lib
from repro.train import trainer as trainer_lib


def build(args):
    cfg = registry.get_reduced(args.arch) if args.reduced else registry.get_config(args.arch)
    over = {}
    if args.d_model:
        over.update(
            d_model=args.d_model,
            num_heads=max(4, args.d_model // 64),
            num_kv_heads=max(1, args.d_model // 128),
            head_dim=64,
            d_ff=args.d_ff or 4 * args.d_model,
            rnn_width=args.d_model if cfg.rnn_width else 0,
        )
    if args.layers:
        over["num_layers"] = args.layers
    if args.vocab:
        over["vocab_size"] = args.vocab
    if over:
        cfg = dataclasses.replace(cfg, **over)
    # interpret=None: Pallas kernels compile for an attached TPU and run in
    # the interpreter only where there is none (core/targets.py).
    enc = EncodingConfig(enabled=not args.no_encoding, backend=args.backend)
    return cfg, enc


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--d-model", type=int, default=0)
    ap.add_argument("--d-ff", type=int, default=0)
    ap.add_argument("--layers", type=int, default=0)
    ap.add_argument("--vocab", type=int, default=0)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--backend", default="xla", choices=["xla", "pallas", "fused", "reference"])
    ap.add_argument("--no-encoding", action="store_true")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    compile_cache.enable()
    cfg, enc = build(args)
    print(f"[train] arch={cfg.name} layers={cfg.num_layers} d={cfg.d_model} "
          f"params~{cfg.param_count()/1e6:.1f}M backend={args.backend} "
          f"encoding={'on' if enc.enabled else 'off'}")

    opt_cfg = opt_lib.OptimizerConfig(
        peak_lr=args.lr, warmup_steps=max(5, args.steps // 20), decay_steps=args.steps
    )
    params = T.model_init(jax.random.PRNGKey(args.seed), cfg, enc)
    opt_state = opt_lib.init(params)
    comp_state = compression.init_state(params) if args.compress_grads else None

    start = 0
    if args.ckpt_dir:
        latest = ckpt_lib.latest_step(args.ckpt_dir)
        if latest is not None:
            state = ckpt_lib.restore(
                args.ckpt_dir, latest, {"params": params, "opt": opt_state}
            )
            params, opt_state = state["params"], state["opt"]
            start = latest
            print(f"[train] resumed from step {start}")

    data = data_lib.SyntheticPacked(
        data_lib.DataConfig(cfg.vocab_size, args.seq, args.batch, seed=args.seed)
    )
    step_fn = jax.jit(
        trainer_lib.make_train_step(
            cfg, enc, opt_cfg,
            microbatches=args.microbatches,
            compress_grads=args.compress_grads,
        )
    )
    saver = ckpt_lib.AsyncCheckpointer(args.ckpt_dir) if args.ckpt_dir else None
    watchdog = wd_lib.StepWatchdog()

    losses = []
    for step in range(start, args.steps):
        batch = jax.tree.map(jnp.asarray, data.batch(step))
        watchdog.step_start()
        params, opt_state, metrics, comp_state = step_fn(
            params, opt_state, batch, comp_state
        )
        loss = float(metrics["loss"])
        watchdog.step_end()
        losses.append(loss)
        if step % args.log_every == 0 or step == args.steps - 1:
            print(f"[train] step={step} loss={loss:.4f} "
                  f"gnorm={float(metrics['grad_norm']):.3f} "
                  f"ewma_s={watchdog.ewma:.3f}" if watchdog.ewma else "")
        if saver and (step + 1) % args.ckpt_every == 0:
            saver.save({"params": params, "opt": opt_state}, step + 1)
    if saver:
        saver.save({"params": params, "opt": opt_state}, args.steps)
        saver.wait()
    print(f"[train] done. first-10 mean={np.mean(losses[:10]):.4f} "
          f"last-10 mean={np.mean(losses[-10:]):.4f}")
    return losses


if __name__ == "__main__":
    main()
