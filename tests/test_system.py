"""End-to-end behaviour tests: the shipped drivers run and do what they say."""

import sys

import numpy as np


def test_train_driver_end_to_end(monkeypatch, tmp_path):
    from repro.launch import train as train_main

    argv = [
        "train", "--arch", "qwen2-1.5b", "--steps", "8", "--batch", "4",
        "--seq", "32", "--lr", "3e-3", "--ckpt-dir", str(tmp_path),
        "--ckpt-every", "4", "--log-every", "4",
    ]
    monkeypatch.setattr(sys, "argv", argv)
    losses = train_main.main()
    assert len(losses) == 8
    assert all(np.isfinite(l) for l in losses)
    # checkpoints written
    from repro.checkpoint import checkpoint as ckpt_lib
    assert ckpt_lib.latest_step(str(tmp_path)) == 8

    # resume pass: picks up from step 8 and runs to 10
    argv2 = argv[:]
    argv2[argv2.index("--steps") + 1] = "10"
    monkeypatch.setattr(sys, "argv", argv2)
    losses2 = train_main.main()
    assert len(losses2) == 2


def test_serve_driver_end_to_end(monkeypatch):
    from repro.launch import serve as serve_main

    monkeypatch.setattr(sys, "argv", [
        "serve", "--arch", "llama3.2-1b", "--reduced", "--requests", "4",
        "--slots", "2", "--max-new", "4", "--prompt-len", "8",
    ])
    done = serve_main.main()
    assert len(done) == 4
    assert all(len(r.generated) == 4 for r in done)


def test_compile_cache_placed_from_env_or_at_checkout_root(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR, when set, is JAX's to read: the entry
    points set no directory of their own.  Unset, the cache sits at a fixed
    <checkout>/.jax_cache."""
    import os

    import jax

    from repro.launch import compile_cache

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    before = jax.config.jax_compilation_cache_dir
    try:
        jax.config.update("jax_compilation_cache_dir", None)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "placed-by-caller")
        assert compile_cache.enable() == "placed-by-caller"
        assert jax.config.jax_compilation_cache_dir is None
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        expected = os.path.join(root, ".jax_cache")
        assert compile_cache.enable() == expected
        assert jax.config.jax_compilation_cache_dir == expected
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_dryrun_registry_covers_40_cells():
    from repro.configs import registry

    cells = registry.all_cells()
    assert len(cells) == 40
    assert sum(1 for _, _, ok, _ in cells if ok) == 33
