"""chip_smoke.py's phases at the reduced size on the CPU, Pallas kernels in
interpret mode: the control flow of the chip gate (both serving passes,
dispatch recording, logit capture, the float32 reference and its checks)
runs on every change, at no chip time."""

import importlib.util
import os
import subprocess
import sys
import textwrap

import numpy as np

from repro.configs import registry

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_chip_smoke_one_chip_phases_reduced(capsys):
    smoke = _chip_smoke()
    # bf16 like the published config, so the logit tolerances are exercised
    # against a real bf16-vs-f32 gap rather than an exact match.
    cfg = registry.get_reduced("qwen2-1.5b", dtype="bfloat16")
    prompts = smoke.make_prompts(cfg.vocab_size, 0, lens=(8, 40))
    failures = smoke.run_one_chip(
        cfg, smoke.CompileClock(), interpret=True, prompts=prompts,
        max_new=3, max_seq=64,
    )
    out = capsys.readouterr().out
    assert failures == [], failures
    # Both op classes resolved through the registry to Pallas kernels in
    # the kernel pass, and the XLA pass stayed on XLA.
    kernel_lines = [l for l in out.splitlines() if l.startswith("[kernels] dispatch")]
    assert any("attn|prefill" in l for l in kernel_lines), out
    assert any("attn|decode" in l for l in kernel_lines), out
    assert any("none|decode" in l for l in kernel_lines), out
    assert all(l.endswith(("-> pallas", "-> fused")) for l in kernel_lines), out
    xla_lines = [l for l in out.splitlines() if l.startswith("[xla] dispatch")]
    assert xla_lines and all(l.endswith("-> xla") for l in xla_lines), out
    # Prefill and decode logits were both checked in both passes.
    for name in ("kernels", "xla"):
        for part in ("prefill", "decode"):
            assert f"[{name}] {part} logits vs f32 reference" in out, out


def test_chip_smoke_flags_non_pallas_and_bad_logits():
    smoke = _chip_smoke()
    result = {
        "degraded": [], "downgrades": [], "status": {0: "ok", 1: "error"},
        "tokens": {0: [1, 2], 1: [3]},
    }
    bad = smoke.pass_failures(
        "kernels", result, 2, 2, resolved={"k1": "fused", "k2": "xla"}
    )
    assert bad == [
        "kernels: k2 resolved to xla",
        "kernels: request 1 finished 'error'",
        "kernels: request 1 generated 1 of 2 tokens",
    ]
    rng = np.random.RandomState(0)
    ref = rng.randn(4, 151936).astype(np.float32)
    # One wrong 128-logit tile: small in RMS, caught by the max bound.
    got = ref.copy()
    got[1, :128] += 1.0
    rel, mx = smoke.check_rows(list(got), list(ref))
    assert rel < smoke.TOL_REL and mx > smoke.TOL_MAX


def test_chip_smoke_four_chip_phase_on_emulated_devices():
    """--chips 4's comparison on four emulated CPU devices (the device count
    must be set before JAX starts, hence the child process)."""
    script = textwrap.dedent(f"""
        import os, sys
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "chip_smoke", {os.path.join(ROOT, "chip_smoke.py")!r})
        smoke = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(smoke)
        from repro.configs import registry
        # Query heads wide enough for wq's packed N tiles to split 4 ways.
        cfg = registry.get_reduced("qwen2-1.5b", d_model=256, num_heads=8,
                                   num_kv_heads=2, head_dim=64, d_ff=512,
                                   dtype="bfloat16")
        prompts = smoke.make_prompts(cfg.vocab_size, 0, lens=(8, 40))
        failures = smoke.run_four_chips(
            cfg, smoke.CompileClock(), prompts=prompts, max_new=3, max_seq=64)
        print("FAILURES", failures)
        sys.exit(1 if failures else 0)
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(ROOT, "src")]
        + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else [])
    )
    r = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    assert "KV cache replicates on every shard" in r.stdout, r.stdout
    assert "[mesh4] prefill logits vs mesh1" in r.stdout, r.stdout
