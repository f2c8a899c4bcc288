"""Sharding rules: sanitization, spec assignment, and a real multi-device
SPMD integration run (8 fake CPU devices in a subprocess)."""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import registry
from repro.core.packed import EncodingConfig
from repro.models import transformer as T
from repro.parallel import sharding


def _mesh11():
    return jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)


def test_sanitize_drops_nondividing_axes():
    mesh = _mesh11()
    # 1x1 mesh divides everything; use spec structure checks instead.
    s = sharding.sanitize(P("data", "model"), (4, 4), mesh)
    assert s == P("data", "model")


def test_param_specs_classification():
    mesh = _mesh11()
    cfg = registry.get_reduced("qwen2-1.5b")
    enc = EncodingConfig(enabled=True, backend="xla")
    params = jax.eval_shape(lambda k: T.model_init(k, cfg, enc), jax.random.PRNGKey(0))
    sh = sharding.params_shardings(params, mesh)
    flat = jax.tree_util.tree_flatten_with_path(sh)[0]
    by_name = {}
    for path, s in flat:
        by_name[jax.tree_util.keystr(path)] = s
    # Column-parallel: wq N1 on model; row-parallel: wo K1 on model.
    wq = next(v.spec for k, v in by_name.items() if "wq" in k and "w_packed" in k)
    wo = next(v.spec for k, v in by_name.items() if "'wo'" in k and "w_packed" in k)
    assert "model" in str(wq[1]) and "model" in str(wo[2]), (wq, wo)
    # Norm scales replicated.
    norm = next(v.spec for k, v in by_name.items() if "final_norm" in k)
    assert all(x is None for x in norm)


def test_moe_expert_specs():
    mesh = _mesh11()
    cfg = registry.get_reduced("mixtral-8x22b")
    enc = EncodingConfig(enabled=True, backend="xla")
    params = jax.eval_shape(lambda k: T.model_init(k, cfg, enc), jax.random.PRNGKey(0))
    sh = sharding.params_shardings(params, mesh)
    flat = jax.tree_util.tree_flatten_with_path(sh)[0]
    for path, s in flat:
        key = jax.tree_util.keystr(path)
        if "moe" in key and "w_gate" in key:
            # (G, E, N1, K1, N0, K0): N1 -> model (TP within expert).
            assert "model" in str(s.spec[2])
            break
    else:
        pytest.fail("no MoE expert weight found")


_SPMD_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import registry
    from repro.core.packed import EncodingConfig
    from repro.models import transformer as T
    from repro.parallel import sharding
    from repro.train import optimizer as opt_lib, trainer as trainer_lib
    from repro.data import pipeline as data_lib

    mesh = jax.make_mesh((4, 2), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    cfg = registry.get_reduced("qwen2-1.5b")
    enc = EncodingConfig(enabled=True, backend="xla", shard_multiple=2)
    with jax.set_mesh(mesh):
        params = T.model_init(jax.random.PRNGKey(0), cfg, enc)
        p_sh = sharding.params_shardings(params, mesh)
        params = jax.device_put(params, p_sh)
        opt_state = opt_lib.init(params)
        opt_cfg = opt_lib.OptimizerConfig(peak_lr=1e-3, warmup_steps=2, decay_steps=20)
        data = data_lib.SyntheticPacked(
            data_lib.DataConfig(cfg.vocab_size, seq_len=16, global_batch=8))
        step = jax.jit(trainer_lib.make_train_step(cfg, enc, opt_cfg))
        losses = []
        for i in range(4):
            batch = jax.device_put(
                data.batch(i), sharding.batch_shardings(
                    jax.tree.map(jnp.asarray, data.batch(i)), mesh))
            params, opt_state, m, _ = step(params, opt_state, batch)
            losses.append(float(m["loss"]))
        # params stayed sharded (not replicated):
        wq = params["groups"][0]["attn"]["wq"]["w_packed"]
        assert not wq.sharding.is_fully_replicated, wq.sharding
        assert all(np.isfinite(l) for l in losses), losses
        print("SPMD_OK", losses[0], losses[-1])
""")


def test_spmd_multidevice_train_subprocess():
    """Real 8-device SPMD training steps (4x2 mesh) in a subprocess."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src")
    )
    r = subprocess.run(
        [sys.executable, "-c", _SPMD_SCRIPT], env=env,
        capture_output=True, text=True, timeout=900,
    )
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-4000:]
    assert "SPMD_OK" in r.stdout


_DECODE_SPMD_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp
    from repro.configs import registry
    from repro.core.packed import EncodingConfig
    from repro.core.encoding import Phase
    from repro.models import transformer as T
    from repro.parallel import sharding

    mesh = jax.make_mesh((4, 2), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    cfg = registry.get_reduced("mixtral-8x22b", capacity_factor=8.0)
    enc = EncodingConfig(enabled=True, backend="xla", shard_multiple=2)
    with jax.set_mesh(mesh):
        params = jax.device_put(
            T.model_init(jax.random.PRNGKey(0), cfg, enc),
            sharding.params_shardings(
                jax.eval_shape(lambda k: T.model_init(k, cfg, enc), jax.random.PRNGKey(0)),
                mesh))
        caches = jax.device_put(
            T.cache_init(cfg, 4, 32),
            sharding.cache_shardings(jax.eval_shape(lambda: T.cache_init(cfg, 4, 32)), mesh))
        toks = jnp.ones((4, 8), jnp.int32)
        logits, caches, _ = jax.jit(
            lambda p, t, c: T.forward(p, {"tokens": t}, cfg=cfg, enc=enc,
                                      phase=Phase.PREFILL, caches=c)
        )(params, toks, caches)
        tok = jnp.ones((4, 1), jnp.int32)
        logits2, caches, _ = jax.jit(
            lambda p, t, c: T.forward(p, {"tokens": t}, cfg=cfg, enc=enc,
                                      phase=Phase.DECODE, caches=c, pos=8)
        )(params, tok, caches)
        assert bool(jnp.isfinite(logits2).all())
        print("DECODE_SPMD_OK")
""")


def test_spmd_decode_subprocess():
    """Sharded MoE prefill+decode on 8 devices."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src")
    )
    r = subprocess.run(
        [sys.executable, "-c", _DECODE_SPMD_SCRIPT], env=env,
        capture_output=True, text=True, timeout=900,
    )
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-4000:]
    assert "DECODE_SPMD_OK" in r.stdout
