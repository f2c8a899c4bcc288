"""Fault tolerance: atomic checkpointing, bitwise restart, corruption
detection, async overlap, reshard-on-restore, elastic planning."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import checkpoint as ckpt_lib
from repro.configs import registry
from repro.core.packed import EncodingConfig
from repro.data import pipeline as data_lib
from repro.models import transformer as T
from repro.runtime import elastic, watchdog as wd_lib
from repro.train import optimizer as opt_lib
from repro.train import trainer as trainer_lib

ENC = EncodingConfig(enabled=True, backend="xla")


def _tiny_state(seed=0):
    cfg = registry.get_reduced("qwen2-1.5b")
    params = T.model_init(jax.random.PRNGKey(seed), cfg, ENC)
    return cfg, {"params": params, "opt": opt_lib.init(params)}


def test_save_restore_bitwise(tmp_path):
    cfg, state = _tiny_state()
    ckpt_lib.save(str(tmp_path), state, step=7)
    assert ckpt_lib.latest_step(str(tmp_path)) == 7
    restored = ckpt_lib.restore(str(tmp_path), 7, state)
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_restart_resumes_identically(tmp_path):
    """Kill-and-restart: training continued from a checkpoint is bitwise
    identical to uninterrupted training (deterministic data keyed by step)."""
    cfg, state = _tiny_state()
    opt_cfg = opt_lib.OptimizerConfig(peak_lr=1e-3, warmup_steps=2, decay_steps=50)
    data = data_lib.SyntheticPacked(
        data_lib.DataConfig(cfg.vocab_size, seq_len=16, global_batch=4)
    )
    step = jax.jit(trainer_lib.make_train_step(cfg, ENC, opt_cfg))

    # Continuous run: 6 steps.
    p, o = state["params"], state["opt"]
    for i in range(6):
        p, o, _, _ = step(p, o, jax.tree.map(jnp.asarray, data.batch(i)))

    # Interrupted run: 3 steps, checkpoint, "crash", restore, 3 more.
    p2, o2 = state["params"], state["opt"]
    for i in range(3):
        p2, o2, _, _ = step(p2, o2, jax.tree.map(jnp.asarray, data.batch(i)))
    ckpt_lib.save(str(tmp_path), {"params": p2, "opt": o2}, step=3)
    del p2, o2  # crash
    rs = ckpt_lib.restore(str(tmp_path), 3, state)
    p3, o3 = rs["params"], rs["opt"]
    for i in range(3, 6):
        p3, o3, _, _ = step(p3, o3, jax.tree.map(jnp.asarray, data.batch(i)))

    for a, b in zip(jax.tree.leaves(p), jax.tree.leaves(p3)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_corruption_detected(tmp_path):
    cfg, state = _tiny_state()
    path = ckpt_lib.save(str(tmp_path), state, step=1)
    victim = os.path.join(path, "leaf_00003.npy")
    with open(victim, "r+b") as f:
        f.seek(128)
        f.write(b"\xde\xad\xbe\xef")
    with pytest.raises(IOError, match="checksum"):
        ckpt_lib.restore(str(tmp_path), 1, state)


def test_atomicity_no_partial_checkpoint(tmp_path):
    """A .tmp dir (simulated crash mid-save) is never listed as a step."""
    cfg, state = _tiny_state()
    ckpt_lib.save(str(tmp_path), state, step=1)
    os.makedirs(os.path.join(str(tmp_path), "step_00000002.tmp"))
    assert ckpt_lib.latest_step(str(tmp_path)) == 1


def test_async_checkpointer(tmp_path):
    cfg, state = _tiny_state()
    saver = ckpt_lib.AsyncCheckpointer(str(tmp_path))
    saver.save(state, 5)
    saver.wait()
    assert ckpt_lib.latest_step(str(tmp_path)) == 5
    restored = ckpt_lib.restore(str(tmp_path), 5, state)
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_reshard_restore(tmp_path):
    """Restore with explicit shardings (single-device mesh here; the path is
    the same one the 512->256 elastic reshard takes)."""
    from repro.parallel import sharding

    cfg, state = _tiny_state()
    ckpt_lib.save(str(tmp_path), state, step=2)
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    sh = {
        "params": sharding.params_shardings(state["params"], mesh),
        "opt": {
            "mu": sharding.params_shardings(state["opt"]["mu"], mesh),
            "nu": sharding.params_shardings(state["opt"]["nu"], mesh),
            "step": sharding.replicated(mesh),
        },
    }
    restored = ckpt_lib.restore(str(tmp_path), 2, state, shardings=sh)
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---- runtime: watchdog + elastic -------------------------------------------


def test_watchdog_flags_straggler():
    t = {"now": 0.0}
    wd = wd_lib.StepWatchdog(clock=lambda: t["now"])
    for i in range(10):
        wd.step_start()
        t["now"] += 1.0
        host_times = {0: 1.0, 1: 1.0, 2: 5.0 if i >= 6 else 1.0}
        wd.step_end(host_times=host_times)
    assert 2 in wd.evicted
    assert wd.should_remesh()
    assert 0 not in wd.evicted and 1 not in wd.evicted


def test_watchdog_tolerates_transient():
    t = {"now": 0.0}
    wd = wd_lib.StepWatchdog(clock=lambda: t["now"])
    for i in range(10):
        wd.step_start()
        t["now"] += 1.0
        host_times = {0: 1.0, 1: 4.0 if i == 6 else 1.0}  # one-off blip
        wd.step_end(host_times=host_times)
    assert not wd.evicted


def test_data_reassignment():
    r = wd_lib.DataReassigner(4)
    r.evict(2)
    shards = sum((r.shards_for(h) for h in range(4)), [])
    assert sorted(shards) == [0, 1, 2, 3]
    assert r.shards_for(2) == []


def test_elastic_plan():
    p = elastic.plan(512)
    assert p.data * p.model == 512 and p.model == 16
    p = elastic.plan(240, prefer_model_parallel=16)  # 16 doesn't divide 240
    assert p.data * p.model == 240
    p = elastic.plan(7)
    assert p.data * p.model == 7


def test_elastic_resume(tmp_path):
    cfg, state = _tiny_state()
    ckpt_lib.save(str(tmp_path), state, step=9)
    mesh = elastic.plan(1).make_mesh()
    restored, step = elastic.resume(str(tmp_path), state, mesh)
    assert step == 9
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
