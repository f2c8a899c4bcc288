"""EngineConfig: construction-time validation, cross-field resolve()
downgrades, argparse routing, and the Engine deprecation shim."""

import argparse
import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import registry
from repro.core.packed import EncodingConfig
from repro.models import transformer as T
from repro.serving import engine as engine_lib
from repro.serving.config import EngineConfig, decode_path

ENC = EncodingConfig(enabled=True, backend="xla")


# ---- validation ------------------------------------------------------------

@pytest.mark.parametrize("kwargs,match", [
    (dict(cache_mode="ring"), "cache_mode"),
    (dict(sample="nucleus"), "sample"),
    (dict(slots=0), "slots"),
    (dict(max_seq=0), "max_seq"),
    (dict(block_size=12), "block_size"),
    (dict(block_size=0), "block_size"),
    (dict(pool_pages=1), "pool_pages"),
    (dict(draft_k=-1), "draft_k"),
    (dict(token_budget=0), "token_budget"),
    (dict(slo_aging_steps=0), "slo_aging_steps"),
    (dict(max_queue=-1), "max_queue"),
    (dict(tenant_quota=0), "tenant_quota"),
    (dict(tenant_quota=-3), "tenant_quota"),
    (dict(mesh_shape=()), "mesh_shape"),
    (dict(mesh_shape=(0,)), "mesh_shape"),
    (dict(mesh_shape=(2, -1)), "mesh_shape"),
    (dict(mesh_shape=(1, 1, 1, 1)), "mesh_shape"),
    (dict(mesh_shape=(2,), tp_axis="tensor"), "tp_axis"),
])
def test_validation_rejects(kwargs, match):
    with pytest.raises(ValueError, match=match):
        EngineConfig(**kwargs)


def test_defaults_are_valid_and_frozen():
    c = EngineConfig()
    assert c.cache_mode == "paged" and c.tp_shards == 1
    with pytest.raises(dataclasses.FrozenInstanceError):
        c.slots = 8


def test_mesh_shape_list_frozen_to_tuple_and_hashable():
    c = EngineConfig(mesh_shape=[2, 4])
    assert c.mesh_shape == (2, 4)
    assert c.tp_shards == 4 and c.mesh_devices == 8
    hash(c)  # frozen + tuple fields => usable as a cache key


def test_tp_axis_name_irrelevant_without_tp():
    # tp_axis is only constrained when it would actually shard something.
    assert EngineConfig(mesh_shape=(1,), tp_axis="anything").tp_shards == 1


# ---- resolve(): cross-field auto-downgrades --------------------------------

def test_resolve_attn_only_is_identity():
    cfg = registry.get_reduced("qwen2-1.5b")
    c = EngineConfig(spec_decode=True, token_budget=32)
    r = c.resolve(cfg)
    assert r is c and r.downgrades == ()


def test_resolve_recurrent_family_downgrades():
    cfg = registry.get_reduced("rwkv6-1.6b")
    r = EngineConfig(spec_decode=True, token_budget=32).resolve(cfg)
    assert decode_path(cfg) == ("grouped", False)
    assert r.cache_mode == "dense"
    assert r.spec_decode is False
    assert r.token_budget is None
    assert "cache_mode:dense(recurrent_blocks)" in r.downgrades


def test_resolve_sliding_window_forces_dense():
    cfg = registry.get_reduced(
        "mixtral-8x22b", capacity_factor=8.0, sliding_window=6
    )
    r = EngineConfig().resolve(cfg)
    assert decode_path(cfg) == ("vectorized", False)
    assert r.cache_mode == "dense"
    assert "cache_mode:dense(sliding_window)" in r.downgrades


def test_resolve_sampling_switches_spec_off():
    cfg = registry.get_reduced("qwen2-1.5b")
    r = EngineConfig(sample="temperature", spec_decode=True,
                     token_budget=32).resolve(cfg)
    assert r.spec_decode is False and "spec_decode:off(sample)" in r.downgrades
    assert r.token_budget is None
    assert "token_budget:off(needs_verify_window)" in r.downgrades


def test_resolve_attn_only_takes_vectorized_paged_batched():
    cfg = registry.get_reduced("qwen2-1.5b")
    r = EngineConfig().resolve(cfg)
    assert decode_path(cfg) == ("vectorized", True)
    assert r.cache_mode == "paged" and r.downgrades == ()
    # The model picks the decode path; no option asks for another.
    for kw in ({"decode_mode": "grouped"}, {"batch_prefill": False}):
        with pytest.raises(TypeError):
            EngineConfig(**kw)


def test_resolve_idempotent():
    cfg = registry.get_reduced("rwkv6-1.6b")
    r1 = EngineConfig(spec_decode=True).resolve(cfg)
    r2 = r1.resolve(cfg)
    assert r1 == r2


# ---- from_args -------------------------------------------------------------

def test_from_args_maps_fields_and_parses_mesh_strings():
    ns = argparse.Namespace(
        slots=2, max_seq=64, cache_mode="dense", mesh_shape="2x4",
        arch="llama3.2-1b",  # non-config attrs are ignored
    )
    c = EngineConfig.from_args(ns)
    assert c.slots == 2 and c.max_seq == 64 and c.cache_mode == "dense"
    assert c.mesh_shape == (2, 4)
    assert EngineConfig.from_args(
        argparse.Namespace(mesh_shape="2")).mesh_shape == (2,)
    assert EngineConfig.from_args(
        argparse.Namespace(mesh_shape="2,2")).mesh_shape == (2, 2)
    # Missing attrs keep defaults.
    assert c.block_size == EngineConfig().block_size
    assert c.prefix_cache is True and c.tenant_quota is None
    # serve.py's --no-prefix-cache / --tenant-quota route by field name.
    c2 = EngineConfig.from_args(
        argparse.Namespace(prefix_cache=False, tenant_quota=12))
    assert c2.prefix_cache is False and c2.tenant_quota == 12


# ---- the Engine deprecation shim -------------------------------------------

def _model():
    cfg = registry.get_reduced("qwen2-1.5b")
    params = T.model_init(jax.random.PRNGKey(0), cfg, ENC)
    return cfg, params


def test_shim_and_config_paths_build_identical_configs():
    cfg, params = _model()
    legacy = engine_lib.Engine(
        params, cfg, ENC, slots=2, max_seq=32, cache_mode="paged",
        block_size=8, spec_decode=True,
    )
    explicit = engine_lib.Engine(
        params, cfg, ENC,
        config=EngineConfig(slots=2, max_seq=32, cache_mode="paged",
                            block_size=8, spec_decode=True),
    )
    assert legacy.config == explicit.config
    assert legacy.spec_decode and legacy.cache_mode == "paged"


def test_shim_rejects_config_plus_kwargs():
    cfg, params = _model()
    with pytest.raises(TypeError, match="not both"):
        engine_lib.Engine(params, cfg, ENC, config=EngineConfig(), slots=2)


def test_shim_rejects_unknown_kwarg():
    cfg, params = _model()
    with pytest.raises(TypeError):
        engine_lib.Engine(params, cfg, ENC, slotz=2)


def test_engine_surfaces_resolved_downgrades_in_stats():
    cfg = registry.get_reduced("rwkv6-1.6b")
    params = T.model_init(jax.random.PRNGKey(0), cfg, ENC)
    eng = engine_lib.Engine(params, cfg, ENC, slots=2, max_seq=32,
                            spec_decode=True)
    assert eng.cache_mode == "dense" and eng.decode_mode == "grouped"
    s = eng.stats
    assert any("recurrent_blocks" in d for d in s["config_downgrades"])


def test_engine_token_output_unchanged_by_config_path():
    cfg, params = _model()

    def run(**kw):
        eng = engine_lib.Engine(params, cfg, ENC, **kw)
        for i in range(3):
            eng.submit(engine_lib.Request(
                uid=i, prompt=(np.arange(4 + i) % 7).astype(np.int32),
                max_new_tokens=5,
            ))
        eng.run()
        return {r.uid: list(r.generated) for r in eng.finished}

    legacy = run(slots=2, max_seq=32, block_size=8)
    explicit = run(config=EngineConfig(slots=2, max_seq=32, block_size=8))
    assert legacy == explicit
